"""`prefill_row_fill(.batch)`: the rows the prefill programs were given
over the rows they computed, from ADMIT and PREFILL_CHUNK events. On
synthetic events against a hand-computed share; on a program that reports
no `rows_computed` it reads nothing; on a tiny cell its rows computed
equal the engine's `prefill_rows_computed` counter; and a traced tiny run
prints it."""
import importlib.util
import math
import time
from types import SimpleNamespace

import bench_fixture
import pytest

from bench.lib import spec

_s = importlib.util.spec_from_file_location("bench_run",
                                            bench_fixture.BENCH / "run.py")
run = importlib.util.module_from_spec(_s)
_s.loader.exec_module(run)


def _reader(name):
    return spec.load_module(bench_fixture.BENCH, "metrics", name).read


def _ev(seq, kind, t, **extra):
    from repro.serving.telemetry import SpanEvent

    return SpanEvent(seq, t, kind, extra=extra or None)


def test_reads_rows_over_rows_computed_in_the_window():
    events = [
        _ev(1, "admit", 11.0, rows=3, rows_computed=16, rids=[1, 2, 3]),
        _ev(2, "prefill_chunk", 12.0, rows=5, rows_computed=8, chunk=256),
        _ev(3, "prefill_chunk", 12.5, rows=2, rows_computed=2, chunk=256),
        _ev(4, "decode_segment", 13.0, live=9, tokens=72),
        _ev(5, "admit", 25.0, rows=1, rows_computed=64),     # past the close
        _ev(6, "admit", 9.0, rows=1, rows_computed=64),      # before the open
        _ev(7, "admit", 14.0, rids=[9]),                     # no rows_computed
    ]
    ctx = SimpleNamespace(window=(10.0, 20.0), events=events)
    # (3 + 5 + 2) rows of (16 + 8 + 2) computed
    assert _reader("prefill_row_fill")(ctx) == pytest.approx(100 * 10 / 26)
    assert _reader("prefill_row_fill.batch")(ctx) == \
        _reader("prefill_row_fill")(ctx)


def test_reads_nothing_from_a_program_without_rows_computed():
    events = [_ev(1, "admit", 11.0, rids=[1, 2]),
              _ev(2, "prefill_chunk", 12.0, rows=4, chunk=16, rids=[1])]
    ctx = SimpleNamespace(window=(10.0, 20.0), events=events)
    assert _reader("prefill_row_fill")(ctx) is None
    assert _reader("prefill_row_fill.batch")(ctx) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = bench_fixture.make_root(tmp_path_factory.mktemp("bench"))
    cells = {"mamba2-370m.voice": ["tiny-ssm.voice"],
             "granite-3-8b-pp2.batch": ["tiny-dense.batch"]}
    for m in spec.load_benchmark(bench_fixture.REPO)["per_layer"]:
        if m["name"].startswith("prefill_row_fill"):
            bench_fixture.add_entry(
                r, "per_layer", dict(m, workloads=cells[m["workloads"][0]]))
    return r


def test_rows_computed_match_the_engine_counter(root):
    import jax

    from bench.lib import serve, traffic, weights

    cell = spec.load_cell("tiny-dense.batch", root)
    cfg = serve.model_config(cell.config)
    sysm = serve.build(cell.config, weights.init_params(cfg, 4, jax.devices()[0]),
                       with_audio=False)
    rt, eng = sysm.runtime, sysm.engine
    planned = traffic.plan(cell.traffic, 11, 3.0, cfg.vocab, 4)[:8]
    rt.submit([serve._request(p, time.monotonic()) for p in planned])
    while rt.busy():
        rt.step()
    events = list(rt.tracer.events)
    rt.close()
    prefill = [e for e in events if e.kind in ("admit", "prefill_chunk")]
    assert prefill
    computed = sum(e.extra["rows_computed"] for e in prefill)
    assert computed == eng.stats["prefill_rows_computed"]
    rows = sum(e.extra["rows"] for e in prefill)
    got = _reader("prefill_row_fill.batch")(
        SimpleNamespace(window=(-math.inf, math.inf), events=events))
    assert got == pytest.approx(100 * rows / computed)
    assert 0 < got <= 100


@pytest.mark.parametrize("cell_name,metric", [
    ("tiny-ssm.voice", "prefill_row_fill"),
    ("tiny-dense.batch", "prefill_row_fill.batch")])
def test_traced_run_prints_the_row_fill(root, cell_name, metric):
    out = run.run_cell(spec.load_cell(cell_name, root), 2**33 + 9, 1.5,
                       trace=True, allow_cpu=True, t_start=time.monotonic())
    m = out["metrics"]
    assert 0 < m[metric]["value"] <= 100
    assert m[metric]["unit"] == "%"
