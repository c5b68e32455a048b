"""The program's spans and counters as the benchmark reads them.

On the tiny cells (CPU): the four parts of a voice request's time to
first token telescope to first token - ingest, each request is in one
dpu_exec and one admission span, and the program's `tokens_served`
counter and the `live` rows on DECODE_SEGMENT equal what the harness reads
from the engine's private state today. On a synthetic trace whose host
clock is shifted from the profiler's: the two device-clock readers give
the hand-computed shares, and nothing where the anchor disagrees. Traced
tiny runs report the new program-span metrics and, on the CPU, none of
the device ones."""
import importlib.util
import time
from types import SimpleNamespace

import bench_fixture
import pytest

from bench.lib import spans, spec
from bench.lib import trace as T

_s = importlib.util.spec_from_file_location("bench_run",
                                            bench_fixture.BENCH / "run.py")
run = importlib.util.module_from_spec(_s)
_s.loader.exec_module(run)

TTFT_PARTS = ("dpu_queue_p90_ms", "dpu_exec_p90_ms", "admit_handoff_p90_ms",
              "admit_exec_p90_ms")
DEVICE_METRICS = ("dpu_launch_device_share", "host_idle_share",
                  "host_idle_share.batch")


def _entries():
    """This benchmark's new per-layer entries, on the tiny cells."""
    cells = {"mamba2-370m.voice": ["tiny-ssm.voice"],
             "granite-3-8b-pp2.batch": ["tiny-dense.batch"]}
    bench = spec.load_benchmark(bench_fixture.REPO)
    return [dict(m, workloads=cells[m["workloads"][0]])
            for m in bench["per_layer"]
            if m["name"] in TTFT_PARTS + DEVICE_METRICS]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = bench_fixture.make_root(tmp_path_factory.mktemp("bench"))
    for e in _entries():
        bench_fixture.add_entry(r, "per_layer", e)
    return r


def _system(root, cell_name, probe=False):
    import jax

    from bench.lib import serve, weights

    cell = spec.load_cell(cell_name, root)
    cfg = serve.model_config(cell.config)
    params = weights.init_params(cfg, 5, jax.devices()[0])
    sysm = serve.build(cell.config, params,
                       with_audio=bool(cell.traffic.get("audio")))
    pr = None
    if probe:
        cost = None
        if cell.traffic.get("audio"):
            cost = spec.load_module(cell.bench_dir, "flops",
                                    "dpu_audio").clip_cost
        pr = serve.Probe(sysm, spec.load_module(
            cell.bench_dir, "flops", cell.config["flops"]), cost)
    return cell, cfg, sysm, serve, pr


def test_ttft_parts_telescope_on_the_voice_cell(root):
    from bench.lib import traffic

    cell, cfg, sysm, serve, _ = _system(root, "tiny-ssm.voice")
    planned = traffic.plan(cell.traffic, 2**33 + 5, 3.0, cfg.vocab, 4)[:10]
    win = serve.drive(sysm, cell.traffic, planned, 3.0, 60.0)
    events = list(sysm.runtime.tracer.events)
    sysm.runtime.close()
    done = [s for s in win.served if s.status == "completed"]
    assert len(done) == len(planned)
    ctx = SimpleNamespace(served=win.served, events=events)
    parts = spans.ttft_parts(ctx)
    assert sorted(parts) == sorted(s.plan.index for s in done)
    ingest = {e.rid: e.t for e in events if e.kind == "ingest"}
    for s in done:
        p = parts[s.plan.index]
        assert min(p) >= 0.0, p
        assert sum(p) == pytest.approx(
            s.req.first_token_at - ingest[s.plan.index], abs=1e-6)
    # every served request is in exactly one launch and one admission, and
    # each launch's end is the request's finish (PREPROCESS_DONE's `t` is
    # the harvesting tick's, which may have begun before it)
    for kind in ("dpu_exec", "admission"):
        rids = [r for e in events if e.kind == kind and e.extra
                for r in e.extra.get("rids", ())]
        want = sorted(s.plan.index for s in done)
        assert sorted(r for r in rids if r >= 0) == want, kind
    dpu = spans.spans_by_rid(events, "dpu_exec")
    for s in done:
        d = dpu[s.plan.index]
        assert s.req.preprocess_finished_at == pytest.approx(d.t + d.dur)
        assert (d.extra or {}).get("error") is None
    # the launch's phases are recorded as its children, in order
    kids = [e for e in events if (e.extra or {}).get("parent") == d.seq]
    assert [e.kind for e in kids] == ["dpu_exec.stage", "dpu_exec.device",
                                      "dpu_exec.readback"]


@pytest.mark.parametrize("cell_name", ["tiny-dense.batch", "tiny-ssm.voice"])
def test_program_counters_match_the_harness_readings(root, cell_name):
    """`tokens_served` equals serve.tokens_served after every step, and
    the `live` rows of each DECODE_SEGMENT equal the Probe's live_rows of
    the same segment call."""
    from repro.serving import telemetry as tm

    from bench.lib import traffic

    cell, cfg, sysm, serve, probe = _system(root, cell_name, probe=True)
    rt, eng = sysm.runtime, sysm.engine
    planned = traffic.plan(cell.traffic, 3, 3.0, cfg.vocab, 4)[:6]
    rt.submit([serve._request(p, time.monotonic()) for p in planned])
    steps = 0
    while rt.busy():
        rt.step()
        steps += 1
        assert eng.stats["tokens_served"] == serve.tokens_served(eng)
    assert eng.stats["tokens_served"] == sum(p.max_new for p in planned)
    live = [c.live_rows for c in probe.calls if c.kind == "bench.segment"]
    segs = rt.tracer.of(tm.DECODE_SEGMENT)
    assert live and [e.extra["live"] for e in segs] == live
    assert [e.extra["tokens"] for e in segs] == \
        [e.extra["tokens"] for e in rt.tracer.of(tm.SEGMENT)]
    rt.close()


# --- the device-clock readers on a synthetic trace ----------------------


def _ev(seq, kind, t, dur, **extra):
    from repro.serving.telemetry import SpanEvent

    return SpanEvent(seq, t, kind, dur=dur, extra=extra or None)


def _ctx(host_window=(10.0, 14.0)):
    """Trace clock = host clock + 90 s; a 4 s window."""
    dev = T.DeviceTrace("/device:TPU:0")
    dev.modules = [("jit_audio_pipeline_batch(7)", 100.6, 100.8),
                   ("jit_audio_pipeline_batch(7)", 102.1, 102.2),
                   ("jit_audio_pipeline_batch(7)", 103.0, 103.5),
                   ("jit__segment(9)", 101.5, 103.0)]
    dev.ops = [("%fusion.1 = f32[8] fusion(a)", 100.0, 101.0),
               ("%fusion.2 = f32[8] fusion(b)", 101.5, 103.0)]
    tr = T.Trace(devices=[dev], host_spans=[], window=(100.0, 104.0))
    events = [
        _ev(1, "dpu_exec", 10.5, 0.5, rids=[1]),
        _ev(2, "dpu_exec", 12.0, 0.5, rids=[2]),
        _ev(3, "dpu_exec.device", 10.55, 0.4, parent=1),
        _ev(4, "admission.host", 10.9, 0.2, parent=9),
        _ev(5, "admission.prepare", 11.2, 0.2, parent=10),
        _ev(6, "segment.host", 12.9, 0.3, parent=11),
        _ev(7, "segment.wait", 13.5, 0.4, parent=11),
        _ev(8, "segment", 12.0, 2.0),
    ]
    return SimpleNamespace(trace=tr, trace_window_host=host_window,
                           events=events)


def _reader(name):
    return spec.load_module(bench_fixture.BENCH, "metrics", name).read


def test_device_clock_readers_on_a_shifted_anchor():
    ctx = _ctx()
    assert spans.trace_offset(ctx) == pytest.approx(90.0)
    # launches at 100.5-101.0 and 102.0-102.5 on the trace clock hold
    # 0.2 + 0.1 s of the program; the run at 103.0 lies outside them
    assert _reader("dpu_launch_device_share")(ctx) == pytest.approx(30.0)
    # idle gaps 101.0-101.5 and 103.0-104.0; host phases 100.9-101.1
    # (0.1 idle), 101.2-101.4 (0.2) and 102.9-103.2 (0.2); the wait phase
    # 103.5-103.9 and the whole segment span do not count: 0.5 s of 4 s
    assert _reader("host_idle_share")(ctx) == pytest.approx(12.5)
    assert _reader("host_idle_share.batch")(ctx) == pytest.approx(12.5)


@pytest.mark.parametrize("name", ["dpu_launch_device_share",
                                  "host_idle_share"])
def test_device_clock_readers_read_nothing_without_an_anchor(name):
    # the host window is 10 ms longer than the trace's: no anchor
    assert _reader(name)(_ctx(host_window=(10.0, 14.01))) is None
    # a program without spans (as before they existed): nothing to read
    ctx = _ctx()
    ctx.events = [e for e in ctx.events if e.kind == "segment"]
    assert _reader(name)(ctx) is None
    ctx.trace = None
    assert _reader(name)(ctx) is None


def test_overlap_of_interval_sets():
    a = [(0.0, 1.0), (2.0, 3.0), (0.5, 1.5)]
    b = [(1.2, 2.5), (2.8, 4.0)]
    # union a = [0,1.5] + [2,3]; b meets it on [1.2,1.5], [2,2.5], [2.8,3]
    assert spans.overlap_s(a, b) == pytest.approx(0.3 + 0.5 + 0.2)
    assert spans.overlap_s([], b) == 0.0


# --- traced tiny runs -------------------------------------------------------


@pytest.mark.parametrize("cell_name", ["tiny-ssm.voice", "tiny-dense.batch"])
def test_traced_run_reads_program_span_metrics(root, cell_name):
    out = run.run_cell(spec.load_cell(cell_name, root), 2**33 + 7, 1.5,
                       trace=True, allow_cpu=True, t_start=time.monotonic())
    m = out["metrics"]
    if cell_name == "tiny-ssm.voice":
        for name in TTFT_PARTS:
            assert m[name]["value"] > 0, name
            assert m[name]["unit"] == "ms"
    else:
        assert not set(TTFT_PARTS) & set(m)
        assert m["slot_occupancy.batch"]["value"] > 0
    # no TPU plane on the CPU: the device readers find nothing to read
    assert not set(DEVICE_METRICS) & set(m)
    assert out["device"]["window_s"] > 0
