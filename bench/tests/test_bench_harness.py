"""The harness end to end on the CPU at a tiny size (its look for a chip
skipped): a sound run is correct; the correctness control and each fault
the cells can have (a token altered where it is produced, a decode step
that returns its state unchanged, a DPU answer altered) come out as not
correct; a traced run reads its host-side per-layer metrics.

Tiny limits (tests/data/configs, untied heads so that the logits do not
just echo the input token): over three seeds on the CPU the program read
at most 4.0e-3 token gap and 3.1e-4 front-end error, the controls at
least 1.46e-2 and 2.99e-2, so the limits are 1e-2 and 5e-3."""
import importlib.util
import time

import bench_fixture
import jax.numpy as jnp
import pytest

from bench.lib import spec

_s = importlib.util.spec_from_file_location("bench_run",
                                            bench_fixture.BENCH / "run.py")
run = importlib.util.module_from_spec(_s)
_s.loader.exec_module(run)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_fixture.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, seed=2**33 + 1, **kw):
    return run.run_cell(spec.load_cell(cell, root), seed, 1.5,
                        allow_cpu=True, t_start=time.monotonic(), **kw)


def test_sound_run_is_correct_and_control_is_not(root):
    out = _run(root, "tiny-ssm.voice", control=True)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    ck, cal = out["checks"], out["calibration"]
    assert cal["token_gap_control"] > ck["token_gap"]["limit"]
    assert cal["dpu_err_control"] > ck["dpu_max_abs_err"]["limit"]


def test_closed_loop_reports_throughput(root):
    out = _run(root, "tiny-dense.batch")
    assert out["correct"] is True
    assert out["metrics"]["output_tokens_per_s"]["value"] > 0


def _tiny_system(root, cell_name="tiny-dense.batch"):
    import jax

    from bench.lib import serve, weights

    cell = spec.load_cell(cell_name, root)
    cfg = serve.model_config(cell.config)
    params = weights.init_params(cfg, 5, jax.devices()[0])
    return cell, cfg, params, serve


def test_build_serves_the_benchmarks_weights_through_the_program(root):
    """serve.build goes through build_pipelined_runtime, with the policy
    build_engine derives, and allocates no second parameter tree."""
    from repro.models import api
    from repro.serving.engine import build_engine

    cell, cfg, params, serve = _tiny_system(root)
    draws = []
    orig = api.init_params
    api.init_params = lambda *a, **k: draws.append(a) or orig(*a, **k)
    try:
        sysm = serve.build(cell.config, params, with_audio=False)
    finally:
        api.init_params = orig
    assert draws == [] and sysm.engine.params is params
    assert sysm.runtime.engine is sysm.engine
    assert sysm.engine.policy == build_engine(cfg, ec=sysm.ec).policy
    sysm.runtime.close()


def test_tokens_served_counts_rows_in_flight(root):
    """The window's token count moves with every segment, not only when a
    request completes, and ends at the sum of the served budgets."""
    import numpy as np

    from bench.lib import traffic

    cell, cfg, params, serve = _tiny_system(root)
    sysm = serve.build(cell.config, params, with_audio=False)
    rt, eng = sysm.runtime, sysm.engine
    planned = traffic.plan(cell.traffic, 3, 1.0, cfg.vocab, 4)[:6]
    rt.submit([serve._request(p, time.monotonic()) for p in planned])
    seen = [serve.tokens_served(eng)]
    mid_flight = False
    while rt.busy():
        rt.step()
        seen.append(serve.tokens_served(eng))
        mid_flight |= seen[-1] > sum(len(r.payload) for r in eng.completed)
    assert mid_flight and seen == sorted(seen)
    assert seen[-1] - seen[0] == sum(p.max_new for p in planned)
    assert sum(len(r.payload) for r in eng.completed) == seen[-1]
    sysm.runtime.close()


def test_traced_batch_run_reads_live_row_occupancy(root):
    out = _run(root, "tiny-dense.batch", trace=True)
    assert 0 < out["metrics"]["slot_occupancy.batch"]["value"] <= 100


def _altered_tokens(orig):
    def f(params, cache, tok, pos0, cfg, **kw):
        toks, cache = orig(params, cache, tok, pos0, cfg, **kw)
        return (toks + 1) % cfg.vocab, cache
    return f


def _state_unchanged(orig):
    def f(params, cache, tok, pos0, cfg, **kw):
        toks, _ = orig(params, cache, tok, pos0, cfg, **kw)
        return toks, cache
    return f


@pytest.mark.parametrize("fault", [_altered_tokens, _state_unchanged])
def test_decode_faults_are_caught(root, monkeypatch, fault):
    from repro.models import lm

    monkeypatch.setattr(lm, "decode_segment", fault(lm.decode_segment))
    out = _run(root, "tiny-dense.chat")
    assert out["correct"] is False
    assert out["checks"]["token_gap"]["value"] > \
        out["checks"]["token_gap"]["limit"]


def test_dpu_fault_is_caught(root, monkeypatch):
    from repro.kernels import ops

    orig = ops.audio_pipeline_batch
    monkeypatch.setattr(ops, "audio_pipeline_batch",
                        lambda x, *a, **k: orig(x, *a, **k).at[:, 0, 0].add(
                            jnp.float32(0.5)))
    out = _run(root, "tiny-ssm.voice")
    assert out["correct"] is False
    assert out["checks"]["dpu_max_abs_err"]["value"] >= 0.4


def test_traced_run_reads_host_metrics(root):
    out = _run(root, "tiny-ssm.voice", trace=True)
    m = out["metrics"]
    for name in ("admission_wait_p90_ms", "dpu_stage_p90_ms",
                 "prefill_pad_share"):
        assert m[name]["value"] > 0, name
    # no TPU plane on the CPU: the device readers find nothing to read
    for name in ("dpu_roofline", "decode_step_ms", "mfu",
                 "device_idle_share"):
        assert name not in m
    assert 0 < m["prefill_pad_share"]["value"] < 100
    assert out["device"]["window_s"] > 0
