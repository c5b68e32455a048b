"""End-to-end behaviour tests for the paper's system claims, on the
event-driven simulator (calibrated cost models) and the real-exec engine."""
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.batching import analytical_decode_latency, analytical_knee, derive_policy
from repro.core.batching.knee import kv_bytes_per_token
from repro.serving.requests import WorkloadSpec, generate_requests
from repro.serving.simulator import SimConfig, simulate


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("whisper-base")
    n = cfg.active_param_count()
    kvb = kv_bytes_per_token(cfg)
    profiles = {
        b: analytical_knee(n, chips=16, context_len=int((b + 0.5) * 250),
                           kv_bytes_per_token=kvb)
        for b in range(12)
    }
    policy = derive_policy(profiles, n_slices=16, bucket_width=2.5)

    def exec_lat(batch):
        ctx = int(batch.max_length * 100)
        return 20 * analytical_decode_latency(
            n, batch.size, chips=16, context_len=ctx, kv_bytes_per_token=kvb
        )

    pre_cost = lambda ln: 0.030 * ln / 7.5  # CPU preprocessing per input length
    reqs = generate_requests(WorkloadSpec(rate_qps=400, seed=1), 2000)
    return policy, exec_lat, pre_cost, reqs


def _run(setup, **kw):
    policy, exec_lat, pre_cost, reqs = setup
    import copy

    return simulate(copy.deepcopy(reqs), policy, exec_lat, pre_cost,
                    SimConfig(n_slices=16, **kw))


def test_preba_beats_cpu_baseline(setup):
    """Paper Fig. 17/18: DPU preprocessing sustains much higher goodput and
    lower tail latency than the CPU-core pool."""
    dpu = _run(setup, preprocess="dpu")
    cpu = _run(setup, preprocess="cpu", cpu_cores=32)
    assert dpu.qps > 1.5 * cpu.qps or dpu.p95_ms < 0.5 * cpu.p95_ms
    assert dpu.p95_ms < cpu.p95_ms


def test_dpu_close_to_ideal(setup):
    """Paper: PREBA reaches >91.6% of the no-preprocessing Ideal."""
    dpu = _run(setup, preprocess="dpu")
    ideal = _run(setup, preprocess="none")
    assert dpu.qps >= 0.85 * ideal.qps


def test_ablation_ordering(setup):
    """Fig. 22: Base < Base+DPU <= full PREBA (throughput)."""
    policy, exec_lat, pre_cost, reqs = setup
    import copy
    import dataclasses

    static = dataclasses.replace(policy, batch_max={0: 1})
    base = simulate(copy.deepcopy(reqs), static, exec_lat, pre_cost,
                    SimConfig(n_slices=16, preprocess="cpu"))
    dpu_only = simulate(copy.deepcopy(reqs), static, exec_lat, pre_cost,
                        SimConfig(n_slices=16, preprocess="dpu"))
    full = simulate(copy.deepcopy(reqs), policy, exec_lat, pre_cost,
                    SimConfig(n_slices=16, preprocess="dpu"))
    assert dpu_only.qps >= base.qps
    assert full.p95_ms <= dpu_only.p95_ms * 1.5
    assert full.batches <= dpu_only.batches  # dynamic batching coalesces


def test_slice_failure_no_request_lost(setup):
    policy, exec_lat, pre_cost, reqs = setup
    res = _run(setup, preprocess="dpu", fail_slice_at=(3, 1.0))
    assert len(res.completed) == len(reqs)


def test_straggler_hedging_bounds_tail(setup):
    slow = _run(setup, preprocess="dpu", straggler_prob=0.05,
                straggler_slowdown=20.0, hedge_factor=2.0)
    assert slow.hedges > 0
    assert len(slow.completed) == 2000


def test_serving_engine_end_to_end():
    """Real-execution path on a reduced model."""
    from repro.serving.engine import EngineConfig, build_engine

    cfg = reduced("tinyllama-1.1b")
    engine = build_engine(cfg, ec=EngineConfig(max_new_tokens=4))
    reqs = generate_requests(
        WorkloadSpec(modality="text", rate_qps=100, mean_len=24, max_len=48), 8
    )
    for r in reqs:
        engine.submit(r)
    done = engine.run_until_idle()
    assert len(done) == 8
    assert all(r.payload is not None and len(r.payload) == 4 for r in done)


@pytest.mark.parametrize("kind,known", [("TPU v5 lite", True),
                                        ("cpu", False), ("TPU v4", False)])
def test_peaks_keyed_by_device_kind(kind, known):
    """Peak rates come from a table keyed by device_kind; a kind without a
    published entry raises instead of borrowing another chip's numbers."""
    from repro.analysis import roofline

    if known:
        p = roofline.peaks_for(kind)
        assert (p.flops, p.hbm_bw) == (197e12, 819e9)
    else:
        with pytest.raises(ValueError, match="no published peaks"):
            roofline.peaks_for(kind)
