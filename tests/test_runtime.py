"""Stage-pipelined serving runtime proofs (serving/runtime.py +
core/dpu/service.py): DpuService same-shape batching and ordering, the
double-buffered hand-off, virtual-clock determinism, per-request
bit-identity vs the synchronous submit_many path (single- and multi-slice,
including under backpressure-induced sheds), SLO-aware front-door shedding,
and preservation of the compile-once invariant."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs import reduced
from repro.core.batching.buckets import Request
from repro.core.dpu.runtime import DpuConfig
from repro.core.dpu.service import DoubleBuffer, DpuService, DpuServiceConfig
from repro.data import preprocess_cpu as pp
from repro.serving import telemetry as tm
from repro.serving.engine import EngineConfig, build_engine
from repro.serving.runtime import (
    PipelinedRuntime, RuntimeConfig, build_pipelined_runtime,
)

# canonical request set: prompts are deterministic per rid, so payloads
# depend only on (rid, length, budget) — the sync reference covers every test
SPEC = [(17, 8), (23, 5), (19, 8), (25, 6), (21, 3), (30, 7),
        (18, 4), (28, 8), (22, 2), (26, 6)]


def _ec():
    return EngineConfig(continuous=True, max_slots=4, segment_len=4,
                        max_new_tokens=8, max_prompt_len=32)


def _mk(i, *, arrival=0.0, audio=None):
    n, b = SPEC[i]
    payload = None
    if audio is not None:
        rng = np.random.default_rng(4000 + i)
        payload = rng.standard_normal(audio).astype(np.float32)
    return Request(rid=6000 + i, arrival=arrival, length=float(n),
                   max_new_tokens=b, payload=payload)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced("tinyllama-1.1b")
    sync = build_engine(cfg, ec=_ec())
    sync.submit_many([_mk(i) for i in range(len(SPEC))])
    sync.run_until_idle()
    ref = {r.rid: np.asarray(r.payload) for r in sync.completed}
    assert len(ref) == len(SPEC)
    return cfg, ref


def _check(done, ref):
    for r in done:
        np.testing.assert_array_equal(np.asarray(r.payload), ref[r.rid])


def _fail_on_shed(svc):
    """A launch that raised sheds its group and leaves busy() true until
    take_failed(): surface it as a test failure instead of a hang."""
    failed = svc.take_failed()
    if failed:
        pytest.fail(f"DPU launch shed rids {[r.rid for r in failed]}: "
                    f"{svc.last_error!r}")


def _drain_virtual(svc, max_steps=1000):
    """Step a virtual-clock service until idle (bounded), returning its
    outputs in completion order; a shed group fails the test."""
    now, out = 0.0, []
    for _ in range(max_steps):
        if not svc.busy():
            return out
        svc.step(now)
        out.extend(svc.poll(now))
        _fail_on_shed(svc)
        nxt = svc.next_ready()
        now = nxt if nxt is not None else now
    pytest.fail(f"DPU service still busy after {max_steps} steps")


# ---------------------------------------------------------------------------
# DoubleBuffer + DpuService
# ---------------------------------------------------------------------------


def test_double_buffer_bounds_and_fifo():
    db = DoubleBuffer(2)
    assert db.put("a") and db.put("b")
    assert not db.put("c")          # back full -> backpressure
    assert db.drain(1) == ["a"]     # swap happened; FIFO preserved
    assert db.put("c")              # back freed by the swap
    # the consumer finishes the front first; "c" (produced into the back
    # during the drain) only surfaces at the NEXT drain boundary — the
    # double-buffer property that isolates producer from consumer
    assert db.drain() == ["b"]
    assert db.drain() == ["c"]
    assert len(db) == 0 and db.free() == 2


def test_dpu_service_virtual_groups_and_matches_reference():
    """Same-shape requests share one batched CU launch; outputs match the
    per-request CPU pipeline; completion order follows the modeled clock and
    is identical run to run (virtual-clock determinism)."""
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal(48000).astype(np.float32) for _ in range(3)]
    xs.append(rng.standard_normal(32000).astype(np.float32))

    def run():
        svc = DpuService(DpuServiceConfig(clock="virtual", max_group=8))
        reqs = [Request(rid=i, arrival=0.0, length=3.0, payload=x.copy())
                for i, x in enumerate(xs)]
        for r in reqs:
            assert svc.submit(r)
        return svc, _drain_virtual(svc)

    svc, out = run()
    assert svc.stats["groups"] == 2          # one 48000-stack + one 32000
    assert [r.rid for r in out] == [r.rid for r in run()[1]]  # deterministic
    assert all(r.preprocessed_at is not None for r in out)
    for r in sorted(out, key=lambda r: r.rid):
        np.testing.assert_allclose(r.payload, pp.audio_pipeline(xs[r.rid]),
                                   rtol=1e-4, atol=1e-4)


def test_dpu_service_wall_worker_matches_reference():
    """Wall-clock mode: the background worker produces the same outputs as
    the inline pipeline (the overlap changes timing, never values)."""
    import time

    rng = np.random.default_rng(1)
    xs = [rng.standard_normal(16000).astype(np.float32) for _ in range(4)]
    svc = DpuService(DpuServiceConfig(clock="wall", max_group=4))
    reqs = [Request(rid=i, arrival=0.0, length=1.0, payload=x.copy())
            for i, x in enumerate(xs)]
    for r in reqs:
        assert svc.submit(r)
    done, t0 = [], time.monotonic()
    while svc.busy() and time.monotonic() - t0 < 60:
        svc.step(time.monotonic())
        done.extend(svc.poll(time.monotonic()))
        _fail_on_shed(svc)
        time.sleep(0.001)
    svc.close()
    assert len(done) == 4
    for r in sorted(done, key=lambda r: r.rid):
        np.testing.assert_allclose(r.payload, pp.audio_pipeline(xs[r.rid]),
                                   rtol=1e-4, atol=1e-4)


def test_dpu_service_fused_pallas_launch():
    """backend='dpu' audio services auto-fuse the whole front-end into ONE
    jitted program per pow2-padded group (kernels/ops.audio_pipeline_batch);
    outputs match the per-FU CPU pipeline within kernel tolerance."""
    svc = DpuService(DpuServiceConfig(
        clock="virtual", dpu=DpuConfig(backend="dpu"), max_group=4))
    assert svc._fused and svc._bucket
    rng = np.random.default_rng(2)
    xs = [rng.standard_normal(16000).astype(np.float32) for _ in range(3)]
    reqs = [Request(rid=i, arrival=0.0, length=1.0, payload=x.copy())
            for i, x in enumerate(xs)]
    for r in reqs:
        assert svc.submit(r)
    out = _drain_virtual(svc)
    assert len(out) == 3 and svc.stats["groups"] == 1  # one padded launch
    for r in sorted(out, key=lambda r: r.rid):
        np.testing.assert_allclose(r.payload, pp.audio_pipeline(xs[r.rid]),
                                   rtol=2e-2, atol=2e-2)


def test_dpu_service_fused_image_launch():
    """backend='dpu' image services auto-fuse the whole JPEG front-end —
    decode-IDCT -> resize -> crop -> normalize — into ONE jitted program
    per pow2-padded group (kernels/ops.image_pipeline_batch, mirroring the
    audio path); outputs match the per-FU CPU pipeline within kernel
    tolerance, and mixed qtables fall back to the per-FU batched path."""
    svc = DpuService(DpuServiceConfig(
        clock="virtual", dpu=DpuConfig(backend="dpu", modality="image"),
        max_group=4))
    assert svc._fused and svc._bucket
    rng = np.random.default_rng(9)
    qt = rng.integers(1, 16, (8, 8)).astype(np.float32)
    cos = [rng.integers(-32, 32, (32, 32, 8, 8)).astype(np.float32)
           for _ in range(3)]
    reqs = [Request(rid=i, arrival=0.0, length=1.0,
                    payload={"coeffs": c.copy(), "qtable": qt.copy()})
            for i, c in enumerate(cos)]
    for r in reqs:
        assert svc.submit(r)
    out = _drain_virtual(svc)
    assert len(out) == 3 and svc.stats["groups"] == 1  # one padded launch
    for r in sorted(out, key=lambda r: r.rid):
        np.testing.assert_allclose(r.payload, pp.image_pipeline(cos[r.rid], qt),
                                   rtol=2e-2, atol=2e-2)
    # mixed qtables: same group key (shapes match) but no shared table —
    # the per-FU batched fallback must still produce per-request results
    svc2 = DpuService(DpuServiceConfig(
        clock="virtual", dpu=DpuConfig(backend="dpu", modality="image"),
        max_group=4, bucket_pow2=False))
    qts = [qt, qt + 1.0]
    reqs2 = [Request(rid=i, arrival=0.0, length=1.0,
                     payload={"coeffs": cos[i].copy(), "qtable": qts[i].copy()})
             for i in range(2)]
    for r in reqs2:
        assert svc2.submit(r)
    out2 = _drain_virtual(svc2)
    for r in sorted(out2, key=lambda r: r.rid):
        np.testing.assert_allclose(
            r.payload, pp.image_pipeline(cos[r.rid], qts[r.rid]),
            rtol=2e-2, atol=2e-2)


def test_wall_worker_failure_sheds_group_and_keeps_serving(setup):
    """A batched launch that raises (malformed payload) must shed ONLY its
    group — recorded in runtime.shed with the error kept on
    service.last_error — while the worker keeps preprocessing later groups
    and the pipeline drains instead of wedging busy() forever."""
    cfg, ref = setup
    svc = DpuService(DpuServiceConfig(clock="wall", max_group=1))
    # validation off: this test pins the IN-SERVICE failure contract (the
    # front-door validator would shed the bad payload before the worker)
    rt = build_pipelined_runtime(
        cfg, ec=_ec(), service=svc,
        rc=RuntimeConfig(clock="wall", validate_payloads=False))
    bad = _mk(0)
    bad.payload = object()              # numpy pipeline will raise on this
    good = _mk(1, audio=8000)
    rt.submit([bad, good])
    done = rt.run_until_idle()
    rt.close()
    assert [r.rid for r in done] == [good.rid]
    _check(done, ref)
    assert rt.shed == [bad] and rt.stats["shed_error"] == 1
    assert svc.stats["failed"] == 1 and svc.last_error is not None
    assert not rt.busy()


def test_worker_failure_as_last_work_still_recorded(setup):
    """Failed requests count as service-busy until collected, so a run
    whose ONLY work fails still drains: run_until_idle returns with the
    request recorded in shed, not stranded inside the service."""
    cfg, ref = setup
    svc = DpuService(DpuServiceConfig(clock="wall"))
    rt = build_pipelined_runtime(
        cfg, ec=_ec(), service=svc,
        rc=RuntimeConfig(clock="wall", validate_payloads=False))
    bad = _mk(2)
    bad.payload = object()
    rt.submit([bad])
    done = rt.run_until_idle()
    rt.close()
    assert done == [] and rt.shed == [bad]
    assert rt.stats["shed_error"] == 1 and not rt.busy()


def test_virtual_clock_failure_sheds_group_too(setup):
    """The virtual clock honors the same shed-the-group contract as the
    wall worker: a raising launch must not crash step() or lose requests,
    and later groups still preprocess."""
    cfg, ref = setup
    svc = DpuService(DpuServiceConfig(clock="virtual", max_group=1))
    rt = build_pipelined_runtime(
        cfg, ec=_ec(), service=svc,
        rc=RuntimeConfig(validate_payloads=False))
    bad = _mk(3)
    bad.payload = object()
    good = _mk(4, audio=8000)
    rt.submit([bad, good], now=0.0)
    done = rt.run_until_idle()
    assert [r.rid for r in done] == [good.rid]
    _check(done, ref)
    assert rt.shed == [bad] and rt.stats["shed_error"] == 1
    assert svc.stats["failed"] == 1 and svc.last_error is not None


def test_dpu_service_backpressure_bounds():
    svc = DpuService(DpuServiceConfig(clock="virtual", max_pending=2,
                                      max_ready=2, max_group=2))
    x = np.zeros(8000, np.float32)
    reqs = [Request(rid=i, arrival=0.0, length=1.0, payload=x.copy())
            for i in range(5)]
    assert svc.submit(reqs[0]) and svc.submit(reqs[1])
    assert not svc.submit(reqs[2])   # pending full -> shed upstream
    svc.step(0.0)
    # launched work frees pending capacity
    assert svc.submit(reqs[2])


# ---------------------------------------------------------------------------
# Pipelined runtime: bit-identity vs the synchronous path
# ---------------------------------------------------------------------------


def test_pipelined_bit_identical_to_sync_single_engine(setup):
    """Virtual clock, audio payloads on half the requests: every output is
    bit-identical to submit_many + run_until_idle on the same engine
    config — the runtime changes when work happens, never what is
    computed."""
    cfg, ref = setup
    svc = DpuService(DpuServiceConfig(clock="virtual"))
    rt = build_pipelined_runtime(cfg, ec=_ec(), service=svc)
    reqs = [_mk(i, audio=16000 if i % 2 == 0 else None)
            for i in range(len(SPEC))]
    assert rt.submit(reqs, now=0.0) == len(SPEC)
    done = rt.run_until_idle()
    assert len(done) == len(SPEC) and not rt.shed
    _check(done, ref)
    assert rt.stats["offered"] == len(SPEC)


def test_pipelined_bit_identical_multislice(setup):
    """Same proof over the multi-slice engine: shared admission backlog,
    per-slice dispatch, per-slice compile-once (2 steady traces each)."""
    cfg, ref = setup
    svc = DpuService(DpuServiceConfig(clock="virtual"))
    rt = build_pipelined_runtime(cfg, n_slices=2, ec=_ec(), service=svc)
    reqs = [_mk(i, audio=16000 if i % 3 == 0 else None)
            for i in range(len(SPEC))]
    rt.submit(reqs, now=0.0)
    done = rt.run_until_idle()
    assert len(done) == len(SPEC)
    _check(done, ref)
    assert rt.engine.trace_counts() == {0: 2, 1: 2}


def test_pipelined_wall_clock_bit_identical(setup):
    """Wall-clock mode (real overlap: worker thread + monotonic clock)
    completes every request with the same outputs."""
    cfg, ref = setup
    svc = DpuService(DpuServiceConfig(clock="wall"))
    rt = build_pipelined_runtime(
        cfg, ec=_ec(), service=svc, rc=RuntimeConfig(clock="wall"))
    reqs = [_mk(i, audio=16000) for i in range(6)]
    rt.submit(reqs)
    done = rt.run_until_idle()
    rt.close()
    assert len(done) == 6
    _check(done, ref)


# ---------------------------------------------------------------------------
# Backpressure + SLO shedding at the front door
# ---------------------------------------------------------------------------


def test_backpressure_shed_completes_survivors_bit_identical(setup):
    """Tiny queue bounds: overflow is shed AT THE FRONT DOOR (recorded, not
    silently dropped), every accepted request completes, and survivors stay
    bit-identical to the synchronous path."""
    cfg, ref = setup
    svc = DpuService(DpuServiceConfig(clock="virtual", max_pending=2,
                                      max_ready=2))
    rt = build_pipelined_runtime(
        cfg, ec=_ec(), service=svc,
        rc=RuntimeConfig(max_ingest=3, max_backlog=2))
    reqs = [_mk(i, audio=8000 if i % 2 == 0 else None)
            for i in range(len(SPEC))]
    accepted = rt.submit(reqs, now=0.0)   # one burst >> ingest bound
    assert accepted == 3
    assert rt.stats["shed_backpressure"] == len(SPEC) - 3
    done = rt.run_until_idle()
    # accepted ∪ shed partitions the submission; nothing lost or duplicated
    assert len(done) == accepted
    assert {r.rid for r in done} | {r.rid for r in rt.shed} == \
        {r.rid for r in reqs}
    assert not ({r.rid for r in done} & {r.rid for r in rt.shed})
    _check(done, ref)


def test_slo_shed_expired_requests(setup):
    """SLO-aware shedding: a request whose deadline is already blown at the
    front door (arrival + slo_s < now + modeled preprocess time) is shed;
    fresh requests are served."""
    cfg, ref = setup
    svc = DpuService(DpuServiceConfig(clock="virtual"))
    rt = build_pipelined_runtime(
        cfg, ec=_ec(), service=svc,
        rc=RuntimeConfig(slo_s=0.5))
    stale = _mk(0, arrival=0.0)           # submitted at now=1.0: expired
    fresh = _mk(1, arrival=1.0)
    assert rt.submit([stale, fresh], now=1.0) == 1
    assert rt.stats["shed_slo"] == 1 and rt.shed == [stale]
    done = rt.run_until_idle()
    assert [r.rid for r in done] == [fresh.rid]
    _check(done, ref)


def test_decode_backlog_folds_into_slo_shed(setup):
    """ISSUE 5 satellite: the front-door SLO estimate folds in a decode-
    backlog term (admission depth + slot occupancy x the measured execution
    EMA), so a saturated slot pool sheds a request the DPU-only model (no
    payload => zero preprocessing estimate) would have accepted — and then
    starved waiting for a KV slot."""
    cfg, ref = setup
    rt = build_pipelined_runtime(cfg, ec=_ec(), rc=RuntimeConfig(slo_s=0.5))
    # an idle engine sheds nothing: the backlog term is zero
    assert rt.decode_backlog_s() == 0.0
    probe = _mk(0)
    assert rt.submit([probe], now=0.0) == 1
    rt.run_until_idle()
    rt.completed.clear()
    # saturate: every slot occupied / queued. Pin the execution EMA BEFORE
    # submitting so the estimate is deterministic (wall-measured timings
    # vary per host): the per-request service term (chunks + segments,
    # <= 3 x EMA each here) must stay well under slo_s for the batch to be
    # accepted, then a larger pinned EMA below drives the backlog shed.
    rt.seg_ema = 0.01
    reqs = [_mk(i) for i in range(1, len(SPEC))]
    rt.submit(reqs, now=0.0)
    rt.step(0.0)
    assert rt.engine.admission_depth() + rt.engine.slots_in_use() > 0
    rt.seg_ema = 0.2
    assert rt.decode_backlog_s() > 0.5
    late = Request(rid=6990, arrival=0.0, length=20.0, max_new_tokens=4)
    assert rt.submit([late], now=0.0) == 0
    assert rt.stats["shed_slo"] == 1 and late in rt.shed
    # accepted survivors still complete bit-identically
    rt.seg_ema = None  # stop shedding; drain
    done = rt.run_until_idle()
    assert {r.rid for r in done} == {r.rid for r in reqs}
    _check(done, ref)


def test_front_door_validation_rejects_before_enqueue(setup):
    cfg, ref = setup
    rt = build_pipelined_runtime(cfg, ec=_ec())
    with pytest.raises(ValueError, match="max_prompt_len"):
        rt.submit([Request(rid=1, arrival=0.0, length=40.0)], now=0.0)
    bad = Request(rid=2, arrival=0.0, length=9.0,
                  prompt=np.arange(5, dtype=np.int32))
    with pytest.raises(ValueError, match="prompt carries"):
        rt.submit([bad], now=0.0)
    assert not rt.busy()                  # nothing half-enqueued


def test_clock_mismatch_rejected(setup):
    cfg, ref = setup
    svc = DpuService(DpuServiceConfig(clock="wall"))
    with pytest.raises(ValueError, match="clock mismatch"):
        build_pipelined_runtime(cfg, ec=_ec(), service=svc,
                                rc=RuntimeConfig(clock="virtual"))
    svc.close()


# ---------------------------------------------------------------------------
# Compile-once + telemetry
# ---------------------------------------------------------------------------


def test_runtime_preserves_compile_once(setup):
    """Three waves through the pipelined runtime trace exactly TWO programs
    (one admit bucket + one segment) — decoupling preprocessing must not
    perturb the engine's executable cache."""
    cfg, ref = setup
    svc = DpuService(DpuServiceConfig(clock="virtual"))
    rt = build_pipelined_runtime(cfg, ec=_ec(), service=svc)
    for wave in range(3):
        rt.submit([_mk(i, audio=8000 if wave else None)
                   for i in range(len(SPEC))], now=float(wave))
        rt.run_until_idle()
    eng = rt.engine
    assert eng.stats["prefill_traces"] == 1
    assert eng.stats["segment_traces"] == 1
    assert eng.stats["generate_traces"] == 0
    assert len(rt.completed) == 3 * len(SPEC)


def test_stage_telemetry_shapes(setup):
    cfg, ref = setup
    svc = DpuService(DpuServiceConfig(clock="virtual"))
    rt = build_pipelined_runtime(cfg, ec=_ec(), service=svc)
    rt.submit([_mk(i, audio=16000) for i in range(6)], now=0.0)
    rt.run_until_idle()
    depths = rt.stage_summary()
    assert set(depths) == {"ingest", "preprocess", "ready", "admission",
                           "slots"}
    for st in depths.values():
        assert st["max"] >= st["mean"] >= 0.0
    # the DPU's busy time is its dpu_exec spans: one per launched group,
    # covering the six requests once each
    launches = rt.tracer.of(tm.DPU_EXEC)
    assert launches and all(e.dur >= 0.0 for e in launches)
    assert sorted(r for e in launches for r in e.extra["rids"]) \
        == [6000 + i for i in range(6)]
