"""Online partition controller proofs (ISSUE 10): the closed resize() loop.

Three layers, mirroring the module split:

* Pure decision-logic tests drive `PartitionController` against a fake
  runtime — the hysteresis (cooldown, improvement threshold, switch
  budget), the knee cost model's direction (fine for a burst of short
  requests, coarse for a long-prompt mix), the drain-cost gate, the
  per-tenant re-apportionment, and the byte-determinism of the decision
  log, all without compiling a model.
* Real-engine integration: the controller bound to a PipelinedRuntime over
  a MultiSliceEngine actually fires mid-replay, its decisions are
  byte-identical across two same-seed virtual replays, and every switch is
  observable (`fleet_reconfigs_total` + `reconfig` spans).
* The resize() regression the tentpole depends on: an elastic re-slice
  mid-trace with LIVE prefix-store leases AND multi-tenant slot quotas —
  exactly-once requeue, every lease released, per-tenant conservation,
  bit-identical survivor payloads — plus the warm partition cache
  (switching back restores the drained generation without recompiling)
  and the phase-shifting trace generator both benches and these tests
  share.
"""
from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs import reduced
from repro.core.batching.buckets import Request
from repro.core.batching.knee import KneeProfile
from repro.core.control import ControllerConfig, PartitionController
from repro.core.metrics import MetricsRegistry
from repro.models import api
from repro.serving.engine import EngineConfig
from repro.serving.faults import replay_virtual
from repro.serving.multislice import TenantSpec, build_multislice_engine
from repro.serving.requests import Phase, WorkloadSpec, generate_requests
from repro.serving.runtime import PipelinedRuntime, RuntimeConfig
from repro.serving.telemetry import Tracer

# ---------------------------------------------------------------------------
# Decision logic against a fake runtime (no model, no compile)
# ---------------------------------------------------------------------------

_PROFILE = KneeProfile(batch_sizes=(1, 2, 4, 8),
                       latencies=(0.010, 0.011, 0.012, 0.020),
                       batch_knee=4, time_knee=0.012)


class _FakeEngine:
    """Duck-typed stand-in for MultiSliceEngine: exactly the surface the
    controller reads (pod width, inflight, backlog, ec geometry, knee
    profiles, tenants) plus a resize() that records its calls."""

    def __init__(self, n, *, tenants=None, inflight=0, backlog=0):
        self.ec = SimpleNamespace(
            bucket_width=64.0, max_new_tokens=8, segment_len=4,
            max_slots=4, chunk_lens=(16,), prefix_cache_bytes=1 << 20,
        )
        self.pod = SimpleNamespace(slices=list(range(n)))
        self._chunked = True
        self._knee_profiles = {b: _PROFILE for b in range(9)}
        self._tenants = tenants or {}
        self._inflight = {i: object() for i in range(inflight)}
        self._backlog = backlog
        self.hedges = 0
        self.resize_calls = []

    def admission_depth(self):
        return self._backlog

    def resize(self, n_slices, now=0.0):
        self.resize_calls.append((n_slices, now))
        self.pod = SimpleNamespace(slices=list(range(n_slices)))
        return len(self._inflight)


def _fake_rt(eng):
    return SimpleNamespace(
        engine=eng,
        stats={"shed_slo": 0, "shed_backpressure": 0, "shed_error": 0,
               "shed_malformed": 0, "dead": 0},
        registry=MetricsRegistry(),
        tracer=Tracer(),
    )


def _cc(**kw):
    base = dict(menu=(1, 2, 4), eval_interval_s=0.01, window_s=0.5,
                cooldown_s=0.1, improve_frac=0.15, amortize_horizon_s=1.0,
                max_reconfigs=6, min_observations=4, slo_target_s=0.05)
    base.update(kw)
    return ControllerConfig(**base)


def _feed(ctl, now, n, length, model=None):
    for _ in range(n):
        ctl.observe(SimpleNamespace(length=length, model=model), now)


def test_menu_must_be_ascending_unique():
    with pytest.raises(ValueError):
        PartitionController(ControllerConfig(menu=(4, 2, 1)))
    with pytest.raises(ValueError):
        PartitionController(ControllerConfig(menu=(1, 2, 2)))


def test_bind_rejects_non_resizable_engine_and_starved_menu():
    ctl = PartitionController(_cc())
    with pytest.raises(ValueError):
        ctl.bind(SimpleNamespace(engine=object()))   # no resize()
    # every menu point smaller than the tenant count: nowhere to host them
    eng = _FakeEngine(2, tenants={"a": object(), "b": object(),
                                  "c": object()})
    ctl2 = PartitionController(_cc(menu=(1, 2)))
    with pytest.raises(ValueError):
        ctl2.bind(_fake_rt(eng))


def test_burst_goes_fine_then_heavy_goes_coarse():
    """The cost model's direction: a backlog of short requests scores the
    fine menu point up (slot capacity, fewer queueing waves); a long-prompt
    mix with a prefix cache scores the coarse point up (one consolidated
    store; chunked-prefill work shrinks)."""
    eng = _FakeEngine(1, inflight=4, backlog=12)
    ctl = PartitionController(_cc())
    ctl.bind(_fake_rt(eng))
    _feed(ctl, 0.10, 12, 8.0)                     # short-request burst
    dec = ctl.maybe_reconfigure(0.10)
    assert dec is not None and dec.to_slices == 4
    assert dec.reason == "burst_fine"
    assert dec.requeued == 4 and eng.resize_calls == [(4, 0.10)]

    # ... burst drains, the mix turns long-prompt
    eng._inflight = {0: object()}
    eng._backlog = 3
    ctl._arrivals.clear()
    _feed(ctl, 0.30, 6, 480.0)                    # heavy mix
    dec2 = ctl.maybe_reconfigure(0.30)
    assert dec2 is not None and dec2.to_slices == 1
    assert dec2.reason == "heavy_coarse"


def test_cooldown_and_eval_interval_gate_thrash():
    eng = _FakeEngine(1, inflight=2, backlog=12)
    ctl = PartitionController(_cc(cooldown_s=0.2))
    ctl.bind(_fake_rt(eng))
    _feed(ctl, 0.10, 12, 8.0)
    assert ctl.maybe_reconfigure(0.10) is not None
    # same compelling signals, inside the cooldown: nothing fires
    eng.pod = SimpleNamespace(slices=[0])         # pretend it's coarse again
    _feed(ctl, 0.15, 12, 8.0)
    assert ctl.maybe_reconfigure(0.15) is None
    assert ctl.maybe_reconfigure(0.25) is None    # still < 0.10 + 0.2
    assert ctl.maybe_reconfigure(0.31) is not None  # cooldown expired
    # and between evals the controller doesn't even look
    assert ctl.maybe_reconfigure(0.311) is None


def test_switch_budget_exhausts_and_next_wakeup_goes_quiet():
    eng = _FakeEngine(1, inflight=1, backlog=12)
    ctl = PartitionController(_cc(max_reconfigs=1, cooldown_s=0.0))
    ctl.bind(_fake_rt(eng))
    _feed(ctl, 0.10, 12, 8.0)
    assert ctl.next_wakeup() is not None
    assert ctl.maybe_reconfigure(0.10) is not None
    eng.pod = SimpleNamespace(slices=[0])
    _feed(ctl, 0.30, 12, 8.0)
    assert ctl.maybe_reconfigure(0.30) is None    # budget spent
    assert ctl.next_wakeup() is None              # stops self-waking too


def test_improvement_threshold_and_min_observations():
    eng = _FakeEngine(1, inflight=1, backlog=12)
    ctl = PartitionController(_cc(improve_frac=1e9))
    ctl.bind(_fake_rt(eng))
    _feed(ctl, 0.10, 12, 8.0)
    assert ctl.maybe_reconfigure(0.10) is None    # gain can't clear bar
    ctl2 = PartitionController(_cc(min_observations=50))
    ctl2.bind(_fake_rt(_FakeEngine(1, inflight=1, backlog=12)))
    _feed(ctl2, 0.10, 12, 8.0)
    assert ctl2.maybe_reconfigure(0.10) is None   # too few observations


def test_drain_cost_gate_blocks_expensive_switch():
    """A fleet deep in flight pays resize() with redone work; when the
    predicted gain can't amortize that inside the horizon, hold."""
    eng = _FakeEngine(1, inflight=400, backlog=12)
    ctl = PartitionController(_cc(amortize_horizon_s=1e-4))
    ctl.bind(_fake_rt(eng))
    _feed(ctl, 0.10, 12, 8.0)
    assert ctl.maybe_reconfigure(0.10) is None
    assert eng.resize_calls == []


def test_idle_fleet_never_reconfigures():
    eng = _FakeEngine(1, inflight=0, backlog=0)   # demand == 0
    ctl = PartitionController(_cc())
    ctl.bind(_fake_rt(eng))
    _feed(ctl, 0.10, 12, 8.0)
    assert ctl.maybe_reconfigure(0.10) is None


def test_apportionment_follows_windowed_arrival_share():
    """Multi-tenant switch re-divides the new slice count by windowed
    arrival share (largest remainder, >= 1 each) and writes the asks the
    next _build reads."""
    tenants = {"a": SimpleNamespace(n_slices_ask=1),
               "b": SimpleNamespace(n_slices_ask=1)}
    eng = _FakeEngine(2, tenants=tenants, inflight=2, backlog=12)
    ctl = PartitionController(_cc(menu=(2, 4)))
    ctl.bind(_fake_rt(eng))
    _feed(ctl, 0.10, 9, 8.0, model="a")           # a takes the burst
    _feed(ctl, 0.10, 3, 8.0, model="b")
    dec = ctl.maybe_reconfigure(0.10)
    assert dec is not None and dec.to_slices == 4
    assert dict(dec.apportion) == {"a": 3, "b": 1}
    assert tenants["a"].n_slices_ask == 3 and tenants["b"].n_slices_ask == 1


def test_switch_is_observable_and_log_is_deterministic():
    def run():
        eng = _FakeEngine(1, inflight=2, backlog=12)
        ctl = PartitionController(_cc(cooldown_s=0.05))
        rt = _fake_rt(eng)
        ctl.bind(rt)
        _feed(ctl, 0.10, 12, 8.0)
        ctl.maybe_reconfigure(0.10)
        eng._inflight, eng._backlog = {0: object()}, 2
        ctl._arrivals.clear()
        _feed(ctl, 0.30, 6, 480.0)
        ctl.maybe_reconfigure(0.30)
        return rt, ctl

    rt1, c1 = run()
    rt2, c2 = run()
    assert len(c1.decisions) == 2
    assert c1.decisions_json() == c2.decisions_json()
    # labeled counter sums across {from,to,reason} rows
    assert rt1.registry.value("fleet_reconfigs_total") == 2
    assert len(rt1.tracer.of("reconfig")) == 2
    ev = rt1.tracer.of("reconfig")[0]
    assert ev.extra["reason"] == "burst_fine"
    # reset() clears the log for a measured replay
    c1.reset()
    assert c1.decisions == [] and c1.decisions_json() == "[]"


# ---------------------------------------------------------------------------
# Real engine: resize() regression + warm cache + closed loop
# ---------------------------------------------------------------------------

TA, TB = "ta", "tb"


@pytest.fixture(scope="module")
def model():
    cfg = reduced("tinyllama-1.1b")
    params = api.init_params(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype)
    return cfg, params


def _prefix_ec():
    # chunked prefill + prefix store + tight slot quota: the geometry the
    # resize regression must survive
    return EngineConfig(max_new_tokens=8, continuous=True, max_slots=2,
                        segment_len=4, max_prompt_len=64, chunk_lens=(16,),
                        prefix_cache_bytes=64 << 20)


def _template_reqs(cfg, name, base, k=5):
    """k requests per tenant sharing a 48-token template prefix (distinct
    per tenant) with unique 8-token tails: prefix-store hits + leases."""
    rng = np.random.default_rng(base)
    template = rng.integers(1, cfg.vocab, size=48, dtype=np.int32)
    out = []
    for i in range(k):
        tail = rng.integers(1, cfg.vocab, size=8, dtype=np.int32)
        prompt = np.concatenate([template, tail])
        out.append(Request(rid=base + i, arrival=0.0,
                           length=float(len(prompt)), prompt=prompt,
                           max_new_tokens=4 + (i % 3), model=name))
    return out


def _two_tenant_fleet(cfg, params):
    return build_multislice_engine(
        n_slices=2, ec=_prefix_ec(),
        tenants=[TenantSpec(cfg=cfg, name=TA, n_slices=1, params=params),
                 TenantSpec(cfg=cfg, name=TB, n_slices=1, params=params)])


def test_resize_with_live_prefix_leases_and_tenant_quotas(model):
    """The tentpole's enabling regression: resize() mid-trace while the
    prefix store holds LIVE leases and both tenants have backlogged work
    behind 2-slot quotas. Every request completes exactly once with
    bit-identical payloads, every lease is released, and conservation
    holds per tenant."""
    cfg, params = model
    # K per tenant: after the resize a tenant's K-1 hit-wave requests
    # outnumber its 4 post-resize slots even when the wave's first hit
    # group already retired before the loop below saw it (a loaded host),
    # so the new generation must queue one behind a retirement and take a
    # hit from its fresh store
    K = 8
    reqs = lambda: _template_reqs(cfg, TA, 9300, K) \
        + _template_reqs(cfg, TB, 9400, K)

    # undisturbed reference run on the same geometry
    ref_ms = _two_tenant_fleet(cfg, params)
    ref_ms.submit_many(reqs())
    ref = {r.rid: np.asarray(r.payload) for r in ref_ms.run_until_idle()}
    assert len(ref) == 2 * K

    ms = _two_tenant_fleet(cfg, params)
    batch = reqs()
    warm, rest = [batch[0], batch[K]], batch[1:K] + batch[K + 1:]
    ms.submit_many(warm)                          # retire -> insert templates
    assert len(ms.run_until_idle()) == 2
    assert ms.prefix_stats()["prefix_inserts"] >= 2
    ms.submit_many(rest)                          # the hit wave
    # the builder-derived policy holds a batch-formation window; step until
    # a prefix-hit admission is genuinely mid-flight, holding a live lease
    for _ in range(10_000):
        ms.step()
        if ms._inflight and \
                sum(e.prefix_lease_count() for e in ms.engines.values()):
            break
    assert ms._inflight                           # genuinely mid-trace
    assert ms.slot_scheduler.backlog() >= 1       # quota'd work waiting
    leases = sum(e.prefix_lease_count() for e in ms.engines.values())
    assert leases >= 1                            # live template leases
    requeued = ms.resize(n_slices=4)
    assert requeued >= 1                          # exactly-once carry-over
    assert len(ms.engines) == 4
    done = list(ms.run_until_idle())              # cumulative: warm + rest
    assert len(done) == 2 * K and len({r.rid for r in done}) == 2 * K
    for r in done:
        np.testing.assert_array_equal(np.asarray(r.payload), ref[r.rid])
    # per-tenant conservation: each tenant's K all land on its own slices
    by = {TA: 0, TB: 0}
    for r in done:
        by[r.model] += 1
    assert by == {TA: K, TB: K}
    # every lease released once the fleet drains — old AND new generations
    assert all(e.prefix_lease_count() == 0 for e in ms.engines.values())
    assert ms.prefix_stats()["prefix_hits"] >= 1  # the store really engaged


def test_warm_partition_cache_restores_drained_generation(model):
    """Switching away stashes the drained generation (engines + prefix
    stores); switching back restores the very same engine objects with no
    recompiles — the mechanism that keeps the controller's switch-back
    cheap."""
    cfg, params = model
    ec = _prefix_ec()
    ms = build_multislice_engine(cfg, n_slices=1, ec=ec, params=params)
    ms.submit_many(_template_reqs(cfg, None, 9500))
    assert len(ms.run_until_idle()) == 5
    gen0 = list(ms.engines.values())
    traces0 = dict(ms.trace_counts())
    ms.resize(n_slices=2)                         # drained -> cached
    ms.resize(n_slices=1)                         # ... and restored
    assert [e is g for e, g in zip(ms.engines.values(), gen0)] == [True]
    ms.submit_many(_template_reqs(cfg, None, 9600))
    done = ms.run_until_idle()                    # cumulative across waves
    assert len(done) == 10 and len({r.rid for r in done}) == 10
    assert ms.trace_counts() == traces0           # no recompiles anywhere


def test_controller_closes_loop_on_real_fleet_deterministically(model):
    """End to end on the real engine: a short-request backlog makes the
    bound controller fire resize() mid-virtual-replay; two same-seed
    replays produce byte-identical decision logs; every switch shows up in
    the metrics registry and the trace timeline; nothing is lost."""
    cfg, params = model
    ec = EngineConfig(max_new_tokens=4, continuous=True, max_slots=4,
                      segment_len=4, max_prompt_len=32)

    def run():
        ms = build_multislice_engine(cfg, n_slices=1, ec=ec, params=params)
        ms.fixed_expected_s = 1.0                 # no wall-EMA hedging
        ctl = PartitionController(ControllerConfig(
            menu=(1, 2), eval_interval_s=0.004, window_s=0.05,
            cooldown_s=0.05, improve_frac=0.2, amortize_horizon_s=0.5,
            max_reconfigs=2, min_observations=2, slo_target_s=0.02))
        rt = PipelinedRuntime(ms, None, RuntimeConfig(clock="virtual"),
                              controller=ctl)
        # a tight burst: 16 arrivals inside ~2 ticks against a 4-slot
        # slice, so admission backlog really accumulates at eval time
        reqs = [Request(rid=9700 + i, arrival=0.01 + 0.0002 * i,
                        length=17.0 + (i % 4), max_new_tokens=4)
                for i in range(16)]
        done = replay_virtual(rt, reqs, tick=2e-3)
        return rt, ctl, done

    rt1, c1, done1 = run()
    rt2, c2, done2 = run()
    assert len(c1.decisions) >= 1
    assert c1.decisions[0].reason == "burst_fine"
    assert c1.decisions_json() == c2.decisions_json()
    assert len(done1) == 16 and len({r.rid for r in done1}) == 16
    assert rt1.conservation_ok()
    assert rt1.registry.value("fleet_reconfigs_total") == len(c1.decisions)
    assert len(rt1.tracer.of("reconfig")) == len(c1.decisions)
    # payload bit-identity across the two replays, switch and all
    p1 = {r.rid: np.asarray(r.payload) for r in done1}
    for r in done2:
        np.testing.assert_array_equal(np.asarray(r.payload), p1[r.rid])


# ---------------------------------------------------------------------------
# Knee calibration (the profile source `serve.py --calibrate-knee` writes)
# ---------------------------------------------------------------------------

def test_calibrate_knees_finds_per_bucket_knee_and_json_round_trips():
    from repro.core.batching.knee import (
        calibrate_knees, profiles_from_json, profiles_to_json,
    )

    def measure(batch, context_len):
        # synthetic device: throughput doubles per batch doubling until a
        # context-dependent saturation batch, then latency scales linearly
        sat = 8 if context_len < 96 else 4
        return 0.010 * max(1.0, batch / sat) * (1 + context_len / 1000)

    profiles = calibrate_knees(measure, buckets=(0, 1, 2), bucket_width=64,
                               max_batch=32)
    assert sorted(profiles) == [0, 1, 2]
    assert profiles[0].batch_knee == 8      # context 32: saturates at 8
    assert profiles[2].batch_knee == 4      # context 160: memory-bound sooner
    for p in profiles.values():
        assert p.time_knee == pytest.approx(
            p.latencies[p.batch_sizes.index(p.batch_knee)])
        assert list(p.batch_sizes) == sorted(p.batch_sizes)
    # the calibration artifact round-trips exactly through JSON
    text = profiles_to_json(profiles)
    back = profiles_from_json(text)
    assert back == profiles
    assert profiles_to_json(back) == text


# ---------------------------------------------------------------------------
# Phase-shifting trace generator (shared by bench part 9 and these tests)
# ---------------------------------------------------------------------------

def test_phased_generator_follows_schedule():
    spec = WorkloadSpec(modality="text", rate_qps=50.0, mean_len=200.0,
                        sigma=0.05, max_len=255.0, vocab=128, seed=3,
                        phases=(Phase(0.5, 4.0, mean_len=200.0),
                                Phase(0.25, 400.0, mean_len=12.0,
                                      sigma=0.1, max_len=31.0)))
    reqs = generate_requests(spec, 60)
    assert [r.rid for r in reqs] == list(range(60))
    assert all(reqs[i].arrival <= reqs[i + 1].arrival for i in range(59))
    early = [r for r in reqs if r.arrival < 0.5]
    late = [r for r in reqs if r.arrival >= 0.5]
    # ~2 arrivals in the 4 qps phase vs dozens in the 400 qps phase
    assert len(early) <= 6 and len(late) >= 40
    assert np.mean([r.length for r in early]) > \
        4 * np.mean([r.length for r in late])
    for r in reqs:                                # real tokens ride along
        assert len(np.asarray(r.prompt)) == int(r.length)


def test_phased_generator_is_deterministic_and_legacy_path_unchanged():
    phased = WorkloadSpec(modality="text", rate_qps=50.0, mean_len=64.0,
                          sigma=0.2, max_len=127.0, vocab=64, seed=9,
                          phases=(Phase(0.1, 30.0), Phase(0.1, 300.0)))
    a, b = generate_requests(phased, 40), generate_requests(phased, 40)
    for ra, rb in zip(a, b):
        assert (ra.arrival, ra.length) == (rb.arrival, rb.length)
        np.testing.assert_array_equal(np.asarray(ra.prompt),
                                      np.asarray(rb.prompt))
    # phases=None keeps the PR 4 single-stream contract byte-for-byte
    legacy = WorkloadSpec(modality="text", rate_qps=50.0, mean_len=64.0,
                          sigma=0.2, max_len=127.0, vocab=64, seed=9)
    c, d = generate_requests(legacy, 40), generate_requests(legacy, 40)
    assert [(r.arrival, r.length) for r in c] == \
        [(r.arrival, r.length) for r in d]
    # last phase is open-ended: arrivals keep coming past the schedule
    tail = generate_requests(phased, 200)
    assert tail[-1].arrival > 0.2
