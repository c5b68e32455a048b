"""MultiSliceEngine proof tests: the paper's system shape (one continuous-
batching engine per MIG-analogue slice behind a shared admission queue) on
real reduced-model execution.

The invariants proved here are the multi-slice analogues of the PR 1/2
hot-path proofs: per-request outputs are bit-identical to a single-slice
engine no matter how batches are routed, a hedged batch completes exactly
once (first slice to finish wins, the twin is cancelled mid-flight), and an
elastic resize() mid-trace loses no requests.
"""
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs import reduced
from repro.core.batching.buckets import Request
from repro.core.batching.policy import BatchPolicy
from repro.core.slicing.mig import PlacementAsk, plan_placement, rebalance_slices
from repro.models import api
from repro.serving.engine import EngineConfig, build_engine
from repro.serving.multislice import (
    MultiSliceEngine, TenantSpec, build_multislice_engine,
)

# canonical request set: every test serves (a prefix of) these; prompts are
# deterministic per rid, so payloads depend only on (rid, length, budget)
LENS = [17.0 + i for i in range(9)]          # one (.., 32) prompt bucket
BUDGETS = [3, 5, 8, 2, 7, 4, 6, 1, 8]


def _ec():
    return EngineConfig(max_new_tokens=8, continuous=True, max_slots=4,
                        segment_len=4, max_prompt_len=32)


def _fresh(k=9):
    return _pick(range(k))


def _pick(idxs):
    return [
        Request(rid=7000 + i, arrival=0.0, length=LENS[i],
                max_new_tokens=BUDGETS[i])
        for i in idxs
    ]


def _policy(n_slices):
    # immediate flush: formation timing is not under test here
    return BatchPolicy(batch_max={0: 4}, time_queue=0.0, time_knee=0.1,
                       n_slices=n_slices, bucket_width=64.0)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced("tinyllama-1.1b")
    params = api.init_params(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype)
    # reference payloads from the single-slice continuous engine (same seed)
    single = build_engine(cfg, ec=_ec())
    single.submit_many(_fresh())
    single.run_until_idle()
    ref = {r.rid: np.asarray(r.payload) for r in single.completed}
    assert len(ref) == 9
    return cfg, params, ref


def _check_done(done, ref, k):
    assert len(done) == k
    assert len({r.rid for r in done}) == k  # exactly once each
    for r in done:
        np.testing.assert_array_equal(np.asarray(r.payload), ref[r.rid])
        # the engine's retire timestamps survive scheduler bookkeeping
        # (sched.complete must not clobber completed_at with step-start
        # time, which would run backwards for budget-1 requests)
        assert r.completed_at >= r.dispatched_at > 0.0


def test_outputs_bit_identical_to_single_slice_engine(setup):
    cfg, params, ref = setup
    ms = MultiSliceEngine(cfg, params, _policy(2), _ec(), n_slices=2)
    ms.submit_many(_fresh())
    done = ms.run_until_idle()
    _check_done(done, ref, 9)
    # the work really spread across slices (least-loaded request streaming),
    # each with its own slot pool
    st = ms.slice_stats()
    assert sum(1 for v in st.values() if v["admitted"] > 0) == 2
    assert all(0.0 < v["mean_slot_occupancy"] <= 1.0 for v in st.values())


def test_stream_joins_busy_slice_mid_flight(setup):
    """The request -> slot refactor's core behaviour: a later admission
    group joins a BUSY slice's pool mid-flight instead of queueing behind
    the resident work (the old batch-granularity dispatcher reserved a
    slice for one formed batch at a time)."""
    cfg, params, ref = setup
    ms = MultiSliceEngine(cfg, params, _policy(1), _ec(), n_slices=1)
    ms.submit_many(_pick([2, 8]))        # budget-8 residents: several segments
    ms.step()
    e = ms.engines[0]
    assert e.slots_in_use() == 2 and e.stats["retired"] == 0  # mid-flight
    ms.submit_many(_pick([0, 4]))        # arrive while the slice is busy
    ms.step()
    # joined the same busy slice's pool without waiting for it to drain:
    # admission ran before this step's segment, while both residents (0
    # retired above) still occupied their slots
    assert e.stats["admitted"] == 4
    done = ms.run_until_idle()
    _check_done(done, ref, 4)


def test_hedged_request_completes_exactly_once_twin_wins(setup):
    """A stalled slice (hung device) is detected as a straggler; each of
    its REQUESTS is cloned onto a healthy twin's free slot, the twin's
    completion wins, and the stalled engine's copies are cancelled
    mid-flight — every request exactly once."""
    cfg, params, ref = setup
    ms = MultiSliceEngine(cfg, params, _policy(2), _ec(), n_slices=2,
                          hedge_factor=1.5)
    ms.fixed_expected_s = 1e-4   # deterministic straggler detection
    ms.submit_many(_fresh(2))
    # dispatch only (no _advance): streaming hands requests straight to
    # slice admission, so a full ms.step() could admit, decode and retire
    # these small requests in one iteration — the stall must be injected
    # before the slice engine ever advances
    ms._dispatch(time.monotonic())
    assert len(ms._inflight) == 2
    sid = next(iter(next(iter(ms._inflight.values())).copies))
    ms.stalled_slices.add(sid)   # that slice never advances again
    done = ms.run_until_idle()
    _check_done(done, ref, 2)
    assert ms.hedges >= 1
    assert ms.stats["hedge_wins"] >= 1
    assert ms.stats["cancelled"] >= 1       # stalled copies were killed
    assert ms._inflight == {}


def test_hedge_original_wins_and_clone_is_cancelled(setup):
    """A TRANSIENT stall: the slice hangs after several segments, the
    hedge fires, the device recovers — the original (segments ahead of the
    freshly-admitted clone) finishes first, the clone is cancelled
    mid-flight, and nothing completes twice."""
    cfg, params, ref = setup
    # segment_len=2: the budget-8 request spans 4 segments
    ec = EngineConfig(max_new_tokens=8, continuous=True, max_slots=4,
                      segment_len=2, max_prompt_len=32)
    ms = MultiSliceEngine(cfg, params, _policy(2), ec, n_slices=2,
                          hedge_factor=1.5)
    ms.fixed_expected_s = 1e-4
    ms.submit_many(_pick([2]))       # budget 8
    ms.step()                        # admit + first segment
    (rid,) = list(ms._inflight)
    (sid,) = ms._inflight[rid].copies
    ms.step()                        # another segment: original is ahead
    ms.stalled_slices.add(sid)       # transient hang
    t0 = time.monotonic()
    while ms.hedges == 0 and time.monotonic() - t0 < 30:
        ms.step()                    # no progress on sid -> straggler
    assert ms.hedges == 1
    ms.stalled_slices.discard(sid)   # device recovers, segments ahead
    done = ms.run_until_idle()
    _check_done(done, ref, 1)
    assert ms.stats["hedge_wins"] == 0      # the original won
    assert ms.stats["cancelled"] >= 1       # the clone was killed mid-flight
    for e in ms.engines.values():
        assert not e.busy()


def test_healthy_loaded_slices_never_hedge(setup):
    """Progress-gated straggler detection: slices that keep advancing are
    never hedged, however small the expected time and however saturated
    the pools — elapsed-only detection would clone most of this workload
    (each streamed resident's wall time stretches with load)."""
    cfg, params, ref = setup
    ms = MultiSliceEngine(cfg, params, _policy(2), _ec(), n_slices=2,
                          hedge_factor=0.5)
    ms.fixed_expected_s = 1e-6       # absurdly tight budget
    ms.submit_many(_fresh())         # 9 requests > 8 slots: saturated
    done = ms.run_until_idle()
    _check_done(done, ref, 9)
    assert ms.hedges == 0
    assert ms.stats["cancelled"] == 0


def test_resize_mid_trace_loses_no_requests(setup):
    """Elastic re-slice to a different menu entry mid-trace: every in-flight
    request is requeued (exactly once, by rid), the shared admission
    backlog survives the scheduler rebuild, engines are rebuilt, and every
    request completes with the same tokens as an undisturbed run."""
    cfg, params, ref = setup
    # 9 requests > 2 slices x 4 slots: some stay in the shared admission
    # backlog at resize time, which a rebuild must not lose
    ms = MultiSliceEngine(cfg, params, _policy(2), _ec(), n_slices=2)
    ms.submit_many(_fresh())
    ms.step()                                # stream + first segments
    assert ms._inflight                      # genuinely mid-trace
    assert ms.slot_scheduler.backlog() >= 1  # over-capacity work waiting
    requeued = ms.resize(n_slices=3)
    assert requeued >= 1
    assert ms.slot_scheduler.backlog() >= 1  # backlog carried across rebuild
    assert len(ms.engines) == 3 and ms.pod.spec.n_slices == 3
    done = ms.run_until_idle()
    _check_done(done, ref, 9)
    assert ms.stats["resizes"] == 1


def test_resize_by_menu_entry_on_partitioned_devices(setup, monkeypatch):
    """With enough (fake) devices the pod really partitions: resize by
    chips_per_slice walks the partition menu. Integer stand-ins cannot hold
    arrays, so here every slice shares the one param tree (placement on
    real devices: test_slices_place_params_and_pool_on_own_devices)."""
    cfg, params, ref = setup
    monkeypatch.setattr(MultiSliceEngine, "_params_for",
                        lambda self, ps, p: p)
    ms = MultiSliceEngine(cfg, params, _policy(4), _ec(), n_slices=4,
                          devices=list(range(64)))
    assert not ms.replicated
    assert ms.pod.spec.name == "1s(4x)"      # 64 chips / 4 = 16-chip slices
    ms.submit_many(_fresh())
    ms.step()
    ms.resize(chips_per_slice=32)
    assert ms.pod.spec.name == "2s(2x)" and len(ms.engines) == 2
    done = ms.run_until_idle()
    _check_done(done, ref, 9)


def test_fail_slice_requeues_and_recovers(setup):
    cfg, params, ref = setup
    ms = MultiSliceEngine(cfg, params, _policy(2), _ec(), n_slices=2)
    ms.submit_many(_fresh(2))
    ms._dispatch(time.monotonic())  # streamed, not yet advanced (see above)
    assert ms._inflight
    sid = next(iter(next(iter(ms._inflight.values())).copies))
    assert ms.fail_slice(sid)                # sole holder -> requeued
    done = ms.run_until_idle()
    _check_done(done, ref, 2)
    assert not ms.sched.slices[sid].healthy
    ms.recover_slice(sid)
    assert ms.sched.slices[sid].healthy


def test_build_multislice_engine_compile_once_per_slice():
    """The builder mirrors build_engine (same seed/params); after warmup
    each slice engine traces exactly two programs (admit bucket + segment)
    and serving more requests retraces nothing."""
    cfg = reduced("tinyllama-1.1b")
    ec = _ec()
    ms = build_multislice_engine(cfg, n_slices=2, ec=ec)
    ms.submit_many(_fresh())
    ms.run_until_idle()
    counts = ms.trace_counts()
    assert all(c <= 2 for c in counts.values()), counts
    before = dict(counts)
    ms.submit_many([Request(rid=7100 + i, arrival=0.0, length=LENS[i],
                            max_new_tokens=BUDGETS[i]) for i in range(4)])
    ms.run_until_idle()
    assert ms.trace_counts() == before       # steady state: no retraces


# ---------------------------------------------------------------------------
# Multi-tenant fleet (ISSUE 8): slice-as-tenancy-unit
# ---------------------------------------------------------------------------

TENANT_A = "tinyllama-1.1b"
TENANT_B = "mamba2-370m"
T_LENS = [17.0, 19.0, 21.0, 23.0, 25.0, 18.0]
T_BUDGETS = [4, 6, 3, 8, 5, 7]
T_BASE = {TENANT_A: 8100, TENANT_B: 8200}


def _treqs(model, k=6, rid_off=0):
    """Fresh request objects per call: engines mutate Request fields, so a
    reference run and a fleet run must never share objects. `rid_off`
    namespaces a follow-up wave (rids must be unique per engine)."""
    return [
        Request(rid=T_BASE[model] + rid_off + i, arrival=0.0,
                length=T_LENS[i], max_new_tokens=T_BUDGETS[i], model=model)
        for i in range(k)
    ]


@pytest.fixture(scope="module")
def two_tenant():
    """Two heterogeneous tenants (attention + SSM) with per-model
    single-slice reference outputs (same seed-0 params the fleet serves)."""
    out = {}
    for name in (TENANT_A, TENANT_B):
        cfg = reduced(name)
        params = api.init_params(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype)
        single = build_engine(cfg, ec=_ec())
        single.params = params
        single.submit_many(_treqs(name))
        single.run_until_idle()
        ref = {r.rid: np.asarray(r.payload) for r in single.completed}
        assert len(ref) == len(T_LENS)
        out[name] = (cfg, params, ref)
    return out


def _fleet(two_tenant, *, na=2, nb=2, **kw):
    cfg_a, params_a, _ = two_tenant[TENANT_A]
    cfg_b, params_b, _ = two_tenant[TENANT_B]
    return build_multislice_engine(
        n_slices=na + nb, ec=_ec(),
        tenants=[TenantSpec(cfg=cfg_a, name=TENANT_A, n_slices=na,
                            params=params_a),
                 TenantSpec(cfg=cfg_b, name=TENANT_B, n_slices=nb,
                            params=params_b)],
        **kw,
    )


def _check_tenant_done(done, two_tenant, k_each):
    assert len(done) == 2 * k_each
    assert len({r.rid for r in done}) == 2 * k_each
    for r in done:
        ref = two_tenant[r.model][2]
        np.testing.assert_array_equal(np.asarray(r.payload), ref[r.rid])


def test_two_tenant_fleet_bit_identical_per_tenant(two_tenant):
    """The tentpole's core proof: two models on disjoint slice sets behind
    ONE admission queue, a mixed trace completes with every tenant's
    outputs bit-identical to a single-slice engine of that model, and the
    routing audit shows no request ever touched a foreign slice."""
    ms = _fleet(two_tenant)
    ms.submit_many(_treqs(TENANT_A) + _treqs(TENANT_B))
    done = ms.run_until_idle()
    _check_tenant_done(done, two_tenant, len(T_LENS))
    # disjoint slice sets, each engine built for its OWNING tenant's model
    a, b = set(ms.slices_of(TENANT_A)), set(ms.slices_of(TENANT_B))
    assert a and b and not (a & b) and a | b == set(ms.engines)
    for sid, e in ms.engines.items():
        assert e.cfg is two_tenant[ms.slice_tenant[sid]][0]
    ts = ms.tenant_stats()
    for name in (TENANT_A, TENANT_B):
        assert ts[name]["completed"] == len(T_LENS)
        assert ts[name]["dead"] == 0
        assert set(ts[name]["routed_to"]) <= set(ms.slices_of(name))
    # both tenants' slices really served work (least-loaded streaming)
    assert all(e.stats["admitted"] > 0 for e in ms.engines.values())


def test_model_router_stamps_and_validates(two_tenant):
    """The front door: a multi-tenant fleet REQUIRES a model id and rejects
    unknown ones before any queue sees the request; a single-tenant fleet
    default-stamps its one model so tenancy invariants hold uniformly."""
    ms = _fleet(two_tenant, na=1, nb=1)
    with pytest.raises(ValueError, match="has no model"):
        ms.submit(Request(rid=8900, arrival=0.0, length=17.0,
                          max_new_tokens=2))
    with pytest.raises(ValueError, match="unknown model"):
        ms.submit(Request(rid=8901, arrival=0.0, length=17.0,
                          max_new_tokens=2, model="gpt-17"))
    assert ms.admission_depth() == 0          # rejected at the door
    cfg_a, params_a, _ = two_tenant[TENANT_A]
    single = MultiSliceEngine(cfg_a, params_a, _policy(2), _ec(), n_slices=2)
    r = Request(rid=8902, arrival=0.0, length=17.0, max_new_tokens=2)
    single.submit(r)
    assert r.model == cfg_a.name              # default-routed, stamped
    done = single.run_until_idle()
    assert [x.rid for x in done] == [r.rid]


def test_hedge_twin_never_crosses_tenant(two_tenant):
    """Straggler hedging is tenant-constrained: a stalled slice's requests
    clone onto the SAME tenant's healthy slice (never a foreign model's),
    complete exactly once, and stay bit-identical."""
    ms = _fleet(two_tenant, hedge_factor=1.5)
    ms.fixed_expected_s = 1e-4               # deterministic detection
    # offer(): backlog intake with no formation delay (tenant-derived
    # policies carry a real Time_queue, unlike the legacy tests' 0.0), so
    # the stall can be injected before any engine advances
    ms.offer(_treqs(TENANT_A, 2) + _treqs(TENANT_B, 2))
    ms._dispatch(time.monotonic())
    assert len(ms._inflight) == 4
    a_slices = set(ms.slices_of(TENANT_A))
    sid = next(s for tr in ms._inflight.values()
               for s in tr.copies if s in a_slices)
    ms.stalled_slices.add(sid)               # tenant A slice hangs
    done = ms.run_until_idle()
    _check_tenant_done(done, two_tenant, 2)
    assert ms.hedges >= 1
    assert ms.stats["cancelled"] >= 1        # stalled copies were killed
    ts = ms.tenant_stats()
    assert set(ts[TENANT_A]["routed_to"]) <= a_slices
    assert set(ts[TENANT_B]["routed_to"]) <= set(ms.slices_of(TENANT_B))


def test_fail_slice_requeues_within_tenant(two_tenant):
    """fail_slice victims redispatch onto the owning tenant's surviving
    slices only — a foreign tenant's idle capacity is never borrowed (its
    engines hold the wrong weights)."""
    ms = _fleet(two_tenant)
    ms.offer(_treqs(TENANT_B, 3))            # tenant B traffic only
    ms._dispatch(time.monotonic())
    assert ms._inflight
    b_slices = set(ms.slices_of(TENANT_B))
    sid = next(s for tr in ms._inflight.values()
               for s in tr.copies if s in b_slices)
    assert ms.fail_slice(sid)                # sole holders -> requeued
    done = ms.run_until_idle()
    assert len(done) == 3
    for r in done:
        np.testing.assert_array_equal(np.asarray(r.payload),
                                      two_tenant[TENANT_B][2][r.rid])
    assert set(ms.tenant_stats()[TENANT_B]["routed_to"]) <= b_slices
    # tenant A's idle slices never admitted tenant B's work
    for sid_a in ms.slices_of(TENANT_A):
        assert ms.engines[sid_a].stats["admitted"] == 0


def test_resize_rebalances_slices_between_tenants(two_tenant):
    """Elastic re-slice with tenants: the new slice count is re-divided
    between tenants (largest remainder, >=1 floor), engines rebuild with
    the RIGHT tenant's model, in-flight work requeues within its tenant,
    and shrinking below the tenant count is rejected up front."""
    ms = _fleet(two_tenant)
    ms.offer(_treqs(TENANT_A) + _treqs(TENANT_B))
    ms.step()
    assert ms._inflight                      # genuinely mid-trace
    with pytest.raises(ValueError):
        ms.resize(n_slices=1)                # 2 tenants need >= 2 slices
    ms.resize(n_slices=3)
    assert len(ms.engines) == 3
    counts = {n: len(ms.slices_of(n)) for n in (TENANT_A, TENANT_B)}
    assert sorted(counts.values()) == [1, 2]  # both kept >= 1
    for sid, e in ms.engines.items():
        assert e.cfg is two_tenant[ms.slice_tenant[sid]][0]
    done = ms.run_until_idle()
    _check_tenant_done(done, two_tenant, len(T_LENS))
    for name in (TENANT_A, TENANT_B):
        assert set(ms.tenant_stats()[name]["routed_to"]) <= \
            set(ms.slices_of(name))
    assert ms.stats["resizes"] == 1


def test_tenant_compile_isolation(two_tenant):
    """Each tenant's slices trace THEIR model's executables only: after a
    mixed trace every slice engine is at the single-tenant steady state
    (admit bucket + segment), and more traffic retraces nothing."""
    ms = _fleet(two_tenant, na=1, nb=1)
    ms.submit_many(_treqs(TENANT_A) + _treqs(TENANT_B))
    ms.run_until_idle()
    counts = ms.trace_counts()
    assert all(c <= 2 for c in counts.values()), counts
    before = dict(counts)
    ms.submit_many(_treqs(TENANT_A, 3, rid_off=50)
                   + _treqs(TENANT_B, 3, rid_off=50))
    done = ms.run_until_idle()               # cumulative across both waves
    assert len({r.rid for r in done}) == 2 * len(T_LENS) + 6
    assert ms.trace_counts() == before       # steady state per tenant


# --- placement / apportionment units (core/slicing/mig.py) -----------------


def test_rebalance_slices_apportionment():
    assert rebalance_slices(4, {"a": 2, "b": 2}) == {"a": 2, "b": 2}
    # largest remainder, deterministic name-order tie-break
    assert rebalance_slices(3, {"a": 2, "b": 2}) == {"a": 2, "b": 1}
    # proportional at scale
    assert rebalance_slices(16, {"a": 3, "b": 1}) == {"a": 12, "b": 4}
    # >=1 floor: a tiny pod never starves a tenant entirely
    assert rebalance_slices(2, {"a": 9, "b": 1}) == {"a": 1, "b": 1}
    with pytest.raises(ValueError):
        rebalance_slices(1, {"a": 1, "b": 1})


def test_plan_placement_fragmentation_accounting():
    p = plan_placement(256, [PlacementAsk("a", 2, 64),
                             PlacementAsk("b", 2, 16)])
    assert p.slice_counts() == {"a": 2, "b": 2}
    assert p.stranded_chips == 256 - (2 * 64 + 2 * 16)
    assert p.fragmentation == pytest.approx(96 / 256)
    # best-fit decreasing: the big ask packs first regardless of ask order
    q = plan_placement(96, [PlacementAsk("small", 1, 16),
                            PlacementAsk("big", 1, 64)])
    assert q.assignments["big"] == [(0, 64)]
    assert q.assignments["small"] == [(64, 16)]
    assert q.stranded_chips == 16
    with pytest.raises(ValueError):
        plan_placement(64, [PlacementAsk("a", 1, 128)])


_PLACEMENT_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.configs import reduced
from repro.core.batching.buckets import Request
from repro.serving.engine import EngineConfig, build_engine
from repro.serving.multislice import build_multislice_engine

def reqs():
    return [Request(rid=9000 + i, arrival=0.0, length=9.0 + 2 * i,
                    max_new_tokens=3 + i % 4) for i in range(12)]

def devs(tree):
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}

cfg = reduced("tinyllama-1.1b")
ec = EngineConfig(max_new_tokens=6, continuous=True, max_slots=4,
                  segment_len=3, max_prompt_len=32)
ms = build_multislice_engine(cfg, n_slices=4, ec=ec)
assert not ms.replicated
ms.submit_many(reqs())
got = {r.rid: np.asarray(r.payload) for r in ms.run_until_idle()}
seen = set()
for ps in ms.pod.slices:
    e = ms.engines[ps.slice_id]
    own = set(ps.devices.reshape(-1))
    assert len(own) == 1 and devs(e.params) == own, (ps.slice_id, devs(e.params))
    assert e.stats["admitted"] > 0, ps.slice_id
    assert devs(e._pool) == own, (ps.slice_id, devs(e._pool))
    seen |= own
assert len(seen) == 4, seen
one = build_engine(cfg, ec=ec)
one.submit_many(reqs())
want = {r.rid: np.asarray(r.payload) for r in one.run_until_idle()}
assert got.keys() == want.keys()
for rid in want:
    np.testing.assert_array_equal(got[rid], want[rid])
print("PLACEMENT_OK")
"""


def test_slices_place_params_and_pool_on_own_devices():
    """One slice per device (4 forced host devices, in a subprocess so the
    device count stays out of this process): each slice engine's params and
    slot pool live on its own device, and outputs are bit-identical to a
    one-slice engine. The CPU rehearsal of chip_smoke.py --four-chips."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_SCRIPT], env=env,
        capture_output=True, text=True, timeout=420,
    )
    assert "PLACEMENT_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]
