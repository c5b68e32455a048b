"""Fleet telemetry proofs (ISSUE 9 tentpole): one metrics registry +
lifecycle tracer behind every layer.

Host-side (no jax): streaming histogram accuracy/quantile bounds, the
registry's schema lint, persistent-counter reset semantics, StatsView's
dict facade, deterministic tracer serialization.

Engine-level (jax): (a) a completed request NEVER retires with
first_token_at=None on ANY admission path — monolithic, chunked, prefix
hit, and the zero-suffix-chunk scatter path (reachable only when the
radix match covers the whole prompt; forced here by lifting the n-1 match
cap); (b) one registry-wide reset() clears every layer together, with
trace counters riding through (they mirror executable caches); (c) two
virtual-clock replays of the same trace export byte-identical timelines;
(d) hypothesis: registry counters reconcile with conservation_ok() across
generated chaos fault plans.
"""
import json
import math

import numpy as np
import pytest

from repro.core.metrics import (
    Histogram, MetricsRegistry, StatsView, lint_rows,
)
from repro.serving import telemetry as tm

# ---------------------------------------------------------------------------
# Histogram sketch: exact moments, bounded-error quantiles
# ---------------------------------------------------------------------------


def test_histogram_exact_moments_and_quantile_error():
    rng = np.random.default_rng(3)
    xs = rng.lognormal(mean=-3.0, sigma=2.0, size=5000)
    h = Histogram("h")
    for x in xs:
        h.observe(float(x))
    assert h.count == len(xs)
    assert math.isclose(h.total, float(xs.sum()), rel_tol=1e-9)
    assert h.vmin == float(xs.min()) and h.vmax == float(xs.max())
    assert math.isclose(h.mean, float(xs.mean()), rel_tol=1e-9)
    s = np.sort(xs)
    for p in (0.01, 0.5, 0.95, 0.99):
        exact = float(s[min(len(s) - 1, int(math.ceil(p * len(s))) - 1)])
        got = h.quantile(p)
        assert abs(got - exact) / exact < 0.03, (p, got, exact)
    # q(0)/q(1) clamp to the observed extremes within one bucket width
    assert h.vmin <= h.quantile(0.0) <= h.vmin * 1.02
    assert h.vmax / 1.02 <= h.quantile(1.0) <= h.vmax


def test_histogram_zero_bucket_and_empty():
    h = Histogram("h")
    assert math.isnan(h.quantile(0.5)) and h.mean == 0.0
    for v in (0.0, 0.0, 0.0, 1.0):
        h.observe(v)
    assert h.zero_count == 3
    assert h.quantile(0.5) == 0.0       # rank 2 of 4 lands in the <=0 bucket
    assert h.quantile(1.0) == 1.0


def test_histogram_merge_matches_union():
    rng = np.random.default_rng(5)
    a, b = Histogram("h"), Histogram("h")
    xs, ys = rng.exponential(1.0, 300), rng.exponential(5.0, 200)
    for x in xs:
        a.observe(float(x))
    for y in ys:
        b.observe(float(y))
    a.merge(b)
    u = np.concatenate([xs, ys])
    assert a.count == 500
    assert math.isclose(a.total, float(u.sum()), rel_tol=1e-9)
    assert a.vmax == float(u.max())
    exact = float(np.sort(u)[int(math.ceil(0.95 * 500)) - 1])
    assert abs(a.quantile(0.95) - exact) / exact < 0.03


# ---------------------------------------------------------------------------
# Registry: schema, labels, reset, composition, exports
# ---------------------------------------------------------------------------


def test_registry_reset_spares_persistent():
    reg = MetricsRegistry("t")
    c = reg.counter("requests_total")
    p = reg.counter("traces_total", persistent=True)
    h = reg.histogram("latency_seconds")
    c.inc(4), p.inc(2), h.observe(1.0)
    reg.reset()
    assert c.value == 0 and h.count == 0
    assert p.value == 2                 # mirrors an executable cache: rides


def test_registry_reset_recurses_children_then_hooks():
    root, child = MetricsRegistry("root"), MetricsRegistry("child")
    root.attach(child)
    cc = child.counter("c")
    cc.inc(3)
    order = []
    child.on_reset(lambda: order.append("child-hook"))
    root.on_reset(lambda: order.append(("root-hook", cc.value)))
    root.reset()
    # the root hook observes the child already zeroed (drain-mark rewinds
    # in the runtime depend on exactly this ordering)
    assert order == ["child-hook", ("root-hook", 0)]


def test_registry_schema_conflicts_raise_and_lint():
    reg = MetricsRegistry("t")
    reg.counter("x_total")
    with pytest.raises(ValueError):
        reg.histogram("x_total")        # same name, different kind
    with pytest.raises(ValueError):
        reg.counter("x_total", labels={"slice": "0"})  # new label keyset
    # duplicate series across ATTACHED registries is a lint problem, not an
    # exception (each child is self-consistent; the fleet root must label)
    a, b = MetricsRegistry("a"), MetricsRegistry("b")
    a.counter("dup_total").inc()
    b.counter("dup_total").inc()
    root = MetricsRegistry("root")
    root.attach(a), root.attach(b)
    assert any("dup_total" in p for p in root.lint())
    # labeled disjoint series are fine
    c, d = MetricsRegistry("c"), MetricsRegistry("d")
    c.counter("ok_total", labels={"slice": "0"})
    d.counter("ok_total", labels={"slice": "1"})
    root2 = MetricsRegistry("root")
    root2.attach(c), root2.attach(d)
    assert root2.lint() == []
    assert lint_rows(root2.snapshot()["metrics"]) == []


def test_registry_value_and_merged_histogram_aggregate():
    root, child = MetricsRegistry("root"), MetricsRegistry("child")
    root.attach(child)
    root.counter("n_total", labels={"slice": "0"}).inc(2)
    child.counter("n_total", labels={"slice": "1"}).inc(5)
    assert root.value("n_total") == 7
    assert root.value("n_total", labels={"slice": "1"}) == 5
    root.histogram("lat", labels={"slice": "0"}).observe(1.0)
    child.histogram("lat", labels={"slice": "1"}).observe(3.0)
    m = root.merged_histogram("lat")
    assert m.count == 2 and m.vmax == 3.0


def test_exports_deterministic_and_prometheus_shape():
    def build():
        reg = MetricsRegistry("t")
        reg.counter("served_total", labels={"tenant": "a"}).inc(3)
        reg.gauge("depth").set(4)
        h = reg.histogram("lat_seconds")
        for v in (0.1, 0.2, 0.4):
            h.observe(v)
        return reg
    assert build().to_json() == build().to_json()
    rows = {r["name"]: r for r in build().snapshot()["metrics"]}
    assert rows["served_total"]["labels"] == {"tenant": "a"}
    assert rows["served_total"]["value"] == 3
    assert rows["lat_seconds"]["count"] == 3


def test_stats_view_is_a_dict_facade():
    reg = MetricsRegistry("t")
    stats = reg.view("eng", ("a", "b"), labels={"slice": "0"})
    stats["a"] += 2
    stats["a"] += 1
    stats["c"] = 9                      # unknown keys lazily create series
    assert stats["a"] == 3 and stats.get("b") == 0 and stats["c"] == 9
    assert set(stats.keys()) == {"a", "b", "c"}
    assert dict(stats.items())["a"] == 3
    assert "a" in stats and len(stats) == 3
    assert reg.value("eng_a", labels={"slice": "0"}) == 3
    reg.reset()
    assert stats["a"] == 0 and stats["c"] == 0


# ---------------------------------------------------------------------------
# Tracer: typed stream, bounded, deterministic serialization
# ---------------------------------------------------------------------------


def _fill(tr):
    tr.event(tm.INGEST, 0.0, rid=1, tenant="a")
    tr.event(tm.ADMIT, 0.5, rid=1, sid=0, bucket=32)
    tr.event(tm.DECODE_SEGMENT, 1.0, sid=0, dur=0.25, steps=8)
    tr.event(tm.RETIRE, 1.5, rid=1, sid=0, tokens=8)


def test_tracer_counts_filter_and_byte_identity():
    a, b = tm.Tracer(), tm.Tracer()
    _fill(a), _fill(b)
    assert a.counts() == {"ingest": 1, "admit": 1, "decode_segment": 1,
                          "retire": 1}
    assert [e.rid for e in a.of(tm.INGEST, tm.RETIRE)] == [1, 1]
    assert a.to_json(0.0) == b.to_json(0.0)
    doc = json.loads(a.to_json(0.0))
    evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
    assert evs[0]["name"] == "ingest" and evs[0]["ts"] == 0.0
    durs = [e for e in evs if e["ph"] == "X"]
    assert len(durs) == 1 and durs[0]["dur"] == 0.25 * 1e6
    # lanes: fleet (0) + one per sid
    assert {e["tid"] for e in evs} == {0, 1}


def test_tracer_bounded_with_drop_count():
    tr = tm.Tracer(max_events=3)
    for i in range(5):
        tr.event(tm.INGEST, float(i), rid=i)
    assert len(tr.events) == 3 and tr.dropped == 2
    tr.reset()
    assert tr.events == [] and tr.dropped == 0


def test_tracer_records_from_many_threads_without_losing_events():
    """The DPU worker records spans beside the event loop: concurrent
    writers get distinct sequence numbers and no event is lost, and each
    thread sees only its own open span."""
    import os
    import sys
    import threading

    tr = tm.Tracer()
    n_threads, per = (os.cpu_count() or 2) + 2, 300
    seen_other = []

    def work(k):
        for i in range(per):
            with tr.timed(tm.DPU_EXEC, lambda: float(i), rids=[k]) as sp:
                sp.phase("device")
                seen_other.append(tr.current() is not sp)
            tr.event(tm.INGEST, float(i), rid=k)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work, args=(k,))
              for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    total = n_threads * per * 3        # span + phase + point event
    assert len(tr.events) == total and tr.dropped == 0
    assert sorted(e.seq for e in tr.events) == list(range(1, total + 1))
    assert not any(seen_other)


def test_span_kind_vocabulary_closed():
    assert len(set(tm.SPAN_KINDS)) == len(tm.SPAN_KINDS)
    for k in (tm.PREFIX_SCATTER, tm.HEDGE, tm.QUARANTINE, tm.BREAKER_TRIP,
              tm.CPU_FALLBACK, tm.FAULT):
        assert k in tm.SPAN_KINDS


# ===========================================================================
# Engine-level: TTFT invariant, unified reset, deterministic export
# ===========================================================================

jax = pytest.importorskip("jax")

from repro.configs import reduced                              # noqa: E402
from repro.core.batching.buckets import Request                # noqa: E402
from repro.serving.engine import EngineConfig, build_engine    # noqa: E402


def _ec(**kw):
    base = dict(continuous=True, max_slots=4, segment_len=4,
                max_new_tokens=8, max_prompt_len=64)
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def cfg():
    return reduced("tinyllama-1.1b")


@pytest.fixture(scope="module")
def params(cfg):
    return build_engine(cfg, ec=_ec()).params


def _reqs(spec, rid0=7000, prompts=None):
    return [Request(rid=rid0 + i, arrival=0.0, length=float(n),
                    max_new_tokens=b,
                    prompt=None if prompts is None else prompts[i])
            for i, (n, b) in enumerate(spec)]


def _assert_ttft(done, n):
    assert len(done) == n
    for r in done:
        assert r.first_token_at is not None, r.rid
        assert r.completed_at is not None and r.dispatched_at is not None


def test_ttft_never_none_monolithic_and_chunked(cfg, params):
    spec = [(23, 5), (9, 3), (40, 8), (17, 4)]
    for kw in ({}, {"chunk_lens": (8,)}):
        e = build_engine(cfg, ec=_ec(**kw))
        e.params = params
        e.submit_many(_reqs(spec))
        done = e.run_until_idle()
        _assert_ttft(done, len(spec))
        # retire events carry every completed rid
        assert {ev.rid for ev in e.tracer.of(tm.RETIRE)} \
            == {r.rid for r in done}
        # TTFT sketch saw every completion
        assert e.registry.merged_histogram("request_ttft_seconds").count \
            == len(spec)


def test_ttft_stamped_at_scatter_for_full_prefix_match(cfg, params):
    """The zero-suffix admission path: when the radix match covers the
    WHOLE prompt, the scatter is the request's first observable progress
    and must stamp TTFT (the final-chunk stamp never fires for that row
    un-restamped). _prefix_match's n-1 cap makes full matches unreachable
    in production, so the cap is lifted here to pin the guard's behaviour:
    completion, non-None TTFT stamped at admission, bit-identical output."""
    from repro.serving.engine import ServingEngine

    n, chunk = 32, 8  # n == its own pow2 bucket: insert covers the prompt
    prompt = np.random.default_rng(11).integers(
        0, cfg.vocab, n).astype(np.int32)

    cold = build_engine(cfg, ec=_ec(chunk_lens=(chunk,)))
    cold.params = params
    cold.submit_many(_reqs([(n, 6)], rid0=7100, prompts=[prompt]))
    ref = np.asarray(cold.run_until_idle()[0].payload)

    e = build_engine(cfg, ec=_ec(chunk_lens=(chunk,),
                                 prefix_cache_bytes=64 << 20))
    e.params = params
    # wave 1 (cold): retire inserts the full prompt's K/V into the store
    e.submit_many(_reqs([(n, 6)], rid0=7200, prompts=[prompt]))
    e.run_until_idle()
    assert e.prefix_store.peek(n, prompt) == n

    def full_match(self, r, lp, ch, nn, pr, hits, s):
        # production match, minus the n-1 cap: a full-prompt lease is used
        self.stats["prefix_prompt_tokens"] += nn
        lease = self.prefix_store.lookup(lp, pr)
        if lease is None:
            return 0
        cap = min(lease.match_len, nn)
        m = cap - ((cap - nn) % ch)
        if m <= 0:
            self.prefix_store.release(lease)
            return 0
        self._prefix_leases[r.rid] = lease
        hits.append((s, m, self.prefix_store.kv_prefix(lease, m)))
        self.stats["prefix_hits"] += 1
        self.stats["prefix_hit_tokens"] += m
        return m

    e._prefix_match = full_match.__get__(e, ServingEngine)
    e.submit_many(_reqs([(n, 6)], rid0=7300, prompts=[prompt]))
    done = [r for r in e.run_until_idle() if r.rid == 7300]
    assert len(done) == 1
    r = done[0]
    assert r.first_token_at is not None
    # stamped at the scatter (same stamp as dispatch), NOT re-stamped by
    # the trailing idempotent chunk
    assert r.first_token_at == r.dispatched_at
    np.testing.assert_array_equal(np.asarray(r.payload), ref)
    # the scatter really fired for the full prompt
    scat = [ev for ev in e.tracer.of(tm.PREFIX_SCATTER)
            if ev.extra and ev.extra.get("tokens") == n]
    assert scat, e.tracer.counts()


def test_unified_reset_covers_every_layer(cfg, params):
    from repro.core.dpu.service import DpuService, DpuServiceConfig
    from repro.serving.runtime import RuntimeConfig, build_pipelined_runtime

    svc = DpuService(DpuServiceConfig(clock="virtual"))
    rt = build_pipelined_runtime(
        cfg, n_slices=2, ec=_ec(), params=params, service=svc,
        rc=RuntimeConfig(clock="virtual"))
    reqs = _reqs([(23, 4), (9, 3), (31, 5)], rid0=7400)
    payload = np.random.default_rng(0).standard_normal(
        16000).astype(np.float32)
    reqs[0].payload = payload.copy()
    rt.submit(reqs, now=0.0)
    rt.run_until_idle()
    assert rt.stats["submitted"] == 3 and len(rt.completed) == 3
    assert svc.stats["processed"] == 1
    assert rt.tracer.events
    traces_before = rt.registry.value("engine_prefill_traces")
    assert traces_before >= 1
    rt.reset_metrics()  # satellite 1: ONE reset, every layer
    assert rt.stats["submitted"] == 0 and rt.stats["offered"] == 0
    assert svc.stats["processed"] == 0 and svc.stats["submitted"] == 0
    assert all(e.stats["retired"] == 0 for e in rt.engine.engines.values())
    assert rt.registry.merged_histogram("request_latency_seconds").count == 0
    assert rt.tracer.events == [] and rt.completed == []
    assert rt.conservation_ok()  # 0 == 0, nothing stuck
    # persistent trace counters ride through (they mirror executable caches)
    assert rt.registry.value("engine_prefill_traces") == traces_before
    # serve again after the reset: counters restart consistently
    rt.submit(_reqs([(12, 3)], rid0=7500), now=1.0)
    rt.run_until_idle()
    assert rt.stats["submitted"] == 1 and len(rt.completed) == 1
    assert rt.conservation_ok()
    rt.close()


def test_virtual_replay_trace_export_byte_identical(cfg, params):
    from repro.serving.faults import replay_virtual
    from repro.serving.runtime import RuntimeConfig, build_pipelined_runtime

    spec = [(23, 4), (9, 3), (31, 5), (14, 4)]
    rel = np.cumsum(np.full(len(spec), 0.01))

    def run():
        rt = build_pipelined_runtime(
            cfg, n_slices=2, ec=_ec(), params=params,
            rc=RuntimeConfig(clock="virtual"))
        reqs = _reqs(spec, rid0=7600)
        for i, r in enumerate(reqs):
            r.arrival = float(rel[i])
        done = replay_virtual(rt, reqs)
        _assert_ttft(done, len(spec))
        out = rt.tracer.to_json(0.0)
        rt.close()
        return out

    a, b = run(), run()
    assert a == b
    doc = json.loads(a)
    kinds = {e["name"] for e in doc["traceEvents"] if e.get("ph") != "M"}
    assert {"ingest", "offer", "dispatch", "retire"} <= kinds


# ---------------------------------------------------------------------------
# Satellite 3 (hypothesis): registry counters reconcile with conservation
# across generated chaos fault plans
# ---------------------------------------------------------------------------

try:  # property test rides only where hypothesis is installed (CI is)
    from hypothesis import given, settings, strategies as st
    _seeded = given(seed=st.integers(min_value=0, max_value=10_000))
    _paced = settings(max_examples=3, deadline=None)
except ImportError:  # pragma: no cover - exercised only without hypothesis
    _seeded = pytest.mark.skip(reason="property tests need hypothesis")
    _paced = lambda f: f  # noqa: E731


@_paced
@_seeded
def test_registry_reconciles_with_conservation_under_chaos(
        cfg, params, seed):
    from repro.core.dpu.service import DpuService, DpuServiceConfig
    from repro.serving.faults import FaultPlan, replay_virtual
    from repro.serving.runtime import RuntimeConfig, build_pipelined_runtime

    rng = np.random.default_rng(seed)
    n = 10
    rel = np.cumsum(rng.exponential(0.012, n))
    reqs = []
    for i in range(n):
        r = Request(rid=7700 + i, arrival=float(rel[i]),
                    length=float(rng.integers(8, 32)),
                    max_new_tokens=int(rng.choice((3, 4, 6))))
        if i % 2:
            r.payload = np.random.default_rng(i).standard_normal(
                16000).astype(np.float32)
        reqs.append(r)
    plan = FaultPlan.generate(
        seed, horizon_s=float(rel[-1]), n_slices=2, n_requests=n,
        rates={"slice_fail": 8.0, "dpu_fail": 10.0, "malformed": 10.0,
               "straggler": 8.0})
    plan.corrupt_payloads(reqs)

    svc = DpuService(DpuServiceConfig(clock="virtual"))
    rt = build_pipelined_runtime(
        cfg, n_slices=2, ec=_ec(), params=params, service=svc,
        rc=RuntimeConfig(clock="virtual", preprocess_retries=1,
                         breaker_threshold=2, breaker_probe_s=0.05),
        watchdog_rounds=5, probe_interval_s=0.02)
    done = replay_virtual(rt, reqs, plan)

    # the ledger and the registry agree, and both agree with conservation
    assert rt.conservation_ok()
    reg = rt.registry
    assert reg.value("runtime_submitted") == n
    assert (len(done) + len(rt.shed) + len(rt.dead)
            == reg.value("runtime_submitted"))
    assert reg.value("runtime_dead") == len(rt.dead)
    shed_counters = (reg.value("runtime_shed_slo")
                     + reg.value("runtime_shed_backpressure")
                     + reg.value("runtime_shed_error")
                     + reg.value("runtime_shed_malformed"))
    assert shed_counters == len(rt.shed)
    # typed reasons reconcile count-for-count with the counters
    assert sum(rt.shed_counts().values()) == shed_counters
    assert sum(rt.dead_counts().values()) == len(rt.dead)
    # every injected fault landed on the timeline and in a labeled counter
    fired = len(rt.injector.log)
    assert len(rt.tracer.of(tm.FAULT)) == fired
    assert reg.value("faults_injected_total") == fired
    # completed requests always carry a first token stamp (satellite 2)
    _assert_ttft(done, len(done))
    rt.close()


# ---------------------------------------------------------------------------
# Timed spans: phases, group rids, no events from idle ticks
# ---------------------------------------------------------------------------


def test_tracer_span_and_timed_phases():
    tr = tm.Tracer()
    clock = iter([1.0, 1.5, 2.5, 3.0, 4.0]).__next__
    assert isinstance(tr.current(), type(tm._NO_SPAN))
    with tr.timed(tm.SEGMENT, clock, sid=0, live=3) as sp:
        assert tr.current() is sp
        sp.phase("prepare")            # 1.5
        sp.phase("wait")               # 2.5
        sp.phase("host")               # 3.0
        sp.set(tokens=12)
    assert tr.current() is not sp      # closed: popped off this thread
    seg, *phases = tr.events
    assert (seg.kind, seg.t, seg.dur, seg.sid) == (tm.SEGMENT, 1.0, 3.0, "0")
    assert seg.extra == {"live": 3, "tokens": 12}
    assert [(p.kind, p.t, p.dur, p.extra["parent"]) for p in phases] == [
        ("segment.prepare", 1.5, 1.0, seg.seq),
        ("segment.wait", 2.5, 0.5, seg.seq),
        ("segment.host", 3.0, 1.0, seg.seq)]
    # a group span carries its rids; a failing block is recorded, flagged
    seq = tr.span(tm.DPU_EXEC, 5.0, 5.25, rids=[3, 4])
    assert tr.events[-1].extra == {"rids": [3, 4]} and tr.events[-1].seq == seq
    with pytest.raises(ValueError):
        with tr.timed(tm.ADMISSION, lambda: 6.0, rids=[5]):
            raise ValueError("boom")
    assert tr.events[-1].extra == {"rids": [5], "error": "ValueError"}
    doc = json.loads(tr.to_json(0.0))
    x = [e for e in doc["traceEvents"] if e.get("name") == "segment.wait"]
    assert x[0]["ph"] == "X" and x[0]["dur"] == 0.5e6


def _runtime_with_dpu(cfg, params, **ec_kw):
    from repro.core.dpu.service import DpuService, DpuServiceConfig
    from repro.serving.runtime import RuntimeConfig, build_pipelined_runtime

    svc = DpuService(DpuServiceConfig(clock="virtual", max_group=2))
    return build_pipelined_runtime(
        cfg, ec=_ec(**ec_kw), params=params, service=svc,
        rc=RuntimeConfig(clock="virtual"))


def _audio_reqs(spec, rid0):
    reqs = _reqs(spec, rid0=rid0)
    for i, r in enumerate(reqs):
        r.arrival = 0.01 * i
        r.payload = np.random.default_rng(i).standard_normal(
            8000).astype(np.float32)
    return reqs


@pytest.mark.parametrize("ec_kw", [{}, {"chunk_lens": (8,)}],
                         ids=["one_pass", "chunked"])
def test_spans_carry_the_rids_they_served(cfg, params, ec_kw):
    """Each request is in exactly one dpu_exec and one admission span,
    whose rids are the requests that launch and that admission served;
    the engine's tokens_served counter and the segments' `tokens` count
    every token the completed requests carry."""
    from repro.serving.faults import replay_virtual

    rt = _runtime_with_dpu(cfg, params, **ec_kw)
    reqs = _audio_reqs([(23, 4), (9, 3), (31, 5), (14, 4), (12, 1)], 7800)
    done = replay_virtual(rt, reqs)
    _assert_ttft(done, len(reqs))
    rids = sorted(r.rid for r in reqs)
    for kind in (tm.DPU_EXEC, tm.ADMISSION):
        spans = rt.tracer.of(kind)
        assert sorted(r for e in spans for r in e.extra["rids"]) == rids
        assert all(e.dur is not None for e in spans)
    # a request's finish on the DPU is carried to its PREPROCESS_DONE
    for e in rt.tracer.of(tm.PREPROCESS_DONE):
        assert e.extra["finished"] <= e.t
    # one dpu_exec per launched group: the launch events name the same rids
    launched = [e.extra["rids"] for e in rt.tracer.of(tm.PREPROCESS_LAUNCH)]
    assert launched == [e.extra["rids"] for e in rt.tracer.of(tm.DPU_EXEC)]
    served = sum(len(r.payload) for r in done)
    assert rt.engine.stats["tokens_served"] == served
    firsts = len(reqs)
    assert sum(e.extra["tokens"] for e in rt.tracer.of(tm.SEGMENT)) \
        == served - firsts
    segs = rt.tracer.of(tm.DECODE_SEGMENT)
    assert [e.extra["tokens"] for e in segs] == \
        [e.extra["tokens"] for e in rt.tracer.of(tm.SEGMENT)]
    assert all(0 < e.extra["live"] <= e.extra["active"] for e in segs)
    if ec_kw:
        chunks = rt.tracer.of(tm.PREFILL_CHUNK)
        assert chunks and all(e.extra["rids"] for e in chunks)
        assert [e.extra["rids"] for e in chunks] == \
            [e.extra["rids"] for e in rt.tracer.of(tm.CHUNK_DISPATCH)]
    rt.close()


def test_idle_steps_record_nothing(cfg, params):
    rt = _runtime_with_dpu(cfg, params)
    from repro.serving.faults import replay_virtual

    replay_virtual(rt, _audio_reqs([(9, 3), (12, 2)], 7900))
    n = len(rt.tracer.events)
    assert n > 0 and not rt.busy()
    for i in range(1000):
        assert not rt.step(10.0 + i * 1e-3)
    assert len(rt.tracer.events) == n
    rt.close()


def test_virtual_replay_with_spans_exports_byte_identical(cfg, params):
    from repro.serving.faults import replay_virtual

    def run():
        rt = _runtime_with_dpu(cfg, params)
        replay_virtual(rt, _audio_reqs([(23, 4), (9, 3), (31, 5)], 8000))
        out = rt.tracer.to_json(0.0)
        rt.close()
        return out

    a, b = run(), run()
    assert a == b
    kinds = {e["name"] for e in json.loads(a)["traceEvents"]}
    assert {tm.DPU_EXEC, tm.ADMISSION, tm.SEGMENT, "admission.prepare",
            "segment.wait"} <= kinds
