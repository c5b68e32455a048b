"""Serving hot-path benchmark: compile-once engine paths vs the legacy path.

Part 1 — compile-once (PR 1): streams ragged same-bucket batches through the
real-execution engine twice:

  old  — legacy path (pad_buckets=False, fused_decode=False): per-batch
         exact-shape prefill (a retrace for every new ragged max length) and
         a per-token Python decode loop;
  new  — compile-once path: power-of-two (batch, len) shape buckets through
         the jitted-executable prefill cache + one fused lax.scan lm.generate
         with the KV cache donated.

Part 2 — continuous batching (PR 2): replays a Poisson arrival trace with
heterogeneous per-request decode budgets (max_new_tokens) through

  rtc  — the run-to-completion engine above: a formed batch occupies the
         model for the full max_new_tokens scan even after most rows finish,
         and new arrivals wait it out (head-of-line blocking);
  cb   — the continuous-batching engine: one fixed KV slot pool of
         `max_slots` rows, serving as a loop of admit -> decode-segment
         (`segment_len` steps per jitted scan) -> retire. Finished rows free
         their slots between segments and queued prefills join mid-flight,
         so the pool stays occupied and short requests never pay for long
         neighbors.

Continuous-batching knobs (EngineConfig): `max_slots` bounds in-flight
requests == the prefill+admit batch width (pinned so admission never
retraces); `segment_len` is the join/leave granularity — lower = admit
sooner (latency), higher = fewer dispatches (throughput). Steady state
traces exactly TWO programs: one prefill+admit bucket + one segment.

Part 3 — multi-slice (PR 3): replays the same style of Poisson trace through
`MultiSliceEngine` at several partition-menu points (fine / medium / full —
the paper's MIG design points, logical replicas sharing the device set on a
single-device host), one continuous-batching engine per slice behind ONE
shared admission queue with request->slot streaming dispatch and
per-request SliceScheduler straggler hedging live. Records
per-slice slot occupancy, useful tokens/s, p50/p99 latency, hedge counts,
and the per-slice compile-once invariant (2 traces per slice in steady
state). On one shared CPU device the replicas serialize, so the sweep
measures scheduling behaviour, not slice parallelism.

Part 4 — preprocess overlap (PR 4): the same style of Poisson trace, but
every request carries a REAL tokenized prompt plus a raw audio payload, so
preprocessing is actual work on the serving path. CPU-inline preprocessing
(synchronous DPU.process_batch inside submit_many — the paper's
preprocessing wall) is compared against the stage-pipelined runtime
(serving/runtime.py) with a decoupled DpuService overlapping preprocessing
with decode; outputs must be bit-identical, and per-stage queue-depth /
DPU busy-share telemetry is recorded.

Part 5 — chunked prefill + streaming (PR 5): a heavy-tailed prompt-length
Poisson trace through the same slice pool under the old batch-granularity
dispatch (one formed batch per slice at a time, monolithic prefill) vs
request->slot streaming with chunked prefill (long prompts admit
chunk-by-chunk between decode segments); the new path must win p99 AND
useful tokens/s with outputs bit-identical to the unchunked single-slice
engine and per-slice executables bounded by #chunk buckets + 1 segment.

Part 6 — radix prefix KV cache (PR 6): a template-heavy Poisson trace (~80%
of prompt tokens shared through one template, heavy-tailed suffixes) through
the chunked engine with the prefix cache off vs on; a hit scatters stored
prefix K/V into the slot and chunk-prefills only the suffix. Gates: >= 50%
of prompt tokens served from the store, cache-on wins useful tokens/s AND
TTFT p99, bit-identical outputs, bounded executables (one scatter program).

Part 7 — chaos soak (PR 7): the Poisson trace replayed deterministically on
the virtual clock under a PUBLISHED FaultPlan (slice flap, DPU launch
failures, malformed payload, straggler stall, mid-trace resize abort).
Gates: request conservation (completed + shed + dead == submitted), typed
shed/dead reasons, surviving outputs bit-identical to the fault-free run,
the quarantined slice re-admitted, and post-recovery useful tokens/s >=
0.9x fault-free.

Part 8 — multi-tenant fleet (PR 8): two different model families (the
attention LM + a Mamba2 SSM) behind ONE shared admission queue,
slice-as-tenancy-unit: each tenant's model owns a disjoint slice set with
its own engines/params/executables, the model router tags and steers every
request. A mixed two-stream Poisson trace (the shared multi-tenant
generator from serving/requests.py) replays through the fleet. Gates
(absolute): per-tenant conservation, per-tenant bit-identity vs that
model's own single-slice engine, zero cross-tenant routing, and per-slice
steady-state executables bounded by the tenant's own 2 programs.

Measures useful tokens/s (per-request budgets only — run-to-completion's
overshoot doesn't count), p50/p99 request latency (completed - arrival),
p50/p99 TTFT (first_token_at - arrival, in every section), and trace
counts; writes BENCH_serve.json (or --out). --smoke shrinks the workload
for CI.

    PYTHONPATH=src python benchmarks/bench_engine.py [--smoke] [--out F]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.configs import reduced
from repro.core.batching.buckets import Batch, Request
from repro.core.dpu.service import DpuService, DpuServiceConfig
from repro.serving.engine import EngineConfig, ServingEngine, build_engine
from repro.serving.multislice import MultiSliceEngine, build_multislice_engine
from repro.serving.requests import Phase, WorkloadSpec, generate_requests
from repro.serving.runtime import PipelinedRuntime, RuntimeConfig
from repro.serving import telemetry as tm

ARCH = "tinyllama-1.1b"
MAX_NEW_TOKENS = 32     # SERVE_MODELS decode_steps for the text LM
BATCHES = 8
BATCH_SIZE = 8
# continuous-batching trace
MAX_SLOTS = 8
SEGMENT_LEN = 8
TRACE_N = 48
MEAN_INTERARRIVAL_S = 0.012  # drives the pool to the knee (queueing visible)
BUDGETS = (4, 8, 16, 32)        # heterogeneous output lengths
PROMPT_RANGE = (17, 32)         # one (8, 32) prompt bucket


def make_stream(n_batches: int, batch_size: int, seed: int = 0):
    """Ragged batches that all land in the same (8, 32) shape bucket, but
    each with a distinct max length (so the legacy path retraces per batch)."""
    rng = np.random.default_rng(seed)
    stream = []
    rid = 0
    for b in range(n_batches):
        lens = rng.integers(17, 25, batch_size)
        lens[0] = 32 - (b % 8)  # distinct per-batch max, still <= 32
        reqs = [
            Request(rid=(rid := rid + 1), arrival=0.0, length=float(l))
            for l in lens
        ]
        stream.append(Batch(requests=reqs, bucket_id=0, formed_at=0.0))
    return stream


def run_path(engine: ServingEngine, stream) -> dict:
    # warmup: first batch pays tracing/compilation for its shapes; the
    # registry-wide reset drops its samples from every histogram so the
    # measured window starts clean (trace counters are persistent and ride
    # through — readers diff them)
    t_w0 = time.monotonic()
    engine._execute(stream[0])
    warmup_s = time.monotonic() - t_w0
    engine.reset_metrics()

    t0 = time.monotonic()
    for b in stream[1:]:
        # stamp arrival at dispatch so TTFT (first_token_at - arrival) is
        # meaningful here too: under run-to-completion the first observable
        # token is the finished batch, so TTFT == full batch execution
        now = time.monotonic()
        for r in b.requests:
            r.arrival = now
        engine._execute(b)
    steady_s = time.monotonic() - t0

    n_steady = len(stream) - 1
    toks = n_steady * BATCH_SIZE * MAX_NEW_TOKENS
    h_exec = engine.registry.merged_histogram("engine_batch_exec_seconds")
    p95 = h_exec.quantile(0.95)
    s = dict(engine.stats)
    tq = _hist_quantile(engine.registry, "request_ttft_seconds")
    return {
        "batches": len(stream),
        "steady_batches": n_steady,
        "warmup_s": round(warmup_s, 4),
        "steady_s": round(steady_s, 4),
        "tokens_per_s": round(toks / steady_s, 1),
        "p95_batch_ms": round(1e3 * p95, 2),
        "ttft_p50_ms": round(1e3 * tq(0.50), 2),
        "ttft_p99_ms": round(1e3 * tq(0.99), 2),
        "prefill_traces": s["prefill_traces"],
        "generate_traces": s["generate_traces"],
        "decode_step_traces": s["decode_step_traces"],
        "total_traces": s["prefill_traces"] + s["generate_traces"]
        + s["decode_step_traces"],
        "prefill_cache_hits": s["prefill_cache_hits"],
    }


# ---------------------------------------------------------------------------
# Continuous batching vs run-to-completion under a Poisson arrival trace
# ---------------------------------------------------------------------------


def make_trace(n: int, mean_gap_s: float, seed: int = 7):
    """Poisson arrivals, prompts in one bucket, heterogeneous decode budgets.
    Returns (relative arrival times, request spec tuples)."""
    rng = np.random.default_rng(seed)
    rel = np.cumsum(rng.exponential(mean_gap_s, n))
    spec = [
        (
            2000 + i,
            int(rng.integers(PROMPT_RANGE[0], PROMPT_RANGE[1] + 1)),
            int(rng.choice(BUDGETS)),
        )
        for i in range(n)
    ]
    return rel, spec


def _fresh_requests(rel, spec, t0: float):
    return [
        Request(rid=rid, arrival=t0 + float(rel[i]), length=float(n),
                max_new_tokens=b)
        for i, (rid, n, b) in enumerate(spec)
    ]


def _warmup(engine: ServingEngine, seed: int = 99):
    """Compile every executable the trace will need, outside the measured
    window: rtc sees pow2 batch widths 1..BATCH_SIZE of the (.., 32) bucket;
    cb sees its single admit bucket + segment program."""
    rng = np.random.default_rng(seed)
    sizes = [MAX_SLOTS]
    if not engine.ec.continuous:
        # every pow2 width up to pow2(TRACE_N): a backlog burst can form a
        # batch as wide as the whole trace, and an unwarmed width would drop
        # a multi-second compile into rtc's measured window
        b = 1
        while b < 2 * TRACE_N:
            sizes.append(b)
            b *= 2
    rid = 900000
    for sz in sizes:
        reqs = [
            Request(rid=(rid := rid + 1), arrival=0.0,
                    length=float(rng.integers(*PROMPT_RANGE)),
                    max_new_tokens=int(min(BUDGETS)))
            for _ in range(sz)
        ]
        if engine.ec.continuous:
            engine.submit_many(reqs)
            engine.run_until_idle()
        else:
            engine._execute(Batch(requests=reqs, bucket_id=0, formed_at=0.0))
    # one registry-wide reset: counters, histograms, completed list, and
    # trace stream all restart together at the warmup boundary (PR 9)
    engine.reset_metrics()


def _replay(engine, rel, spec, factory=None):
    """Wall-clock Poisson replay, shared by the single- and multi-slice
    sections (both engines expose submit/step/busy/batcher): submit each
    request when its arrival time passes, step the engine in between.
    Returns (makespan_s, requests)."""
    t0 = time.monotonic()
    reqs = (_fresh_requests if factory is None else factory)(rel, spec, t0)
    i = 0
    while i < len(reqs) or engine.busy():
        now = time.monotonic()
        while i < len(reqs) and reqs[i].arrival <= now:
            engine.submit(reqs[i])
            i += 1
        worked = engine.step()
        if not worked:
            if i < len(reqs):
                time.sleep(min(max(reqs[i].arrival - time.monotonic(), 0.0), 0.002))
            elif engine.busy():
                dl = engine.batcher.next_deadline()
                wait = 0.0 if dl is None else dl - time.monotonic()
                time.sleep(min(max(wait, 0.0), 0.002))
    return time.monotonic() - t0, reqs


def _hist_quantile(registry, name: str):
    """Quantile reader over a registry histogram, merged across every
    labeled series and child registry (per-slice engines under a fleet
    root). Every engine observes request latency / TTFT into streaming
    sketches at retire time, so the bench quantiles come straight from the
    telemetry layer instead of a re-derived sample list; each section's
    warmup ends in a registry-wide reset, so the sketch holds exactly the
    measured window."""
    h = registry.merged_histogram(name)
    return lambda p: float(h.quantile(p))


def _latency_quantile(engine):
    """Request-latency quantiles (completed_at - arrival) from the engine's
    `request_latency_seconds` sketch."""
    return _hist_quantile(engine.registry, "request_latency_seconds")


def _ttft_quantile(engine):
    """Time-to-first-token quantiles (first_token_at - arrival) from the
    `request_ttft_seconds` sketch: the latency the prefix cache attacks —
    a hit skips most of prefill, so the first token lands segments earlier
    even when total decode time is unchanged."""
    return _hist_quantile(engine.registry, "request_ttft_seconds")


def run_trace(engine: ServingEngine, rel, spec) -> dict:
    """Replay the trace through one engine; measure useful tokens/s +
    request latency + trace counts."""
    _warmup(engine)
    before = dict(engine.stats)
    traces_before = (before["prefill_traces"] + before["generate_traces"]
                     + before["segment_traces"] + before["decode_step_traces"])
    makespan, reqs = _replay(engine, rel, spec)
    traces_after = (engine.stats["prefill_traces"]
                    + engine.stats["generate_traces"]
                    + engine.stats["segment_traces"]
                    + engine.stats["decode_step_traces"])

    done = engine.completed
    assert len(done) == len(reqs), (len(done), len(reqs))
    useful = sum(len(r.payload) for r in done)
    q = _latency_quantile(engine)
    tq = _ttft_quantile(engine)
    out = {
        "requests": len(done),
        "makespan_s": round(makespan, 4),
        "useful_tokens": useful,
        "tokens_per_s": round(useful / makespan, 1),
        "p50_latency_ms": round(1e3 * q(0.50), 2),
        "p99_latency_ms": round(1e3 * q(0.99), 2),
        "ttft_p50_ms": round(1e3 * tq(0.50), 2),
        "ttft_p99_ms": round(1e3 * tq(0.99), 2),
        "trace_count_total": traces_after,
        "trace_count_during_trace": traces_after - traces_before,
    }
    if engine.ec.continuous:
        out["segments"] = engine.stats["segments"] - before["segments"]
        out["admitted"] = engine.stats["admitted"] - before["admitted"]
        out["retired"] = engine.stats["retired"] - before["retired"]
        out["mean_slot_occupancy"] = round(engine.mean_slot_occupancy(), 3)
    return out


def bench_continuous(cfg, trace_n: int, mean_gap_s: float) -> dict:
    rel, spec = make_trace(trace_n, mean_gap_s)

    rtc = build_engine(cfg, ec=EngineConfig(max_new_tokens=MAX_NEW_TOKENS))
    rtc_res = run_trace(rtc, rel, spec)

    cb = build_engine(cfg, ec=EngineConfig(
        max_new_tokens=MAX_NEW_TOKENS, continuous=True,
        max_slots=MAX_SLOTS, segment_len=SEGMENT_LEN, max_prompt_len=32))
    cb_res = run_trace(cb, rel, spec)

    return {
        "trace": {
            "requests": trace_n,
            "mean_interarrival_ms": round(1e3 * mean_gap_s, 1),
            "budgets": list(BUDGETS),
            "prompt_range": list(PROMPT_RANGE),
            "max_slots": MAX_SLOTS,
            "segment_len": SEGMENT_LEN,
        },
        "run_to_completion": rtc_res,
        "continuous": cb_res,
        "tokens_per_s_speedup": round(
            cb_res["tokens_per_s"] / rtc_res["tokens_per_s"], 2),
        "p99_latency_speedup": round(
            rtc_res["p99_latency_ms"] / cb_res["p99_latency_ms"], 2),
        "steady_state_traces": cb_res["trace_count_total"],
        "compile_once": cb_res["trace_count_total"] == 2
        and cb_res["trace_count_during_trace"] == 0,
        # typed-shed telemetry (uniform across sections): these engine-only
        # paths admit every request, so an empty histogram IS the invariant
        "shed_reasons": {},
    }


# ---------------------------------------------------------------------------
# Multi-slice serving across the partition menu
# ---------------------------------------------------------------------------

# logical menu points: paper's fine / medium / full MIG design points scaled
# to the local host (replicated engines when devices < slices)
MULTI_SLICE_POINTS = (("fine", 4), ("medium", 2), ("full", 1))


def _warmup_multi(ms: MultiSliceEngine, seed: int = 123):
    """One full admission batch per slice (min budget), so every slice
    engine compiles its admit bucket + segment program outside the measured
    window, then reset per-request metrics."""
    rng = np.random.default_rng(seed)
    rid = 980000
    reqs = [
        Request(rid=(rid := rid + 1), arrival=0.0,
                length=float(rng.integers(*PROMPT_RANGE)),
                max_new_tokens=int(min(BUDGETS)))
        for _ in range(len(ms.engines) * MAX_SLOTS)
    ]
    ms.submit_many(reqs)
    ms.run_until_idle()
    ms.reset_metrics()


def run_trace_multi(ms: MultiSliceEngine, rel, spec) -> dict:
    """Replay the trace through the multi-slice engine (same protocol as
    run_trace), with per-slice accounting."""
    _warmup_multi(ms)
    traces_before = ms.trace_counts()
    hedges_before = ms.hedges
    stats_before = ms.slice_stats()
    dispatched_before = ms.stats["dispatched"]
    makespan, reqs = _replay(ms, rel, spec)
    traces_after = ms.trace_counts()

    done = ms.completed
    assert len(done) == len(reqs), (len(done), len(reqs))
    useful = sum(len(r.payload) for r in done)
    q = _latency_quantile(ms)
    stats = ms.slice_stats()
    per_slice = {  # counters diffed to the measured window (warmup excluded)
        str(sid): {
            "admitted": stats[sid]["admitted"] - stats_before[sid]["admitted"],
            "segments": stats[sid]["segments"] - stats_before[sid]["segments"],
            "completed_requests": stats[sid]["completed_requests"]
            - stats_before[sid]["completed_requests"],
            "mean_slot_occupancy": stats[sid]["mean_slot_occupancy"],
            "steady_state_traces": traces_after[sid],
        }
        for sid in sorted(traces_after)
    }
    return {
        "spec": ms.pod.spec.name,
        "n_slices": len(ms.engines),
        "replicated": ms.replicated,
        "requests": len(done),
        "makespan_s": round(makespan, 4),
        "useful_tokens": useful,
        "tokens_per_s": round(useful / makespan, 1),
        "p50_latency_ms": round(1e3 * q(0.50), 2),
        "p99_latency_ms": round(1e3 * q(0.99), 2),
        "ttft_p50_ms": round(1e3 * _ttft_quantile(ms)(0.50), 2),
        "ttft_p99_ms": round(1e3 * _ttft_quantile(ms)(0.99), 2),
        "hedges": ms.hedges - hedges_before,
        "dispatched_requests": ms.stats["dispatched"] - dispatched_before,
        "mean_slot_occupancy": round(ms.mean_slot_occupancy(), 3),
        "trace_count_during_trace": sum(traces_after.values())
        - sum(traces_before.values()),
        "per_slice": per_slice,
    }


def bench_multi_slice(cfg, trace_n: int, mean_gap_s: float) -> dict:
    rel, spec = make_trace(trace_n, mean_gap_s, seed=11)
    points = {}
    params = None  # init once; every menu point re-slices the same model
    for name, n_slices in MULTI_SLICE_POINTS:
        ms = build_multislice_engine(
            cfg, n_slices=n_slices, params=params, ec=EngineConfig(
                max_new_tokens=MAX_NEW_TOKENS, continuous=True,
                max_slots=MAX_SLOTS, segment_len=SEGMENT_LEN,
                max_prompt_len=32))
        params = ms.params
        points[name] = run_trace_multi(ms, rel, spec)
    return {
        "trace": {
            "requests": trace_n,
            "mean_interarrival_ms": round(1e3 * mean_gap_s, 1),
            "budgets": list(BUDGETS),
            "prompt_range": list(PROMPT_RANGE),
            "max_slots": MAX_SLOTS,
            "segment_len": SEGMENT_LEN,
            "menu_points": {name: n for name, n in MULTI_SLICE_POINTS},
        },
        "shed_reasons": {},  # engine-only path: every request admitted
        "points": points,
        "compile_once_per_slice": all(
            p["trace_count_during_trace"] == 0
            and all(s["steady_state_traces"] == 2
                    for s in p["per_slice"].values())
            for p in points.values()
        ),
    }


# ---------------------------------------------------------------------------
# Part 5 — chunked prefill + request->slot streaming vs batch dispatch
# ---------------------------------------------------------------------------
#
# ISSUE 5 tentpole: the old dispatcher handed each slice exactly one formed
# batch at a time (slot occupancy collapsed between dispatches) and admitted
# whole prompts in one prefill (a long prompt froze the resident decoders).
# This section replays a Poisson trace with a HEAVY-TAILED prompt-length mix
# through the same single-slice pool twice:
#
#   batch_dispatch — dispatch="batch": a slice takes a max_slots-sized group
#                    only when fully idle, monolithic prefill (the old
#                    batch-granularity regime, kept as the baseline);
#   stream_chunked — request->slot streaming (any slice with a free slot,
#                    least-loaded; later groups join a busy pool mid-flight)
#                    + chunked prefill (long prompts admit chunk-by-chunk
#                    between decode segments).
#
# Gates: streaming+chunked beats batch dispatch on p99 AND useful tokens/s;
# per-request outputs are bit-identical to the unchunked single-slice
# engine; and the steady-state executable count per slice is bounded:
# bucket-64 prompts admit monolithically (64 == CHUNK_LEN, not chunked),
# bucket-256 prompts run one (64, 256) chunk program, plus one segment —
# exactly 3 programs per slice.

CHUNK_TRACE_N = 32
CHUNK_MEAN_GAP_S = 0.03
CHUNK_MAX_PROMPT = 256
# chunk only what hurts: bucket-64 prompts admit monolithically (a chunked
# short admission pays extra calls for nothing), bucket-256 prompts split
# into 4 chunks so residents keep decoding through the long prefill
CHUNK_LEN = 64
# ONE slice: on the single shared CI device a slice is a real device, and
# the comparison isolates exactly the batch-granularity head-of-line the
# refactor removes (multi-slice streaming/hedging races are covered by
# tests and the multi_slice section, which now streams too)
CHUNK_SLICES = 1
# decode-heavy budgets: slot occupancy (what streaming raises: 0.32 -> 0.5+)
# pays off in the segment calls, so the regime where batch-granularity
# dispatch actually hurts is many decode segments per admission
CHUNK_BUDGETS = (16, 32, 48, 64)
CHUNK_MAX_NEW = 64


def make_heavy_trace(n: int, mean_gap_s: float, seed: int = 31):
    """Poisson arrivals with a heavy-tailed prompt-length mix: short
    (33..64 -> bucket 64) with a heavy long tail (129..224 -> bucket 256)
    whose monolithic prefill would freeze a slice's resident decoders."""
    rng = np.random.default_rng(seed)
    rel = np.cumsum(rng.exponential(mean_gap_s, n))
    spec = []
    for i in range(n):
        ln = (int(rng.integers(129, 225)) if rng.random() < 0.4
              else int(rng.integers(33, 65)))
        spec.append((3000 + i, ln, int(rng.choice(CHUNK_BUDGETS))))
    return rel, spec


def _warmup_lengths(ms: MultiSliceEngine, lengths) -> None:
    """Compile every executable the replay can hit on EVERY slice: one full
    pool of requests per prompt bucket (batch mode hands each idle slice a
    max_slots group; stream mode spreads by load), then reset metrics."""
    rid = 960000
    for ln in lengths:
        reqs = [
            Request(rid=(rid := rid + 1), arrival=0.0, length=float(ln),
                    max_new_tokens=int(min(CHUNK_BUDGETS)))
            for _ in range(len(ms.engines) * MAX_SLOTS)
        ]
        ms.submit_many(reqs)
        ms.run_until_idle()
    ms.reset_metrics()


def bench_chunked_prefill(cfg, trace_n: int, mean_gap_s: float) -> dict:
    from dataclasses import replace as dc_replace

    rel, spec = make_heavy_trace(trace_n, mean_gap_s)
    ec = EngineConfig(max_new_tokens=CHUNK_MAX_NEW, continuous=True,
                      max_slots=MAX_SLOTS, segment_len=SEGMENT_LEN,
                      max_prompt_len=CHUNK_MAX_PROMPT)

    # bit-identity reference: the unchunked single-slice engine (untimed)
    ref_engine = build_engine(cfg, ec=ec)
    ref_engine.submit_many(_fresh_requests(rel, spec, 0.0))
    ref_engine.run_until_idle()
    ref_out = {r.rid: np.asarray(r.payload) for r in ref_engine.completed}

    def run(ms: MultiSliceEngine):
        tb = ms.trace_counts()
        hedges_b = ms.hedges
        makespan, reqs = _replay(ms, rel, spec)
        done = ms.completed
        assert len(done) == len(reqs), (len(done), len(reqs))
        useful = sum(len(r.payload) for r in done)
        q = _latency_quantile(ms)
        ta = ms.trace_counts()
        res = {
            "requests": len(done),
            "makespan_s": round(makespan, 4),
            "useful_tokens": useful,
            "tokens_per_s": round(useful / makespan, 1),
            "p50_latency_ms": round(1e3 * q(0.50), 2),
            "p99_latency_ms": round(1e3 * q(0.99), 2),
            "ttft_p50_ms": round(1e3 * _ttft_quantile(ms)(0.50), 2),
            "ttft_p99_ms": round(1e3 * _ttft_quantile(ms)(0.99), 2),
            "mean_slot_occupancy": round(ms.mean_slot_occupancy(), 3),
            "hedges": ms.hedges - hedges_b,
            "trace_count_during_trace": sum(ta.values()) - sum(tb.values()),
            "per_slice_traces": {str(k): v for k, v in ta.items()},
        }
        return res, {r.rid: np.asarray(r.payload) for r in done}

    base = build_multislice_engine(cfg, n_slices=CHUNK_SLICES,
                                   params=ref_engine.params, ec=ec,
                                   dispatch="batch")
    _warmup_lengths(base, (50, 200))   # admit buckets 64 + 256
    base_res, base_out = run(base)

    ec_chunk = dc_replace(ec, chunk_lens=(CHUNK_LEN,))
    stream = build_multislice_engine(cfg, n_slices=CHUNK_SLICES,
                                     params=ref_engine.params, ec=ec_chunk)
    _warmup_lengths(stream, (50, 200))  # ONE chunk program covers both
    stream_res, stream_out = run(stream)

    bit_identical = (
        set(stream_out) == set(ref_out) == set(base_out)
        and all(np.array_equal(stream_out[k], ref_out[k]) for k in ref_out)
        and all(np.array_equal(base_out[k], ref_out[k]) for k in ref_out)
    )
    return {
        "trace": {
            "requests": trace_n,
            "mean_interarrival_ms": round(1e3 * mean_gap_s, 1),
            "budgets": list(CHUNK_BUDGETS),
            "prompt_mix": "60% in 33..64, 40% in 129..224 (buckets 64/256)",
            "max_prompt_len": CHUNK_MAX_PROMPT,
            "chunk_len": CHUNK_LEN,
            "n_slices": CHUNK_SLICES,
            "max_slots": MAX_SLOTS,
            "segment_len": SEGMENT_LEN,
            # compile-once bound: one chunk program per (chunk len, prompt
            # bucket) pair the trace hits + one segment, per slice
            "expected_traces_per_slice": 3,
        },
        "shed_reasons": {},  # engine-only path: every request admitted
        "batch_dispatch": base_res,
        "stream_chunked": stream_res,
        "tokens_per_s_speedup": round(
            stream_res["tokens_per_s"] / base_res["tokens_per_s"], 2),
        "p99_latency_speedup": round(
            base_res["p99_latency_ms"] / stream_res["p99_latency_ms"], 2),
        "bit_identical_to_unchunked": bit_identical,
        # per slice: one monolithic admit program (bucket 64 == CHUNK_LEN,
        # not chunked) + one (64, 256) chunk program + ONE segment = 3
        "executables_bounded": (
            stream_res["trace_count_during_trace"] == 0
            and all(v == 3
                    for v in stream_res["per_slice_traces"].values())
        ),
    }


# ---------------------------------------------------------------------------
# Part 6 — radix prefix KV cache: shared-prefix prefill reuse (PR 6)
# ---------------------------------------------------------------------------
#
# ISSUE 6 tentpole: template-heavy serving (system prompts, few-shot
# scaffolds) re-prefills the same prefix tokens for every request. The radix
# prefix store keeps retired requests' K/V keyed by token prefix; a new
# request whose prompt extends a stored prefix scatters the cached rows into
# its slot and chunk-prefills ONLY the suffix. Same Poisson trace (~80% of
# prompt tokens shared via one template, heavy-tailed suffixes, a cold
# minority) through the same chunked single-slice engine twice:
#
#   cache_off — prefix_cache_bytes=0: every prompt prefills cold (the
#               parts-1..5 engine, unchanged);
#   cache_on  — radix store enabled: later template requests resume
#               mid-prefill from cached K/V.
#
# Gates: >= 50% of measured-window prompt tokens come from the store
# (prefill FLOPs saved — token count IS the FLOPs ratio at fixed bucket),
# hit rate > 0, cache-on wins useful tokens/s AND TTFT p99, outputs
# bit-identical per request, executables bounded (zero new programs during
# the measured window; ONE scatter program total — a single lp bucket).

PREFIX_TRACE_N = 32
PREFIX_MEAN_GAP_S = 0.03
PREFIX_TEMPLATE_LEN = 200
PREFIX_MAX_PROMPT = 256
PREFIX_CHUNK = 64
PREFIX_BUDGETS = (4, 8, 16)      # prefill-heavy regime: TTFT is the story
PREFIX_MAX_NEW = 16
PREFIX_CACHE_BYTES = 256 << 20   # generous: eviction races live in tests
PREFIX_TEMPLATE_FRAC = 0.85


def make_template_trace(cfg, n: int, mean_gap_s: float, seed: int = 47):
    """Poisson arrivals; ~85% of requests share one 200-token template with
    heavy-tailed suffixes (1..55, exponential), the rest are cold random
    prompts of comparable length — every prompt lands in the lp=256 bucket.
    Returns (rel, spec, template, shared_token_frac); spec entries are
    (rid, prompt, budget)."""
    rng = np.random.default_rng(seed)
    rel = np.cumsum(rng.exponential(mean_gap_s, n))
    template = rng.integers(0, cfg.vocab, PREFIX_TEMPLATE_LEN).astype(np.int32)
    spec, shared, total = [], 0, 0
    for i in range(n):
        if rng.random() < PREFIX_TEMPLATE_FRAC:
            sl = 1 + min(54, int(rng.exponential(12.0)))
            prompt = np.concatenate(
                [template, rng.integers(0, cfg.vocab, sl).astype(np.int32)])
            shared += PREFIX_TEMPLATE_LEN
        else:
            prompt = rng.integers(
                0, cfg.vocab, int(rng.integers(201, 256))).astype(np.int32)
        spec.append((4000 + i, prompt, int(rng.choice(PREFIX_BUDGETS))))
        total += len(prompt)
    return rel, spec, template, shared / total


def _fresh_prompt_requests(rel, spec, t0: float):
    # prompt arrays are read-only: both paths may share them
    return [
        Request(rid=rid, arrival=t0 + float(rel[i]), length=float(len(p)),
                prompt=p, max_new_tokens=b)
        for i, (rid, p, b) in enumerate(spec)
    ]


def _warmup_prefix(engine: ServingEngine, cfg, template) -> dict:
    """Compile every executable the replay can hit — the (chunk, 256)
    program, the segment, and (cache on) the scatter program via a wave of
    template hits — and seed the store so the measured window starts warm.
    Returns the post-warmup stats snapshot."""
    rng = np.random.default_rng(53)
    rid = 940000
    for wave in range(2):  # wave 2 takes hits -> scatter program compiled
        reqs = []
        for k in range(engine.ec.max_slots):
            sl = 1 + int(rng.integers(1, 40))
            prompt = np.concatenate(
                [template, rng.integers(0, cfg.vocab, sl).astype(np.int32)])
            reqs.append(Request(rid=(rid := rid + 1), arrival=0.0,
                                length=float(len(prompt)), prompt=prompt,
                                max_new_tokens=int(min(PREFIX_BUDGETS))))
        engine.submit_many(reqs)
        engine.run_until_idle()
    engine.reset_metrics()
    return dict(engine.stats)


def bench_prefix_cache(cfg, trace_n: int, mean_gap_s: float) -> dict:
    rel, spec, template, shared_frac = make_template_trace(
        cfg, trace_n, mean_gap_s)
    base_ec = EngineConfig(
        max_new_tokens=PREFIX_MAX_NEW, continuous=True, max_slots=MAX_SLOTS,
        segment_len=SEGMENT_LEN, max_prompt_len=PREFIX_MAX_PROMPT,
        chunk_lens=(PREFIX_CHUNK,))

    def run(engine):
        before = _warmup_prefix(engine, cfg, template)
        tb = (before["prefill_traces"] + before["generate_traces"]
              + before["segment_traces"] + before["decode_step_traces"]
              + before["prefix_scatter_traces"])
        makespan, reqs = _replay(engine, rel, spec,
                                 factory=_fresh_prompt_requests)
        s = engine.stats
        ta = (s["prefill_traces"] + s["generate_traces"]
              + s["segment_traces"] + s["decode_step_traces"]
              + s["prefix_scatter_traces"])
        done = engine.completed
        assert len(done) == len(reqs), (len(done), len(reqs))
        useful = sum(len(r.payload) for r in done)
        q = _latency_quantile(engine)
        tq = _ttft_quantile(engine)
        hits = s["prefix_hits"] - before["prefix_hits"]
        hit_toks = s["prefix_hit_tokens"] - before["prefix_hit_tokens"]
        prompt_toks = (s["prefix_prompt_tokens"]
                       - before["prefix_prompt_tokens"])
        res = {
            "requests": len(done),
            "makespan_s": round(makespan, 4),
            "useful_tokens": useful,
            "tokens_per_s": round(useful / makespan, 1),
            "p50_latency_ms": round(1e3 * q(0.50), 2),
            "p99_latency_ms": round(1e3 * q(0.99), 2),
            "ttft_p50_ms": round(1e3 * tq(0.50), 2),
            "ttft_p99_ms": round(1e3 * tq(0.99), 2),
            "mean_slot_occupancy": round(engine.mean_slot_occupancy(), 3),
            "prefix_hits": hits,
            "prefix_hit_rate": round(hits / len(done), 3),
            "prefix_hit_tokens": hit_toks,
            "prompt_tokens": prompt_toks,
            "prefill_flops_saved_frac": round(
                hit_toks / prompt_toks, 3) if prompt_toks else 0.0,
            "prefix_scatter_traces": s["prefix_scatter_traces"],
            "trace_count_during_trace": ta - tb,
        }
        return res, {r.rid: np.asarray(r.payload) for r in done}

    from dataclasses import replace as dc_replace

    off = build_engine(cfg, ec=base_ec)
    off_res, off_out = run(off)

    on = build_engine(cfg, ec=dc_replace(
        base_ec, prefix_cache_bytes=PREFIX_CACHE_BYTES))
    on.params = off.params
    on_res, on_out = run(on)
    store = on.prefix_store
    on_res["store"] = {
        "bytes_used": store.bytes_used,
        "bytes_budget": store.bytes_budget,
        "nodes": store.node_count(),
        "evictions": store.stats["evictions"],
    }

    bit_identical = set(on_out) == set(off_out) and all(
        np.array_equal(on_out[k], off_out[k]) for k in off_out)
    return {
        "trace": {
            "requests": trace_n,
            "mean_interarrival_ms": round(1e3 * mean_gap_s, 1),
            "budgets": list(PREFIX_BUDGETS),
            "template_len": PREFIX_TEMPLATE_LEN,
            "template_request_frac": PREFIX_TEMPLATE_FRAC,
            "shared_prefix_token_frac": round(shared_frac, 3),
            "max_prompt_len": PREFIX_MAX_PROMPT,
            "chunk_len": PREFIX_CHUNK,
            "max_slots": MAX_SLOTS,
            "segment_len": SEGMENT_LEN,
            "cache_bytes": PREFIX_CACHE_BYTES,
        },
        "shed_reasons": {},  # engine-only path: every request admitted
        "cache_off": off_res,
        "cache_on": on_res,
        "tokens_per_s_speedup": round(
            on_res["tokens_per_s"] / off_res["tokens_per_s"], 2),
        "ttft_p99_speedup": round(
            off_res["ttft_p99_ms"] / on_res["ttft_p99_ms"], 2),
        "p99_latency_speedup": round(
            off_res["p99_latency_ms"] / on_res["p99_latency_ms"], 2),
        "hit_rate": on_res["prefix_hit_rate"],
        "prefill_flops_saved_frac": on_res["prefill_flops_saved_frac"],
        "flops_saved_gate": on_res["prefill_flops_saved_frac"] >= 0.5,
        "wins": (on_res["tokens_per_s"] > off_res["tokens_per_s"]
                 and on_res["ttft_p99_ms"] < off_res["ttft_p99_ms"]),
        "bit_identical": bit_identical,
        # one (64, 256) chunk program + one segment compiled in warmup, one
        # scatter program for the single lp bucket, nothing new during the
        # measured window — on either path
        "executables_bounded": (
            on_res["trace_count_during_trace"] == 0
            and off_res["trace_count_during_trace"] == 0
            and on_res["prefix_scatter_traces"] == 1),
    }


# ---------------------------------------------------------------------------
# Part 4 — decoupled DPU preprocessing vs CPU-inline (preprocess overlap)
# ---------------------------------------------------------------------------
#
# The paper's headline: inline preprocessing starves the slices — every
# submit stalls the serve loop for a full preprocessing pass — while a
# decoupled DPU service runs preprocessing CONCURRENTLY with decode. Both
# paths replay the same Poisson trace of requests that carry REAL tokenized
# prompts (Request.prompt) plus a raw audio payload (the preprocessing
# work), through the same 2-slice continuous-batching pool:
#
#   inline    — MultiSliceEngine(preprocess="dpu"): DPU.process_batch runs
#               synchronously inside submit_many, blocking arrivals and
#               decode for the full pass;
#   pipelined — PipelinedRuntime + DpuService (wall clock): bounded-queue
#               stages, preprocessing on the service worker overlapping the
#               decode loop, admission pulling from the preprocess-complete
#               double buffer.
#
# Outputs must be bit-identical per request (the runtime changes when work
# happens, never what is computed); the section records per-stage queue
# depth, the DPU busy share and the per-slice compile-once invariant.

PREPROCESS_SAMPLES = 192000   # 12 s audio @16k: a real preprocessing wall
OVERLAP_SLICES = 2


def _overlap_requests(cfg, rel, spec, t0: float):
    """Fresh request objects for one replay path: deterministic per-rid
    tokenized prompt + audio payload (payloads are consumed by
    preprocessing, then overwritten by decode outputs, so each path needs
    its own copies)."""
    out = []
    for i, (rid, n, b) in enumerate(spec):
        rng = np.random.default_rng(rid)
        out.append(Request(
            rid=rid, arrival=t0 + float(rel[i]), length=float(n),
            max_new_tokens=b,
            prompt=rng.integers(0, cfg.vocab, n).astype(np.int32),
            payload=rng.standard_normal(PREPROCESS_SAMPLES).astype(np.float32),
        ))
    return out


def _replay_overlap(engine, cfg, rel, spec):
    """Wall-clock Poisson replay (same protocol as _replay) over the
    payload-carrying request factory."""
    t0 = time.monotonic()
    reqs = _overlap_requests(cfg, rel, spec, t0)
    i = 0
    while i < len(reqs) or engine.busy():
        now = time.monotonic()
        while i < len(reqs) and reqs[i].arrival <= now:
            engine.submit(reqs[i])
            i += 1
        worked = engine.step()
        if not worked:
            if i < len(reqs):
                time.sleep(min(max(reqs[i].arrival - time.monotonic(), 0.0), 0.002))
            elif engine.busy():
                time.sleep(0.002)
    return time.monotonic() - t0, reqs


def _overlap_metrics(engine, done, reqs, makespan, traces_before,
                     traces_after):
    assert len(done) == len(reqs), (len(done), len(reqs))
    useful = sum(len(r.payload) for r in done)
    q = _latency_quantile(engine)
    tq = _ttft_quantile(engine)
    return {
        "requests": len(done),
        "makespan_s": round(makespan, 4),
        "useful_tokens": useful,
        "tokens_per_s": round(useful / makespan, 1),
        "p50_latency_ms": round(1e3 * q(0.50), 2),
        "p99_latency_ms": round(1e3 * q(0.99), 2),
        "ttft_p50_ms": round(1e3 * tq(0.50), 2),
        "ttft_p99_ms": round(1e3 * tq(0.99), 2),
        "trace_count_during_trace": sum(traces_after.values())
        - sum(traces_before.values()),
        "per_slice_traces": {str(k): v for k, v in traces_after.items()},
    }


def bench_preprocess_overlap(cfg, trace_n: int, mean_gap_s: float) -> dict:
    rel, spec = make_trace(trace_n, mean_gap_s, seed=23)
    ec = EngineConfig(
        max_new_tokens=MAX_NEW_TOKENS, continuous=True, max_slots=MAX_SLOTS,
        segment_len=SEGMENT_LEN, max_prompt_len=32)

    # --- inline: synchronous DPU pass inside submit_many -------------------
    from dataclasses import replace as dc_replace

    inline = build_multislice_engine(
        cfg, n_slices=OVERLAP_SLICES, ec=dc_replace(ec, preprocess="dpu"))
    _warmup_multi(inline)
    # warm the preprocessing path too (numpy constants lru_cache etc.)
    w = _overlap_requests(cfg, [0.0], [(970001, 20, int(min(BUDGETS)))], 0.0)
    inline.submit_many(w)
    inline.run_until_idle()
    inline.reset_metrics()
    tb = inline.trace_counts()
    makespan, reqs = _replay_overlap(inline, cfg, rel, spec)
    inline_res = _overlap_metrics(
        inline, inline.completed, reqs, makespan, tb, inline.trace_counts())
    inline_out = {r.rid: np.asarray(r.payload) for r in inline.completed}

    # --- pipelined: decoupled DPU service (batched Pallas CU launches,
    # pow2-bucketed stacks), wall clock ------------------------------------
    from repro.core.dpu.runtime import DpuConfig

    engine = build_multislice_engine(
        cfg, n_slices=OVERLAP_SLICES, params=inline.params, ec=ec)
    service = DpuService(DpuServiceConfig(
        clock="wall", dpu=DpuConfig(backend="dpu")))
    rt = PipelinedRuntime(engine, service, RuntimeConfig(
        clock="wall", max_ingest=4 * trace_n, max_backlog=4 * trace_n))
    _warmup_multi(engine)
    # compile every pow2 fused-launch stack shape the trace can launch
    wx = np.zeros(PREPROCESS_SAMPLES, np.float32)
    m = 1
    while m <= service.cfg.max_group:
        service._process_group(
            [Request(rid=0, arrival=0.0, length=1.0, payload=wx)] * m)
        m *= 2
    w = _overlap_requests(cfg, [0.0], [(970002, 20, int(min(BUDGETS)))], 0.0)
    rt.submit(w)
    rt.run_until_idle()
    # preprocessing numerics spot-check: decode consumes Request.prompt, so
    # the served tokens (the bit_identical gate below) cannot see the
    # features — verify directly that the two front-ends agree on a real
    # payload within kernel tolerance (numpy CPU pipeline vs the service's
    # fused Pallas CU launch)
    probe = np.random.default_rng(5).standard_normal(
        PREPROCESS_SAMPLES).astype(np.float32)
    want = inline.dpu.process(probe.copy())
    got = service._process_group(
        [Request(rid=1, arrival=0.0, length=1.0, payload=probe.copy())])[0]
    pre_ok = bool(np.allclose(np.asarray(got), np.asarray(want),
                              rtol=2e-2, atol=2e-2))
    rt.reset_metrics()  # ONE registry-wide reset: runtime + engines +
    #                     service + prefix stores; warmup work excluded
    tb = engine.trace_counts()
    makespan, reqs = _replay_overlap(rt, cfg, rel, spec)
    rt.close()
    pipe_res = _overlap_metrics(
        engine, engine.completed, reqs, makespan, tb, engine.trace_counts())
    pipe_res["stage_queue_depth"] = rt.stage_summary()
    # the DPU's busy share: its launches (dpu_exec spans) over the replay
    pipe_res["dpu_busy_share"] = round(sum(
        e.dur for e in rt.tracer.of(tm.DPU_EXEC)) / makespan, 4)
    pipe_res["shed"] = len(rt.shed)
    pipe_res["shed_reasons"] = rt.shed_counts()
    pipe_res["dead_reasons"] = rt.dead_counts()
    pipe_res["service"] = {
        "groups": service.stats["groups"],
        "processed": service.stats["processed"],
        "max_pending_depth": service.stats["max_pending_depth"],
        "max_ready_depth": service.stats["max_ready_depth"],
    }
    pipe_out = {r.rid: np.asarray(r.payload) for r in engine.completed}

    bit_identical = set(inline_out) == set(pipe_out) and all(
        np.array_equal(inline_out[rid], pipe_out[rid]) for rid in inline_out
    )
    return {
        "trace": {
            "requests": trace_n,
            "mean_interarrival_ms": round(1e3 * mean_gap_s, 1),
            "budgets": list(BUDGETS),
            "prompt_range": list(PROMPT_RANGE),
            "payload_samples": PREPROCESS_SAMPLES,
            "n_slices": OVERLAP_SLICES,
            "max_slots": MAX_SLOTS,
            "segment_len": SEGMENT_LEN,
            # the paper's comparison: host-CPU kernels run inline at submit
            # vs the DPU's batched Pallas CUs decoupled behind the service
            "inline_backend": "cpu",
            "pipelined_backend": "dpu",
        },
        "inline": inline_res,
        "pipelined": pipe_res,
        "tokens_per_s_speedup": round(
            pipe_res["tokens_per_s"] / inline_res["tokens_per_s"], 2),
        "p99_latency_speedup": round(
            inline_res["p99_latency_ms"] / pipe_res["p99_latency_ms"], 2),
        # served tokens identical per request across the two paths (decode
        # is driven by Request.prompt; preprocessing numerics are checked
        # separately since the backends only agree to kernel tolerance)
        "bit_identical": bit_identical,
        "preprocess_numerics_ok": pre_ok,
        "compile_once_per_slice": (
            inline_res["trace_count_during_trace"] == 0
            and pipe_res["trace_count_during_trace"] == 0
            and all(v == 2 for v in inline_res["per_slice_traces"].values())
            and all(v == 2 for v in pipe_res["per_slice_traces"].values())
        ),
    }


# ---------------------------------------------------------------------------
# Part 7 — chaos soak: the Poisson trace under a published FaultPlan
# ---------------------------------------------------------------------------

CHAOS_TRACE_N = 32
CHAOS_MEAN_GAP_S = 0.012
CHAOS_TICK = 2e-3               # fixed virtual tick: fully deterministic
CHAOS_PAYLOAD_SAMPLES = 16000   # 1 s audio: preprocessing present, not the wall
POST_WAVE_N = 16                # post-recovery probe wave size


def _chaos_requests(cfg, rel, spec):
    """Fresh request objects for one soak: deterministic per-rid tokenized
    prompt, audio payload on every other request (so the DPU-failure and
    malformed-payload faults have traffic to hit while the rest proves the
    payload-free path rides through untouched)."""
    out = []
    for i, (rid, n, b) in enumerate(spec):
        rng = np.random.default_rng(rid)
        payload = (rng.standard_normal(CHAOS_PAYLOAD_SAMPLES)
                   .astype(np.float32) if i % 2 else None)
        out.append(Request(
            rid=rid, arrival=float(rel[i]), length=float(n),
            max_new_tokens=b,
            prompt=rng.integers(0, cfg.vocab, n).astype(np.int32),
            payload=payload,
        ))
    return out


def _post_recovery_tokens_per_s(rt, cfg, rid_base: int) -> float:
    """Post-recovery useful tokens/s: one WARM wave (pays the re-admitted
    slice's recompilation — the price of recovery, excluded from steady
    state) then three measured waves; best-of-3 damps wall-clock noise. The
    waves carry no payloads: this measures the decode fleet the faults
    degraded, on identical work for both runtimes."""
    rng = np.random.default_rng(rid_base)
    best = 0.0
    for k in range(4):
        reqs = []
        for i in range(POST_WAVE_N):
            rid = rid_base + 1000 * k + i
            n = int(rng.integers(PROMPT_RANGE[0], PROMPT_RANGE[1] + 1))
            prompt = np.random.default_rng(rid).integers(
                0, cfg.vocab, n).astype(np.int32)
            reqs.append(Request(rid=rid, arrival=0.0, length=float(n),
                                max_new_tokens=16, prompt=prompt))
        t0 = time.monotonic()
        rt.submit(reqs, now=rt._now)
        rt.run_until_idle()
        dt = time.monotonic() - t0
        if k > 0:  # wave 0 is warmup
            toks = sum(len(np.asarray(r.payload)) for r in reqs)
            best = max(best, toks / dt)
    return best


def bench_chaos_soak(cfg) -> dict:
    """Section 7: the Poisson trace replayed on the virtual clock under a
    PUBLISHED FaultPlan (slice flap -> watchdog quarantine -> probe ->
    readmit; repeated DPU launch failures -> retry budget -> poison
    dead-letter + breaker -> CPU fallback; a malformed payload -> typed
    front-door shed; a straggler stall -> hedging; a mid-trace resize
    abort -> bounded retries). Gates: request conservation (completed +
    shed + dead == submitted, nothing stuck), survivors bit-identical to
    the fault-free run, the quarantined slice re-admitted, and
    post-recovery useful tokens/s >= 0.9x fault-free."""
    from repro.models import api
    from repro.serving.faults import (
        DPU_FAIL, MALFORMED, RESIZE_ABORT, SLICE_FLAP, STRAGGLER,
        FaultEvent, FaultPlan, replay_virtual,
    )
    from repro.serving.runtime import build_pipelined_runtime

    rel, spec = make_trace(CHAOS_TRACE_N, CHAOS_MEAN_GAP_S, seed=53)
    ec = EngineConfig(
        max_new_tokens=MAX_NEW_TOKENS, continuous=True, max_slots=MAX_SLOTS,
        segment_len=SEGMENT_LEN, max_prompt_len=32)
    import jax

    params = api.init_params(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype)

    def _mk_rt():
        svc = DpuService(DpuServiceConfig(clock="virtual"))
        return build_pipelined_runtime(
            cfg, n_slices=2, ec=ec, params=params, service=svc,
            rc=RuntimeConfig(preprocess_retries=1, breaker_threshold=2,
                             breaker_probe_s=0.05),
            watchdog_rounds=5, probe_interval_s=0.02)

    # the published plan (recorded verbatim in the artifact). Events are
    # tuned to the trace: the flap window covers the arrival burst so the
    # watchdog has busy-no-advance rounds to count; the straggler stall is
    # shorter than watchdog_rounds ticks so hedging (not quarantine)
    # absorbs it; DPU_FAIL's two launches + preprocess_retries=1 force at
    # least one poison dead-letter and trip the breaker_threshold=2.
    plan = FaultPlan([
        FaultEvent(at=0.0, kind=DPU_FAIL, param=2),
        FaultEvent(at=0.0, kind=MALFORMED, target=5),    # an odd (payload) idx
        FaultEvent(at=0.06, kind=SLICE_FLAP, target=0, duration=0.2),
        FaultEvent(at=0.3, kind=STRAGGLER, target=1, duration=0.008),
        FaultEvent(at=0.45, kind=RESIZE_ABORT, target=0, param=1),
    ], seed=7)

    # --- fault-free baseline (pristine trace copies) -----------------------
    rt_ok = _mk_rt()
    t0 = time.monotonic()
    done_ok = replay_virtual(rt_ok, _chaos_requests(cfg, rel, spec),
                             tick=CHAOS_TICK)
    ok_wall_s = time.monotonic() - t0
    assert len(done_ok) == CHAOS_TRACE_N, len(done_ok)
    ref = {r.rid: np.asarray(r.payload) for r in done_ok}
    ok_tps = _post_recovery_tokens_per_s(rt_ok, cfg, 910000)
    rt_ok.close()

    # --- chaos run under the plan ------------------------------------------
    rt = _mk_rt()
    reqs = _chaos_requests(cfg, rel, spec)
    bad = plan.corrupt_payloads(reqs)
    t0 = time.monotonic()
    done = replay_virtual(rt, reqs, plan, tick=CHAOS_TICK)
    chaos_wall_s = time.monotonic() - t0
    ms = rt.engine

    all_rids = sorted(r.rid for r in reqs)
    out_rids = sorted([r.rid for r in done] + [r.rid for r in rt.shed]
                      + [r.rid for r in rt.dead])
    bit_identical = all(
        np.array_equal(np.asarray(r.payload), ref[r.rid]) for r in done)
    # telemetry gates (PR 9), captured BEFORE the post-recovery waves add
    # events: (a) the registry's own submitted counter reconciles with the
    # conservation ledger, (b) the exported virtual-clock timeline is a
    # pure function of trace + plan — a second replay of the same seed
    # must serialize byte-identically
    registry_reconciles = (
        rt.registry.value("runtime_submitted")
        == len(done) + len(rt.shed) + len(rt.dead))
    trace_json = rt.tracer.to_json(0.0)
    fault_events_traced = len(rt.tracer.of("fault"))
    rt2 = _mk_rt()
    reqs2 = _chaos_requests(cfg, rel, spec)
    plan.corrupt_payloads(reqs2)
    replay_virtual(rt2, reqs2, plan, tick=CHAOS_TICK)
    trace_deterministic = rt2.tracer.to_json(0.0) == trace_json
    rt2.close()
    post_tps = _post_recovery_tokens_per_s(rt, cfg, 920000)
    rt.close()
    ratio = post_tps / ok_tps if ok_tps else 0.0

    return {
        "trace": {
            "requests": CHAOS_TRACE_N,
            "mean_interarrival_ms": round(1e3 * CHAOS_MEAN_GAP_S, 1),
            "payload_samples": CHAOS_PAYLOAD_SAMPLES,
            "n_slices": 2, "max_slots": MAX_SLOTS,
            "segment_len": SEGMENT_LEN, "virtual_tick_s": CHAOS_TICK,
            "watchdog_rounds": 5, "probe_interval_s": 0.02,
            "preprocess_retries": 1, "breaker_threshold": 2,
        },
        "plan": plan.to_json(),
        "fired": [list(e) for e in rt.injector.log],
        "fault_free": {
            "completed": len(done_ok),
            "soak_wall_s": round(ok_wall_s, 4),
            "post_tokens_per_s": round(ok_tps, 1),
        },
        "chaos": {
            "completed": len(done),
            "shed": len(rt.shed),
            "dead": len(rt.dead),
            "shed_reasons": rt.shed_counts(),
            "dead_reasons": rt.dead_counts(),
            "soak_wall_s": round(chaos_wall_s, 4),
            "post_tokens_per_s": round(post_tps, 1),
            "breaker_trips": rt.stats["breaker_trips"],
            "cpu_fallback": rt.stats["cpu_fallback"],
            "pp_retries": rt.stats["pp_retries"],
            "quarantined": ms.stats["quarantined"],
            "readmitted": ms.stats["readmitted"],
            "requeued": ms.stats["requeued"],
            "resizes": ms.stats["resizes"],
            "hedges": ms.hedges,
            "dead_lettered_engine": ms.stats["dead_lettered"],
        },
        # --- gates ---
        "conservation_ok": bool(rt.conservation_ok()),
        "accounted_exactly_once": out_rids == all_rids,
        "malformed_shed": len(bad) >= 1 and all(
            rt.shed_reasons[rid].value == "malformed" for rid in bad),
        "bit_identical_survivors": bool(bit_identical),
        "slice_readmitted": ms.stats["quarantined"] >= 1
        and ms.stats["readmitted"] >= 1,
        "fleet_healthy_after": all(
            s.healthy for s in ms.sched.slices.values()),
        "dead_letter_exercised": len(rt.dead) >= 1,
        "breaker_exercised": rt.stats["breaker_trips"] >= 1
        and rt.stats["cpu_fallback"] >= 1,
        "post_recovery_ratio": round(ratio, 3),
        "post_recovery_ok": ratio >= 0.9,
        # --- telemetry gates (PR 9) ---
        "registry_reconciles": bool(registry_reconciles),
        "fault_events_traced": fault_events_traced,
        "trace_export_deterministic": bool(trace_deterministic),
    }


# ---------------------------------------------------------------------------
# Part 8 — multi-tenant multi-model fleet (ISSUE 8)
# ---------------------------------------------------------------------------
#
# Two DIFFERENT model families (the attention LM + a Mamba2 SSM) share one
# fleet: slice-as-tenancy-unit, each tenant's model gets its own slice set
# (its own engines, params, slot pools, executables) behind ONE shared
# admission queue with the model router tagging and steering every request.
# Gates are ABSOLUTE (routing, conservation, and bit-identity are
# deterministic; there is no tokens/s floor because two models on the one
# CI device serialize, which measures scheduling, not capacity):
#
#   conservation_per_tenant  — every generated request of every tenant
#                              completes (nothing shed, dead, or stuck);
#   bit_identical_per_tenant — fleet outputs == that model's own
#                              single-slice engine on the same requests;
#   no_cross_tenant_routing  — the routing audit (tenant_stats) shows each
#                              tenant's requests only ever landed on its
#                              own disjoint slice set;
#   executables_bounded      — nothing compiles during the measured trace
#                              and each slice holds at most its own
#                              tenant's 2 steady-state programs.

MT_TENANT_B_ARCH = "mamba2-370m"
MT_TRACE_N = 32
MT_RATE_QPS = 40.0       # per tenant; the merged stream arrives ~2x that
MT_SLICES_EACH = 2       # fine partition: 2 slices per tenant, 4 total
MT_MAX_NEW = 16
# one prompt bucket per tenant: lognormal(mean 24, sigma 0.05) stays inside
# 18..31 at 6 sigma, so every prompt lands in the (16, 32] admit bucket and
# each slice's steady state is exactly admit + segment (2 programs)
MT_MEAN_LEN = 24.0
MT_SIGMA = 0.05


def _mt_specs(cfgs):
    """One Poisson stream per tenant, equal weights. This is satellite 2's
    shared generator (serving/requests.py): rids live in disjoint per-tenant
    namespaces and every request carries its tenant's model id plus a REAL
    tokenized prompt drawn from that tenant's own vocab."""
    return [
        (WorkloadSpec(modality="text", rate_qps=MT_RATE_QPS,
                      mean_len=MT_MEAN_LEN, sigma=MT_SIGMA, max_len=32.0,
                      vocab=c.vocab, model=name, seed=61 + k), 1.0)
        for k, (name, c) in enumerate(sorted(cfgs.items()))
    ]


def _warmup_tenants(ms: MultiSliceEngine, names, seed: int = 129):
    """Per-tenant warm wave (one full admission batch per slice of that
    tenant's set), so every slice engine compiles ITS model's admit bucket
    + segment program outside the measured window."""
    rng = np.random.default_rng(seed)
    rid = 985000
    reqs = []
    for name in names:
        n = len(ms.slices_of(name)) * MAX_SLOTS
        reqs += [
            Request(rid=(rid := rid + 1), arrival=0.0,
                    length=float(rng.integers(*PROMPT_RANGE)),
                    max_new_tokens=int(min(BUDGETS)), model=name)
            for _ in range(n)
        ]
    ms.submit_many(reqs)
    ms.run_until_idle()
    ms.reset_metrics()


def bench_multi_tenant(cfg) -> dict:
    import jax

    from repro.models import api
    from repro.serving.multislice import TenantSpec

    cfg_b = reduced(MT_TENANT_B_ARCH)
    cfgs = {ARCH: cfg, MT_TENANT_B_ARCH: cfg_b}
    ec = EngineConfig(
        max_new_tokens=MT_MAX_NEW, continuous=True, max_slots=MAX_SLOTS,
        segment_len=SEGMENT_LEN, max_prompt_len=32)
    specs = _mt_specs(cfgs)

    # per-tenant single-slice references: same PRNGKey(0) init, the same
    # requests (the generator is deterministic), arrivals zeroed — the
    # fleet's per-request outputs must match these bit-for-bit
    refs, ref_counts = {}, {}
    for name, c in cfgs.items():
        single = build_engine(c, ec=ec)
        mine = [r for r in generate_requests(specs, MT_TRACE_N)
                if r.model == name]
        ref_counts[name] = len(mine)
        for r in mine:
            r.arrival = 0.0
        single.submit_many(mine)
        single.run_until_idle()
        refs[name] = {r.rid: np.asarray(r.payload) for r in single.completed}
    assert sum(ref_counts.values()) == MT_TRACE_N, ref_counts

    params = {name: api.init_params(c, jax.random.PRNGKey(0), dtype=c.dtype)
              for name, c in cfgs.items()}
    ms = build_multislice_engine(
        n_slices=len(cfgs) * MT_SLICES_EACH, ec=ec,
        tenants=[TenantSpec(cfg=c, name=name, n_slices=MT_SLICES_EACH,
                            params=params[name])
                 for name, c in cfgs.items()])
    _warmup_tenants(ms, list(cfgs))
    traces_before = ms.trace_counts()
    stats_before = ms.slice_stats()
    hedges_before = ms.hedges

    def _factory(_rel, _spec, t0):
        reqs = generate_requests(specs, MT_TRACE_N)
        for r in reqs:
            r.arrival += t0
        return reqs

    makespan, reqs = _replay(ms, None, None, factory=_factory)
    traces_after = ms.trace_counts()
    stats = ms.slice_stats()

    done = ms.completed
    assert len(done) == len(reqs), (len(done), len(reqs))
    bit_identical = all(
        np.array_equal(np.asarray(r.payload), refs[r.model][r.rid])
        for r in done)
    ts = ms.tenant_stats()
    by_tenant = {}
    for name in cfgs:
        mine = [r for r in done if r.model == name]
        by_tenant[name] = {
            "requests": ref_counts[name],
            "completed": len(mine),
            "useful_tokens": sum(len(r.payload) for r in mine),
            "slices": sorted(ts[name]["slices"]),
            "routed_to": sorted(set(ts[name]["routed_to"])),
        }
    slice_sets = [set(t["slices"]) for t in by_tenant.values()]
    disjoint = all(a.isdisjoint(b) for i, a in enumerate(slice_sets)
                   for b in slice_sets[i + 1:])

    useful = sum(len(r.payload) for r in done)
    q = _latency_quantile(ms)
    tq = _ttft_quantile(ms)
    per_slice = {  # counters diffed to the measured window (warmup excluded)
        str(sid): {
            "model": stats[sid]["model"],
            "admitted": stats[sid]["admitted"] - stats_before[sid]["admitted"],
            "segments": stats[sid]["segments"] - stats_before[sid]["segments"],
            "mean_slot_occupancy": stats[sid]["mean_slot_occupancy"],
            "steady_state_traces": traces_after[sid],
        }
        for sid in sorted(traces_after)
    }
    return {
        "trace": {
            "requests": MT_TRACE_N,
            "per_tenant_rate_qps": MT_RATE_QPS,
            "tenants": {name: MT_SLICES_EACH for name in cfgs},
            "max_new_tokens": MT_MAX_NEW,
            "max_slots": MAX_SLOTS,
            "segment_len": SEGMENT_LEN,
            "prompt_bucket": 32,
        },
        "n_slices": len(ms.engines),
        "requests": len(done),
        "makespan_s": round(makespan, 4),
        "useful_tokens": useful,
        "tokens_per_s": round(useful / makespan, 1),
        "p50_latency_ms": round(1e3 * q(0.50), 2),
        "p99_latency_ms": round(1e3 * q(0.99), 2),
        "ttft_p50_ms": round(1e3 * tq(0.50), 2),
        "ttft_p99_ms": round(1e3 * tq(0.99), 2),
        "hedges": ms.hedges - hedges_before,
        "trace_count_during_trace": sum(traces_after.values())
        - sum(traces_before.values()),
        "per_tenant": by_tenant,
        "per_slice": per_slice,
        # --- gates ---
        "conservation_per_tenant": bool(
            not ms.dead and not ms.busy()
            and all(t["completed"] == t["requests"] > 0
                    for t in by_tenant.values())),
        "bit_identical_per_tenant": bool(bit_identical),
        "no_cross_tenant_routing": bool(disjoint and all(
            set(t["routed_to"]) <= set(t["slices"])
            for t in by_tenant.values())),
        "executables_bounded": bool(
            sum(traces_after.values()) == sum(traces_before.values())
            and all(c <= 2 for c in traces_after.values())),
    }


# --- part 9: online partition controller (PR 10) -------------------------
#
# A phase-shifting trace replayed on the virtual clock through every static
# menu point (1 / 2 / 4 slices) and through the closed-loop controller:
#
#   phase 1  heavy  — long template-prefix prompts at moderate rate: one
#            coarse slice consolidates the prefix store (one cold prefill
#            total); fine slices scatter the template across n stores and
#            pay ~n cold prefills;
#   phase 2  burst  — a hot wave of small cold prompts: the fine pool's
#            n x max_slots capacity rides it out while coarse/medium queue
#            at the front door;
#   phase 3  heavy  — the template mix returns (gentle ramp, then fast):
#            the controller folds back to coarse and the warm partition
#            cache restores the template-bearing store intact, so the
#            switch-back serves hits from the first request.
#
# Useful tokens/s is GOODPUT: tokens of requests that completed within
# P9_SLO_S of arrival, per second of virtual makespan — raw completed
# tokens would tie (every busy engine steps once per tick, so fine slot
# capacity weakly dominates); what the controller buys is tokens delivered
# on time. p99 and goodput both come from virtual request stamps, which
# survive resize() (registry histograms detach with old engine sets).
#
# Gates (absolute): the controller beats EVERY static point on p99 AND
# goodput; 1 <= reconfigurations <= P9_MAX_RECONFIGS with both decision
# directions exercised; conservation + exactly-once accounting; survivor
# outputs bit-identical to the static-fine reference; decision log and
# trace timeline byte-identical across two same-seed replays.

P9_TICK = 2e-3                   # fixed virtual tick (chaos-soak contract)
P9_SEED = 71
P9_TRACE_N = 196
P9_TEMPLATE_LEN = 448
P9_MAX_PROMPT = 512
P9_CHUNK = 32                    # 14 cold chunks vs <=2 hit chunks: the gap
P9_MAX_NEW = 8                   # the SLO separates
P9_SEG = 4
P9_SLOTS = 4                     # per-slice slots: menu spans 4..16 total
P9_MENU = (1, 2, 4)
P9_MAX_RECONFIGS = 4
P9_SLO_S = 0.030                 # goodput deadline: hits + burst clear it,
P9_CACHE_BYTES = 256 << 20       # cold template prefills (~35ms) blow it
P9_HEAVY_CUT = 100.0             # generated length above this => template
# measured window: requests arriving before this are the warm-in (they
# seed the template solo, one isolated cold for every config alike) and
# are excluded from the scoreboard — steady-state measurement, the same
# reason every other section warms up before reset_metrics()
P9_WARM_S = 0.12
P9_PHASES = (
    Phase(0.12, 20.0, mean_len=480.0, sigma=0.05, max_len=511.0),   # warm-in
    Phase(0.15, 400.0, mean_len=480.0, sigma=0.05, max_len=511.0),  # heavy
    Phase(0.03, 20.0, mean_len=480.0, sigma=0.05, max_len=511.0),   # dip
    Phase(0.03, 2600.0, mean_len=48.0, sigma=0.20, max_len=63.0),   # burst
    Phase(0.06, 30.0, mean_len=480.0, sigma=0.05, max_len=511.0),   # restart
    Phase(0.30, 400.0, mean_len=480.0, sigma=0.05, max_len=511.0),  # heavy
)


def make_controller_trace(cfg):
    """Phase-shifting trace from the shared phased generator (ISSUE 10
    satellite: bench and tests replay the same schedule machinery), with
    prompts rebuilt per phase: heavy-phase requests share one
    P9_TEMPLATE_LEN-token template plus a per-rid suffix (all in the
    lp=512 bucket), burst requests are small cold prompts (lp=32).
    Returns (spec, template); spec rows are (rid, arrival, prompt)."""
    base = generate_requests(
        WorkloadSpec(modality="text", rate_qps=100.0, mean_len=480.0,
                     sigma=0.05, max_len=511.0, vocab=cfg.vocab,
                     seed=P9_SEED, phases=P9_PHASES), P9_TRACE_N)
    rng = np.random.default_rng(P9_SEED + 1)
    template = rng.integers(0, cfg.vocab, P9_TEMPLATE_LEN).astype(np.int32)
    spec = []
    for r in base:
        if r.length > P9_HEAVY_CUT:
            sl = int(min(max(r.length - P9_TEMPLATE_LEN, 1), 63))
            prompt = np.concatenate(
                [template, rng.integers(0, cfg.vocab, sl).astype(np.int32)])
        else:
            prompt = rng.integers(
                0, cfg.vocab, max(1, int(r.length))).astype(np.int32)
        spec.append((r.rid, float(r.arrival), prompt))
    return spec, template


def _fresh_controller_requests(spec):
    return [
        Request(rid=rid, arrival=arr, length=float(len(p)), prompt=p,
                max_new_tokens=P9_MAX_NEW)
        for rid, arr, p in spec
    ]


def _controller_point(rt, reqs, done) -> dict:
    """Per-run scoreboard from virtual request stamps only (registry
    histograms detach with pre-resize engine sets; request stamps
    survive). Measured over the steady-state window (arrival >=
    P9_WARM_S): p99 of request latency, and useful tokens/s as GOODPUT —
    tokens of window requests that completed within P9_SLO_S, per second
    of window makespan. A shed request completes nothing: its tokens are
    lost from the numerator by construction."""
    win = [r for r in done if float(r.arrival) >= P9_WARM_S]
    n_win = sum(1 for r in reqs if float(r.arrival) >= P9_WARM_S)
    lat = sorted(float(r.completed_at - r.arrival) for r in win)
    p99 = lat[int(0.99 * (len(lat) - 1))] if lat else float("inf")
    good = [r for r in win
            if float(r.completed_at - r.arrival) <= P9_SLO_S]
    good_toks = int(sum(len(np.asarray(r.payload)) for r in good))
    makespan = max(
        (float(r.completed_at) for r in win), default=P9_WARM_S + 1.0
    ) - P9_WARM_S
    shed = int(rt.stats["shed_slo"] + rt.stats["shed_backpressure"]
               + rt.stats["shed_error"] + rt.stats["shed_malformed"])
    return {
        "requests": len(reqs),
        "window_requests": n_win,
        "completed": len(done),
        "shed": shed,
        "p99_latency_ms": round(1e3 * p99, 3),
        "slo_attained_frac": round(len(good) / max(1, n_win), 4),
        "goodput_tokens_per_s": round(good_toks / makespan, 1),
        "makespan_s": round(makespan, 4),
        "conservation_ok": bool(rt.conservation_ok()),
    }


def bench_partition_controller(cfg) -> dict:
    import jax

    from repro.core.control import ControllerConfig, PartitionController
    from repro.models import api
    from repro.serving.faults import replay_virtual

    spec, _template = make_controller_trace(cfg)
    ec = EngineConfig(
        max_new_tokens=P9_MAX_NEW, continuous=True, max_slots=P9_SLOTS,
        segment_len=P9_SEG, max_prompt_len=P9_MAX_PROMPT,
        chunk_lens=(P9_CHUNK,), prefix_cache_bytes=P9_CACHE_BYTES)
    params = api.init_params(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype)

    def _mk_rt(n_slices, controller=None):
        ms = build_multislice_engine(
            cfg, n_slices=n_slices, ec=ec, params=params)
        ms.fixed_expected_s = 1.0   # pin hedging off the wall-clock EMA
        return PipelinedRuntime(
            ms, None, RuntimeConfig(clock="virtual"), controller=controller)

    def _ctl():
        return PartitionController(ControllerConfig(
            menu=P9_MENU, eval_interval_s=0.004, window_s=0.03,
            cooldown_s=0.05, improve_frac=0.3, amortize_horizon_s=0.5,
            max_reconfigs=P9_MAX_RECONFIGS, min_observations=2,
            slo_target_s=P9_SLO_S))

    # static menu sweep (the PREBA hand-picked design points)
    statics = {}
    ref_payloads = {}
    for n in P9_MENU:
        rt = _mk_rt(n)
        reqs = _fresh_controller_requests(spec)
        done = replay_virtual(rt, reqs, None, tick=P9_TICK)
        statics[str(n)] = _controller_point(rt, reqs, done)
        if n == max(P9_MENU):   # fine completes everything: the reference
            ref_payloads = {r.rid: np.asarray(r.payload) for r in done}

    # the closed loop, twice: same seed, byte-identical decisions required
    runs = []
    for _rep in range(2):
        ctl = _ctl()
        rt = _mk_rt(P9_MENU[0], controller=ctl)
        reqs = _fresh_controller_requests(spec)
        done = replay_virtual(rt, reqs, None, tick=P9_TICK)
        runs.append((rt, ctl, reqs, done))
    rt, ctl, reqs, done = runs[0]
    ctl_point = _controller_point(rt, reqs, done)
    decisions = [d.to_row() for d in ctl.decisions]
    reasons = {d["reason"] for d in decisions}

    bit_identical = all(
        r.rid in ref_payloads
        and np.array_equal(np.asarray(r.payload), ref_payloads[r.rid])
        for r in done)
    beats = {
        n: bool(ctl_point["p99_latency_ms"] < p["p99_latency_ms"]
                and ctl_point["goodput_tokens_per_s"]
                > p["goodput_tokens_per_s"])
        for n, p in statics.items()
    }
    return {
        "trace": {
            "n": P9_TRACE_N, "seed": P9_SEED, "tick_s": P9_TICK,
            "slo_s": P9_SLO_S, "menu": list(P9_MENU),
            "max_reconfigs": P9_MAX_RECONFIGS, "warm_window_s": P9_WARM_S,
            "phases": [
                {"duration_s": p.duration_s, "rate_qps": p.rate_qps,
                 "mean_len": p.mean_len} for p in P9_PHASES
            ],
        },
        "static": statics,
        "controller": ctl_point,
        "decisions": decisions,
        "reconfigs": len(decisions),
        "beats_static": beats,
        # --- gates ---
        "wins_every_point": bool(all(beats.values())),
        "reconfigs_bounded": bool(1 <= len(decisions) <= P9_MAX_RECONFIGS),
        "both_directions": bool({"burst_fine", "heavy_coarse"} <= reasons),
        "conservation_ok": bool(
            ctl_point["conservation_ok"]
            and all(p["conservation_ok"] for p in statics.values())),
        "bit_identical_survivors": bool(bit_identical),
        "decision_log_deterministic": bool(
            runs[0][1].decisions_json() == runs[1][1].decisions_json()),
        "trace_deterministic": bool(
            runs[0][0].tracer.to_json(0.0) == runs[1][0].tracer.to_json(0.0)),
        "reconfig_observable": bool(
            int(rt.registry.value("fleet_reconfigs_total")) == len(decisions)
            and len(rt.tracer.of("reconfig")) == len(decisions)),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small workload for CI (same checks, ~3x faster)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    # smoke trims only the slow legacy (per-token loop) stream; the new-path
    # stream and the continuous trace stay at full size so their tokens/s
    # remain comparable to the committed reference (a shorter run
    # over-weights warmup/tail drain and makes the CI floor noisy)
    cfg = reduced(ARCH)
    old_stream = make_stream(4 if args.smoke else BATCHES, BATCH_SIZE)
    new_stream = make_stream(BATCHES, BATCH_SIZE)

    old_engine = build_engine(cfg, ec=EngineConfig(
        max_new_tokens=MAX_NEW_TOKENS, pad_buckets=False, fused_decode=False))
    old = run_path(old_engine, old_stream)

    new_engine = build_engine(cfg, ec=EngineConfig(max_new_tokens=MAX_NEW_TOKENS))
    new = run_path(new_engine, new_stream)

    speedup = new["tokens_per_s"] / old["tokens_per_s"]
    result = {
        "arch": f"{ARCH} (reduced)",
        "max_new_tokens": MAX_NEW_TOKENS,
        "batch_size": BATCH_SIZE,
        "smoke": args.smoke,
        "old": old,
        "new": new,
        "tokens_per_s_speedup": round(speedup, 2),
        "compile_once": new["total_traces"] == 2,
        "continuous_batching": bench_continuous(cfg, TRACE_N, MEAN_INTERARRIVAL_S),
        # chunked runs before the bigger sections: executable accumulation
        # late in the run inflates per-call overhead, which would skew its
        # call-count-sensitive streaming-vs-batching comparison
        "chunked_prefill": bench_chunked_prefill(
            cfg, CHUNK_TRACE_N, CHUNK_MEAN_GAP_S),
        "prefix_cache": bench_prefix_cache(
            cfg, PREFIX_TRACE_N, PREFIX_MEAN_GAP_S),
        "multi_slice": bench_multi_slice(cfg, TRACE_N, MEAN_INTERARRIVAL_S),
        "preprocess_overlap": bench_preprocess_overlap(
            cfg, TRACE_N, MEAN_INTERARRIVAL_S),
        # deterministic virtual-clock replay: same size in smoke and full
        "chaos_soak": bench_chaos_soak(cfg),
        # two-model fleet: same size in smoke and full (gates are absolute)
        "multi_tenant": bench_multi_tenant(cfg),
        # closed-loop controller vs the static menu: same size in smoke
        # and full (virtual-clock replay, absolute gates)
        "partition_controller": bench_partition_controller(cfg),
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result, indent=2))
    cbr = result["continuous_batching"]
    print(f"\ncompile-once: {speedup:.2f}x tokens/s; "
          f"traces old={old['total_traces']} new={new['total_traces']}")
    print(f"continuous:   {cbr['tokens_per_s_speedup']:.2f}x useful tokens/s, "
          f"{cbr['p99_latency_speedup']:.2f}x p99 latency, "
          f"traces={cbr['steady_state_traces']}")
    msr = result["multi_slice"]
    for name, p in msr["points"].items():
        print(f"multi[{name:6s}] {p['spec']:8s}: "
              f"{p['tokens_per_s']:.1f} useful tokens/s, "
              f"p99={p['p99_latency_ms']:.1f}ms, "
              f"occupancy={p['mean_slot_occupancy']:.3f}, "
              f"hedges={p['hedges']}, "
              f"traces/slice=2x{p['n_slices']}")
    po = result["preprocess_overlap"]
    print(f"overlap:      {po['tokens_per_s_speedup']:.2f}x useful tokens/s, "
          f"{po['p99_latency_speedup']:.2f}x p99 latency "
          f"(decoupled DPU vs CPU-inline), "
          f"bit_identical={po['bit_identical']}, "
          f"compile_once={po['compile_once_per_slice']}")
    cp = result["chunked_prefill"]
    print(f"chunked:      {cp['tokens_per_s_speedup']:.2f}x useful tokens/s, "
          f"{cp['p99_latency_speedup']:.2f}x p99 latency "
          f"(stream+chunked vs batch dispatch), "
          f"occupancy {cp['batch_dispatch']['mean_slot_occupancy']:.3f} -> "
          f"{cp['stream_chunked']['mean_slot_occupancy']:.3f}, "
          f"bit_identical={cp['bit_identical_to_unchunked']}, "
          f"executables_bounded={cp['executables_bounded']}")
    px = result["prefix_cache"]
    print(f"prefix:       {px['tokens_per_s_speedup']:.2f}x useful tokens/s, "
          f"{px['ttft_p99_speedup']:.2f}x TTFT p99 (cache on vs off), "
          f"hit_rate={px['hit_rate']:.3f}, "
          f"flops_saved={px['prefill_flops_saved_frac']:.3f}, "
          f"bit_identical={px['bit_identical']}, "
          f"executables_bounded={px['executables_bounded']}")
    ch = result["chaos_soak"]
    print(f"chaos:        conservation={ch['conservation_ok']}, "
          f"bit_identical={ch['bit_identical_survivors']}, "
          f"readmitted={ch['slice_readmitted']}, "
          f"dead_letter={ch['dead_letter_exercised']}, "
          f"breaker={ch['breaker_exercised']}, "
          f"post_recovery={ch['post_recovery_ratio']:.3f}x "
          f"(ok={ch['post_recovery_ok']}), "
          f"trace_deterministic={ch['trace_export_deterministic']}")
    mt = result["multi_tenant"]
    print(f"tenants:      {mt['tokens_per_s']:.1f} useful tokens/s, "
          f"{len(mt['per_tenant'])} models x {MT_SLICES_EACH} slices each, "
          f"conservation={mt['conservation_per_tenant']}, "
          f"bit_identical={mt['bit_identical_per_tenant']}, "
          f"isolation={mt['no_cross_tenant_routing']}, "
          f"executables_bounded={mt['executables_bounded']}")
    pc = result["partition_controller"]
    print(f"controller:   p99={pc['controller']['p99_latency_ms']:.1f}ms "
          f"goodput={pc['controller']['goodput_tokens_per_s']:.1f} tok/s "
          f"vs static "
          + " ".join(
              f"[{n}]={p['p99_latency_ms']:.1f}ms/"
              f"{p['goodput_tokens_per_s']:.1f}"
              for n, p in pc["static"].items())
          + f"; reconfigs={pc['reconfigs']} "
          f"wins_every_point={pc['wins_every_point']}, "
          f"both_directions={pc['both_directions']}, "
          f"deterministic={pc['decision_log_deterministic']}, "
          f"bit_identical={pc['bit_identical_survivors']}")


if __name__ == "__main__":
    main()
