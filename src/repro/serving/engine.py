"""Real-execution serving engine (reduced models, CPU or a pod slice).

Compile-once hot path: prefill inputs are left-padded to power-of-two
(batch, length) shape buckets and dispatched through `_prefill_cache`, a
jitted-executable cache keyed on the padded shape; padded positions are
masked out of attention and the KV cache (lm.forward pos_offset), so padding
never changes a request's logits.

Two decode regimes share that prefill discipline:

* run-to-completion (`continuous=False`): each formed batch runs one fused
  jitted `lm.generate` — `max_new_tokens` steps in one `lax.scan` with the KV
  cache donated. Simple, but a batch occupies the model for the full scan
  even after most rows finish, and new arrivals wait it out (head-of-line
  blocking at the latency/throughput knee).

* continuous batching (`continuous=True`): the KV cache is ONE fixed-shape
  slot pool `[max_slots, pool_cache_len]` allocated up front; serving is a
  loop of admit -> decode-segment -> retire. Admission prefills a left-padded
  prompt bucket and scatters it into free row slots (`lm.prefill_into_slots`,
  one executable per prompt bucket, `row_tile` rows wide; a larger group
  runs as several calls of it); decode runs `lm.decode_segment`
  (`segment_len` steps in one jitted scan, pool donated); finished/EOS rows
  free their slots between segments and queued requests join without waiting
  for the pool to drain. A single scalar clock is the shared padded write
  position; per-slot `pos_offset` maps it to each request's true position,
  so a request's tokens are bit-identical to decoding it alone (see
  tests/test_engine_hotpath.py). Steady-state serving traces exactly two
  programs: one prefill bucket + one segment.

* chunked prefill (`chunk_lens` non-empty, model permitting —
  `lm.supports_chunked_prefill`): a prompt bucket longer than the policy-
  chosen chunk length admits across MULTIPLE engine steps, one
  `lm.prefill_chunk_into_slots` call per step interleaved with the decode
  segments, so a huge prompt never freezes resident decoders (the last
  head-of-line source). Mid-prefill rows hold their slots but are not
  `live`: segments skip their token production, and their `pos_offset` is
  refreshed to `clock - filled` before every segment so the segment's
  (ignored) write for such a row always lands at ring slot >= the filled
  prefix — stale garbage sits only above the row's current position, where
  the same causal masking that covers unwritten decode slots hides it, and
  later chunks / decode steps overwrite it before it can ever be read.
  Outputs are bit-identical to monolithic admission (the chunk program
  writes the same TRUE-POSITION cache layout), each chunk program touches
  only the ring prefix [0, prompt bucket) — so a chunk costs its share of
  the bucket's monolithic prefill, not a full-ring scan — and the
  executable set is one per (chunk length, prompt bucket): steady-state
  executables = #chunk buckets + 1 segment.

Composes the DPU/CPU preprocess runtime (same-shape pending requests are
preprocessed through one batched CU launch at submit), the BucketedBatcher
(knee-driven batch formation), and the SlotScheduler (admission order +
segment length). The legacy per-batch-shape / per-token path is kept behind
EngineConfig (pad_buckets=False, fused_decode=False) as the benchmark
baseline.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.batching.buckets import (
    Batch, BucketedBatcher, Request, next_pow2,
)
from repro.core.batching.policy import BatchPolicy, pick_chunk_len
from repro.core.batching.scheduler import SlotScheduler
from repro.core.dpu.runtime import DPU, DpuConfig
from repro.core.metrics import MetricsRegistry
from repro.core.prefix import PrefixLease, PrefixStore
from repro.models import api, lm
from repro.serving import telemetry as tm


@dataclass
class EngineConfig:
    max_new_tokens: int = 8        # decode budget cap (per-request budgets clamp to it)
    bucket_width: float = 64.0     # prompt-length buckets (tokens)
    preprocess: str = "none"       # none | dpu (audio/image frontends)
    pad_buckets: bool = True       # pow2 (batch, len) shape buckets + masking
    fused_decode: bool = True      # lax.scan lm.generate vs per-token loop
    min_prompt_len: int = 8        # shortest padded prompt length
    # --- continuous batching (slot pool + segmented decode) ---
    continuous: bool = False       # slot-pool admit/segment/retire loop
    max_slots: int = 8             # KV slot-pool rows (in-flight requests)
    segment_len: int = 8           # decode steps per jitted segment
    segment_lens: Tuple[int, ...] = ()  # scheduler choices; () = fixed segment_len
    max_prompt_len: int = 64       # largest padded prompt bucket the pool accepts
    pool_cache_len: int = 0        # 0 -> max_prompt_len + max_new_tokens + max segment
    eos_id: Optional[int] = None   # retire a row early when it emits this token
    # --- chunked prefill (long-prompt admission split across steps) ---
    # candidate chunk lengths (pow2); () disables chunking. The policy picks
    # one per admission (policy.pick_chunk_len); buckets longer than the
    # pick admit chunk-by-chunk, interleaved with decode segments. Silently
    # inert for model families lm.supports_chunked_prefill rejects.
    chunk_lens: Tuple[int, ...] = ()
    # --- radix prefix KV cache (cross-request shared-prefix reuse) ---
    # host byte budget for the per-engine radix store; 0 disables. Requires
    # chunked prefill (hits resume suffix chunks at the matched length), so
    # it is silently inert without chunk_lens or on unsupported families.
    prefix_cache_bytes: int = 0


_next_pow2 = next_pow2  # shared shape-bucket formula (buckets.next_pow2)

# Tokens one prefill call computes, summed over its rows, for the call to be
# compute-bound. A v5e does 197 TFLOP/s against 819 GB/s of HBM, a ridge of
# ~240 FLOP per byte; a bf16 matmul does 2 FLOP per 2-byte weight per token,
# so from ~240 tokens a call no longer waits on reading the weights. 512 is
# about twice that: splitting a group into calls of row_tile rows costs no
# matmul efficiency, and a call of a few live rows no longer computes the
# whole pool.
PREFILL_CALL_TOKENS = 512


def row_tile(max_slots: int, row_tokens: int) -> int:
    """Rows of one prefill call whose rows each contribute `row_tokens`
    positions (the prompt bucket of a one-pass admission, the chunk length
    of a chunk): the power of two reaching PREFILL_CALL_TOKENS, capped at
    the pool. One width per program, so the program set is unchanged."""
    return min(max_slots, _next_pow2(-(-PREFILL_CALL_TOKENS // row_tokens)))


def validate_requests(reqs: List[Request], ec: EngineConfig,
                      *, check_bucket: bool) -> None:
    """Front-door request validation, shared by every intake path (eager
    submit_many AND the stage-pipelined runtime): a malformed request must
    fail BEFORE anything is enqueued — raising at admission time would drop
    the whole already-popped admission group, valid requests included.

    * a real tokenized prompt (Request.prompt) must carry exactly
      max(1, int(length)) ids — length drives bucket choice and cache
      sizing, so a mismatch would silently corrupt positions;
    * on the slot-pool path the padded prompt bucket must fit
      max_prompt_len (run-to-completion sizes its cache per batch)."""
    for r in reqs:
        n = max(1, int(r.length))
        if r.prompt is not None and len(r.prompt) != n:
            raise ValueError(
                f"request {r.rid}: prompt carries {len(r.prompt)} tokens "
                f"but length={r.length} implies {n}"
            )
        if check_bucket:
            lp = max(ec.min_prompt_len, _next_pow2(n))
            if lp > ec.max_prompt_len:
                raise ValueError(
                    f"request {r.rid}: prompt bucket {lp} exceeds "
                    f"max_prompt_len={ec.max_prompt_len}; raise "
                    "EngineConfig.max_prompt_len"
                )


def enqueue_requests(reqs: List[Request], *, ec: EngineConfig,
                     dpu: Optional[DPU], batcher: BucketedBatcher,
                     stats: Dict[str, int], validate_prompts: bool) -> None:
    """Shared admission contract for ServingEngine and MultiSliceEngine:
    validate every request up front (see validate_requests), run ONE batched
    DPU preprocessing pass over the submission (DPU.process_batch groups
    same-shape requests into a single Pallas launch per functional unit),
    then enqueue."""
    validate_requests(reqs, ec, check_bucket=validate_prompts)
    if dpu is not None:
        idx = [i for i, r in enumerate(reqs) if r.payload is not None]
        if idx:
            outs = dpu.process_batch([reqs[i].payload for i in idx])
            for i, y in zip(idx, outs):
                reqs[i].payload = y
            stats["dpu_batches"] += 1
    now = time.monotonic()
    for r in reqs:
        r.preprocessed_at = now
        batcher.enqueue(r)


@dataclass
class _Slot:
    """Host-side state of one occupied pool row.

    `live=False` marks a mid-prefill row (chunked admission in progress):
    it holds its slot but produces no tokens and never retires; `filled`
    is its TRUE-position prefix length written so far (the garbage-write
    floor for interleaved decode segments)."""

    req: Request
    budget: int
    produced: List[int]
    live: bool = True
    filled: int = 0


@dataclass
class _ChunkAdmission:
    """One in-flight chunked admission group: a bucket-pure left-padded
    prompt block being written into the pool chunk-by-chunk. `toks`/`off`
    are host arrays laid out on POOL ROWS (row s is slot s; only the rows
    of `slots` are read)."""

    reqs: List[Request]
    slots: List[int]
    toks: np.ndarray         # [max_slots, lp] left-padded prompt tokens
    off: np.ndarray          # [max_slots] left-pad of the rows in `slots`
    lp: int
    chunk: int
    pos: int = 0             # next padded column to process (past base)
    # prefix-cache resume: first padded column this admission actually
    # computes (a chunk multiple; columns [0, base) were either scattered
    # from the radix store at true positions [0, match) or are left-pad).
    # Hit groups are split per base so each admission stays column-pure;
    # classes of the same (chunk, lp) still merge into one program call.
    base: int = 0
    # rows whose TTFT was already stamped at the scatter step (entire
    # prompt served from the prefix store — see _begin_chunked); the
    # final chunk must not overwrite their earlier stamp
    stamped: List[int] = field(default_factory=list)


class ServingEngine:
    """Single-slice engine: enqueue requests, run_until_idle() drains them
    through preprocess -> dynamic batching -> prefill -> decode.

    `stats` is a registry-backed view tracking the compile-once invariant:
    `prefill_traces` / `generate_traces` / `segment_traces` /
    `decode_step_traces` increment only while JAX is tracing (Python side
    effects don't run on cached executables), and `prefill_cache_hits`
    counts bucket reuse. Continuous batching adds `admitted` / `retired` /
    `segments` counters and the `engine_slot_occupancy_ratio` histogram
    (active-slot fraction per segment). Exec times, request latency, and
    TTFT are streaming histograms on the same registry; lifecycle events
    (admit / prefill_chunk / prefix_scatter / decode_segment / retire) land
    on the shared tracer.
    """

    # trace/compile counters mirror the jitted-executable caches, which a
    # metrics reset does NOT evict — they are registered `persistent` and
    # readers diff across the warmup boundary (the bench harness already
    # does exactly that)
    _PERSISTENT_STATS = (
        "prefill_compiles", "prefill_cache_hits", "prefill_traces",
        "generate_traces", "segment_traces", "decode_step_traces",
        "prefix_scatter_traces",
    )

    def __init__(self, cfg: ModelConfig, params, policy: BatchPolicy,
                 ec: Optional[EngineConfig] = None, *,
                 knee_profiles: Optional[Dict[int, Any]] = None,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[tm.Tracer] = None,
                 slice_id: Optional[int] = None,
                 tenant: Optional[str] = None):
        # mutable-default hazard: a shared EngineConfig() default instance
        # would leak field mutations across engines — build a fresh one here.
        ec = EngineConfig() if ec is None else ec
        self.cfg = cfg
        self.params = params
        self.policy = policy
        self.ec = ec
        # measured/analytical latency knees per prompt bucket (build_engine
        # supplies them); pick_chunk_len uses them to bound how long a chunk
        # may stall resident decoders instead of the pure pressure heuristic
        self._knee_profiles = knee_profiles or {}
        self.batcher = BucketedBatcher(policy)
        self.dpu = DPU(DpuConfig()) if ec.preprocess == "dpu" else None
        self.completed: List[Request] = []
        self.batch_exec_s: List[float] = []
        # telemetry: every counter/histogram lives in the registry (a fresh
        # engine gets a fresh registry, so slice rebuilds keep their
        # fresh-counter semantics; composing layers attach it as a child).
        # The tracer is shared downward by the composing layer; timestamps
        # come from the caller's clock under virtual replay (_stamp).
        self._sid = slice_id
        self._tenant = tenant
        self._labels = {"slice": "-" if slice_id is None else str(slice_id),
                        "tenant": tenant if tenant is not None else "-"}
        self.registry = registry if registry is not None \
            else MetricsRegistry("engine")
        self.tracer = tracer if tracer is not None else tm.Tracer()
        self._virtual = False  # virtual-clock stamping (set by the runtime)
        self.stats = self.registry.view("engine", (
            "batches",
            "prefill_compiles",
            "prefill_cache_hits",
            "prefill_traces",
            "generate_traces",
            "segment_traces",
            "decode_step_traces",
            "admitted",
            "retired",
            "segments",
            "dpu_batches",
            # radix prefix cache (zero when disabled; bench/CI read these
            # uniformly): hit admissions, K/V tokens reused instead of
            # recomputed, total prompt tokens admitted, store inserts, and
            # the hit path's own trace counter (one scatter program per
            # bucket, compiled at warmup — steady state retraces nothing)
            "prefix_hits",
            "prefix_hit_tokens",
            "prefix_prompt_tokens",
            "prefix_inserts",
            "prefix_scatter_traces",
            # output tokens appended to slot rows (first tokens at admit and
            # final chunk, segment tokens within budget, up to an EOS)
            "tokens_served",
            # rows the admit and chunk programs computed (row tile x calls),
            # padding rows included
            "prefill_rows_computed",
        ), labels=self._labels, persistent=self._PERSISTENT_STATS)
        self._h_exec = self.registry.histogram(
            "engine_batch_exec_seconds", self._labels)
        self._h_occ = self.registry.histogram(
            "engine_slot_occupancy_ratio", self._labels)
        self._h_lat = self.registry.histogram(
            "request_latency_seconds", self._labels)
        self._h_ttft = self.registry.histogram(
            "request_ttft_seconds", self._labels)
        self.registry.on_reset(self._reset_state)
        # (padded_batch, padded_len) -> jitted prefill executable
        self._prefill_cache: Dict[Tuple[int, int], Any] = {}

        def _generate(p, cache, logits, pos0, off):
            self.stats["generate_traces"] += 1  # trace-time only
            return lm.generate(p, cache, logits, pos0, cfg,
                               steps=ec.max_new_tokens, pos_offset=off)

        # donate the KV cache: the scan consumes it in place, no copies
        self._generate_jit = jax.jit(_generate, donate_argnums=(1,))

        def _decode_step(p, c, t, pos, off):
            self.stats["decode_step_traces"] += 1  # trace-time only
            return lm.decode(p, c, t, pos, cfg, pos_offset=off)

        self._decode_jit = jax.jit(_decode_step)

        # --- continuous-batching state (slot pool) -------------------------
        self.slot_scheduler: Optional[SlotScheduler] = None
        if ec.continuous:
            seg_max = max(ec.segment_lens or (ec.segment_len,))
            self.pool_len = ec.pool_cache_len or (
                ec.max_prompt_len + ec.max_new_tokens + seg_max
            )
            assert self.pool_len >= ec.max_prompt_len + ec.max_new_tokens, (
                "pool_cache_len too small for max_prompt_len + max_new_tokens"
            )
            # profile_for lets pick_segment_len bound the segment by the
            # measured batch knee (same wiring as pick_chunk_len): a long
            # segment stalls queued admissions for S sequential steps, so
            # the knee of the dominant waiting prompt bucket caps S
            self.slot_scheduler = SlotScheduler(
                policy, max_slots=ec.max_slots,
                segment_len=ec.segment_len, segment_lens=ec.segment_lens,
                profile_for=self._profile_for,
            )
            self._pool = None                     # allocated on first admit
            self._slots: List[Optional[_Slot]] = [None] * ec.max_slots
            self._pool_off = np.zeros(ec.max_slots, np.int32)
            self._tok = np.zeros((ec.max_slots, 1), np.int32)
            # clock >= any padded prompt bucket keeps pos_offset
            # (= clock - prompt_len) non-negative; reset when idle. Ring
            # placement itself is clock-independent (true-position indexed
            # per row, lm._attn_decode), so outputs never depend on WHEN a
            # request is admitted.
            self._clock = ec.max_prompt_len
            # lp -> jitted prefill+admit executable
            self._admit_cache: Dict[int, Any] = {}
            # --- chunked prefill ---
            # chunk lengths the policy may pick; empty when disabled or the
            # model family has no chunk path (monolithic admission fallback)
            self._chunk_lens: Tuple[int, ...] = (
                tuple(sorted(set(int(c) for c in ec.chunk_lens)))
                if ec.chunk_lens and lm.supports_chunked_prefill(cfg) else ()
            )
            self._chunk_q: List[_ChunkAdmission] = []
            # (chunk len, prompt bucket) -> chunk executable
            self._chunk_cache: Dict[Tuple[int, int], Any] = {}
            # --- radix prefix KV cache (per-engine store; multi-slice
            # engines each own one, so hits never copy KV across slices) ---
            self.prefix_store: Optional[PrefixStore] = None
            if ec.prefix_cache_bytes and self._chunk_lens:
                from repro.core.batching import kv_bytes_per_token
                tb = kv_bytes_per_token(cfg)
                assert tb > 0, cfg.name  # attn-only families (chunk-gated)
                self.prefix_store = PrefixStore(
                    ec.prefix_cache_bytes, tb, registry=self.registry,
                    labels=self._labels)
            self._prefix_leases: Dict[int, PrefixLease] = {}  # rid -> pin
            self._prefix_scatter_cache: Dict[int, Any] = {}   # lp -> jit

            def _segment(p, cache, tok, clock, off, steps):
                self.stats["segment_traces"] += 1  # trace-time only
                return lm.decode_segment(p, cache, tok, clock, cfg,
                                         steps=steps, pos_offset=off)

            self._segment_jit = jax.jit(
                _segment, static_argnums=(5,), donate_argnums=(1,)
            )

    # --- queueing ----------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.submit_many([req])

    def submit_many(self, reqs: List[Request]) -> None:
        """Enqueue requests; with preprocess='dpu', pending requests carrying
        raw inputs in `payload` are preprocessed as ONE batched CU pass
        instead of one launch per request. Prompt buckets are validated only
        on the slot-pool path (run-to-completion sizes its cache per
        batch)."""
        enqueue_requests(reqs, ec=self.ec, dpu=self.dpu,
                         batcher=self.batcher, stats=self.stats,
                         validate_prompts=self.ec.continuous)

    def offer(self, reqs: List[Request]) -> None:
        """Stage-pipelined admission intake (serving/runtime.py): requests
        whose preprocessing already completed join the SlotScheduler's EDF
        backlog directly — the preprocess-complete queue replaces
        submit_many's eager inline DPU pass. The runtime validates at its
        front door (validate_requests), and plan() still forms bucket-pure
        left-padded groups, so the compile-once invariant holds."""
        if not self.ec.continuous:
            raise ValueError("pipelined admission requires continuous=True")
        self.slot_scheduler.offer(reqs)

    def admission_depth(self) -> int:
        """Requests waiting for a KV slot (batcher + scheduler backlog) —
        the pipelined runtime's backpressure signal for this stage."""
        d = self.batcher.pending()
        if self.ec.continuous:
            d += self.slot_scheduler.depth()
        return d

    def cancel(self, rids: Iterable[int]) -> int:
        """Abandon requests by rid wherever they are: queued in the batcher,
        backlogged in the slot scheduler, occupying a pool slot mid-decode,
        or already finished but not yet harvested (`completed`). Used by the
        multi-slice engine to kill a hedge twin's copies once the other slice
        wins, and to drain a slice for an elastic re-slice. A cancelled
        slot's stale KV stays masked (pos_offset is rewritten on the next
        admission), exactly like a normal retire. Returns the number of
        live (not-yet-completed) requests removed."""
        rids = set(rids)
        n = 0
        for bucket in self.batcher.buckets.values():
            kept = [r for r in bucket.queue if r.rid not in rids]
            n += len(bucket.queue) - len(kept)
            bucket.queue = deque(kept)
        if self.ec.continuous:
            n += self.slot_scheduler.cancel(rids)
            # mid-chunk cancellation: drop the row from its in-flight chunked
            # admission (later chunk calls name only the admission's slots,
            # so none touches it); the slot loop below frees and counts it
            # like any occupied row
            for adm in list(self._chunk_q):
                keep = [(r, s) for r, s in zip(adm.reqs, adm.slots)
                        if r.rid not in rids]
                adm.reqs = [r for r, _ in keep]
                adm.slots = [s for _, s in keep]
                if not adm.reqs:
                    self._chunk_q.remove(adm)
            for s, st in enumerate(self._slots):
                if st is not None and st.req.rid in rids:
                    self._slots[s] = None
                    n += 1
            # drop prefix-store pins of every cancelled request (queued OR
            # slotted): a hedge loser / resize victim must not keep its
            # matched path unevictable forever
            if self.prefix_store is not None:
                for rid in rids:
                    lease = self._prefix_leases.pop(rid, None)
                    if lease is not None:
                        self.prefix_store.release(lease)
        self.completed = [r for r in self.completed if r.rid not in rids]
        return n

    def busy(self) -> bool:
        if self.batcher.pending():
            return True
        if self.ec.continuous:
            return bool(self.slot_scheduler.backlog()) or any(
                s is not None for s in self._slots
            )
        return False

    def step(self, now: Optional[float] = None) -> bool:
        """One engine iteration; returns True if any work was done.

        Run-to-completion: execute every batch due at `now`. Continuous:
        admit due requests into free slots, run one decode segment, retire
        finished rows."""
        now = time.monotonic() if now is None else now
        if not self.ec.continuous:
            batches = self.batcher.poll(now)
            for b in batches:
                self._execute(b)
            return bool(batches)

        plan = self.slot_scheduler.plan(
            self.batcher, now, free_slots=self._free_slots()
        )
        progressed = False
        for group in plan.admissions:
            lp = max(self.ec.min_prompt_len,
                     _next_pow2(max(max(1, int(r.length)) for r in group)))
            c = self._pick_chunk(lp)
            if c:
                self._begin_chunked(group, lp, c, now)
            else:
                self._admit(group, now)
            progressed = True
        # advance every in-flight chunked admission by ONE chunk, so chunk
        # work and the decode segment below interleave step by step and a
        # long prompt never freezes resident decoders
        progressed |= self._advance_chunks(now)
        if any(st is not None and st.live for st in self._slots):
            self._decode_segment(plan.segment_len, now)
            progressed = True
        elif all(st is None for st in self._slots) \
                and not self.slot_scheduler.backlog() \
                and not self.batcher.pending():
            # pool drained: rewind the clock so int32 positions stay small
            # (placement is clock-independent; this is pure hygiene).
            # Mid-prefill-only pools skip the segment entirely — it would
            # decode nothing but masked garbage rows.
            self._clock = self.ec.max_prompt_len
            self._pool_off[:] = 0
        return progressed

    def run_until_idle(self) -> List[Request]:
        while self.busy():
            progressed = self.step()
            if not progressed:
                # advance the logical clock to the earliest real flush
                # deadline (no busy spin, and formed_at records the true
                # flush time instead of a fabricated now + time_queue)
                deadline = self.batcher.next_deadline()
                self.step(deadline if deadline is not None else time.monotonic())
        return self.completed

    # --- telemetry ----------------------------------------------------------
    def _stamp(self, now: Optional[float]) -> float:
        """Timestamp for request lifecycle stamps and tracer events: the
        caller's clock under virtual replay — so exported timelines are a
        deterministic pure function of trace + fault plan — and wall time
        otherwise, so wall-mode TTFT still includes real prefill execution
        (identical to the historical stamping)."""
        if self._virtual and now is not None:
            return now
        return time.monotonic()

    def _timed(self, kind: str, now: Optional[float], **fields):
        """An open tracer span of this engine, timed on `_stamp`'s clock."""
        return self.tracer.timed(kind, lambda: self._stamp(now),
                                 sid=self._sid, tenant=self._tenant, **fields)

    def _reset_state(self) -> None:
        """Registry reset hook: clear Python-side accumulators alongside
        the counters so no signal survives the warmup boundary unpaired.
        `batch_exec_s` is also the EMA drain buffer of composing layers;
        their own hooks rewind the drain marks in the same reset pass."""
        self.completed.clear()
        self.batch_exec_s.clear()
        self.tracer.reset()

    def reset_metrics(self) -> None:
        """One registry-wide reset (warmup boundary): zeroes every
        non-persistent counter/histogram (prefix-store counters included —
        the registry is shared) and runs the reset hooks. Trace/compile
        counters persist (they mirror executable caches); readers diff."""
        self.registry.reset()

    # --- hot path ----------------------------------------------------------
    def bucket_shape(self, batch_size: int, max_len: int) -> Tuple[int, int]:
        """Power-of-two (batch, length) shape bucket for a ragged batch."""
        if not self.ec.pad_buckets:
            return batch_size, max(self.ec.min_prompt_len, max_len)
        return (
            _next_pow2(batch_size),
            max(self.ec.min_prompt_len, _next_pow2(max_len)),
        )

    def _prompt_tokens(self, req: Request, n: int) -> np.ndarray:
        """Prompt tokens for a request: the explicit token array when the
        request carries one (req.prompt — real tokenized workloads, length
        validated at the front door), else the deterministic per-rid
        synthetic generator (the benchmark workload)."""
        if req.prompt is not None:
            return np.asarray(req.prompt, np.int32)
        rng = np.random.default_rng(req.rid)
        return rng.integers(0, self.cfg.vocab, n)

    def _budget(self, req: Request) -> int:
        b = self.ec.max_new_tokens if req.max_new_tokens is None else req.max_new_tokens
        return max(1, min(b, self.ec.max_new_tokens))

    def _left_pad_prompts(self, reqs: List[Request], lens: List[int],
                          bp: int, lp: int):
        """Shared left-pad fill for prefill and slot admission: returns
        (tokens [bp, lp], pos_offset [bp]); rows beyond len(reqs) stay fully
        padded (offset == lp)."""
        toks = np.zeros((bp, lp), np.int32)
        off = np.full(bp, lp, np.int32)
        for i, r in enumerate(reqs):
            n = lens[i]
            toks[i, lp - n:] = self._prompt_tokens(r, n)
            off[i] = lp - n
        return toks, off

    def _pad_batch(self, batch: Batch):
        """Left-pad prompts into the shape bucket. Returns (tokens [Bp, Lp],
        pos_offset [Bp] or None, (Bp, Lp)). Rows beyond the real batch are
        fully padded (offset == Lp) and their outputs discarded."""
        lens = [max(1, int(r.length)) for r in batch.requests]
        bp, lp = self.bucket_shape(len(batch.requests), max(lens))
        if self.ec.pad_buckets:
            toks, off = self._left_pad_prompts(batch.requests, lens, bp, lp)
            return jnp.asarray(toks), jnp.asarray(off), (bp, lp)
        toks = np.zeros((bp, lp), np.int32)
        for i, r in enumerate(batch.requests):  # legacy: right-pad with zeros
            toks[i, :lens[i]] = self._prompt_tokens(r, lens[i])
        return jnp.asarray(toks), None, (bp, lp)

    def _get_prefill(self, bp: int, lp: int):
        """Jitted-executable cache keyed on the padded shape bucket."""
        key = (bp, lp)
        fn = self._prefill_cache.get(key)
        if fn is not None:
            self.stats["prefill_cache_hits"] += 1
            return fn
        cache_len = lp + self.ec.max_new_tokens  # decode ring never wraps

        def _prefill(p, toks, off, _cl=cache_len):
            self.stats["prefill_traces"] += 1  # trace-time only
            return lm.prefill(p, toks, self.cfg, pos_offset=off, cache_len=_cl)

        fn = jax.jit(_prefill)
        self._prefill_cache[key] = fn
        self.stats["prefill_compiles"] += 1
        return fn

    def _execute(self, batch: Batch) -> None:
        t0 = time.monotonic()
        toks, off, (bp, lp) = self._pad_batch(batch)
        logits, cache = self._get_prefill(bp, lp)(self.params, toks, off)
        if self.ec.fused_decode:
            out, _ = self._generate_jit(self.params, cache, logits, jnp.int32(lp), off)
            tokens = np.asarray(out)
        else:
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            outs = [tok]
            pos = lp
            for _ in range(self.ec.max_new_tokens - 1):
                logits, cache = self._decode_jit(
                    self.params, cache, tok, jnp.int32(pos), off
                )
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                outs.append(tok)
                pos += 1
            tokens = np.concatenate([np.asarray(o) for o in outs], axis=1)
        done = time.monotonic()
        self.stats["batches"] += 1
        self.batch_exec_s.append(done - t0)
        self._h_exec.observe(done - t0)
        for i, r in enumerate(batch.requests):
            r.dispatched_at = t0
            r.completed_at = done
            # run-to-completion materializes all tokens at once: first token
            # observable no earlier than the batch finishing
            r.first_token_at = done
            # run-to-completion decodes the full scan regardless; honor the
            # per-request budget by truncation (the wasted steps are the cost
            # continuous batching removes)
            r.payload = self._truncate(tokens[i], self._budget(r))
            self.completed.append(r)
            self._h_lat.observe(done - r.arrival)
            self._h_ttft.observe(done - r.arrival)
            self.tracer.event(tm.RETIRE, done, rid=r.rid, sid=self._sid,
                              tenant=self._tenant, tokens=len(r.payload))

    def _truncate(self, tokens, budget: int) -> np.ndarray:
        out = np.asarray(tokens[:budget], np.int32)
        if self.ec.eos_id is not None:
            hits = np.flatnonzero(out == self.ec.eos_id)
            if hits.size:
                out = out[: hits[0] + 1]
        return out

    # --- continuous batching (slot pool + segmented decode) ----------------
    def _free_slots(self) -> int:
        return sum(1 for s in self._slots if s is None)

    def _ensure_pool(self) -> None:
        """Allocate the slot pool where the params live (a slice engine's
        own devices), and send every later host input there directly."""
        if self._pool is None:
            self._dev = jax.tree.leaves(self.params)[0].sharding
            self._pool = lm.alloc_slot_pool(
                self.cfg, self.ec.max_slots, self.pool_len, self._dev
            )

    def _put(self, x) -> jax.Array:
        """Host array -> this engine's devices (pool allocated first). A
        plain transfer: unlike `jnp.asarray(x, device=...)`, it compiles no
        conversion program for each new input shape."""
        return jax.device_put(x, self._dev)

    def _get_admit(self, lp: int):
        """Jitted prefill+admit executable, one per padded prompt length.
        Its row width is pinned to row_tile(max_slots, lp) so the program
        never retraces as group sizes vary (compile-once over the whole
        stream); a wider group runs as several calls."""
        fn = self._admit_cache.get(lp)
        if fn is not None:
            self.stats["prefill_cache_hits"] += 1
            return fn

        def _admit(p, toks, off, pool, slot_ids, clock):
            self.stats["prefill_traces"] += 1  # trace-time only
            return lm.prefill_into_slots(
                p, toks, pool, slot_ids, clock, self.cfg, pos_offset=off
            )

        fn = jax.jit(_admit, donate_argnums=(3,))
        self._admit_cache[lp] = fn
        self.stats["prefill_compiles"] += 1
        return fn

    def _admit(self, reqs: List[Request],
               now: Optional[float] = None) -> None:
        """Prefill a left-padded admission group and join it into free
        slots, in calls of the bucket's row tile (all dispatched, the pool
        chained through them, then one sync). Its `admission` span runs
        from the group's admission start: `prepare` (host padding up to the
        last dispatch), `wait` (to the first tokens on the host), `host`
        (stamping, retiring)."""
        with self._timed(tm.ADMISSION, now, rids=[r.rid for r in reqs],
                         rows=len(reqs)) as sp:
            sp.phase("prepare")
            self._ensure_pool()
            free = [i for i, s in enumerate(self._slots) if s is None]
            assert len(reqs) <= len(free), (len(reqs), len(free))
            lens = [max(1, int(r.length)) for r in reqs]
            lp = max(self.ec.min_prompt_len, _next_pow2(max(lens)))
            assert lp <= self.ec.max_prompt_len, lp  # enforced at submit
            assert self._clock >= lp  # starts at max_prompt_len, only grows
            sp.set(bucket=lp)
            rt = row_tile(self.ec.max_slots, lp)
            n_rows = -(-len(reqs) // rt) * rt
            toks, off = self._left_pad_prompts(reqs, lens, n_rows, lp)
            # padding rows name a row past the pool: their write is dropped
            sids = np.full(n_rows, self.ec.max_slots, np.int32)
            sids[: len(reqs)] = free[: len(reqs)]
            fn = self._get_admit(lp)
            clock = self._put(np.int32(self._clock))
            outs = []
            for i in range(0, n_rows, rt):
                tok, self._pool = fn(
                    self.params, self._put(toks[i:i + rt]),
                    self._put(off[i:i + rt]), self._pool,
                    self._put(sids[i:i + rt]), clock,
                )
                outs.append(tok)
            sp.phase("wait")
            tok0 = np.concatenate([np.asarray(t) for t in outs])
            sp.phase("host")
            t = self._stamp(now)
            for i, r in enumerate(reqs):
                s = free[i]
                self._pool_off[s] = self._clock - lens[i]
                self._tok[s] = tok0[i]
                self._slots[s] = _Slot(req=r, budget=self._budget(r),
                                       produced=[int(tok0[i, 0])])
                r.dispatched_at = t
                r.first_token_at = t  # TTFT: prefill emits the first token
                self.stats["prefix_prompt_tokens"] += lens[i]
            self.stats["admitted"] += len(reqs)
            self.stats["tokens_served"] += len(reqs)
            self.stats["prefill_rows_computed"] += n_rows
            self.tracer.event(tm.ADMIT, t, sid=self._sid,
                              tenant=self._tenant, bucket=lp,
                              rids=[r.rid for r in reqs], rows=len(reqs),
                              rows_computed=n_rows)
            self._retire_finished(t)  # budget-1 / instant-EOS requests

    # --- chunked prefill ----------------------------------------------------
    def _pick_chunk(self, lp: int) -> int:
        """Chunk length for a prompt bucket of padded length lp; 0 means
        monolithic admission (chunking disabled, unsupported family, or the
        bucket fits in one policy-chosen chunk)."""
        if not self._chunk_lens:
            return 0
        resident = sum(1 for s in self._slots if s is not None)
        waiting = self.slot_scheduler.backlog() + self.batcher.pending()
        c = pick_chunk_len(self._chunk_lens, resident=resident,
                           waiting=waiting,
                           profile=self._profile_for(lp))
        return c if c < lp else 0

    def _profile_for(self, lp: int):
        """Knee profile for a prompt bucket (nearest-bucket fallback like
        BatchPolicy.batch_max_for); None without profiles — pick_chunk_len
        then keeps the pure pool-pressure heuristic."""
        if not self._knee_profiles:
            return None
        b = int(lp / self.policy.bucket_width)
        key = min(self._knee_profiles, key=lambda k: abs(k - b))
        return self._knee_profiles[key]

    def prefix_peek(self, lp: int, tokens: np.ndarray) -> int:
        """Longest stored prefix match for affinity routing (multi-slice
        dispatch prefers the slice whose store knows the prompt best)."""
        if self.prefix_store is None:
            return 0
        return self.prefix_store.peek(lp, tokens)

    def prefix_peek_req(self, r: Request) -> int:
        """prefix_peek for a whole request: derives the prompt bucket and
        token ids the engine itself would use at admission, so the affinity
        router and the admission path can never disagree on the match."""
        if self.prefix_store is None:
            return 0
        n = max(1, int(r.length))
        lp = max(self.ec.min_prompt_len, _next_pow2(n))
        return self.prefix_store.peek(lp, self._prompt_tokens(r, n))

    def _begin_chunked(self, reqs: List[Request], lp: int, chunk: int,
                       now: Optional[float] = None) -> None:
        """Reserve slots for a chunked admission group and queue its prompt
        block; chunks run one per engine step (_advance_chunks), interleaved
        with decode segments.

        With a prefix store, each request's prompt is first looked up in the
        radix tree: a hit pins the matched path (lease held until retire or
        cancel), scatters the stored K/V into the row's true positions
        [0, m) in one batched per-bucket scatter program, and resumes chunk
        prefill at padded column off + m — a chunk multiple, so the suffix
        rides the existing (chunk, lp) executables with no new shapes. m is
        the largest usable match: m <= n-1 (the final chunk must still run
        to produce the first token at column lp-1) and m ≡ n (mod chunk)
        (off = lp - n, lp ≡ 0 mod chunk, so the resume column lands on the
        chunk grid). The group splits into one _ChunkAdmission per resume
        column; same-class admissions still merge into one call per step.

        Its `admission` span has one phase, `prepare`: the chunks and their
        sync come in later steps."""
        with self._timed(tm.ADMISSION, now, rids=[r.rid for r in reqs],
                         rows=len(reqs), bucket=lp) as sp:
            sp.phase("prepare")
            self._queue_chunked(reqs, lp, chunk, now)

    def _queue_chunked(self, reqs: List[Request], lp: int, chunk: int,
                       now: Optional[float]) -> None:
        self._ensure_pool()
        free = [i for i, s in enumerate(self._slots) if s is None]
        assert len(reqs) <= len(free), (len(reqs), len(free))
        assert lp % chunk == 0, (lp, chunk)  # both pow2, chunk < lp
        assert self._clock >= lp  # clock starts at max_prompt_len, only grows
        bp = self.ec.max_slots
        toks = np.zeros((bp, lp), np.int32)
        off = np.full(bp, lp, np.int32)
        slots = free[: len(reqs)]
        t = self._stamp(now)
        by_base: Dict[int, Tuple[List[Request], List[int]]] = {}
        hits: List[Tuple[int, int, Any]] = []  # (slot, m, host K/V tree)
        pre_stamped: set = set()
        for i, r in enumerate(reqs):
            n = max(1, int(r.length))
            s = slots[i]
            prompt = self._prompt_tokens(r, n)
            toks[s, lp - n:] = prompt
            off[s] = lp - n
            m = self._prefix_match(r, lp, chunk, n, prompt, hits, s)
            self._slots[s] = _Slot(req=r, budget=self._budget(r), produced=[],
                                   live=False, filled=m)
            self._pool_off[s] = self._clock - m  # refreshed per segment
            r.dispatched_at = t
            # hit rows resume at their aligned column; cold rows start at 0
            # (left-pad columns are fully masked, same as before)
            col = (lp - n) + m if m else 0
            if m and col >= lp:
                # the ENTIRE prompt was served from the store (zero suffix
                # chunks): the final-chunk TTFT stamp in _chunk_step can
                # never fire for this row, so the scatter below IS its first
                # observable progress — stamp TTFT here, then re-run the
                # last chunk anyway (an idempotent true-position K/V
                # rewrite) purely to produce the first-token logits that
                # seed decode. _prefix_match's n-1 cap makes this branch
                # unreachable today; it guards the invariant that a
                # completed request NEVER retires with first_token_at=None
                # (regression-tested in tests/test_telemetry.py).
                r.first_token_at = t
                pre_stamped.add(s)
                self._slots[s].filled = lp - int(off[s]) - chunk
                col = lp - chunk
            g = by_base.setdefault(col, ([], []))
            g[0].append(r)
            g[1].append(s)
        if hits:
            self._scatter_hits(hits, lp, t)
        for base, (greqs, gslots) in sorted(by_base.items()):
            self._chunk_q.append(_ChunkAdmission(
                reqs=greqs, slots=gslots, toks=toks, off=off, lp=lp,
                chunk=chunk, base=base,
                stamped=[s for s in gslots if s in pre_stamped],
            ))

    def _prefix_match(self, r: Request, lp: int, chunk: int, n: int,
                      prompt: np.ndarray, hits: List, s: int) -> int:
        """Radix lookup for one admission row: returns the usable matched
        length m (0 = cold), records the pinned lease and the assembled
        host K/V for the batched scatter."""
        self.stats["prefix_prompt_tokens"] += n
        if self.prefix_store is None:
            return 0
        lease = self.prefix_store.lookup(lp, prompt)
        if lease is None:
            return 0
        cap = min(lease.match_len, n - 1)
        m = cap - ((cap - n) % chunk)  # largest m <= cap with m ≡ n (mod c)
        if m <= 0:
            self.prefix_store.release(lease)
            return 0
        self._prefix_leases[r.rid] = lease
        hits.append((s, m, self.prefix_store.kv_prefix(lease, m)))
        self.stats["prefix_hits"] += 1
        self.stats["prefix_hit_tokens"] += m
        return m

    def _get_prefix_scatter(self, lp: int):
        """Jitted hit-scatter executable, one per prompt bucket (compiled
        at warmup alongside the bucket's chunk program; the hit path adds
        no shapes in steady state)."""
        fn = self._prefix_scatter_cache.get(lp)
        if fn is not None:
            self.stats["prefill_cache_hits"] += 1
            return fn

        def _scatter(pool, pre, sids, _lp=lp):
            self.stats["prefix_scatter_traces"] += 1  # trace-time only
            return lm.scatter_prefix_into_slots(pool, pre, sids, _lp)

        fn = jax.jit(_scatter, donate_argnums=(0,))
        self._prefix_scatter_cache[lp] = fn
        return fn

    def _scatter_hits(self, hits: List[Tuple[int, int, Any]], lp: int,
                      t: float) -> None:
        """Batched scatter of this admission's prefix hits: assemble one
        prefill-cache-shaped host tree (hit rows at their slot index, true
        positions [0, m) filled, rest zero — the zeros land on columns the
        suffix chunks overwrite or causal masking hides forever) and run
        the bucket's scatter program with the pool donated."""
        bp = self.ec.max_slots
        sids = np.full(bp, bp, np.int32)  # out-of-range rows -> dropped

        def _alloc(leaf):
            if leaf.ndim == 3:            # per-layer [m, kh, hd]
                return np.zeros((bp, lp) + leaf.shape[1:], leaf.dtype)
            return np.zeros((leaf.shape[0], bp, lp) + leaf.shape[2:],
                            leaf.dtype)  # stacked body [nb, m, kh, hd]

        batch = jax.tree.map(_alloc, hits[0][2])
        for s, m, kv in hits:
            sids[s] = s

            def _put(dst, src):
                if src.ndim == 3:
                    dst[s, :m] = src
                else:
                    dst[:, s, :m] = src

            jax.tree.map(_put, batch, kv)
        self._pool = self._get_prefix_scatter(lp)(
            self._pool, jax.tree.map(self._put, batch), self._put(sids)
        )
        self.tracer.event(
            tm.PREFIX_SCATTER, t, sid=self._sid, tenant=self._tenant,
            bucket=lp, rows=len(hits), tokens=sum(m for _, m, _ in hits))

    def _advance_chunks(self, now: Optional[float] = None) -> bool:
        """Advance every in-flight chunked admission by ONE chunk, merging
        admissions of the same (chunk len, prompt bucket) class into the
        same program calls (per-row start positions): trickled
        single-request admissions share a call instead of each paying one
        per chunk."""
        if not self._chunk_q:
            return False
        classes: Dict[Tuple[int, int], List[_ChunkAdmission]] = {}
        for adm in self._chunk_q:
            classes.setdefault((adm.chunk, adm.lp), []).append(adm)
        for (c, lp), adms in classes.items():
            self._chunk_step(c, lp, adms, now)
        self._chunk_q = [a for a in self._chunk_q if a.base + a.pos < a.lp]
        return True

    def _get_chunk(self, c: int, lp: int):
        """Jitted chunk executable, one per (chunk length, prompt bucket),
        row_tile(max_slots, c) rows wide: the program touches only the ring
        prefix [0, lp) of the rows it names, so each chunk costs what its
        share of the bucket's monolithic prefill would — the compile-once
        bound is #chunk buckets + 1 segment."""
        key = (c, lp)
        fn = self._chunk_cache.get(key)
        if fn is not None:
            self.stats["prefill_cache_hits"] += 1
            return fn

        def _chunk(p, toks, off, pool, start, slot_ids, _lp=lp):
            self.stats["prefill_traces"] += 1  # trace-time only
            return lm.prefill_chunk_into_slots(
                p, toks, pool, start, slot_ids, self.cfg, pos_offset=off,
                lp=_lp
            )

        fn = jax.jit(_chunk, donate_argnums=(3,))
        self._chunk_cache[key] = fn
        self.stats["prefill_compiles"] += 1
        return fn

    def _chunk_step(self, c: int, lp: int,
                    adms: List[_ChunkAdmission],
                    now: Optional[float] = None) -> None:
        """Run one chunk for every admission of a (chunk, bucket) class,
        its rows in calls of the chunk's row tile (per-row start and slot
        id; all dispatched, the pool chained through them); admissions
        reaching their final chunk flip their rows live (decode starts at
        the next segment).

        No sync precedes the final chunk's, so the `chunk_dispatch` span,
        PREFILL_CHUNK's `dur` and this call's exec time all measure the
        host's preparation and dispatch; the chunk's device time is read
        from a profile."""
        rids = [r.rid for a in adms for r in a.reqs]
        cols = [a.base + a.pos for a in adms]  # prefix hits resume past base
        with self._timed(tm.CHUNK_DISPATCH, now, bucket=lp, chunk=c,
                         rids=rids, cols=cols):
            t0 = time.monotonic()
            slots = [s for adm in adms for s in adm.slots]
            rt = row_tile(self.ec.max_slots, c)
            n_rows = -(-len(slots) // rt) * rt
            toks = np.zeros((n_rows, c), np.int32)
            # padding rows: fully masked (offset lp) and naming a row past
            # the pool, so nothing of theirs is read or written
            off = np.full(n_rows, lp, np.int32)
            start = np.zeros(n_rows, np.int32)
            sids = np.full(n_rows, self.ec.max_slots, np.int32)
            i = 0
            for adm, col in zip(adms, cols):
                for s in adm.slots:
                    toks[i] = adm.toks[s, col:col + c]
                    off[i] = adm.off[s]
                    start[i] = col
                    sids[i] = s
                    i += 1
            fn = self._get_chunk(c, lp)
            outs = []
            for i in range(0, n_rows, rt):
                tok, self._pool = fn(
                    self.params, self._put(toks[i:i + rt]),
                    self._put(off[i:i + rt]), self._pool,
                    self._put(start[i:i + rt]), self._put(sids[i:i + rt]),
                )
                outs.append(tok)
            exec_s = time.monotonic() - t0
        self.batch_exec_s.append(exec_s)
        self._h_exec.observe(exec_s)
        self.stats["prefill_rows_computed"] += n_rows
        self.tracer.event(
            tm.PREFILL_CHUNK, self._stamp(now), sid=self._sid,
            tenant=self._tenant, bucket=lp, chunk=c, rows=len(slots),
            rows_computed=n_rows, rids=rids,
            dur=None if self._virtual else exec_s)
        finished: List[_ChunkAdmission] = []
        for adm in adms:
            adm.pos += c
            for s in adm.slots:
                self._slots[s].filled = max(
                    0, adm.base + adm.pos - int(adm.off[s]))
            if adm.base + adm.pos >= adm.lp:
                finished.append(adm)
        if not finished:
            return
        # final chunk: column lp-1 is every row's last true prompt position,
        # so its greedy tokens seed decode exactly like prefill_into_slots
        tok0 = dict(zip(slots, np.concatenate([np.asarray(t) for t in outs])))
        t = self._stamp(now)
        for adm in finished:
            for s in adm.slots:
                st = self._slots[s]
                n = adm.lp - int(adm.off[s])
                self._pool_off[s] = self._clock - n
                self._tok[s] = tok0[s]
                st.produced = [int(tok0[s][0])]
                st.live = True
                if s not in adm.stamped:  # scatter-stamped rows keep theirs
                    st.req.first_token_at = t  # TTFT: final chunk greedy tok
            self.stats["admitted"] += len(adm.reqs)
            self.stats["tokens_served"] += len(adm.slots)
        self._retire_finished(t)

    def _decode_segment(self, steps: int,
                        now: Optional[float] = None) -> None:
        """One fused segment over the whole pool; finished rows retire
        after. Its `segment` span has the phases `prepare` (up to the
        dispatch), `wait` (to the tokens on the host) and `host` (token
        bookkeeping, retiring), and carries `live` (rows that decode) and
        `tokens` (tokens appended), as DECODE_SEGMENT does."""
        live = sum(1 for st in self._slots if st is not None and st.live)
        with self._timed(tm.SEGMENT, now, steps=int(steps), live=live) as sp:
            sp.phase("prepare")
            # mid-prefill rows: pin the (ignored) segment write to ring slot
            # `filled` — at or above the written prefix, below the pool ring
            # — so interleaved garbage can never land on real prompt KV and
            # stays behind the causal mask until a later chunk/decode
            # overwrites it
            for s, st in enumerate(self._slots):
                if st is not None and not st.live:
                    self._pool_off[s] = self._clock - st.filled
            t0 = time.monotonic()
            toks, self._pool = self._segment_jit(
                self.params, self._pool, self._put(self._tok),
                self._put(np.int32(self._clock)), self._put(self._pool_off),
                int(steps),
            )
            sp.phase("wait")
            toks = np.asarray(toks)
            sp.phase("host")
            self._clock += steps
            if self._clock >= self.ec.max_prompt_len + 8 * self.pool_len:
                self._rebase_clock()
            self._tok = toks[:, -1:].astype(np.int32).copy()
            done = time.monotonic()
            exec_s = done - t0
            self.batch_exec_s.append(exec_s)
            self._h_exec.observe(exec_s)
            self.stats["segments"] += 1
            n_active = self.ec.max_slots - self._free_slots()
            self._h_occ.observe(n_active / self.ec.max_slots)
            stamp = now if (self._virtual and now is not None) else done
            eos = self.ec.eos_id
            served = 0
            for s, st in enumerate(self._slots):
                if st is None or not st.live:
                    continue  # mid-prefill rows produce nothing yet
                take = min(steps, st.budget - len(st.produced))
                if take > 0:
                    new = [int(t) for t in toks[s, :take]]
                    st.produced.extend(new)
                    # tokens past an EOS are cut at retire: not served
                    served += new.index(eos) + 1 if eos in new else take
            self.stats["tokens_served"] += served
            sp.set(tokens=served)
            self.tracer.event(
                tm.DECODE_SEGMENT, stamp, sid=self._sid, tenant=self._tenant,
                steps=int(steps), active=n_active, live=live, tokens=served,
                dur=None if self._virtual else exec_s)
            self._retire_finished(stamp)

    def _rebase_clock(self) -> None:
        """Shift the clock and every slot offset down by a multiple of the
        ring length. slot_pos/qpos/kpos and the ring write index are all
        invariant under pos -> pos - k*ring (offsets shifted alike), so
        in-flight rows are bit-unaffected — and int32 positions stay bounded
        under sustained (never-idle) serving."""
        k = (self._clock - self.ec.max_prompt_len) // self.pool_len
        if k <= 0:
            return
        self._clock -= k * self.pool_len
        self._pool_off -= np.int32(k * self.pool_len)
        for s, st in enumerate(self._slots):
            if st is None:
                self._pool_off[s] = 0  # keep free-row offsets bounded too

    def _retire_finished(self, now: float) -> None:
        eos = self.ec.eos_id
        for s, st in enumerate(self._slots):
            if st is None or not st.live:
                continue
            done = len(st.produced) >= st.budget or (
                eos is not None and eos in st.produced
            )
            if not done:
                continue
            r = st.req
            # same budget-clamp + first-eos cut as the run-to-completion path
            r.payload = self._truncate(np.asarray(st.produced, np.int32),
                                       st.budget)
            r.completed_at = now
            self.completed.append(r)
            self._h_lat.observe(now - r.arrival)
            if r.first_token_at is not None:
                self._h_ttft.observe(r.first_token_at - r.arrival)
            self.tracer.event(tm.RETIRE, now, rid=r.rid, sid=self._sid,
                              tenant=self._tenant, tokens=len(r.payload))
            # prefix store maintenance BEFORE the slot is freed: the row's
            # prompt K/V (true positions [0, n), untouched by decode — the
            # ring never wraps into them) is the donor material for future
            # shared-prefix hits
            self._prefix_insert_on_retire(s, st)
            # free the slot; its stale KV stays masked for the next occupant
            # (pos_offset is rewritten at the next admission)
            self._slots[s] = None
            self.stats["retired"] += 1

    def _prefix_insert_on_retire(self, s: int, st: _Slot) -> None:
        """Release the row's lookup lease and insert its prompt's K/V into
        the radix store, truncated to the chunk quantum (entries stay
        aligned with the (chunk, bucket) executables and, on template
        traffic, the dedupe peek below skips the device->host extraction
        entirely once the template's blocks are stored — no steady-state
        syncs)."""
        if self.prefix_store is None:
            return
        r = st.req
        lease = self._prefix_leases.pop(r.rid, None)
        if lease is not None:
            self.prefix_store.release(lease)
        n = max(1, int(r.length))
        q = min(self._chunk_lens)
        m_ins = (n // q) * q
        lp = max(self.ec.min_prompt_len, _next_pow2(n))
        if m_ins <= 0:
            return
        prompt = self._prompt_tokens(r, n)
        if self.prefix_store.peek(lp, prompt[:m_ins]) >= m_ins:
            return  # already stored bit-for-bit; skip the device sync
        kv = self._extract_prefix(s, m_ins)
        self.prefix_store.insert(lp, prompt[:m_ins], kv)
        self.stats["prefix_inserts"] += 1

    def _extract_prefix(self, s: int, m: int):
        """Host copy of pool row s, true positions [0, m) — shaped like one
        store payload row (per-layer [m, kh, hd], stacked body [nb, m, ...])."""
        def f(leaf):
            if leaf.ndim == 4:                 # [max_slots, wc, kh, hd]
                return np.asarray(leaf[s, :m])
            return np.asarray(leaf[:, s, :m])  # stacked body leaves
        return jax.tree.map(f, self._pool)

    def mean_slot_occupancy(self) -> float:
        """Exact mean of the per-segment active-slot fraction (the
        occupancy histogram keeps exact sum/count; 0.0 before any segment)."""
        return float(self._h_occ.mean)

    def slots_in_use(self) -> int:
        """Occupied KV pool rows right now (pipelined-runtime telemetry)."""
        if not self.ec.continuous:
            return 0
        return self.ec.max_slots - self._free_slots()

    def slot_capacity(self) -> int:
        return self.ec.max_slots if self.ec.continuous else 0

    def prefix_lease_count(self) -> int:
        """Prefix-store pins currently held by this engine's in-flight
        requests. Failure-semantics invariant (regression-tested): after
        cancel/fail/dead-letter of every owner this must be 0 — a ghost pin
        would make the store's leaf-only eviction unable to reach budget."""
        return len(getattr(self, "_prefix_leases", {}) or {})


def build_engine(cfg: ModelConfig, *, seed: int = 0,
                 ec: Optional[EngineConfig] = None) -> ServingEngine:
    from repro.core.batching import analytical_knee, derive_policy, kv_bytes_per_token

    ec = EngineConfig() if ec is None else ec
    params = api.init_params(cfg, jax.random.PRNGKey(seed), dtype=cfg.dtype)
    n_active = cfg.active_param_count()
    profiles = {
        b: analytical_knee(
            n_active, chips=1, context_len=int((b + 0.5) * ec.bucket_width),
            kv_bytes_per_token=kv_bytes_per_token(cfg),
        )
        for b in range(8)
    }
    policy = derive_policy(profiles, n_slices=1, bucket_width=ec.bucket_width)
    return ServingEngine(cfg, params, policy, ec, knee_profiles=profiles)
