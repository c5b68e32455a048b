"""Per-request lifecycle tracer + exportable timelines.

Every serving layer stamps typed span events onto one shared `Tracer`:

    ingest -> preprocess_launch/preprocess_done -> offer -> dispatch ->
    admit | prefill_chunk* | prefix_scatter -> decode_segment* ->
    retire | shed | dead_letter

plus the fleet-health transitions (hedge, requeue, quarantine, readmit,
resize, fault, breaker_trip/breaker_close, cpu_fallback), and timed spans
of the work itself (`Tracer.span` / `Tracer.timed`): the DPU worker's
launch of a group (dpu_exec), an engine admission (admission), a chunk's
dispatch (chunk_dispatch) and a decode segment (segment), each split into
phases recorded as child spans `<kind>.<phase>`. Events carry the
(tenant, slice, bucket) labels of the issue plus the request id and an
open extras dict; timestamps are the CALLER's clock, so on the virtual
clock the whole timeline is a deterministic pure function of trace + fault
plan — `to_json()` serializes with sorted keys and stable ordering, and
two replays of the same seed must export byte-identical files (a CI gate).

Export formats: `to_chrome_trace()` emits Chrome trace-event JSON
(load in chrome://tracing or Perfetto; slices lane per `tid`), and
`events` is the raw typed stream for programmatic checks. The tracer is
bounded (`max_events`, drop-counted) so a long soak cannot grow without
limit — it is a telemetry stream, not a log.
"""
from __future__ import annotations

import json
import threading
from typing import Callable, List, Optional

from jax.profiler import TraceAnnotation

# -- span kinds (the typed lifecycle vocabulary) ----------------------------
INGEST = "ingest"
PREPROCESS_LAUNCH = "preprocess_launch"
PREPROCESS_DONE = "preprocess_done"
PREPROCESS_FAIL = "preprocess_fail"
OFFER = "offer"                    # admission queue accepted the request
DISPATCH = "dispatch"              # fleet handed the request to a slice
ADMIT = "admit"                    # monolithic prefill+admit into a slot
PREFILL_CHUNK = "prefill_chunk"    # one chunked-prefill step
PREFIX_SCATTER = "prefix_scatter"  # cached-prefix K/V scattered into slots
DECODE_SEGMENT = "decode_segment"  # one segment_len decode scan
RETIRE = "retire"
SHED = "shed"
DEAD_LETTER = "dead_letter"
HEDGE = "hedge"
REQUEUE = "requeue"
QUARANTINE = "quarantine"
READMIT = "readmit"
RESIZE = "resize"
RECONFIG = "reconfig"              # partition controller repartitioned
FAULT = "fault"                    # injector fired a FaultEvent
BREAKER_TRIP = "breaker_trip"
BREAKER_CLOSE = "breaker_close"
CPU_FALLBACK = "cpu_fallback"
# timed spans (t = start, dur = length); their phases are child spans
# named "<kind>.<phase>" that carry the parent's seq
DPU_EXEC = "dpu_exec"              # DPU worker: group popped -> handed back
ADMISSION = "admission"            # engine admission of a group (one-pass
#                                    prefill, or a chunked group's set-up)
CHUNK_DISPATCH = "chunk_dispatch"  # one chunk program's host prep + dispatch
SEGMENT = "segment"                # one decode segment, dispatch to retire

SPAN_KINDS = (
    INGEST, PREPROCESS_LAUNCH, PREPROCESS_DONE, PREPROCESS_FAIL, OFFER,
    DISPATCH, ADMIT, PREFILL_CHUNK, PREFIX_SCATTER, DECODE_SEGMENT, RETIRE,
    SHED, DEAD_LETTER, HEDGE, REQUEUE, QUARANTINE, READMIT, RESIZE, RECONFIG,
    FAULT, BREAKER_TRIP, BREAKER_CLOSE, CPU_FALLBACK,
    DPU_EXEC, ADMISSION, CHUNK_DISPATCH, SEGMENT,
)


class SpanEvent:
    """One typed lifecycle event: kind + timestamp + (tenant, slice,
    bucket) labels + optional duration and extras."""

    __slots__ = ("seq", "t", "kind", "rid", "tenant", "sid", "bucket",
                 "dur", "extra")

    def __init__(self, seq: int, t: float, kind: str, rid=None, tenant=None,
                 sid=None, bucket=None, dur: Optional[float] = None,
                 extra=None):
        self.seq = seq
        self.t = t
        self.kind = kind
        self.rid = rid
        self.tenant = tenant
        self.sid = sid
        self.bucket = bucket
        self.dur = dur
        self.extra = extra

    def to_json(self) -> dict:
        d = {"seq": self.seq, "t": round(self.t, 9), "kind": self.kind}
        for k in ("rid", "tenant", "sid", "bucket"):
            v = getattr(self, k)
            if v is not None:
                d[k] = v
        if self.dur is not None:
            d["dur"] = round(self.dur, 9)
        if self.extra:
            d.update(self.extra)
        return d

    def __repr__(self) -> str:
        return f"SpanEvent({self.to_json()!r})"


class Timed:
    """An open span (`Tracer.timed`). Entering it also enters
    `jax.profiler.TraceAnnotation("repro.<kind>")`, so the block shows on
    the profiler's clock in any profile taken meanwhile; `phase(name)`
    starts a named phase (ending the one before, each with an annotation
    "repro.<kind>.<name>"). On exit the span is recorded with `fields`
    (an exception adds `error`), then each phase as a child span
    "<kind>.<name>" whose `parent` is the span's seq."""

    __slots__ = ("tracer", "kind", "clock", "fields", "t0", "t1",
                 "_phases", "_ann", "_pann")

    def __init__(self, tracer: "Tracer", kind: str,
                 clock: Callable[[], float], fields: dict):
        self.tracer = tracer
        self.kind = kind
        self.clock = clock
        self.fields = fields
        self.t0 = self.t1 = None
        self._phases: List[tuple] = []
        self._pann = None

    def __enter__(self) -> "Timed":
        self._ann = TraceAnnotation("repro." + self.kind)
        self._ann.__enter__()
        tl = self.tracer._open
        if not hasattr(tl, "stack"):
            tl.stack = []
        tl.stack.append(self)
        self.t0 = self.clock()
        return self

    def phase(self, name: str) -> None:
        t = self.clock()
        if self._pann is not None:
            self._pann.__exit__(None, None, None)
        self._phases.append((name, t))
        self._pann = TraceAnnotation(f"repro.{self.kind}.{name}")
        self._pann.__enter__()

    def set(self, **extra) -> None:
        """Add fields known only once the work is done."""
        self.fields.update(extra)

    def __exit__(self, et, ev, tb) -> bool:
        self.t1 = self.clock()
        if self._pann is not None:
            self._pann.__exit__(None, None, None)
        self._ann.__exit__(et, ev, tb)
        self.tracer._open.stack.pop()
        if et is not None:
            self.fields["error"] = et.__name__
        seq = self.tracer.span(self.kind, self.t0, self.t1, **self.fields)
        ends = [t for _, t in self._phases[1:]] + [self.t1]
        for (name, a), b in zip(self._phases, ends):
            self.tracer.span(f"{self.kind}.{name}", a, b, parent=seq,
                             sid=self.fields.get("sid"))
        return False


class _NoSpan:
    """Stand-in for `Tracer.current()` outside any span."""

    def phase(self, name: str) -> None:
        pass

    def set(self, **extra) -> None:
        pass


_NO_SPAN = _NoSpan()


class Tracer:
    """Bounded, append-only lifecycle event stream shared by every layer
    of one pipeline (the composing layer injects itself via set_tracer)."""

    def __init__(self, max_events: int = 200_000):
        self.max_events = max_events
        self.events: List[SpanEvent] = []
        self.dropped = 0
        self._seq = 0
        # the DPU worker thread records its spans beside the event loop
        self._lock = threading.Lock()
        self._open = threading.local()   # per-thread stack of open spans

    def event(self, kind: str, t: float, *, rid=None, tenant=None, sid=None,
              bucket=None, dur: Optional[float] = None, **extra) -> int:
        """Record one event; returns its sequence number (counted even
        when the bound drops the event)."""
        with self._lock:
            self._seq += 1
            if len(self.events) >= self.max_events:
                self.dropped += 1
                return self._seq
            self.events.append(SpanEvent(
                self._seq, float(t), kind, rid=rid, tenant=tenant,
                sid=None if sid is None else str(sid), bucket=bucket,
                dur=dur, extra=extra or None))
            return self._seq

    def span(self, kind: str, t0: float, t1: float, *, rid=None, rids=None,
             parent: Optional[int] = None, **extra) -> int:
        """Record the interval [t0, t1] as one event (t = t0, dur = t1 - t0).
        A span over a group carries the group's `rids`; a phase names its
        enclosing span's seq as `parent`. Returns the span's seq."""
        if rids is not None:
            extra["rids"] = rids
        if parent is not None:
            extra["parent"] = parent
        return self.event(kind, t0, rid=rid, dur=t1 - t0, **extra)

    def timed(self, kind: str, clock: Callable[[], float],
              **fields) -> "Timed":
        """Context-manager form of `span`: times the block on `clock`
        (the caller's clock, so virtual replays stay deterministic)."""
        return Timed(self, kind, clock, fields)

    def current(self) -> "Timed":
        """The innermost span the calling thread has open (a no-op stand-in
        when it has none), so code called inside a span can mark its
        phases without being handed it."""
        stack = getattr(self._open, "stack", None)
        return stack[-1] if stack else _NO_SPAN

    def reset(self) -> None:
        """Clear the stream (the registry reset hook calls this at the
        warmup boundary, so exported timelines start at the measured
        window)."""
        with self._lock:
            self.events.clear()
            self.dropped = 0
            self._seq = 0

    def counts(self) -> dict:
        out: dict = {}
        for e in self.events:
            out[e.kind] = out.get(e.kind, 0) + 1
        return out

    def of(self, *kinds: str) -> List[SpanEvent]:
        want = set(kinds)
        return [e for e in self.events if e.kind in want]

    # -- exporters ---------------------------------------------------------
    def to_chrome_trace(self, t0: Optional[float] = None) -> dict:
        """Chrome trace-event / Perfetto JSON. Point events render as
        instants, events carrying `dur` as complete ('X') slices; one lane
        (tid) per slice id, lane 0 for fleet-level events. Timestamps are
        rebased to the first event (or `t0`) in microseconds."""
        if t0 is None:
            t0 = self.events[0].t if self.events else 0.0
        out = []
        lanes: dict = {}
        for e in self.events:
            lane = 0
            if e.sid is not None:
                lane = lanes.setdefault(e.sid, len(lanes) + 1)
            args = {k: v for k, v in e.to_json().items()
                    if k not in ("seq", "t", "kind", "dur")}
            ts = round(1e6 * (e.t - t0), 3)
            ev = {"name": e.kind, "cat": "serving", "pid": 0, "tid": lane,
                  "ts": ts, "args": args}
            if e.dur is not None:
                ev["ph"] = "X"
                ev["dur"] = round(1e6 * e.dur, 3)
            else:
                ev["ph"] = "i"
                ev["s"] = "t"
            out.append(ev)
        meta = [
            {"name": "thread_name", "ph": "M", "pid": 0, "tid": lane,
             "args": {"name": f"slice {sid}"}}
            for sid, lane in sorted(lanes.items(), key=lambda kv: kv[1])
        ]
        meta.insert(0, {"name": "thread_name", "ph": "M", "pid": 0,
                        "tid": 0, "args": {"name": "fleet"}})
        return {"traceEvents": meta + out,
                "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped,
                              "event_count": len(self.events)}}

    def to_json(self, t0: Optional[float] = None) -> str:
        """Deterministic serialization of the Chrome trace: sorted keys,
        fixed separators — byte-identical across replays of the same
        virtual-clock trace + plan (a CI regression gate)."""
        return json.dumps(self.to_chrome_trace(t0), sort_keys=True,
                          separators=(",", ":"))
