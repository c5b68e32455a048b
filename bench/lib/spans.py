"""The program's own spans, as the per-layer readers use them.

The program records timed spans on its tracer (`repro.serving.telemetry`):
`dpu_exec` (the DPU worker's launch of a group), `admission` (an engine
admission), `chunk_dispatch` and `segment`, each with phases recorded as
child spans `<kind>.<phase>`. They are stamped on the host's monotonic
clock; a profile's events are on the profiler's. A traced run opens the
harness's `bench.window` span on both clocks (`ctx.trace_window_host` and
`ctx.trace.window`), and the difference of the two starts carries a span
onto the profile. Where the two lengths disagree by more than
`ANCHOR_TOL_S`, the anchor is not trusted and nothing is carried.

A voice request's time to first token splits into four parts that
telescope exactly, from its INGEST event to `first_token_at`:

    dpu_queue   INGEST -> start of its dpu_exec span
    dpu_exec    that span's length (stack, device, readback)
    handoff     end of dpu_exec -> start of its admission span (harvest
                tick, OFFER tick, admission queue)
    admit       start of its admission span -> first token
"""
from __future__ import annotations

from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence

from bench.lib.stats import event_times, pct
from bench.lib.trace import Interval, union

ANCHOR_TOL_S = 1e-3


class TtftParts(NamedTuple):
    dpu_queue: float
    dpu_exec: float
    handoff: float
    admit: float


def trace_offset(ctx) -> Optional[float]:
    """Seconds to add to a monotonic time to put it on the trace's clock;
    None without a trace or where the two window lengths disagree."""
    if ctx.trace is None:
        return None
    h0, h1 = ctx.trace_window_host
    w0, w1 = ctx.trace.window
    if h1 <= h0 or abs((h1 - h0) - (w1 - w0)) > ANCHOR_TOL_S:
        return None
    return w0 - h0


def intervals(events, kinds: Iterable[str], offset: float) -> List[Interval]:
    """(start, end) of every span of the given kinds, shifted by offset."""
    want = set(kinds)
    return [(e.t + offset, e.t + e.dur + offset) for e in events
            if e.kind in want and e.dur is not None]


def overlap_s(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the points that lie in some interval of a and in some
    interval of b."""
    ua, ub = union(a), union(b)
    i = j = 0
    tot = 0.0
    while i < len(ua) and j < len(ub):
        lo, hi = max(ua[i][0], ub[j][0]), min(ua[i][1], ub[j][1])
        if hi > lo:
            tot += hi - lo
        if ua[i][1] < ub[j][1]:
            i += 1
        else:
            j += 1
    return float(tot)


def spans_by_rid(events, kind: str, last: bool = False) -> Dict[int, object]:
    """{rid: span} over the spans of a kind that carry `rids`: the first
    span of each rid, or the last (a DPU launch that failed is retried)."""
    out: Dict[int, object] = {}
    for e in events:
        if e.kind != kind or e.dur is None:
            continue
        for r in (e.extra or {}).get("rids", ()):
            if last or r not in out:
                out[r] = e
    return out


def ttft_parts(ctx) -> Dict[int, TtftParts]:
    """The four parts of each window request that completed and has an
    INGEST event, a dpu_exec span and an admission span."""
    ingest = event_times(ctx.events, "ingest")
    dpu = spans_by_rid(ctx.events, "dpu_exec", last=True)
    adm = spans_by_rid(ctx.events, "admission")
    out: Dict[int, TtftParts] = {}
    for s in ctx.served:
        r, rid = s.req, s.plan.index
        if s.status != "completed" or r.first_token_at is None or \
                rid not in ingest or rid not in dpu or rid not in adm:
            continue
        d, a = dpu[rid], adm[rid]
        out[rid] = TtftParts(d.t - ingest[rid], d.dur,
                             a.t - (d.t + d.dur), r.first_token_at - a.t)
    return out


def part_p90_ms(ctx, part: str) -> Optional[float]:
    """90th percentile of one TTFT part over the window, in ms."""
    vals = [getattr(p, part) for p in ttft_parts(ctx).values()]
    return 1e3 * pct(vals, 90) if vals else None

