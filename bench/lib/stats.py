"""Tails and small reductions shared by the end-to-end metrics and the
per-layer readers."""
from __future__ import annotations

import math
from typing import Sequence


def pct(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    sample at or below it (inf stays inf, so a failed request that counts
    as +inf is never averaged away)."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of an empty sample")
    k = max(1, math.ceil(q / 100.0 * len(v)))
    return float(v[k - 1])


def event_times(events, kind: str, per_rid: bool = True):
    """{rid: first time} of a tracer event kind (events carrying a `rids`
    list count for each rid in it)."""
    out = {}
    for e in events:
        if e.kind != kind:
            continue
        rids = [e.rid] if e.rid is not None else (e.extra or {}).get("rids", [])
        for r in rids:
            out.setdefault(r, e.t)
    return out
