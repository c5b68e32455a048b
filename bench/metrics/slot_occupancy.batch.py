"""Mean share of the slot pool's rows that decode: over the decode
segments of the window, the live rows (mid-prefill rows hold a slot but
decode nothing) over max_slots."""


def read(ctx):
    t0, t1 = ctx.window
    rows = [c.live_rows for c in ctx.calls
            if c.kind == "bench.segment" and t0 <= c.t0 <= t1]
    if not rows:
        return None
    return 100.0 * sum(rows) / (len(rows) * ctx.max_slots)
