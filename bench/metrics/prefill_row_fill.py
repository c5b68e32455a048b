"""Share of the rows the prefill programs computed that held a request:
100 x the rows admitted (`rows`) over the rows computed (`rows_computed`,
each call's row width times the calls) summed over the window's ADMIT and
PREFILL_CHUNK events. Events without `rows_computed` (a program that does
not report it) give nothing to read."""

KINDS = ("admit", "prefill_chunk")


def read(ctx):
    t0, t1 = ctx.window
    rows = computed = 0
    for e in ctx.events:
        x = e.extra or {}
        if e.kind in KINDS and "rows_computed" in x and t0 <= e.t <= t1:
            rows += int(x["rows"])
            computed += int(x["rows_computed"])
    if computed <= 0:
        return None
    return 100.0 * rows / computed
