"""Share of the trace window in which the chip is idle while the event
loop is in the host phases of the engine's own calls: the `prepare`
(padding, inputs, dispatch) and `host` (token bookkeeping, stamping,
retiring) phases of its `admission` and `segment` spans, carried onto the
trace's clock by the window anchor (bench/lib/spans.py). Idle is as in
device_idle_share: no "XLA Ops" interval of the first chip."""
from bench.lib.spans import intervals, overlap_s, trace_offset
from bench.lib.trace import idle_gaps

PHASES = ("admission.prepare", "admission.host", "segment.prepare",
          "segment.host")


def read(ctx):
    off = trace_offset(ctx)
    if off is None or not ctx.trace.devices:
        return None
    host = intervals(ctx.events, PHASES, off)
    if not host:
        return None
    win = ctx.trace.window
    gaps = idle_gaps([(s, e) for _, s, e in ctx.trace.devices[0].ops], win)
    return 100.0 * overlap_s(gaps, host) / ctx.trace.window_s
