"""Share of the trace window in which no operation ran on the chip:
1 - (union of the "XLA Ops" intervals) / window."""
from bench.lib.trace import device_busy


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - device_busy(ctx.trace) / ctx.trace.window_s)
