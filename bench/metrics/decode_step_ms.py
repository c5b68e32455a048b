"""Device time of one decode step: the device time of the segment
programs (`jit__segment` executions in the trace window) over the steps
they ran (executions x segment_len)."""
from bench.lib.trace import program_time


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    sec, n = program_time(ctx.trace.devices[0], ctx.trace.window,
                          ["jit__segment"])
    if n == 0:
        return None
    return 1e3 * sec / (n * ctx.segment_len)
