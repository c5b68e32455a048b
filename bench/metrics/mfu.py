"""Whole-step model FLOP utilization: the useful model FLOPs of the admit,
chunk and segment calls in the trace window (true prompt positions, and
decode tokens of live rows within their budget, per bench/flops/<family>)
over the device time of those programs times the chip's peak."""
from bench.lib.trace import program_time

PROGRAMS = ["jit__admit", "jit__chunk", "jit__segment"]
CALLS = ("bench.admit", "bench.chunk", "bench.segment")


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    sec, n = program_time(ctx.trace.devices[0], ctx.trace.window, PROGRAMS)
    m0, m1 = ctx.trace_window_host
    flops = sum(c.flops for c in ctx.calls
                if c.kind in CALLS and m0 <= c.t0 <= m1)
    if n == 0 or sec <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (sec * ctx.peaks["flops"])
