"""Whole-step model FLOP utilization of a fleet: the useful model FLOPs
of every slice's admit, chunk and segment calls in the trace window (as
in mfu, per bench/flops/<family>) over the sum over the chips of those
programs' device time, times one chip's peak."""
from bench.lib.spec import BENCH_DIR, load_module
from bench.lib.trace import program_time

mfu = load_module(BENCH_DIR, "metrics", "mfu")


def read(ctx):
    if ctx.trace is None or len(ctx.trace.devices) < 2:
        return None
    sec = n = 0
    for d in ctx.trace.devices:
        s, k = program_time(d, ctx.trace.window, mfu.PROGRAMS)
        sec, n = sec + s, n + k
    m0, m1 = ctx.trace_window_host
    flops = sum(c.flops for c in ctx.calls
                if c.kind in mfu.CALLS and m0 <= c.t0 <= m1)
    if n == 0 or sec <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (sec * ctx.peaks["flops"])
