"""Roofline share of the DPU's fused audio program: the least time its
launches in the trace window could take, sum over launches of
max(ops / peak FLOP/s, bytes / peak HBM bandwidth) with ops and bytes from
bench/flops/dpu_audio.py (real clips only; power-of-two padding rows are
not work), over the device time of the `jit_audio_pipeline_batch`
executions in the window."""
from bench.lib.trace import program_time


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    sec, n = program_time(ctx.trace.devices[0], ctx.trace.window,
                          ["jit_audio_pipeline_batch"])
    m0, m1 = ctx.trace_window_host
    least = sum(max(c.ops / ctx.peaks["flops"],
                    c.bytes / ctx.peaks["hbm_bytes_per_s"])
                for c in ctx.calls
                if c.kind == "bench.dpu_launch" and m0 <= c.t0 <= m1)
    if n == 0 or sec <= 0 or least <= 0:
        return None
    return 100.0 * least / sec
