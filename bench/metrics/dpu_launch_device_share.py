"""Share of the DPU worker's launches that the chip spends running the
fused audio program: the device time of `jit_audio_pipeline_batch`
executions inside the program's `dpu_exec` spans, over the length of
those spans, both clipped to the trace window. The spans are carried onto
the trace's clock by the window anchor (bench/lib/spans.py)."""
from bench.lib.spans import intervals, overlap_s, trace_offset
from bench.lib.trace import busy_s, clip, program_name


def read(ctx):
    off = trace_offset(ctx)
    if off is None or not ctx.trace.devices:
        return None
    win = ctx.trace.window
    launches = clip(intervals(ctx.events, ["dpu_exec"], off), win)
    total = busy_s(launches, win)
    if total <= 0:
        return None
    prog = clip([(s, e) for n, s, e in ctx.trace.devices[0].modules
                 if program_name(n) == "jit_audio_pipeline_batch"], win)
    return 100.0 * overlap_s(prog, launches) / total
