"""90th percentile of the length of the `dpu_exec` span that launched a
voice request: the DPU worker's stack and transfer, the fused program's
run (queued behind the model's programs on the same chip) and the
readback, over the window's completed requests (bench/lib/spans.py)."""
from bench.lib.spans import part_p90_ms


def read(ctx):
    return part_p90_ms(ctx, "dpu_exec")
