"""90th percentile of the time a voice request waits for the DPU: its
INGEST event to the start of the `dpu_exec` span that launched it (the
service's input queue and the worker's work queue), over the window's
completed requests (bench/lib/spans.py)."""
from bench.lib.spans import part_p90_ms


def read(ctx):
    return part_p90_ms(ctx, "dpu_queue")
