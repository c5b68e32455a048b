"""90th percentile of a voice request's hand-off from the DPU to the
engine: the end of its `dpu_exec` span to the start of its `admission`
span (the runtime tick that harvests it, the tick that offers it, and
its wait in the admission queue), over the window's completed requests
(bench/lib/spans.py)."""
from bench.lib.spans import part_p90_ms


def read(ctx):
    return part_p90_ms(ctx, "handoff")
