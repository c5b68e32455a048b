"""90th percentile of a voice request's admission: the start of its
`admission` span to its first token (`Request.first_token_at`): on the
one-pass path the host padding, the prefill program and its sync, over
the window's completed requests (bench/lib/spans.py)."""
from bench.lib.spans import part_p90_ms


def read(ctx):
    return part_p90_ms(ctx, "admit")
