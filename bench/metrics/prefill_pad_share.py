"""Share of the prefill work that was padding: 1 - (true prompt tokens
admitted) / (rows x positions the admit and chunk programs computed) over
the window. Admit calls (ADMIT events) compute max_slots x bucket
positions and chunk calls (PREFILL_CHUNK events) max_slots x chunk; the
true tokens are the engine's `prefix_prompt_tokens` counter, which counts
each admitted prompt's length once."""


def read(ctx):
    t0, t1 = ctx.window
    rows = ctx.max_slots
    computed = 0
    for e in ctx.events:
        if not t0 <= e.t <= t1:
            continue
        if e.kind == "admit":
            computed += rows * int(e.bucket)
        elif e.kind == "prefill_chunk":
            computed += rows * int((e.extra or {})["chunk"])
    true = ctx.counters_end["prefix_prompt_tokens"] - \
        ctx.counters_start["prefix_prompt_tokens"]
    if computed <= 0:
        return None
    return 100.0 * (1.0 - true / computed)
