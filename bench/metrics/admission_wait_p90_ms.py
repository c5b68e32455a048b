"""90th percentile of the wait from the runtime's OFFER (the request
reaches the admission queue) to `Request.dispatched_at` (its admission
begins), over the window's dispatched requests. On the chunked path
dispatched_at is stamped when the first chunk is queued; on the one-pass
path it is stamped after the admission group's prefill has synced, so
there it includes that prefill."""
from bench.lib.stats import event_times, pct


def read(ctx):
    offer = event_times(ctx.events, "offer")
    w = [s.req.dispatched_at - offer[s.plan.index] for s in ctx.served
         if s.req is not None and s.req.dispatched_at is not None
         and s.plan.index in offer]
    return pct(w, 90) * 1e3 if w else None
