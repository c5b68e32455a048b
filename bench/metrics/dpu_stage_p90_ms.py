"""90th percentile of the time a request spends in the DPU stage: its
INGEST event (accepted at the front door) to its PREPROCESS_DONE event
(features harvested into the ready queue), over the window's requests."""
from bench.lib.stats import event_times, pct


def read(ctx):
    start = event_times(ctx.events, "ingest")
    done = event_times(ctx.events, "preprocess_done")
    w = [done[r] - start[r] for r in done
         if r in start and r >= 0 and r in ctx.rids]
    return pct(w, 90) * 1e3 if w else None
