"""device_idle_share of a fleet: the mean over its chips of the share of
the trace window in which no operation ran on the chip (moves
tpot_p90_ms; same reading as device_idle_share, which averages over the
chips in the trace)."""
from bench.lib.spec import BENCH_DIR, load_module

read = load_module(BENCH_DIR, "metrics", "device_idle_share").read
