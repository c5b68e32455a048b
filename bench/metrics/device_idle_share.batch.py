"""device_idle_share in the offline batch cell (moves
output_tokens_per_s)."""
from bench.lib.spec import BENCH_DIR, load_module

read = load_module(BENCH_DIR, "metrics", "device_idle_share").read
