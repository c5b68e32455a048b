"""host_idle_share in the offline batch cell (moves
output_tokens_per_s)."""
from bench.lib.spec import BENCH_DIR, load_module

read = load_module(BENCH_DIR, "metrics", "host_idle_share").read
