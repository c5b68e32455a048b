"""prefill_row_fill in the offline batch cell, where it moves
output_tokens_per_s."""
from bench.lib.spec import BENCH_DIR, load_module

read = load_module(BENCH_DIR, "metrics", "prefill_row_fill").read
