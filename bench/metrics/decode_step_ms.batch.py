"""decode_step_ms in the offline batch cell, where it moves
output_tokens_per_s (same reading as decode_step_ms)."""
from bench.lib.spec import BENCH_DIR, load_module

read = load_module(BENCH_DIR, "metrics", "decode_step_ms").read
