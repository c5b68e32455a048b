"""Benchmark harness entry point: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV per the deliverable contract."""
from __future__ import annotations

import json
import time
import traceback


def _derived(rows):
    """Pick the headline number for the CSV 'derived' column."""
    if not rows:
        return ""
    last = rows[-1]
    for key in ("preba_vs_cpu", "speedup_vs_base", "cost_eff_gain", "qps",
                "batch_knee", "cores_required", "roofline_pct", "utilization"):
        if isinstance(last, dict) and key in last:
            return f"{key}={last[key]}"
    return ""


def main() -> None:
    from benchmarks import (
        fig5_util_vs_batch,
        fig6_knee,
        fig7_breakdown,
        fig8_preproc,
        fig9_scaling,
        fig14_knee_heatmap,
        fig17_throughput,
        fig18_latency,
        fig21_tco,
        fig22_ablation,
        roofline_table,
    )

    benches = [
        ("fig5_util_vs_batch", fig5_util_vs_batch.run),
        ("fig6_knee", fig6_knee.run),
        ("fig7_breakdown", fig7_breakdown.run),
        ("fig8_preproc", fig8_preproc.run),
        ("fig9_scaling", fig9_scaling.run),
        ("fig14_knee_heatmap", fig14_knee_heatmap.run),
        ("fig17_throughput", fig17_throughput.run),
        ("fig18_latency", fig18_latency.run),
        ("fig21_tco", fig21_tco.run),
        ("fig22_ablation", fig22_ablation.run),
        ("roofline_table", roofline_table.run),
    ]
    print("name,us_per_call,derived")
    all_rows = {}
    failed = []
    for name, fn in benches:
        t0 = time.perf_counter()
        try:
            rows = fn()
            us = (time.perf_counter() - t0) * 1e6
            print(f"{name},{us:.0f},{_derived(rows)}", flush=True)
            all_rows[name] = rows
        except Exception as e:  # noqa: BLE001 - report every figure, then fail
            us = (time.perf_counter() - t0) * 1e6
            print(f"{name},{us:.0f},ERROR:{type(e).__name__}", flush=True)
            traceback.print_exc()
            failed.append(name)
    import pathlib

    out = pathlib.Path("results/benchmarks.json")
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(all_rows, indent=1, default=str))
    if failed:
        raise SystemExit(f"{len(failed)} figure(s) raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
