"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model from the seed on the device, warms every program
its traffic can use, serves the traffic for --seconds as its client,
drains, checks the served outputs against the plain references, and
prints one JSON object as the last line of standard output. --trace 0
reports the cell's end-to-end metrics, --trace 1 its per-layer metrics
from a profiler trace of part of the window. Diagnostics go to standard
error; its last lines are the numbers compared for `correct`, each with
its limit. Without a TPU (or with fewer chips than the cell asks for) it
exits non-zero and prints no result.

Calibration only (never used by the benchmark's own runs):
  --control 1   also compute the correctness controls (fp8 reference,
                bfloat16 front end) on the same sample
  --rate <qps>  override an open-loop mix's mean rate (the knee sweep)
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import numpy as np  # noqa: E402

from bench.lib import check, serve, spec, stats, traffic, weights  # noqa: E402
from bench.lib.peaks import peaks_for  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_S = 4.0          # length of the profiled part of a --trace 1 window


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class Hooks:
    """Window callbacks: the profiler's part of the window (traced runs),
    and the counter snapshots at the window's open and close."""

    def __init__(self, sysm, trace_dir):
        self.sysm = sysm
        self.trace_dir = trace_dir
        self.t_trace = None
        self.tracing = False
        self.ann = None
        self.host_window = None
        self.start = self.end = None

    def _counters(self):
        eng = self.sysm.engine
        return {"stats": serve.counters(eng), "at": time.monotonic(),
                "tokens": serve.tokens_served(eng)}

    def window_open(self, t0, t1):
        self.t1 = t1
        self.start = self._counters()
        if self.trace_dir:
            self.t_trace = t0 + max(0.0, (t1 - t0 - TRACE_S) / 2)

    def tick(self, now):
        import jax

        if self.t_trace is not None and not self.tracing and now >= self.t_trace:
            jax.profiler.start_trace(self.trace_dir)
            self.ann = jax.profiler.TraceAnnotation("bench.window")
            self.ann.__enter__()
            self.host_window = [time.monotonic(), None]
            self.tracing = True
        elif self.tracing and self.host_window[1] is None and \
                now >= self.host_window[0] + TRACE_S:
            # close the traced span; the profiler itself stops after the
            # drain, so writing the trace never stalls the window's client
            self.host_window[1] = time.monotonic()
            self.ann.__exit__(None, None, None)
        if self.end is None and now >= self.t1:
            self.end = self._counters()

    def window_closed(self):
        import jax

        if self.tracing:
            if self.host_window[1] is None:
                self.host_window[1] = time.monotonic()
                self.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        if self.end is None:
            self.end = self._counters()


def end_to_end(win, hooks, setup_s, names):
    ttft, tpot = [], []
    for s in win.served:
        r = s.req
        if s.status == "completed":
            ttft.append(r.first_token_at - s.due)
            n = len(s.tokens)
            if n >= 2:
                tpot.append((r.completed_at - r.first_token_at) / (n - 1))
        else:
            ttft.append(float("inf"))
    a, b = hooks.start, hooks.end
    vals = {
        "ttft_p90_ms": (lambda: 1e3 * stats.pct(ttft, 90), "ms"),
        "tpot_p90_ms": (lambda: 1e3 * stats.pct(tpot, 90), "ms"),
        # every token served between the window's open and close, those of
        # requests still in flight at either end included
        "output_tokens_per_s": (
            lambda: (b["tokens"] - a["tokens"]) / (b["at"] - a["at"]),
            "tokens/s"),
        "setup_s": (lambda: setup_s, "s"),
    }
    return {n: {"value": vals[n][0](), "unit": vals[n][1]} for n in names}


def knee_report(win, limits):
    """Calibration: the share of requests meeting both latency limits."""
    ok = 0
    for s in win.served:
        r = s.req
        if s.status != "completed":
            continue
        n = len(s.tokens)
        tp = (r.completed_at - r.first_token_at) / (n - 1) if n > 1 else 0.0
        ok += r.first_token_at - s.due <= limits["ttft_s"] and \
            tp <= limits["tpot_s"]
    return {"met_share": ok / max(1, len(win.served))}


def run_cell(cell, seed, seconds, trace=False, control=False, rate=None,
             allow_cpu=False, t_start=None):
    """One run of one cell; returns the result dict (last line)."""
    import jax

    t_start = T_START if t_start is None else t_start
    config, mix = cell.config, dict(cell.traffic)
    if rate is not None:
        mix["rate_qps"] = float(rate)
    devs = jax.devices()
    dev = devs[0]
    if len(devs) < cell.chips or not allow_cpu and dev.platform != "tpu":
        raise SystemExit(f"needs {cell.chips} TPU chip(s); JAX sees "
                         f"{len(devs)} {dev.platform} device(s)")
    peaks = None if dev.platform != "tpu" else peaks_for(dev.device_kind)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **_: compiles.append(time.monotonic())
        if ev == COMPILE_EVENT else None)

    cfg = serve.model_config(config)
    slots = int(config["engine"]["max_slots"])
    planned = traffic.plan(mix, seed, seconds, cfg.vocab, slots)
    t = time.monotonic()
    params = weights.init_params(cfg, seed, dev)
    t_w = time.monotonic() - t
    sysm = serve.build(config, params, with_audio=bool(mix.get("audio")),
                       chips=cell.chips)
    t = time.monotonic()
    n_warm = serve.warm(sysm, mix, np.random.default_rng([seed, 9]))
    t_warm = time.monotonic() - t
    flops_mod = spec.load_module(cell.bench_dir, "flops", config["flops"])
    probe = None
    trace_dir = None
    if trace:
        dpu_cost = None
        if mix.get("audio"):
            dpu_cost = spec.load_module(cell.bench_dir, "flops",
                                        "dpu_audio").clip_cost
        probe = serve.Probe(sysm, flops_mod, dpu_cost)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    hooks = Hooks(sysm, trace_dir)
    gc.collect()
    setup_s = time.monotonic() - t_start
    log(f"setup {setup_s:.3f} s (weights {t_w:.3f} s, warm-up {t_warm:.3f} s,"
        f" {n_warm} warm-up requests, {len(compiles)} compiles)")
    n_compiles_before = len(compiles)
    drain_s = float(mix.get("drain_s", 60.0))
    win = serve.drive(sysm, mix, planned, seconds, drain_s, hooks)
    if win.ran_dry:
        raise SystemExit("a closed-loop client ran out of requests before "
                         "the window closed: raise requests_per_client")
    in_window = sum(1 for c in compiles[n_compiles_before:] if c <= win.t1)
    memory_peak = None
    if dev.platform == "tpu":
        peaks_by_chip = [int(d.memory_stats()["peak_bytes_in_use"])
                         for d in devs[:cell.chips]]
        memory_peak = max(peaks_by_chip)    # the fullest chip's
        if cell.chips > 1:
            log(f"memory peak by chip: {peaks_by_chip}")
    failed = sum(1 for s in win.served if s.status != "completed")
    late = sorted(win.lateness) or [0.0]
    log(f"window {seconds} s: {len(win.served)} requests due, "
        f"{len(win.served) - failed} completed, {failed} failed "
        f"(shed {sum(s.status == 'shed' for s in win.served)}, dead "
        f"{sum(s.status == 'dead' for s in win.served)}, unfinished "
        f"{sum(s.status == 'unfinished' for s in win.served)}); "
        f"drained {win.end - win.t1:.3f} s past the close")
    log(f"generator lateness: p50 {1e3 * stats.pct(late, 50):.3f} ms, "
        f"p99 {1e3 * stats.pct(late, 99):.3f} ms, max {1e3 * late[-1]:.3f} ms")
    log(f"compiles inside the window: {in_window}")
    events = list(sysm.runtime.tracer.events)
    if sysm.runtime.tracer.dropped:
        log(f"tracer dropped {sysm.runtime.tracer.dropped} events")
    occ = [int((e.extra or {})["active"]) for e in events
           if e.kind == "decode_segment" and win.t0 <= e.t <= win.t1]
    log(f"in the window: {hooks.end['tokens'] - hooks.start['tokens']} "
        f"tokens served in {hooks.end['at'] - hooks.start['at']:.3f} s; "
        f"occupied rows per segment mean {np.mean(occ or [0]):.2f}, max "
        f"{max(occ or [0])} of {slots}")
    fleet = len(sysm.engines) > 1
    slice_of = check.retiring_slice(events) if fleet else None
    if fleet:
        served_by = {}
        for s in win.served:
            k = slice_of.get(s.plan.index)
            served_by[k] = served_by.get(k, 0) + 1
        log(f"fleet of {len(sysm.engines)}: window requests by slice "
            f"{dict(sorted(served_by.items(), key=str))}; fleet counters "
            + json.dumps({k: hooks.end["stats"][k] - hooks.start["stats"][k]
                          for k in hooks.end["stats"]
                          if k.startswith("fleet.")}))
    tr = None
    if trace:
        from bench.lib import trace as tracemod

        t = time.monotonic()
        tr = tracemod.read(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace read in {time.monotonic() - t:.3f} s: "
            f"{sum(len(d.ops) for d in tr.devices)} device ops, "
            f"window {tr.window_s:.6f} s; busy by chip "
            + ", ".join(f"{tracemod.chip_label(d)} "
                        f"{tracemod.busy_s(tracemod.busy_intervals(d), tr.window):.6f} s"
                        for d in tr.devices))
    mc = serve.config_numbers(sysm.cfg)
    ctx = SimpleNamespace(
        served=win.served, window=(win.t0, win.t1), events=events,
        rids={s.plan.index for s in win.served},
        counters_start=hooks.start["stats"], counters_end=hooks.end["stats"],
        trace=tr, calls=probe.calls if probe else [],
        trace_window_host=tuple(hooks.host_window or (0.0, 0.0)),
        peaks=peaks, max_slots=slots, segment_len=sysm.ec.segment_len,
        model=mc)
    # the program's state goes before the reference runs; the weights stay
    sysm.runtime.close()
    sysm = probe = hooks.sysm = None
    gc.collect()

    ck = config["check"]
    ref = spec.load_module(cell.bench_dir, "reference", config["reference"])
    t = time.monotonic()
    sample = check.token_sample(win.served, seed, int(ck["sample_tokens"]),
                                slice_of)
    with jax.default_matmul_precision("highest"):
        gap, cgap, n_tok = check.token_gaps(ref, params, sample, mc, control)
    log(f"reference: {len(sample)} requests, {n_tok} served tokens, "
        f"{time.monotonic() - t:.3f} s")
    checks = {"token_gap": {"value": gap, "limit": ck["token_gap_limit"]}}
    calib = {}
    if control:
        calib["token_gap_control"] = cgap
    if mix.get("audio"):
        t = time.monotonic()
        err, cerr, n_clip = check.dpu_errors(win.served, seed,
                                             int(ck["dpu_sample"]), control)
        log(f"front end: {n_clip} clips compared, "
            f"{time.monotonic() - t:.3f} s")
        checks["dpu_max_abs_err"] = {"value": err,
                                     "limit": ck["dpu_err_limit"]}
        if control:
            calib["dpu_err_control"] = cerr
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = spec.load_module(cell.bench_dir, "metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = end_to_end(win, hooks, setup_s,
                             [m["name"] for m in cell.end_to_end])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": len(win.served),
           "failed": failed, "metrics": metrics, "device": device}
    if trace and tr is not None:
        from bench.lib import trace as tracemod

        device["busy_s"] = tracemod.device_busy(tr)
        device["window_s"] = tr.window_s
    if trace and tr is not None and tr.devices:
        out["breakdown"] = tracemod.breakdown(tr)
    if rate is not None:
        calib["knee"] = knee_report(win, config["knee"])
        calib["rate_qps"] = mix["rate_qps"]
    if calib:
        out["calibration"] = calib
    out["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return out


def sweep(cell, seed, seconds, rates, unloaded_n=12):
    """Calibration: the cell's unloaded latencies (one request at a time)
    and, for each open-loop rate, the share of requests meeting the knee
    limits, from one process (one set-up). Each drive numbers its
    requests after the last one's: a fleet records a request id once."""
    import jax

    config, mix = cell.config, dict(cell.traffic)
    dev = jax.devices()[0]
    cfg = serve.model_config(config)
    slots = int(config["engine"]["max_slots"])
    params = weights.init_params(cfg, seed, dev)
    sysm = serve.build(config, params, with_audio=bool(mix.get("audio")),
                       chips=cell.chips)
    serve.warm(sysm, mix, np.random.default_rng([seed, 9]))
    one = dict(mix, kind="closed", clients_per_slot=1,
               requests_per_client=unloaded_n)
    plan1 = [p for p in traffic.plan(one, seed, seconds, cfg.vocab, slots)
             if p.client == 0]
    win = serve.drive(sysm, one, plan1, 600.0, 60.0)
    first = max(p.index for p in plan1) + 1
    done = [s for s in win.served if s.status == "completed"]
    ttft = [s.req.first_token_at - s.due for s in done]
    tpot = [(s.req.completed_at - s.req.first_token_at) / (len(s.tokens) - 1)
            for s in done if len(s.tokens) > 1]
    log(f"unloaded: {len(done)} requests, ttft p50 {stats.pct(ttft, 50):.4f}"
        f" p90 {stats.pct(ttft, 90):.4f} max {max(ttft):.4f} s; tpot p50 "
        f"{stats.pct(tpot, 50):.4f} p90 {stats.pct(tpot, 90):.4f} s")
    limits = config["knee"]
    out = {"unloaded": {"ttft_p50_s": stats.pct(ttft, 50),
                        "ttft_p90_s": stats.pct(ttft, 90),
                        "tpot_p50_s": stats.pct(tpot, 50),
                        "tpot_p90_s": stats.pct(tpot, 90)},
           "limits": limits, "rates": []}
    for r in rates:
        m = dict(mix, rate_qps=float(r))
        planned = [dataclasses.replace(p, index=p.index + first)
                   for p in traffic.plan(m, seed, seconds, cfg.vocab, slots)]
        first += len(planned)
        win = serve.drive(sysm, m, planned, seconds, float(m.get("drain_s", 60)))
        rep = knee_report(win, limits)
        ttft = [s.req.first_token_at - s.due if s.status == "completed"
                else float("inf") for s in win.served]
        rep["samples"] = [
            [round(s.req.first_token_at - s.due, 4),
             round((s.req.completed_at - s.req.first_token_at)
                   / max(1, len(s.tokens) - 1), 5)]
            if s.status == "completed" else None for s in win.served]
        rep.update(rate_qps=r, n=len(win.served),
                   failed=sum(s.status != "completed" for s in win.served),
                   ttft_p90_s=stats.pct(ttft, 90),
                   drained_s=win.end - win.t1)
        log(f"rate {r}: " + json.dumps({k: v for k, v in rep.items()
                                         if k != "samples"}))
        out["rates"].append(rep)
    sysm.runtime.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--sweep", default=None,
                    help="calibration: comma-separated open-loop rates")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax

    # the persistent compile cache lives at a fixed path inside the checkout
    jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if args.sweep:
        out = sweep(cell, args.seed, args.seconds,
                    [float(x) for x in args.sweep.split(",")])
    else:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       bool(args.control), args.rate)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
