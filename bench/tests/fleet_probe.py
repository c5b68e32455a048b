"""The harness on a four-replica fleet, on four host-platform CPU devices.

A process that has touched JAX keeps its device count, so the fleet tests
run this file in a process of its own:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python bench/tests/fleet_probe.py <out.json>

It builds the tiny fleet cell of tests/data (tiny-ssm.voice_fleet4), runs
it as bench/run.py would (its look for a chip skipped), runs it again
with one slice's decode tokens altered where they are produced, and
writes what it observed as JSON for test_bench_fleet.py to judge.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import bench_fixture
import numpy as np

CELL = "tiny-ssm.voice_fleet4"
SPAN_METRICS = ("admission_wait_p90_ms", "dpu_queue_p90_ms",
                "dpu_exec_p90_ms", "admit_handoff_p90_ms")


def _load_run():
    s = importlib.util.spec_from_file_location("bench_run",
                                               bench_fixture.BENCH / "run.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def build_and_serve(root, out):
    """Four slices, each on its own device with the benchmark's weights;
    the harness's token count against the slices' own counters."""
    import jax

    from bench.lib import serve, spec, traffic, weights

    cell = spec.load_cell(CELL, root)
    cfg = serve.model_config(cell.config)
    params = weights.init_params(cfg, 11, jax.devices()[0])
    sysm = serve.build(cell.config, params, with_audio=False, chips=4)

    def on(tree):
        return sorted({d.id for x in jax.tree.leaves(tree)
                       for d in x.devices()})

    out["fleet_type"] = type(sysm.engine).__name__
    out["slice_types"] = [type(e).__name__ for e in sysm.engines]
    out["params_on"] = [on(e.params) for e in sysm.engines]
    out["weights_equal"] = [serve.fingerprint(e.params) ==
                            serve.fingerprint(params) for e in sysm.engines]
    # a slice whose tree differs by one bit is refused
    leaves, tdef = jax.tree.flatten(sysm.engines[2].params)
    bad = list(leaves)
    bad[0] = bad[0].at[(0,) * bad[0].ndim].add(1)
    try:
        serve.check_replicas(
            [sysm.engines[0], sysm.engines[1],
             type("E", (), {"params": jax.tree.unflatten(tdef, bad)})(),
             sysm.engines[3]], params, 4)
        out["altered_replica_refused"] = False
    except RuntimeError:
        out["altered_replica_refused"] = True
    text = dict(cell.traffic, audio=None)
    n_warm = serve.warm(sysm, text, np.random.default_rng(1))
    out["warm_requests"] = n_warm
    out["admit_buckets_by_slice"] = {
        str(e._sid): sorted({int(ev.bucket) for ev in sysm.runtime.tracer.events
                             if ev.kind == "admit" and ev.sid == str(e._sid)})
        for e in sysm.engines}
    planned = traffic.plan(text, 3, 2.0, cfg.vocab, 4)[:16]
    rt = sysm.runtime

    def counts():
        return [serve.tokens_served(sysm.engine),
                sum(e.stats["tokens_served"] for e in sysm.engines)]

    out["tokens_before"] = counts()
    rt.submit([serve._request(p, time.monotonic()) for p in planned])
    checks = []
    while rt.busy():
        rt.step()
        checks.append(counts())
    out["tokens_checks"] = checks
    out["tokens_budget"] = sum(p.max_new for p in planned)
    out["admitted_by_slice"] = [e.stats["admitted"] for e in sysm.engines]
    c = serve.counters(sysm.engine)
    out["counters_admitted"] = c["admitted"]
    out["counters_fleet_dispatched"] = c.get("fleet.dispatched")
    rt.close()


def traced_run(root, run, out):
    """A traced run of the fleet cell, with each program-span reader's
    sample counted against the window's completed requests."""
    from bench.lib import check, spans, spec, stats

    seen = {}
    real_load = run.spec.load_module
    real_pct = spans.pct

    def counting(name):
        def pct(values, q):
            seen[name] = len(values)
            return real_pct(values, q)
        return pct

    def load_module(bench_dir, kind, name):
        mod = real_load(bench_dir, kind, name)
        if kind != "metrics":
            return mod
        inner = mod.read

        def read(ctx):
            seen.setdefault("_ctx", ctx)
            spans.pct = counting(name)
            if hasattr(mod, "pct"):
                mod.pct = counting(name)
            try:
                return inner(ctx)
            finally:
                spans.pct = real_pct
                if hasattr(mod, "pct"):
                    mod.pct = stats.pct
        return type("M", (), {"read": staticmethod(read)})

    run.spec.load_module = load_module
    try:
        res = run.run_cell(spec.load_cell(CELL, root), 2**33 + 5, 1.5,
                           trace=True, allow_cpu=True,
                           t_start=time.monotonic())
    finally:
        run.spec.load_module = real_load
    ctx = seen.pop("_ctx")
    slice_of = check.retiring_slice(ctx.events)
    done = [s for s in ctx.served if s.status == "completed"]
    out["traced"] = {
        "correct": res["correct"], "failed": res["failed"],
        "attempted": res["attempted"], "metrics": sorted(res["metrics"]),
        "completed": len(done),
        "completed_slices": sorted({str(slice_of.get(s.plan.index))
                                    for s in done}),
        "samples": {k: seen.get(k) for k in SPAN_METRICS},
        "sample_slices": sorted({str(slice_of.get(s.plan.index)) for s in
                                 check.token_sample(ctx.served, 2**33 + 5, 80,
                                                    slice_of)}),
    }


def faulty_slice_run(root, run, out):
    """One slice's decode tokens altered where they are produced."""
    from bench.lib import serve, spec

    real_build = serve.build

    def build(*a, **k):
        sysm = real_build(*a, **k)
        e = sysm.engines[-1]
        orig = e._segment_jit

        def altered(*args):
            toks, pool = orig(*args)
            return (toks + 1) % sysm.cfg.vocab, pool

        e._segment_jit = altered
        return sysm

    serve.build = build
    try:
        res = run.run_cell(spec.load_cell(CELL, root), 2**33 + 6, 1.5,
                           allow_cpu=True, t_start=time.monotonic())
    finally:
        serve.build = real_build
    out["faulty"] = {"correct": res["correct"],
                     "token_gap": res["checks"]["token_gap"]}


def main(path: str) -> None:
    import jax

    out = {"devices": [d.id for d in jax.devices()]}
    root = bench_fixture.make_root(Path(tempfile.mkdtemp(prefix="fleet-")))
    run = _load_run()
    build_and_serve(root, out)
    traced_run(root, run, out)
    faulty_slice_run(root, run, out)
    Path(path).write_text(json.dumps(out))
    shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
