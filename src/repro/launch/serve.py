"""Serving launcher: PREBA inference server over a (sliced) pod or locally.

Local smoke: PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
                 --reduced --requests 32 --rate 50
Continuous batching (slot pool + segmented decode): add --continuous
                 [--max-slots 8 --segment-len 8]
Multi-slice (one continuous engine per MIG-analogue slice): --slices N
Multi-tenant fleet (slice-as-tenancy-unit, one model per slice set,
one shared admission queue + model router):
                 --tenants tinyllama-1.1b:2,mamba2-370m:2 --reduced
Stage-pipelined runtime (decoupled DPU preprocessing overlapped with
decode, bounded queues + SLO shedding): add --pipelined
                 [--preprocess dpu --slo 2.0]
"""
from __future__ import annotations

import argparse

MENU_HELP = """\
partition menu (MIG analogue, core/slicing/mig.py): the pod's device grid is
partitioned into disjoint sub-meshes at a 16-chip granularity, one serving
replica per slice, mirroring the paper's three design points on a 256-chip
pod:

  fine    1s(16x)   16 slices x  16 chips   ~ A100 1g.5gb(7x)
  medium  4s(4x)     4 slices x  64 chips   ~ A100 2g.10gb(3x)
  full    16s(1x)    1 slice  x 256 chips   ~ A100 7g.40gb(1x)

--slices N picks the number of replicas; with fewer local devices than
slices (CPU smoke) the replicas share the device set. Entries that do not
divide the pod strand chips, which are reported, not hidden. The engine can
re-slice elastically at runtime (MultiSliceEngine.resize), requeueing
in-flight work without losing requests.
"""


def _export(registry, tracer, metrics_out: str, trace_out: str,
            virtual: bool) -> None:
    """Write the metrics snapshot and/or the lifecycle timeline. The
    registry is schema-linted first — a name bound to two kinds or label
    keysets, or a duplicate series, is a bug worth failing the run over.
    Virtual clock: the trace is rebased to t=0 so two replays of the same
    seed export byte-identical files (the CI determinism gate)."""
    if not (metrics_out or trace_out):
        return
    problems = registry.lint()
    if problems:
        raise SystemExit("metric schema lint failed:\n  "
                         + "\n  ".join(problems))
    if metrics_out:
        with open(metrics_out, "w") as f:
            f.write(registry.to_json())
        print(f"  metrics -> {metrics_out}")
    if trace_out:
        with open(trace_out, "w") as f:
            f.write(tracer.to_json(0.0 if virtual else None))
        print(f"  trace   -> {trace_out}")


def _calibrate_knee(cfg, ec, out_path: str, *, max_batch: int) -> None:
    """The paper's offline profiling pass (§3.2, 'several minutes,
    amortized over millions of queries'): for every context bucket the
    engine serves, sweep batch sizes through a REAL timed decode step
    (prefill a padded context, then time lm.decode with the cache
    resident) and find the Batch_knee/Time_knee. Writes the {bucket:
    profile} JSON artifact `--knee-profiles` loads back."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.core.batching.knee import calibrate_knees, profiles_to_json
    from repro.models import api, lm

    params = api.init_params(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype)
    step = jax.jit(lambda p, c, t, pos: lm.decode(p, c, t, pos, cfg))

    def measure(batch: int, context_len: int) -> float:
        ctx = max(int(ec.min_prompt_len), context_len)
        toks = jnp.zeros((batch, ctx), jnp.int32)
        _, cache = jax.jit(
            lambda p, t: lm.prefill(p, t, cfg, cache_len=ctx + 2)
        )(params, toks)
        tok = jnp.zeros((batch, 1), jnp.int32)
        pos = jnp.int32(ctx)
        jax.block_until_ready(step(params, cache, tok, pos))  # compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(step(params, cache, tok, pos))
            best = min(best, time.perf_counter() - t0)
        return best

    bw = max(1, int(ec.bucket_width))
    buckets = list(range(max(1, ec.max_prompt_len // bw)))
    profiles = calibrate_knees(measure, buckets, bw, max_batch=max_batch)
    with open(out_path, "w") as f:
        f.write(profiles_to_json(profiles))
    for b, p in sorted(profiles.items()):
        print(f"  bucket {b} (ctx~{int((b + 0.5) * bw)}): "
              f"Batch_knee={p.batch_knee} "
              f"Time_knee={1e3 * p.time_knee:.2f}ms")
    print(f"  knee profiles -> {out_path}")


def main():
    ap = argparse.ArgumentParser(
        epilog=MENU_HELP,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--arch")
    ap.add_argument("--tenants", default="",
                    help="comma-separated model:slices asks (e.g. "
                         "'tinyllama-1.1b:2,mamba2-370m:1'): a multi-tenant "
                         "fleet — every tenant's model gets its own slice "
                         "set (its own engines, slot pools, executables) "
                         "behind ONE shared admission queue, requests are "
                         "tagged and routed per model, and the total slice "
                         "count is the sum of the asks; replaces "
                         "--arch/--slices")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=50.0)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--continuous", action="store_true",
                    help="slot-pool continuous batching (in-flight join/leave)")
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--segment-len", type=int, default=8)
    ap.add_argument("--chunk-lens", default="",
                    help="comma-separated chunked-prefill lengths (pow2, "
                         "e.g. '32' or '16,64'): prompt buckets longer than "
                         "the policy-chosen chunk admit chunk-by-chunk, "
                         "interleaved with decode segments, so long prompts "
                         "never stall resident decoders (attention+MLP "
                         "models; empty = monolithic admission)")
    ap.add_argument("--slices", type=int, default=1,
                    help="number of MIG-analogue slices, each its own "
                         "continuous-batching engine behind one shared "
                         "admission queue (see partition menu below)")
    ap.add_argument("--hedge-factor", type=float, default=3.0,
                    help="straggler threshold: hedge a slice past this "
                         "multiple of the expected batch time")
    ap.add_argument("--pipelined", action="store_true",
                    help="stage-pipelined runtime: ingest -> DPU preprocess "
                         "-> admission -> decode with bounded queues; "
                         "preprocessing overlaps decode on a wall clock")
    ap.add_argument("--preprocess", choices=("none", "dpu"), default="none",
                    help="attach raw audio payloads and preprocess them "
                         "(inline at submit, or on the decoupled DPU "
                         "service with --pipelined)")
    ap.add_argument("--slo", type=float, default=float("inf"),
                    help="front-door latency SLO in seconds (--pipelined): "
                         "requests that cannot meet it are shed")
    ap.add_argument("--clock", choices=("wall", "virtual"), default="wall",
                    help="--pipelined clock: wall = real serving (the DPU "
                         "worker overlaps decode in real time); virtual = "
                         "deterministic replay (arrivals drive the clock — "
                         "two runs of the same seed export byte-identical "
                         "timelines, the CI determinism gate)")
    ap.add_argument("--controller", action="store_true",
                    help="close the resize() loop (--pipelined): an online "
                         "partition controller watches arrival rate / "
                         "prompt-length mix / queue depths and re-slices "
                         "the fleet mid-serve — fine slices for bursts, "
                         "coarse for long-prompt mixes; decisions are "
                         "hysteretic, cost-modeled against the knee "
                         "profiles, and deterministic under --clock "
                         "virtual")
    ap.add_argument("--controller-menu", default="",
                    help="comma-separated slice counts the controller may "
                         "pick from (ascending; default '1,2,4'); --slices "
                         "must be one of them (the starting point)")
    ap.add_argument("--calibrate-knee", default="", metavar="OUT",
                    help="run the offline Batch_knee/Time_knee profiling "
                         "pass (paper §3.2: sweep batch sizes per context "
                         "bucket through a real timed decode step, knee = "
                         "where throughput plateaus) and write the "
                         "{bucket: profile} JSON to OUT, then exit; feed "
                         "it back with --knee-profiles")
    ap.add_argument("--knee-profiles", default="", metavar="IN",
                    help="load a --calibrate-knee JSON artifact and use "
                         "the measured knees (instead of the analytical "
                         "roofline default) for admission batching and "
                         "the partition controller's cost model")
    ap.add_argument("--metrics-out", default="",
                    help="write the full metrics-registry snapshot (every "
                         "layer: runtime, engines, DPU service, prefix "
                         "stores) as JSON to this path after serving")
    ap.add_argument("--trace-out", default="",
                    help="write the request-lifecycle timeline as Chrome "
                         "trace-event JSON (chrome://tracing / Perfetto) "
                         "to this path after serving")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    import numpy as np

    from repro.configs import get_config, reduced
    from repro.serving.engine import EngineConfig, build_engine
    from repro.serving.requests import WorkloadSpec, generate_requests

    tenant_asks = []
    for part in args.tenants.split(","):
        part = part.strip()
        if not part:
            continue
        arch, _, n = part.partition(":")
        try:
            n = int(n) if n else 1
        except ValueError:
            ap.error(f"--tenants entries are model:slices (got {part!r})")
        if n < 1:
            ap.error(f"--tenants slice asks must be >= 1 (got {part!r})")
        tenant_asks.append((arch.strip(), n))
    if args.tenants and not tenant_asks:
        ap.error("--tenants given but holds no model:slices entries")
    if not tenant_asks and not args.arch:
        ap.error("--arch is required unless --tenants is given")
    if args.controller and not args.pipelined:
        ap.error("--controller closes the loop over the pipelined "
                 "runtime; add --pipelined")

    cfg = (reduced(args.arch) if args.reduced else get_config(args.arch)) \
        if args.arch else None
    chunk_lens = tuple(
        int(c) for c in args.chunk_lens.split(",") if c.strip()
    )
    for c in chunk_lens:
        # fail at parse time, not mid-serve: the engine asserts pow2
        # divisibility against pow2 prompt buckets at admission
        if c <= 0 or c & (c - 1):
            ap.error(f"--chunk-lens entries must be positive powers of two "
                     f"(got {c})")
    ec = EngineConfig(
        max_new_tokens=args.max_new, continuous=args.continuous or bool(tenant_asks),
        max_slots=args.max_slots, segment_len=args.segment_len,
        max_prompt_len=128,  # covers the workload's max_len=120 prompt bucket
        preprocess=args.preprocess if not args.pipelined else "none",
        chunk_lens=chunk_lens,
    )

    if args.calibrate_knee:
        if cfg is None:
            ap.error("--calibrate-knee needs --arch (one model per pass)")
        _calibrate_knee(cfg, ec, args.calibrate_knee,
                        max_batch=args.max_slots)
        return

    knee_profiles = None
    if args.knee_profiles:
        from repro.core.batching.knee import profiles_from_json

        with open(args.knee_profiles) as f:
            knee_profiles = profiles_from_json(f.read())
        print(f"  knee profiles <- {args.knee_profiles} "
              f"({len(knee_profiles)} context buckets)")

    tenants = None
    if tenant_asks:
        from repro.serving.multislice import TenantSpec

        # duplicate archs get @k-suffixed tenant names (tenant names must
        # be unique even when two tenants serve the same model config)
        seen: dict = {}
        tenants, specs = [], []
        for i, (arch, n) in enumerate(tenant_asks):
            tcfg = reduced(arch) if args.reduced else get_config(arch)
            k = seen.get(arch, 0)
            seen[arch] = k + 1
            name = arch if k == 0 else f"{arch}@{k}"
            tenants.append(TenantSpec(cfg=tcfg, name=name, n_slices=n,
                                      seed=i))
            # one Poisson stream per tenant, traffic share ~ slice ask
            specs.append((WorkloadSpec(
                modality="text", rate_qps=args.rate, mean_len=48,
                max_len=120, vocab=tcfg.vocab, model=name, seed=i,
                payload_samples=48000 if args.preprocess == "dpu" else 0,
            ), float(n)))
        n_slices = sum(n for _, n in tenant_asks)
        reqs = generate_requests(specs, args.requests)
    else:
        n_slices = args.slices
        reqs = generate_requests(
            WorkloadSpec(modality="text", rate_qps=args.rate, mean_len=48,
                         max_len=120, vocab=cfg.vocab,  # real tokenized prompts
                         payload_samples=48000 if args.preprocess == "dpu" else 0),
            args.requests,
        )

    if args.pipelined:
        from repro.core.dpu.service import DpuService, DpuServiceConfig
        from repro.serving import telemetry as tm
        from repro.serving.runtime import RuntimeConfig, build_pipelined_runtime

        import time

        service = None
        if args.preprocess == "dpu":
            from repro.core.dpu.runtime import DpuConfig

            # the decoupled path runs the REAL DPU backend (pow2-bucketed
            # fused Pallas launches) — the cpu backend is the inline
            # baseline, not the service
            service = DpuService(DpuServiceConfig(
                clock=args.clock, dpu=DpuConfig(backend="dpu")))
        controller = None
        if args.controller:
            from repro.core.control import (
                ControllerConfig, PartitionController,
            )

            menu = tuple(
                int(x) for x in args.controller_menu.split(",") if x.strip()
            ) or (1, 2, 4)
            if n_slices not in menu:
                ap.error(f"--slices {n_slices} must be on the controller "
                         f"menu {menu} (it is the starting point)")
            controller = PartitionController(ControllerConfig(menu=menu))
        rt = build_pipelined_runtime(
            cfg, n_slices=n_slices, ec=ec, service=service,
            rc=RuntimeConfig(clock=args.clock, slo_s=args.slo,
                             max_ingest=max(64, 2 * args.requests)),
            hedge_factor=args.hedge_factor, tenants=tenants,
            controller=controller, knee_profiles=knee_profiles,
        )
        if args.clock == "virtual":
            # deterministic replay: the trace's 0-based arrivals ARE the
            # clock; everything downstream (timestamps, trace events,
            # exported timelines) is a pure function of the trace
            from repro.serving.faults import replay_virtual

            done = replay_virtual(rt, reqs)
            rt.close()
        else:
            # rebase the workload's 0-based arrival times onto the wall
            # clock: the SLO check compares time.monotonic() against
            # arrival + slo, so un-rebased arrivals would make ANY finite
            # --slo shed everything
            t0 = time.monotonic()
            for r in reqs:
                r.arrival += t0
            rt.submit(reqs)
            done = rt.run_until_idle()
            rt.close()
        lats = [r.completed_at - r.dispatched_at for r in done]
        # a tight --slo can shed everything; the summary must still print
        exec_ms = (f"exec p50={1e3*np.percentile(lats,50):.1f}ms "
                   f"p95={1e3*np.percentile(lats,95):.1f}ms"
                   if lats else "exec n/a (nothing served)")
        print(
            f"pipelined: served {len(done)} requests, shed {len(rt.shed)} "
            f"(slo={rt.stats['shed_slo']}, "
            f"backpressure={rt.stats['shed_backpressure']}, "
            f"error={rt.stats['shed_error']}); {exec_ms}"
        )
        # a degraded run must not look healthy: the breaker's CPU path and
        # any failed DPU launch are printed next to the served count
        print(f"  dpu: cpu_fallback={rt.stats['cpu_fallback']} "
              f"breaker_trips={rt.stats['breaker_trips']} "
              f"shed_error={rt.stats['shed_error']} "
              f"last_error={service.last_error if service else None!r}")
        for stage, st in rt.stage_summary().items():
            print(f"  queue[{stage}]: mean={st['mean']:.2f} max={st['max']}")
        launches = rt.tracer.of(tm.DPU_EXEC)
        print(f"  dpu_exec: {len(launches)} launches, "
              f"{sum(e.dur for e in launches):.3f} s")
        if controller is not None:
            print(f"  controller: {len(controller.decisions)} "
                  f"reconfiguration(s), fleet now "
                  f"{len(rt.engine.pod.slices)} slice(s)")
            for d in controller.decisions:
                print(f"    t={d.t:.3f}s {d.from_slices}->{d.to_slices} "
                      f"[{d.reason}] demand={d.demand} "
                      f"gain={d.gain_frac:.2f} requeued={d.requeued}")
        _export(rt.registry, rt.tracer, args.metrics_out, args.trace_out,
                args.clock == "virtual")
        return

    if n_slices > 1 or tenants:
        from repro.serving.multislice import build_multislice_engine

        engine = build_multislice_engine(
            cfg, n_slices=n_slices, ec=ec, hedge_factor=args.hedge_factor,
            tenants=tenants, knee_profiles=knee_profiles,
        )
        engine.submit_many(reqs)
        done = engine.run_until_idle()
        lats = [r.completed_at - r.dispatched_at for r in done]
        print(
            f"served {len(done)} requests on {engine.pod.spec.name} "
            f"({'replicated' if engine.replicated else 'partitioned'}, "
            f"{engine.pod.stranded_chips} chips stranded); "
            f"{engine.stats['dispatched']} dispatched requests, "
            f"{engine.hedges} hedges; "
            f"exec p50={1e3*np.percentile(lats,50):.1f}ms "
            f"p95={1e3*np.percentile(lats,95):.1f}ms"
        )
        for sid, st in sorted(engine.slice_stats().items()):
            print(f"  slice {sid} [{st['model']}]: admitted={st['admitted']} "
                  f"segments={st['segments']} "
                  f"occupancy={st['mean_slot_occupancy']:.3f}")
        if tenants:
            for name, ts in sorted(engine.tenant_stats().items()):
                print(f"  tenant {name}: slices={sorted(ts['slices'])} "
                      f"completed={ts['completed']} dead={ts['dead']} "
                      f"routed_to={sorted(ts['routed_to'])}")
        _export(engine.registry, engine.tracer, args.metrics_out,
                args.trace_out, virtual=False)
        return

    engine = build_engine(cfg, ec=ec)
    for r in reqs:
        engine.submit(r)
    done = engine.run_until_idle()
    lats = [r.completed_at - r.dispatched_at for r in done]
    print(
        f"served {len(done)} requests in {engine.batcher.formed} batches; "
        f"exec p50={1e3*np.percentile(lats,50):.1f}ms p95={1e3*np.percentile(lats,95):.1f}ms"
    )
    _export(engine.registry, engine.tracer, args.metrics_out,
            args.trace_out, virtual=False)


if __name__ == "__main__":
    main()
