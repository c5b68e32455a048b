"""Bring-up check: the PREBA serving path end to end on a TPU.

    python chip_smoke.py               one chip (the default)
    python chip_smoke.py --four-chips  four chips of one host, nothing else

One chip: full-width tinyllama-1.1b (random bf16 weights from seed 0)
behind the stage-pipelined runtime, with the wall-clock DPU preprocessing
service running the Pallas audio front end, continuous batching and
chunked prefill on. Sixteen requests with real token prompts and one
second of 48 kHz audio each are served, and the run fails unless

  * every request completes, none is shed or dead-lettered;
  * the DPU service never failed a launch and the runtime never fell back
    to CPU preprocessing (the breaker is armed, so any fault would show);
  * the DPU outputs match the numpy reference (data/preprocess_cpu.py),
    and each audio kernel matches it alone at its test tolerance;
  * the served tokens are bit-identical to a synchronous engine's;
  * the DPU program was compiled to Mosaic (`tpu_custom_call`).

Four chips: one full-width replica per chip (`build_multislice_engine`,
the 1g-style menu point) serves the same requests; each slice's params and
slot pool must live on that slice's own chip, and the tokens must be
bit-identical to a one-slice engine on chip 0. All four chips are driven
from this one process.

Diagnostic lines come first; the last line of standard output is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Without a TPU, or outside a checkout of the repository, the script exits
non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "tinyllama-1.1b"
N_REQUESTS = 16
AUDIO_SAMPLES = 48000          # one second at 48 kHz, resampled to 16 kHz
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def _start():
    """Import the repo and JAX, insist on a TPU, and arm the compile cache
    and a compile-time counter. Returns (jax, compile_seconds_cell)."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SmokeFailure(f"repository sources not found under {src}")
    sys.path.insert(0, str(src))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: JAX sees {len(jax.devices())} "
            f"{dev.platform} device(s)")
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    print(f"device: {dev.device_kind} x{len(jax.devices())} "
          f"(platform {dev.platform}); compile cache {cache}", flush=True)
    return jax, compile_s


def _engine_config():
    from repro.serving.engine import EngineConfig

    # launch/serve.py's pipelined defaults, with chunked prefill on
    return EngineConfig(max_new_tokens=8, continuous=True, max_slots=8,
                        segment_len=8, max_prompt_len=128, chunk_lens=(32,))


def _requests(cfg):
    """The launch/serve.py --preprocess dpu workload: real token prompts
    (lognormal lengths up to 120) plus a raw audio payload each."""
    from repro.serving.requests import WorkloadSpec, generate_requests

    return generate_requests(
        WorkloadSpec(modality="text", rate_qps=50.0, mean_len=48,
                     max_len=120, vocab=cfg.vocab,
                     payload_samples=AUDIO_SAMPLES),
        N_REQUESTS,
    )


def _text_requests(cfg):
    """The same requests without their audio: payloads never influence
    decode tokens, and the engines below serve text only."""
    reqs = _requests(cfg)
    for r in reqs:
        r.payload = None
    return reqs


def _tokens(done):
    import numpy as np

    return {r.rid: np.asarray(r.payload) for r in done}


def _sync_reference(cfg, ec):
    """Same requests through a synchronous engine (submit_many +
    run_until_idle): the runtime's bit-identity reference."""
    from repro.serving.engine import build_engine

    eng = build_engine(cfg, ec=ec)
    eng.submit_many(_text_requests(cfg))
    return eng, _tokens(eng.run_until_idle())


def _check_identical(got, want, what):
    import numpy as np

    same = got.keys() == want.keys() and all(
        np.array_equal(got[k], want[k]) for k in want)
    check(same, f"{what}: {len(got)} token payloads bit-identical to the "
                f"synchronous engine")


def _check_full_width(jax, cfg, params):
    import jax.numpy as jnp

    from repro.configs import get_config

    leaves = jax.tree.leaves(params)
    n = sum(x.size for x in leaves)
    print(f"model: {cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab}; {n / 1e9:.3f}B params", flush=True)
    check(cfg == get_config(ARCH), "published tinyllama-1.1b widths "
                                   "(not the reduced config)")
    check(all(x.dtype == jnp.bfloat16 for x in leaves), "bf16 serving params")


def _check_dpu_kernels(jax, raw):
    """Each audio kernel alone vs the numpy reference, at the tolerances
    of tests/test_kernels.py, on one served payload."""
    import numpy as np

    from repro.data import preprocess_cpu as pp
    from repro.kernels import ops

    def close(got, want, rtol, atol, what):
        got, want = np.asarray(got), np.asarray(want)
        m = min(len(got), len(want))
        err = float(np.max(np.abs(got[:m] - want[:m])))
        ok = np.allclose(got[:m], want[:m], rtol=rtol, atol=atol)
        check(ok, f"{what} vs numpy: max abs err {err:.3g} "
                  f"(rtol={rtol}, atol={atol})")

    y = pp.resample_poly(raw, 1, 3)
    close(ops.audio_resample(raw, 1, 3), y, 1e-4, 1e-5, "resample kernel")
    feats = pp.mel_spectrogram(y)
    close(ops.mel_spectrogram(y), feats, 2e-4, 2e-4, "mel kernel")
    close(ops.audio_normalize(feats), pp.normalize_meanvar(feats),
          3e-3, 3e-3, "normalize kernel")


def one_chip(jax, compile_s) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.dpu.runtime import DpuConfig
    from repro.core.dpu.service import DpuService, DpuServiceConfig
    from repro.data import preprocess_cpu as pp
    from repro.kernels import ops
    from repro.serving.runtime import RuntimeConfig, build_pipelined_runtime

    cfg = get_config(ARCH)
    ec = _engine_config()
    service = DpuService(DpuServiceConfig(
        clock="wall", dpu=DpuConfig(backend="dpu")))
    # breaker armed: a failing DPU launch would reroute to the CPU path,
    # which the checks below count as a failure
    rt = build_pipelined_runtime(
        cfg, ec=ec, service=service,
        rc=RuntimeConfig(clock="wall", max_ingest=max(64, 2 * N_REQUESTS),
                         preprocess_retries=1, breaker_threshold=1))
    _check_full_width(jax, cfg, rt.engine.params)

    reqs = _requests(cfg)
    raw = {r.rid: np.array(r.payload) for r in reqs}
    dpu_out = {}
    offer = rt.engine.offer

    def offer_and_record(batch):
        # admission receives what the DPU produced: keep it for the check
        for r in batch:
            if r.rid in raw:
                dpu_out[r.rid] = np.array(r.payload)
        return offer(batch)

    rt.engine.offer = offer_and_record
    t0 = time.monotonic()
    for r in reqs:
        r.arrival += t0   # the workload's arrivals are 0-based
    rt.submit(reqs)
    done = rt.run_until_idle()
    rt.close()
    serve_s = time.monotonic() - t0
    compile_serve = compile_s[0]
    n_tok = sum(len(r.payload) for r in done)
    print(f"served {len(done)} requests, {n_tok} tokens in {serve_s:.2f}s "
          f"wall (cold: includes compiling); backend compile "
          f"{compile_serve:.2f}s", flush=True)
    print(f"dpu: groups={service.stats['groups']} "
          f"processed={service.stats['processed']} "
          f"failed={service.stats['failed']} "
          f"last_error={service.last_error!r}", flush=True)

    check(len(done) == N_REQUESTS and {r.rid for r in done} == set(raw),
          f"all {N_REQUESTS} requests completed")
    check(not rt.shed and not rt.dead,
          f"no request shed ({rt.shed_counts()}) or dead-lettered "
          f"({rt.dead_counts()})")
    check(rt.stats["cpu_fallback"] == 0 and rt.stats["breaker_trips"] == 0,
          f"no CPU fallback (cpu_fallback={rt.stats['cpu_fallback']}, "
          f"breaker_trips={rt.stats['breaker_trips']})")
    check(service.stats["failed"] == 0 and service.last_error is None,
          "no failed DPU launch")

    check(dpu_out.keys() == raw.keys(), "every request passed the DPU")
    _check_dpu_kernels(jax, raw[min(raw)])
    # DPU outputs of the served groups vs the numpy front end, at the
    # fused-pipeline tolerance of tests/test_runtime.py
    err = 0.0
    ok = True
    for rid, y in dpu_out.items():
        want = pp.audio_pipeline(raw[rid])
        ok &= y.shape == want.shape and np.allclose(y, want, rtol=2e-2,
                                                    atol=2e-2)
        if y.shape == want.shape:
            err = max(err, float(np.max(np.abs(y - want))))
    check(ok, f"DPU outputs {next(iter(dpu_out.values())).shape} match "
              f"numpy: max abs err {err:.3g} (rtol=2e-2, atol=2e-2)")

    text = ops.audio_pipeline_batch.lower(jax.ShapeDtypeStruct(
        (N_REQUESTS, AUDIO_SAMPLES), jnp.float32)).compile().as_text()
    n_mosaic = text.count("tpu_custom_call")
    check(n_mosaic >= 4, f"DPU program holds {n_mosaic} tpu_custom_call "
                         f"kernels (resample, mel, 2x normalize)")

    _, want = _sync_reference(cfg, ec)
    _check_identical(_tokens(done), want, "pipelined runtime")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"backend compile total {compile_s[0]:.2f}s", flush=True)


def four_chips(jax, compile_s) -> None:
    from repro.configs import get_config
    from repro.serving.engine import build_engine
    from repro.serving.multislice import build_multislice_engine

    devs = jax.devices()
    check(len(devs) >= 4, f"four chips ({len(devs)} found)")
    cfg = get_config(ARCH)
    ec = _engine_config()
    ms = build_multislice_engine(cfg, n_slices=4, ec=ec)
    check(not ms.replicated and len(ms.pod.slices) == 4,
          f"4 one-chip slices ({ms.pod.spec.name})")
    _check_full_width(jax, cfg, ms.engines[0].params)
    t0 = time.monotonic()
    ms.submit_many(_text_requests(cfg))
    got = _tokens(ms.run_until_idle())
    print(f"4 slices served {len(got)} requests in "
          f"{time.monotonic() - t0:.2f}s wall (cold); backend compile "
          f"{compile_s[0]:.2f}s", flush=True)

    def on(tree):
        return {d for x in jax.tree.leaves(tree) for d in x.devices()}

    chips = set()
    for ps in ms.pod.slices:
        e = ms.engines[ps.slice_id]
        own = set(ps.devices.reshape(-1))
        p_on, pool_on = on(e.params), on(e._pool)
        print(f"  slice {ps.slice_id}: admitted={e.stats['admitted']} "
              f"params on {sorted(d.id for d in p_on)}, "
              f"pool on {sorted(d.id for d in pool_on)}", flush=True)
        check(len(own) == 1 and p_on == own and pool_on == own,
              f"slice {ps.slice_id} params and slot pool on its own chip")
        check(e.stats["admitted"] > 0, f"slice {ps.slice_id} served requests")
        chips |= own
    check(chips == set(devs[:4]), "the four slices hold four distinct chips")

    one = build_engine(cfg, ec=ec)
    check(on(one.params) == {devs[0]}, "one-slice reference on chip 0")
    one.submit_many(_text_requests(cfg))
    _check_identical(got, _tokens(one.run_until_idle()), "4-slice fleet")
    for i, d in enumerate(devs[:4]):
        stats = d.memory_stats() or {}
        print(f"chip {i}: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (one replica per "
                         "chip vs a one-slice engine)")
    args = ap.parse_args()
    try:
        jax, compile_s = _start()
        (four_chips if args.four_chips else one_chip)(jax, compile_s)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
