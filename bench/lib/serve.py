"""The system under test and the client that drives it.

`build` builds the cell's server through the program's own entry point,
`repro.serving.runtime.build_pipelined_runtime` (a continuous-batching
`ServingEngine` with the policy `build_engine` derives, behind a
wall-clock `PipelinedRuntime`, with a wall-clock `DpuService` running the
fused Pallas audio front end where the traffic carries audio), and hands
it the benchmark's own weights. `build_engine` draws a parameter tree of
its own before it takes `params`; while it builds, that draw returns the
benchmark's tree instead, so no second tree is ever allocated.

A cell on N > 1 chips, over a configuration that one chip holds whole,
builds a fleet: `build_pipelined_runtime(n_slices=N)` gives a
`MultiSliceEngine` with one one-chip replica (`ServingEngine`) per chip
behind one queue and the one DPU service; the program copies the
benchmark's tree onto each chip. `System.engines` lists the engines that
own slot pools: the one engine, or the fleet's slices.

`warm` compiles every program the cell's traffic can use, `drive` is the
open- or closed-loop client of the measured window, and `Probe` wraps the
calls into the runtime step, the DPU service and every engine's admit,
chunk and segment programs with profiler spans in a traced run, recording
the useful work each call did.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional

import numpy as np

from bench.lib.traffic import Planned

MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "rope_theta", "norm_eps", "tie_embeddings",
              "dtype", "ssm_state", "ssm_expand", "ssm_headdim", "ssm_conv",
              "ssm_chunk", "ssm_ngroups")


def model_config(config: dict):
    """The program's registered model with every size the file states:
    the keys of MODEL_KEYS at the top level of the file, then any further
    field of the program's `ModelConfig` under `model_fields` (experts,
    layer pattern, multipliers, ...). A `model_fields` key that is no
    field, or that MODEL_KEYS already covers, is an error."""
    from repro.configs.base import get_config

    base = get_config(config["model"])
    extra = dict(config.get("model_fields") or {})
    known = {f.name for f in dataclasses.fields(base)}
    bad = sorted(k for k in extra if k not in known or k in MODEL_KEYS)
    if bad:
        raise ValueError(f"{config.get('name')}: model_fields {bad} are not "
                         "ModelConfig fields outside MODEL_KEYS")
    cfg = replace(base, **{k: config[k] for k in MODEL_KEYS if k in config})
    return replace(cfg, **{k: tuple(v) if isinstance(v, list) else v
                           for k, v in extra.items()})


def fleet_size(config: dict, chips: int) -> int:
    """Replicas a cell on `chips` chips serves: one per chip, where one
    chip holds the whole model (the file reduces nothing); 1 for a
    one-chip cell. A share of a model (a pipeline stage, an expert share)
    on several chips needs a sharded build, which the harness has not."""
    if chips > 1 and config.get("reduced"):
        raise ValueError(
            f"{config.get('name')} holds a share of its model (reduced "
            f"{config['reduced']}): {chips} chips would need a sharded "
            "build, not replicas")
    return int(chips)


@dataclass
class System:
    cfg: object
    engine: object                  # the runtime's engine (or fleet)
    runtime: object
    service: object
    ec: object
    engines: list                   # the engines with slot pools


def slice_engines(engine) -> list:
    """The engines that own slot pools: a fleet's slices, in slice order,
    or the engine itself."""
    fleet = getattr(engine, "engines", None)
    return [fleet[k] for k in sorted(fleet)] if fleet is not None \
        else [engine]


def build(config: dict, params, with_audio: bool, chips: int = 1) -> System:
    from unittest import mock

    from repro.core.dpu.runtime import DpuConfig
    from repro.core.dpu.service import DpuService, DpuServiceConfig
    from repro.serving import engine as engine_mod
    from repro.serving.engine import EngineConfig
    from repro.serving.runtime import RuntimeConfig, build_pipelined_runtime

    cfg = model_config(config)
    ec = EngineConfig(continuous=True, preprocess="none",
                      **{k: tuple(v) if isinstance(v, list) else v
                         for k, v in config["engine"].items()})
    service = None
    if with_audio:
        service = DpuService(DpuServiceConfig(
            dpu=DpuConfig(modality="audio", backend="dpu"), clock="wall",
            **config.get("dpu_service", {})))
    rc = RuntimeConfig(clock="wall", **config.get("runtime", {}))
    n = fleet_size(config, chips)
    fleet = {"n_slices": n} if n > 1 else {}
    with mock.patch.object(engine_mod.api, "init_params",
                           lambda *a, **k: params):
        runtime = build_pipelined_runtime(cfg, ec=ec, service=service, rc=rc,
                                          params=params, **fleet)
    engine = runtime.engine
    engines = slice_engines(engine)
    if n == 1:
        if engine.params is not params:
            raise RuntimeError("build_pipelined_runtime did not serve the "
                               "benchmark's weights")
    else:
        check_replicas(engines, params, n)
    return System(cfg, engine, runtime, service, engines[0].ec, engines)


@functools.lru_cache(maxsize=None)
def _fingerprint_fn():
    import jax
    import jax.numpy as jnp

    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}

    def leaf(x):
        u = jax.lax.bitcast_convert_type(x, uint[x.dtype.itemsize])
        u = u.reshape(-1).astype(jnp.uint32)
        i = jnp.arange(u.shape[0], dtype=jnp.uint32)
        return jnp.stack([u.sum(dtype=jnp.uint32),
                          (u * (i * jnp.uint32(2654435761) + 1)).sum(
                              dtype=jnp.uint32)])

    return jax.jit(lambda tree: jax.tree.map(leaf, tree))


def fingerprint(tree) -> list:
    """Two wrapping uint32 sums of each leaf's bits (plain, and weighted by
    position), computed where the leaf lives: equal trees read equal."""
    import jax

    return [np.asarray(x).tolist()
            for x in jax.tree.leaves(_fingerprint_fn()(tree))]


def check_replicas(engines, params, n: int) -> None:
    """A fleet serves the benchmark's weights on every slice: n slices,
    each tree on one chip of its own, leaf for leaf the tree the benchmark
    made (same structure, shapes, types and bits)."""
    import jax

    if len(engines) != n:
        raise RuntimeError(f"the fleet has {len(engines)} slices, not {n}")
    want = jax.tree.structure(params)
    shapes = [(x.shape, x.dtype) for x in jax.tree.leaves(params)]
    bits = fingerprint(params)
    seen = set()
    for i, e in enumerate(engines):
        leaves = jax.tree.leaves(e.params)
        devs = {d for x in leaves for d in x.devices()}
        if jax.tree.structure(e.params) != want or \
                [(x.shape, x.dtype) for x in leaves] != shapes or \
                len(devs) != 1 or devs & seen or fingerprint(e.params) != bits:
            raise RuntimeError(f"slice {i} does not serve the benchmark's "
                               "weights on a chip of its own")
        seen |= devs


def tokens_served(engine) -> int:
    """Output tokens the engine has produced so far: those of the requests
    it completed, and those its live rows hold, summed over a fleet's
    slices (a fleet records each completed request once, whichever slice
    served it). Read from the slot tables between two steps."""
    done = sum(len(r.payload) for r in engine.completed)
    return done + sum(len(st.produced) for e in slice_engines(engine)
                      for st in e._slots if st is not None)


def counters(engine) -> dict:
    """The engines' counters, summed over a fleet's slices, with the
    fleet's own counters beside them as `fleet.<name>`."""
    engines = slice_engines(engine)
    out = dict(engines[0].stats.items())
    for e in engines[1:]:
        for k, v in e.stats.items():
            out[k] = out.get(k, 0) + v
    if engine is not engines[0]:
        out.update({f"fleet.{k}": v for k, v in engine.stats.items()})
    return out


def next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def prompt_buckets(ec, lo: int, hi: int) -> List[int]:
    b = max(ec.min_prompt_len, next_pow2(lo))
    out = []
    while b <= max(ec.min_prompt_len, next_pow2(hi)):
        out.append(b)
        b *= 2
    return out


def warm(sysm: System, traffic: dict, rng: np.random.Generator) -> int:
    """Compile every program the traffic can reach: one request per prompt
    bucket through the runtime (admit / chunk / segment programs), and the
    fused DPU program for every clip length x power-of-two group size. A
    fleet's dispatcher picks the slice a request lands on, so each slice
    then takes one request per prompt bucket of its own, offered to it
    directly: no slice compiles inside the window. Returns the number of
    warm-up requests served."""
    import jax.numpy as jnp

    from repro.core.batching.buckets import Request

    lo, hi = int(traffic["prompt"]["min"]), int(traffic["prompt"]["max"])
    audio = traffic.get("audio")
    clips = [int(round(s * audio["sample_rate"])) for s in audio["clip_s"]] \
        if audio else []
    if clips:
        from repro.kernels import ops as kops

        m = 1
        while m <= sysm.service.cfg.max_group:
            for n in clips:
                xs = [np.zeros(n, np.float32)] * m
                np.asarray(kops.audio_pipeline_batch(jnp.stack(xs)))
            m *= 2
    buckets = prompt_buckets(sysm.ec, lo, hi)

    def requests(first_rid, audio):
        return [Request(
            rid=first_rid - i, arrival=time.monotonic(), length=float(n),
            prompt=rng.integers(0, sysm.cfg.vocab, n).astype(np.int32),
            payload=(rng.standard_normal(clips[i % len(clips)],
                                         dtype=np.float32)
                     if audio else None),
            max_new_tokens=2)
            for i, n in enumerate(min(lp, hi) for lp in buckets)]

    reqs = requests(-1, bool(clips))
    sysm.runtime.submit(reqs)
    while sysm.runtime.busy():
        if not sysm.runtime.step():
            time.sleep(0.001)
    n_warm = len(reqs)
    if len(sysm.engines) > 1:
        for k, e in enumerate(sysm.engines):
            own = requests(-1 - (k + 1) * len(buckets), False)
            e.offer(own)
            while e.busy():
                e.step()
            e.completed = []
            n_warm += len(own)
    return n_warm


@dataclass
class Served:
    """One planned request and what became of it."""

    plan: Planned
    due: float                      # absolute due time (monotonic clock)
    req: object = None              # the Request handed to the runtime
    features: Optional[np.ndarray] = None   # DPU output, captured on harvest
    tokens: Optional[np.ndarray] = None     # served tokens once completed
    status: str = "pending"         # completed | shed | dead | unfinished


@dataclass
class Window:
    t0: float
    t1: float
    served: List[Served]
    end: float = 0.0
    lateness: List[float] = field(default_factory=list)
    ran_dry: bool = False           # a closed-loop client ran out early


def _request(p: Planned, due: float):
    from repro.core.batching.buckets import Request

    return Request(rid=p.index, arrival=due, length=float(len(p.prompt)),
                   prompt=p.prompt, payload=p.audio, max_new_tokens=p.max_new)


class _Ledger:
    """Tracks the runtime's outcome lists incrementally."""

    def __init__(self, runtime):
        self.rt = runtime
        self.marks = {"completed": len(runtime.completed),
                      "shed": len(runtime.shed), "dead": len(runtime.dead)}

    def new(self, kind: str):
        lst = getattr(self.rt, kind)
        out = lst[self.marks[kind]:]
        self.marks[kind] = len(lst)
        return out


def drive(sysm: System, traffic: dict, planned: List[Planned],
          seconds: float, drain_s: float, hooks=None) -> Window:
    """Run the measured window as the traffic's client and drain it.

    Open loop: each request is sent at its due time, whatever the server
    does. Closed loop: each client sends its next request when its last
    one completes (or fails), from `ramp_s` before the window opens until
    it closes. Either way requests are timed from their due time, and every
    request sent is drained until `drain_s` after the close."""
    rt = sysm.runtime
    ledger = _Ledger(rt)
    closed = traffic["kind"] == "closed"
    t_send = time.monotonic()
    t0 = t_send + (float(traffic.get("ramp_s", 0.0)) if closed else 0.0)
    t1 = t0 + seconds
    deadline = t1 + drain_s
    win = Window(t0=t0, t1=t1, served=[])
    by_rid: Dict[int, Served] = {}
    awaiting_features: List[Served] = []
    if closed:
        queues: Dict[int, List[Planned]] = {}
        for p in planned:
            queues.setdefault(p.client, []).append(p)
        for q in queues.values():
            q.reverse()
        ready_clients = {c: t_send for c in queues}
    else:
        nxt = 0
    opened = False
    while True:
        now = time.monotonic()
        if hooks is not None and not opened and now >= t0:
            hooks.window_open(t0, t1)
            opened = True
        if hooks is not None and opened:
            hooks.tick(now)
        batch = []
        if closed:
            if now < t1:
                for c, at in list(ready_clients.items()):
                    if queues[c]:
                        batch.append(Served(plan=queues[c].pop(), due=at))
                    else:
                        win.ran_dry = True
                    del ready_clients[c]
        else:
            while nxt < len(planned) and t0 + planned[nxt].due <= now:
                batch.append(Served(plan=planned[nxt],
                                    due=t0 + planned[nxt].due))
                nxt += 1
        if batch:
            for s in batch:
                s.req = _request(s.plan, s.due)
                win.lateness.append(now - s.due)
                by_rid[s.plan.index] = s
                win.served.append(s)
                if s.plan.audio is not None:
                    awaiting_features.append(s)
            rt.submit([s.req for s in batch], now)
        progressed = rt.step()
        if awaiting_features:
            left = []
            for s in awaiting_features:
                if s.req.preprocessed_at is not None:
                    s.features = s.req.payload
                else:
                    left.append(s)
            awaiting_features = left
        now = time.monotonic()
        for kind, status in (("completed", "completed"), ("shed", "shed"),
                             ("dead", "dead")):
            for r in ledger.new(kind):
                s = by_rid.get(r.rid)
                if s is None:
                    continue
                s.status = status
                if status == "completed":
                    s.tokens = np.asarray(r.payload, np.int32)
                if closed:
                    ready_clients[s.plan.client] = now
        done_sending = (now >= t1 or not any(queues.values())) if closed \
            else nxt >= len(planned)
        if done_sending and all(s.status != "pending" for s in win.served):
            break
        if now >= deadline:
            break
        if not progressed:
            wake = deadline
            if not closed and nxt < len(planned):
                wake = min(wake, t0 + planned[nxt].due)
            time.sleep(max(0.0, min(0.0005, wake - time.monotonic())))
    win.end = time.monotonic()
    for s in win.served:
        if s.status == "pending":
            s.status = "unfinished"
    if hooks is not None:
        hooks.window_closed()
    return win


# --- traced runs --------------------------------------------------------


@dataclass
class Call:
    kind: str
    t0: float
    t1: float
    flops: float = 0.0
    ops: float = 0.0
    bytes: float = 0.0
    live_rows: int = 0            # segment calls: rows that decode


class Probe:
    """Profiler spans around the calls into each layer, opened from the
    benchmark's own code by wrapping the instance's bound methods, and the
    useful work of each call (model FLOPs of true prompt positions and of
    live decode rows; the DPU's algorithmic operations and bytes)."""

    def __init__(self, sysm: System, flops_mod, dpu_cost=None):
        import jax

        self.jax = jax
        self.calls: List[Call] = []
        self._lock = threading.Lock()
        self.fl = flops_mod
        self.mc = config_numbers(sysm.cfg)
        self._wrap(sysm.runtime, "step", "bench.runtime_step")
        for eng in sysm.engines:
            self._wrap(eng, "_admit", "bench.admit", self._admit_flops)
            self._wrap(eng, "_chunk_step", "bench.chunk", self._chunk_flops)
            self._wrap(eng, "_decode_segment", "bench.segment",
                       functools.partial(self._segment_flops, eng))
        if sysm.service is not None:
            self._wrap(sysm.service, "step", "bench.dpu_step")
            self._dpu_cost = dpu_cost
            self._wrap(sysm.service, "_process_group", "bench.dpu_launch",
                       self._dpu_work)

    def _wrap(self, obj, name, span, work=None):
        inner = getattr(obj, name)
        ann = self.jax.profiler.TraceAnnotation

        def wrapped(*a, **k):
            w = work(*a, **k) if work is not None else None
            t0 = time.monotonic()
            with ann(span):
                out = inner(*a, **k)
            if w is not None:
                c = Call(span, t0, time.monotonic(), *w)
                with self._lock:
                    self.calls.append(c)
            return out

        setattr(obj, name, wrapped)

    def _admit_flops(self, reqs, now=None):
        f = 0.0
        for r in reqs:
            n = max(1, int(r.length))
            f += self.fl.prompt_flops(self.mc, 0, n) + self.fl.head_flops(self.mc)
        return (f,)

    def _chunk_flops(self, c, lp, adms, now=None):
        f = 0.0
        for adm in adms:
            col = adm.base + adm.pos
            for s in adm.slots:
                off = int(adm.off[s])
                a, b = max(col, off) - off, min(col + c, lp) - off
                if b > a:
                    f += self.fl.prompt_flops(self.mc, a, b)
                if col + c >= lp:
                    f += self.fl.head_flops(self.mc)
        return (f,)

    def _segment_flops(self, eng, steps, now=None):
        f, live = 0.0, 0
        for st in eng._slots:
            if st is None or not st.live:
                continue
            live += 1
            k = min(int(steps), st.budget - len(st.produced))
            ctx = int(st.req.length) + len(st.produced)
            for i in range(max(0, k)):
                f += self.fl.token_flops(self.mc, ctx + i) + \
                    self.fl.head_flops(self.mc)
        return (f, 0.0, 0.0, live)

    def _dpu_work(self, group):
        ops = nbytes = 0.0
        for r in group:
            o, b = self._dpu_cost(int(np.asarray(r.payload).shape[0]))
            ops, nbytes = ops + o, nbytes + b
        return (0.0, ops, nbytes)


def config_numbers(cfg) -> dict:
    """Every field of the model's `ModelConfig` as a plain dict (what
    flops/ and reference/ read), with `head_dim` resolved."""
    return dataclasses.asdict(cfg) | {"head_dim": cfg.hd}
