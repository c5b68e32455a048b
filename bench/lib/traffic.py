"""One general traffic generator, driven by a mix's data file.

A mix file (bench/traffic/<mix>.json) holds only parameters:

    kind            "open" (arrivals on a schedule) or "closed" (each
                    client sends its next request when the last completes)
    shape_seed      seed of the sizes and arrival gaps (see below)
    rate_qps        open: mean arrival rate
    phases          open, optional: [{"seconds": s, "rate_mult": m}, ...],
                    repeated for the whole window (bursts)
    clients_per_slot, requests_per_client
                    closed: clients = clients_per_slot x the engine's slots
    prompt, output  {"median", "sigma", "min", "max"}: lognormal token
                    counts, clipped and rounded
    audio           optional {"sample_rate", "clip_s": [...], "weights":
                    [...]}: one raw mono clip per request
    ramp_s          closed, optional: seconds the clients send before the
                    window opens, so that the pool is full at its start

Every seed serves the same work: the sizes and the arrival gaps are drawn
once from `shape_seed`, and the run's seed only orders them and draws the
prompt tokens and the audio samples. Open loop: the seed reorders sizes
across requests and gaps within each phase segment, so bursts stay where
they are. Closed loop: each client's sequence of sizes is fixed, and the
seed deals the sequences to the clients; a window serves only the first
few requests of each sequence, so reordering sizes across requests would
change which sizes, and how much work, a window holds. Runs with different
seeds then differ by content and order, not by how much work they carry.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np


@dataclass
class Planned:
    """One request as the client will send it."""

    index: int
    due: Optional[float]          # seconds after the window opens (open)
    client: Optional[int]         # closed loop: the client that sends it
    prompt: np.ndarray            # int32 token ids
    max_new: int                  # output tokens asked for
    audio: Optional[np.ndarray]   # float32 raw clip, or None


def lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """Lognormal token counts with the given median, clipped to [min, max]."""
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _segments(traffic: dict, seconds: float) -> List[Tuple[float, float, float]]:
    """(start, end, rate) pieces of the arrival schedule over the window."""
    rate = float(traffic["rate_qps"])
    phases = traffic.get("phases") or [{"seconds": seconds, "rate_mult": 1.0}]
    out, t, i = [], 0.0, 0
    while t < seconds:
        ph = phases[i % len(phases)]
        end = min(seconds, t + float(ph["seconds"]))
        out.append((t, end, rate * float(ph["rate_mult"])))
        t, i = end, i + 1
    return out


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds): a piecewise Poisson process drawn from
    `shape_seed`, its gaps reshuffled within each segment by `seed`."""
    master = np.random.default_rng(traffic["shape_seed"])
    perm = np.random.default_rng([seed, 0])
    out = []
    for a, b, rate in _segments(traffic, seconds):
        ts, t = [], a
        while True:
            t += master.exponential(1.0 / rate)
            if t >= b:
                break
            ts.append(t)
        gaps = np.diff(np.concatenate([[a], ts, [b]]))
        gaps = gaps[perm.permutation(len(gaps))]
        out.extend(a + np.cumsum(gaps[:-1]))
    return np.asarray(out, np.float64)


def _sizes(traffic: dict, n: int):
    """(prompt lengths, output lengths, clip seconds) for n requests, drawn
    from `shape_seed`."""
    master = np.random.default_rng([traffic["shape_seed"], 1])
    p = lengths(traffic["prompt"], master, n)
    o = lengths(traffic["output"], master, n)
    clips = np.zeros(n)
    audio = traffic.get("audio")
    if audio:
        w = np.asarray(audio["weights"], np.float64)
        clips = np.asarray(audio["clip_s"], np.float64)[
            master.choice(len(w), size=n, p=w / w.sum())]
    return p, o, clips


def _content(traffic: dict, seed: int, vocab: int, p, o, clips, dues,
             clients) -> List[Planned]:
    rng = np.random.default_rng([seed, 2])
    sr = (traffic.get("audio") or {}).get("sample_rate", 0)
    out = []
    for i in range(len(p)):
        prompt = rng.integers(0, vocab, int(p[i])).astype(np.int32)
        audio = None
        if clips[i]:
            audio = rng.standard_normal(int(round(clips[i] * sr)),
                                        dtype=np.float32)
        out.append(Planned(index=i,
                           due=None if dues is None else float(dues[i]),
                           client=None if clients is None else int(clients[i]),
                           prompt=prompt, max_new=int(o[i]), audio=audio))
    return out


def plan(traffic: dict, seed: int, seconds: float, vocab: int,
         slots: int) -> List[Planned]:
    """Every request of one run, in sending order per client (closed) or
    by due time (open)."""
    if traffic["kind"] == "open":
        dues = arrivals(traffic, seconds, seed)
        p, o, clips = _sizes(traffic, len(dues))
        order = np.random.default_rng([seed, 1]).permutation(len(dues))
        return _content(traffic, seed, vocab, p[order], o[order],
                        clips[order], dues, None)
    if traffic["kind"] == "closed":
        n_clients = int(traffic["clients_per_slot"]) * slots
        n = n_clients * int(traffic["requests_per_client"])
        p, o, clips = _sizes(traffic, n)
        deal = np.random.default_rng([seed, 1]).permutation(n_clients)
        clients = deal[np.arange(n) % n_clients]
        return _content(traffic, seed, vocab, p, o, clips, None, clients)
    raise ValueError(f"unknown traffic kind {traffic['kind']!r}")


def bounds(traffic: dict) -> Tuple[int, int]:
    """Shortest and longest prompt the mix can send."""
    return int(traffic["prompt"]["min"]), int(traffic["prompt"]["max"])
