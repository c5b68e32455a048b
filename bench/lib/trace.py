"""Profiler trace -> device busy time, program time, idle gaps.

The JAX profiler writes an .xplane.pb file; `jax.profiler.ProfileData`
reads it. On a TPU each chip is a plane "/device:TPU:<n>" with an
"XLA Modules" line (one event per program execution, named
"jit_<function>(<fingerprint>)") and an "XLA Ops" line (one event per HLO
op execution, loop iterations included). Host spans that the harness
opens with `jax.profiler.TraceAnnotation` land on the host plane's
threads under their own names ("bench.*"). All times here are seconds on
the trace's clock.

The reduction is plain interval arithmetic on (start, end) pairs, kept
apart from the file reading so the tests can feed it synthetic traces.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]
_FP = re.compile(r"\(\d+\)$")


@dataclass
class DeviceTrace:
    """One chip's events: program executions and op executions."""

    name: str
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    ops: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DeviceTrace]
    host_spans: List[Tuple[str, float, float]]
    window: Interval

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def program_name(module_event_name: str) -> str:
    """'jit__segment(1234)' -> 'jit__segment'."""
    return _FP.sub("", module_event_name)


def op_name(op_event_name: str) -> str:
    """'%fusion.8 = bf16[...] fusion(...)' -> 'fusion.8'."""
    return op_event_name.split(" = ", 1)[0].lstrip("%")


def clip(intervals: Sequence[Interval], window: Interval) -> np.ndarray:
    a = np.asarray(intervals, np.float64).reshape(-1, 2)
    a = np.clip(a, window[0], window[1])
    return a[a[:, 1] > a[:, 0]]


def union(intervals: Sequence[Interval]) -> np.ndarray:
    """Disjoint sorted intervals covering the same points."""
    a = np.asarray(intervals, np.float64).reshape(-1, 2)
    if not len(a):
        return a
    a = a[np.argsort(a[:, 0], kind="stable")]
    out = [list(a[0])]
    for s, e in a[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def busy_s(intervals: Sequence[Interval], window: Interval) -> float:
    u = union(clip(intervals, window))
    return float((u[:, 1] - u[:, 0]).sum()) if len(u) else 0.0


def idle_gaps(intervals: Sequence[Interval], window: Interval) -> np.ndarray:
    """The parts of the window in which no interval runs."""
    u = union(clip(intervals, window))
    edges = np.concatenate([[window[0]], u.reshape(-1), [window[1]]])
    g = edges.reshape(-1, 2)
    return g[g[:, 1] > g[:, 0]]


def program_time(dev: DeviceTrace, window: Interval,
                 names: Sequence[str]) -> Tuple[float, int]:
    """(seconds, executions) of the named programs inside the window."""
    want = set(names)
    iv = [(s, e) for n, s, e in dev.modules if program_name(n) in want]
    c = clip(iv, window)
    return float((c[:, 1] - c[:, 0]).sum()) if len(c) else 0.0, len(c)


def attribute(gaps: np.ndarray, spans: Sequence[Tuple[str, float, float]],
              top: int = 10) -> List[list]:
    """Idle seconds by what the host was doing: each gap goes to the
    innermost (latest-starting) host span covering its midpoint."""
    out: Dict[str, float] = {}
    sp = sorted(spans, key=lambda x: x[1])
    starts = np.asarray([s for _, s, _ in sp])
    for a, b in gaps:
        mid = 0.5 * (a + b)
        name = "host:outside-spans"
        k = int(np.searchsorted(starts, mid, side="right"))
        for j in range(k - 1, -1, -1):
            if sp[j][2] >= mid:
                name = sp[j][0]
                break
        out[name] = out.get(name, 0.0) + float(b - a)
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:top]]


_PARENT = ("while", "conditional", "call")


def top_ops(dev: DeviceTrace, window: Interval, top: int = 10) -> List[list]:
    """Device time by program/op, largest first. Control-flow ops (while,
    conditional, call) are left out: their events span the ops of their
    bodies, which are counted themselves."""
    mods = sorted(dev.modules, key=lambda m: m[1])
    mstart = np.asarray([m[1] for m in mods])
    out: Dict[str, float] = {}
    for n, s, e in dev.ops:
        s2, e2 = max(s, window[0]), min(e, window[1])
        if e2 <= s2 or op_name(n).split(".")[0] in _PARENT:
            continue
        k = int(np.searchsorted(mstart, s, side="right")) - 1
        prog = program_name(mods[k][0]) if k >= 0 and mods[k][2] >= s else "?"
        key = f"{prog}/{op_name(n)}"
        out[key] = out.get(key, 0.0) + (e2 - s2)
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:top]]


def read(log_dir: str, window_span: str = "bench.window") -> Trace:
    """Read the newest .xplane.pb under log_dir. The window is the host
    span named `window_span`."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: List[DeviceTrace] = []
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = DeviceTrace(plane.name)
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = [(e.name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9)
                                   for e in line.events]
                elif line.name == "XLA Ops":
                    dev.ops = [(e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                               for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    win = [s for s in spans if s[0] == window_span]
    if not win:
        raise ValueError(f"trace holds no {window_span!r} span")
    devices.sort(key=lambda d: d.name)
    return Trace(devices=devices, host_spans=spans,
                 window=(win[0][1], win[0][2]))


def busy_intervals(dev: DeviceTrace) -> List[Interval]:
    """The chip's op executions, or its program executions where the
    trace holds no ops."""
    return [(s, e) for _, s, e in dev.ops] or \
        [(s, e) for _, s, e in dev.modules]


def device_busy(tr: Trace) -> float:
    """Busy seconds averaged over the chips in the trace."""
    if not tr.devices:
        return 0.0
    return float(np.mean([busy_s(busy_intervals(d), tr.window)
                          for d in tr.devices]))


def chip_label(dev: DeviceTrace) -> str:
    """'/device:TPU:2' -> 'tpu2'."""
    return "tpu" + dev.name.rsplit(":", 1)[-1]


def breakdown(tr: Trace, top: int = 10) -> Dict[str, List[list]]:
    """The device ops that took most time and the longest idle gaps by
    what the host was doing. One chip: as `top_ops` and `attribute` give
    them. Several chips: both lists over every chip, each name prefixed
    with its chip ('tpu<i>/')."""
    if len(tr.devices) == 1:
        d = tr.devices[0]
        return {"device_ops": top_ops(d, tr.window, top),
                "idle_gaps": attribute(idle_gaps(
                    [(s, e) for _, s, e in d.ops], tr.window),
                    tr.host_spans, top)}
    ops: List[list] = []
    gaps: List[list] = []
    for d in tr.devices:
        tag = chip_label(d) + "/"
        ops += [[tag + k, v] for k, v in top_ops(d, tr.window, top)]
        g = idle_gaps([(s, e) for _, s, e in d.ops], tr.window)
        gaps += [[tag + k, v] for k, v in attribute(g, tr.host_spans, top)]
    return {"device_ops": sorted(ops, key=lambda kv: -kv[1])[:top],
            "idle_gaps": sorted(gaps, key=lambda kv: -kv[1])[:top]}
