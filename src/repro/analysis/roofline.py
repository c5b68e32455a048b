"""Roofline model from compiled dry-run artifacts (planning target: TPU v5e).

compute term    = HLO_FLOPs / (chips * 197 TFLOP/s)
memory term     = HLO_bytes / (chips * 819 GB/s)
collective term = wire_bytes / (chips * links * 50 GB/s)

HLO_FLOPs / bytes come from compiled.cost_analysis(); collective bytes are
parsed from the compiled HLO text with ring-algorithm wire formulas:
  all-reduce      2 * size * (n-1)/n
  all-gather      out_size * (n-1)/n
  reduce-scatter  in_size * (n-1)/n
  all-to-all      size * (n-1)/n
  collective-permute  size
where n = replica-group size of the op. Sizes are *global*; wire bytes per
chip = size/n * formula-factor * n / n ... we report per-chip wire bytes as
(global_size/n) * factor(n), i.e. each chip sends/receives its shard along
the ring. See EXPERIMENTS.md §Roofline for the derivation.
"""
from __future__ import annotations

import json
import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# --- hardware peaks, keyed by jax Device.device_kind ----------------------


@dataclass(frozen=True)
class ChipPeaks:
    flops: float       # bf16 FLOP/s per chip
    hbm_bw: float      # HBM bytes/s per chip
    ici_bw: float      # bytes/s per ICI link


# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip (4 links x 50 GB/s).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

# CPU-side analytical planning (dry-run rooflines, knee and DPU cost
# models) plans for this chip, whatever device the planner runs on.
PLANNING_TARGET = "TPU v5 lite"


def peaks_for(device_kind: str) -> ChipPeaks:
    """Published peaks of `device_kind`; a kind not in PEAKS is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to roofline.PEAKS with their source") from None


PEAK_FLOPS = PEAKS[PLANNING_TARGET].flops
HBM_BW = PEAKS[PLANNING_TARGET].hbm_bw
ICI_BW = PEAKS[PLANNING_TARGET].ici_bw
ICI_LINKS = 1              # conservative single-link assumption (documented)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\)|tuple\([^)]*\)|\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    global_bytes: Dict[str, float] = field(default_factory=dict)
    wire_bytes_per_chip: float = 0.0

    def add(self, kind: str, gbytes: float, wire: float):
        self.counts[kind] = self.counts.get(kind, 0) + 1
        self.global_bytes[kind] = self.global_bytes.get(kind, 0.0) + gbytes
        self.wire_bytes_per_chip += wire


def parse_collectives(hlo_text: str, default_group: int) -> CollectiveStats:
    """Sum wire bytes per chip across collective ops in compiled HLO text.

    Post-GSPMD HLO is the *per-device* program, so op result shapes are
    per-device payloads P. Ring wire bytes each chip sends:
      all-reduce       2 * P * (n-1)/n   (reduce-scatter + all-gather)
      all-gather       P_out * (n-1)/n   (output = gathered tensor)
      reduce-scatter   P_out * (n-1)     (output = shard, input = n*P_out)
      all-to-all       P * (n-1)/n
      collective-permute  P
    """
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        shape_str, kind, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":
            continue  # counted at -start
        out_bytes = _shape_bytes(shape_str)
        if out_bytes == 0:
            continue
        gm = _GROUPS_RE.search(line)
        if gm:
            first = gm.group(1).split("}")[0]
            n = max(1, len([x for x in first.replace("{", "").split(",") if x.strip() != ""]))
        else:
            gm2 = _GROUPS_V2_RE.search(line)
            n = int(gm2.group(2)) if gm2 else default_group
        if n <= 1:
            continue
        if kind == "all-reduce":
            wire = 2.0 * out_bytes * (n - 1) / n
        elif kind == "all-gather":
            wire = out_bytes * (n - 1) / n
        elif kind == "reduce-scatter":
            wire = out_bytes * (n - 1)
        elif kind == "all-to-all":
            wire = out_bytes * (n - 1) / n
        else:  # collective-permute
            wire = float(out_bytes)
        stats.add(kind, float(out_bytes), wire)
    return stats


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    wire_bytes_per_chip: float
    model_flops: float
    collectives: Dict[str, int]
    peak_memory_per_device: float = 0.0

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.wire_bytes_per_chip / (ICI_LINKS * ICI_BW)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """MODEL_FLOPS-at-peak over achievable step time (dominant term)."""
        if self.t_bound == 0:
            return 0.0
        return (self.model_flops / (self.chips * PEAK_FLOPS)) / self.t_bound

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "hlo_flops": self.hlo_flops,
            "hlo_bytes": self.hlo_bytes,
            "wire_bytes_per_chip": self.wire_bytes_per_chip,
            "model_flops": self.model_flops,
            "collectives": self.collectives,
            "peak_memory_per_device": self.peak_memory_per_device,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops_for(cfg, shape) -> float:
    """6*N*D (train) / 2*N*D (serve forward), N_active for MoE (per brief)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * n_active * tokens


def build_report(cfg, shape, mesh_name: str, chips: int, compiled,
                 hlo_text: Optional[str] = None) -> RooflineReport:
    """FLOPs/bytes/collectives come from the trip-count-aware HLO parser
    (hlo_parse.py): XLA's cost_analysis counts scan bodies once, which
    undercounts a scanned transformer by n_layers x. Post-SPMD HLO shapes
    are per-device, so totals below are per-chip; the compute/memory terms
    therefore divide by 1, not by `chips`."""
    from repro.analysis import hlo_parse

    text = hlo_text if hlo_text is not None else compiled.as_text()
    cost = hlo_parse.analyze_hlo(text, default_group=chips)
    mem = 0.0
    try:
        ma = compiled.memory_analysis()
        mem = float(
            getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
            - getattr(ma, "alias_size_in_bytes", 0)
        )
    except Exception:
        pass
    return RooflineReport(
        arch=cfg.name,
        shape=shape.name,
        mesh=mesh_name,
        chips=chips,
        hlo_flops=cost.dot_flops * chips,   # aggregate for reporting symmetry
        hlo_bytes=cost.dot_bytes * chips,
        wire_bytes_per_chip=cost.wire_bytes,
        model_flops=model_flops_for(cfg, shape),
        collectives={k: int(v) for k, v in cost.collective_counts.items()},
        peak_memory_per_device=mem,
    )
