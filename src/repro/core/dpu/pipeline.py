"""DPU Compute-Unit pipeline model (paper §4.2, Figs. 11-12).

A CU is an ordered pipeline of functional units (FUs); within a CU, FUs
stream block-granular data to each other (paper: HLS `stream` FIFOs), so a
CU's latency for one request is `sum(stage latencies)` but its *occupancy*
(the interval before it can accept the next request) is `max(stage
latencies)` — the pipelining win of Fig. 12(a).

The audio pipeline is split into TWO CU types (Fig. 11b): `Resample+Mel`
streams, but `Normalize` needs utterance-global mean/var, so fusing it would
serialize back-to-back requests exactly as in Fig. 12(b). Keeping it as a
separate CU restores pipelining across requests (Fig. 12(c)).

Each FU carries: a callable (numpy CPU reference or Pallas DPU op) and an
analytical cost model (seconds per request as a function of input size) used
by the serving simulator; real-execution mode just calls the function.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.roofline import HBM_BW, PEAK_FLOPS


@dataclass
class FunctionalUnit:
    name: str
    fn: Callable[[Any], Any]
    cost_s: Callable[[Any], float]      # analytical per-request latency
    streaming: bool = True              # False => needs full input (Normalize)
    batch_fn: Optional[Callable[[List[Any]], List[Any]]] = None
    # batch_fn processes a stack of same-shape requests in ONE kernel launch
    # (DPU backend); None falls back to a per-request fn loop (CPU baseline).


@dataclass
class ComputeUnit:
    name: str
    units: List[FunctionalUnit]

    def process(self, x: Any) -> Any:
        for u in self.units:
            x = u.fn(x)
        return x

    def process_batch(self, xs: List[Any]) -> List[Any]:
        """Process a stack of same-shape requests through the CU. FUs with a
        batch_fn handle the whole stack in one kernel launch; the rest loop.
        """
        xs = list(xs)
        for u in self.units:
            if u.batch_fn is not None and len(xs) > 1:
                xs = list(u.batch_fn(xs))
            else:
                xs = [u.fn(x) for x in xs]
        return xs

    def latency_s(self, x: Any) -> float:
        """End-to-end single-request latency (sum of pipelined stages)."""
        return sum(u.cost_s(x) for u in self.units)

    def occupancy_s(self, x: Any) -> float:
        """Time before this CU can accept the next request.

        Streaming FUs pipeline => bounded by the slowest stage; a
        non-streaming FU (global stats) serializes the whole CU (Fig. 12b).
        """
        if any(not u.streaming for u in self.units):
            return self.latency_s(x)
        return max(u.cost_s(x) for u in self.units)


# ---------------------------------------------------------------------------
# Cost models (DPU kernels on the planning target, roofline.PLANNING_TARGET)
# ---------------------------------------------------------------------------

_MXU_FLOPS = PEAK_FLOPS * 0.3  # preprocessing kernels are small-matmul
                               # bound; 30% MXU efficiency for tiny tiles
_VPU_BYTES = HBM_BW            # element-wise ops stream at HBM bandwidth
_FIXED_OVERHEAD = 20e-6     # per-kernel dispatch overhead (tens of us)


def _img_decode_cost(x) -> float:
    n_pix = 256 * 256
    flops = n_pix * 2 * 8 * 2          # two 8x8 matmuls per pixel row/col
    return flops / _MXU_FLOPS + _FIXED_OVERHEAD


def _img_resize_cost(x) -> float:
    flops = 256 * 256 * 2 * 2 * 2      # separable matmul pair
    return flops / _MXU_FLOPS + _FIXED_OVERHEAD


def _img_norm_cost(x) -> float:
    return 224 * 224 * 4 * 3 / _VPU_BYTES + _FIXED_OVERHEAD


def _audio_resample_cost(x) -> float:
    n = _audio_len(x)
    return n * 48 * 2 / _MXU_FLOPS + _FIXED_OVERHEAD


def _audio_mel_cost(x) -> float:
    n = _audio_len(x)
    frames = max(1, n // 160)
    flops = frames * (512 * 514 * 2 + 257 * 80 * 2)
    return flops / _MXU_FLOPS + _FIXED_OVERHEAD


def _audio_norm_cost(x) -> float:
    n = _audio_len(x)
    frames = max(1, n // 160)
    return frames * 80 * 4 * 3 / _VPU_BYTES + _FIXED_OVERHEAD


def _audio_len(x) -> int:
    if isinstance(x, np.ndarray):
        return x.shape[-1] if x.ndim == 1 else x.shape[0] * 160
    return int(x)


# ---------------------------------------------------------------------------
# CU builders
# ---------------------------------------------------------------------------


def make_image_cu(backend: str = "cpu") -> ComputeUnit:
    """Single CU integrating all image FUs (sequential dataflow pipelines
    cleanly — paper Fig. 12a)."""
    ops = _image_ops(backend)
    return ComputeUnit(
        "image",
        [
            FunctionalUnit("decode", ops["decode"], _img_decode_cost,
                           batch_fn=ops.get("decode_batch")),
            FunctionalUnit("resize", ops["resize"], _img_resize_cost,
                           batch_fn=ops.get("resize_batch")),
            FunctionalUnit("crop", ops["crop"], _img_norm_cost,
                           batch_fn=ops.get("crop_batch")),
            FunctionalUnit("normalize", ops["normalize"], _img_norm_cost,
                           batch_fn=ops.get("normalize_batch")),
        ],
    )


def make_audio_cus(backend: str = "cpu") -> Tuple[ComputeUnit, ComputeUnit]:
    """Two CU types (paper Fig. 11b): (Resample+Mel) and (Normalize)."""
    ops = _audio_ops(backend)
    cu_a = ComputeUnit(
        "audio_feat",
        [
            FunctionalUnit("resample", ops["resample"], _audio_resample_cost,
                           batch_fn=ops.get("resample_batch")),
            FunctionalUnit("mel", ops["mel"], _audio_mel_cost,
                           batch_fn=ops.get("mel_batch")),
        ],
    )
    cu_b = ComputeUnit(
        "audio_norm",
        [FunctionalUnit("normalize", ops["normalize"], _audio_norm_cost,
                        streaming=False, batch_fn=ops.get("normalize_batch"))],
    )
    return cu_a, cu_b


def make_audio_fused_cu(backend: str = "cpu") -> ComputeUnit:
    """Single-CU audio design (paper Fig. 12b strawman; for the ablation)."""
    ops = _audio_ops(backend)
    return ComputeUnit(
        "audio_fused",
        [
            FunctionalUnit("resample", ops["resample"], _audio_resample_cost),
            FunctionalUnit("mel", ops["mel"], _audio_mel_cost),
            FunctionalUnit("normalize", ops["normalize"], _audio_norm_cost, streaming=False),
        ],
    )


def _image_ops(backend: str) -> Dict[str, Callable]:
    """Per-request ops plus `*_batch` variants (DPU backend): a batch op
    takes/returns a list of same-shape requests and runs the whole stack in
    one kernel launch. The CPU baseline intentionally has none — host cores
    run one request per core (the paper's preprocessing wall)."""
    if backend == "dpu":
        import jax.numpy as jnp

        from repro.kernels import ops as kops

        def decode_batch(cs):
            qt = cs[0]["qtable"]
            if not all(np.array_equal(np.asarray(c["qtable"]), np.asarray(qt)) for c in cs[1:]):
                return [kops.jpeg_decode(c["coeffs"], c["qtable"]) for c in cs]
            stack = jnp.stack([jnp.asarray(c["coeffs"]) for c in cs])
            return list(kops.jpeg_decode_batch(stack, jnp.asarray(qt)))

        return {
            "decode": lambda c: kops.jpeg_decode(c["coeffs"], c["qtable"]),
            "resize": lambda x: kops.image_resize(x, 256, 256),
            "crop": lambda x: kops.center_crop(x, 224, 224),
            "normalize": lambda x: kops.image_normalize(x, 127.5, 64.0),
            "decode_batch": decode_batch,
            "resize_batch": lambda xs: list(
                kops.image_resize_batch(jnp.stack(xs), 256, 256)
            ),
            "crop_batch": lambda xs: list(
                kops.center_crop_batch(jnp.stack(xs), 224, 224)
            ),
            "normalize_batch": lambda xs: list(
                kops.image_normalize_batch(jnp.stack(xs), 127.5, 64.0)
            ),
        }
    from repro.data import preprocess_cpu as pp

    return {
        "decode": lambda c: pp.decode_blocks(c["coeffs"], c["qtable"]),
        "resize": lambda x: pp.resize_bilinear(x, 256, 256),
        "crop": lambda x: pp.center_crop(x, 224, 224),
        "normalize": lambda x: pp.normalize_image(x, 127.5, 64.0),
    }


def _audio_ops(backend: str) -> Dict[str, Callable]:
    if backend == "dpu":
        import jax.numpy as jnp

        from repro.kernels import ops as kops

        return {
            "resample": lambda x: kops.audio_resample(x, 1, 3),
            "mel": kops.mel_spectrogram,
            "normalize": kops.audio_normalize,
            "resample_batch": lambda xs: list(
                kops.audio_resample_batch(jnp.stack(xs), 1, 3)
            ),
            "mel_batch": lambda xs: list(
                kops.mel_spectrogram_batch(jnp.stack(xs))
            ),
            "normalize_batch": lambda xs: list(
                kops.audio_normalize_batch(jnp.stack(xs))
            ),
        }
    from repro.data import preprocess_cpu as pp

    return {
        "resample": lambda x: pp.resample_poly(x, 1, 3),
        "mel": pp.mel_spectrogram,
        "normalize": pp.normalize_meanvar,
    }
