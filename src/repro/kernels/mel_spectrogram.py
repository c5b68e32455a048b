"""Mel-spectrogram DPU kernel (paper 'Mel spectrogram' functional unit).

TPU adaptation (DESIGN.md §2): the FFT butterflies of the FPGA unit become
two dense DFT matmuls (real/imag bases) plus a mel-filterbank matmul — all
MXU-native. Grid tiles the frame axis; per-tile VMEM working set is
frames[128, n_fft] + bases[n_fft, F] + fb[F, n_mels] ≈ 1.6 MB at n_fft=512,
comfortably inside the ~16 MB v5e VMEM with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

FRAME_BLOCK = 128
# f32 contraction on the MXU: at the default (one bf16 pass) the DFT's
# rounding leaks into near-silent mel bands, whose logs then miss the
# reference by whole units
_HI = jax.lax.Precision.HIGHEST


def _mel_kernel(frames_ref, cr_ref, ci_ref, fb_ref, out_ref):
    f = frames_ref[...].astype(jnp.float32)
    re = jnp.dot(f, cr_ref[...], precision=_HI,
                 preferred_element_type=jnp.float32)
    im = jnp.dot(f, ci_ref[...], precision=_HI,
                 preferred_element_type=jnp.float32)
    power = re * re + im * im
    out_ref[...] = jnp.log(
        jnp.dot(power, fb_ref[...], precision=_HI,
                preferred_element_type=jnp.float32) + 1e-6
    )


def mel_spectrogram_pallas(frames: jax.Array, cr: jax.Array, ci: jax.Array,
                           fb: jax.Array, *, interpret: bool) -> jax.Array:
    """frames: [N, n_fft] framed+windowed+zero-padded; cr/ci: [n_fft, F];
    fb: [F, n_mels] -> log-mel [N, n_mels]."""
    n, n_fft = frames.shape
    n_mels = fb.shape[1]
    nb = pl.cdiv(n, FRAME_BLOCK)
    pad = nb * FRAME_BLOCK - n
    if pad:
        frames = jnp.pad(frames, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _mel_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((FRAME_BLOCK, n_fft), lambda i: (i, 0)),
            pl.BlockSpec((n_fft, cr.shape[1]), lambda i: (0, 0)),
            pl.BlockSpec((n_fft, ci.shape[1]), lambda i: (0, 0)),
            pl.BlockSpec((fb.shape[0], n_mels), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((FRAME_BLOCK, n_mels), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * FRAME_BLOCK, n_mels), jnp.float32),
        interpret=interpret,
    )(frames, cr, ci, fb)
    return out[:n]
