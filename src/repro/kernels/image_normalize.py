"""Fused crop + normalize DPU kernel (paper 'Crop'/'Normalize' units).

Pure VPU element-wise work: (x - mean)/std over the center crop. The crop
itself is a slice in the wrapper; the kernel walks row tiles that span the
full crop width, so every block is tile-legal on the chip whatever the crop
width (the last block dim equals the array's).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_ROWS = 256  # rows per grid step (a multiple of 8)


def _crop_norm_kernel(mean, std, img_ref, out_ref):
    out_ref[...] = (img_ref[...].astype(jnp.float32) - mean) * (1.0 / std)


def image_crop_normalize_pallas(img: jax.Array, ch: int, cw: int, mean: float,
                                std: float, *, interpret: bool) -> jax.Array:
    """img: [H, W] -> center-cropped [ch, cw], normalized."""
    h, w = img.shape
    y0, x0 = (h - ch) // 2, (w - cw) // 2
    imgc = jax.lax.slice(img, (y0, x0), (y0 + ch, x0 + cw))
    rows = min(TILE_ROWS, -(-ch // 8) * 8)
    rows_p = -(-ch // rows) * rows
    if rows_p != ch:
        imgc = jnp.pad(imgc, ((0, rows_p - ch), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_crop_norm_kernel, float(mean), float(std)),
        grid=(rows_p // rows,),
        in_specs=[pl.BlockSpec((rows, cw), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, cw), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, cw), jnp.float32),
        interpret=interpret,
    )(imgc)
    return out[:ch]
