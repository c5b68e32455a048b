"""Separable bilinear resize DPU kernel (paper 'Resize' functional unit).

Bilinear interpolation factors into two small dense matmuls (row weights,
column weights) — MXU-native, unlike the FPGA's per-pixel interpolators.
One grid step per output row-tile; weights + image tile live in VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 128
_HI = jax.lax.Precision.HIGHEST   # f32 pixels: full-precision MXU passes


def _rows_kernel(ry_ref, img_ref, out_ref):
    out_ref[...] = jnp.dot(
        ry_ref[...], img_ref[...], precision=_HI,
        preferred_element_type=jnp.float32
    )


def _cols_kernel(tmp_ref, rxt_ref, out_ref):
    out_ref[...] = jnp.dot(
        tmp_ref[...], rxt_ref[...], precision=_HI,
        preferred_element_type=jnp.float32
    )


def _pad_rows(a, mult):
    pad = (-a.shape[0]) % mult
    return (jnp.pad(a, ((0, pad), (0, 0))), a.shape[0]) if pad else (a, a.shape[0])


def _rows_kernel_b(ry_ref, img_ref, out_ref):
    out_ref[0] = jnp.dot(
        ry_ref[...], img_ref[0], precision=_HI,
        preferred_element_type=jnp.float32
    )


def image_resize_batch_pallas(imgs: jax.Array, ry: jax.Array, rx: jax.Array, *,
                              interpret: bool) -> jax.Array:
    """imgs: [N, H, W] same-shape stack -> [N, H_out, W_out]. The row pass
    runs as one launch over grid (N, row-tiles); the column pass flattens the
    stack to [N*H_out, W] rows — two launches total for the whole stack."""
    n, h, w = imgs.shape
    ryp, h_out = _pad_rows(ry.astype(jnp.float32), TILE)
    nb = ryp.shape[0] // TILE
    tmp = pl.pallas_call(
        _rows_kernel_b,
        grid=(n, nb),
        in_specs=[
            pl.BlockSpec((TILE, h), lambda b, i: (i, 0)),
            pl.BlockSpec((1, h, w), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, TILE, w), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, ryp.shape[0], w), jnp.float32),
        interpret=interpret,
    )(ryp, imgs.astype(jnp.float32))[:, :h_out]

    rxt = rx.astype(jnp.float32).T  # [W, W_out]
    flat, rows = _pad_rows(tmp.reshape(n * h_out, w), TILE)
    nb2 = flat.shape[0] // TILE
    out = pl.pallas_call(
        _cols_kernel,
        grid=(nb2,),
        in_specs=[
            pl.BlockSpec((TILE, w), lambda i: (i, 0)),
            pl.BlockSpec((w, rxt.shape[1]), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE, rxt.shape[1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((flat.shape[0], rxt.shape[1]), jnp.float32),
        interpret=interpret,
    )(flat, rxt)
    return out[:rows].reshape(n, h_out, rxt.shape[1])


def image_resize_pallas(img: jax.Array, ry: jax.Array, rx: jax.Array, *,
                        interpret: bool) -> jax.Array:
    """img: [H, W]; ry: [H_out, H]; rx: [W_out, W] -> [H_out, W_out]."""
    h, w = img.shape
    ryp, h_out = _pad_rows(ry.astype(jnp.float32), TILE)
    nb = ryp.shape[0] // TILE
    tmp = pl.pallas_call(
        _rows_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((TILE, h), lambda i: (i, 0)),
            pl.BlockSpec((h, w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ryp.shape[0], w), jnp.float32),
        interpret=interpret,
    )(ryp, img.astype(jnp.float32))[:h_out]

    rxt = rx.astype(jnp.float32).T  # [W, W_out]
    tmpp, h_out2 = _pad_rows(tmp, TILE)
    nb2 = tmpp.shape[0] // TILE
    out = pl.pallas_call(
        _cols_kernel,
        grid=(nb2,),
        in_specs=[
            pl.BlockSpec((TILE, w), lambda i: (i, 0)),
            pl.BlockSpec((w, rxt.shape[1]), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE, rxt.shape[1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tmpp.shape[0], rxt.shape[1]), jnp.float32),
        interpret=interpret,
    )(tmpp, rxt)
    return out[:h_out2]
