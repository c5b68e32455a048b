"""JPEG block-decode backend DPU kernel (paper 'Decode' functional unit).

Entropy (Huffman) decode is bit-serial and host-side by design (DESIGN.md
§2); the arithmetically heavy dequantize + 8x8 IDCT maps to the MXU as a
pair of small matmuls per block, batched 512 blocks per grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.data.preprocess_cpu import idct_matrix

BLOCK_NB = 512
_HI = jax.lax.Precision.HIGHEST   # f32 pixels: full-precision MXU passes


def _idct_kernel(coeffs_ref, qtable_ref, m_ref, out_ref):
    m = m_ref[...]
    c = coeffs_ref[...].astype(jnp.float32) * qtable_ref[...][None]
    # two 8x8 matmuls per block: M @ c @ M^T, batched over the block dim
    tmp = jnp.einsum("ij,bjk->bik", m, c, precision=_HI,
                     preferred_element_type=jnp.float32)
    out_ref[...] = (
        jnp.einsum("bik,lk->bil", tmp, m, precision=_HI,
                   preferred_element_type=jnp.float32) + 128.0
    )


def jpeg_idct_pallas(coeffs: jax.Array, qtable: jax.Array, *,
                     interpret: bool) -> jax.Array:
    """coeffs: [NB, 8, 8] quantized blocks; qtable: [8, 8] -> pixels [NB, 8, 8]."""
    nb_total = coeffs.shape[0]
    nb = pl.cdiv(nb_total, BLOCK_NB)
    pad = nb * BLOCK_NB - nb_total
    cp = jnp.pad(coeffs, ((0, pad), (0, 0), (0, 0))) if pad else coeffs
    m = jnp.asarray(idct_matrix(), jnp.float32)

    out = pl.pallas_call(
        _idct_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((BLOCK_NB, 8, 8), lambda i: (i, 0, 0)),
            pl.BlockSpec((8, 8), lambda i: (0, 0)),
            pl.BlockSpec((8, 8), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_NB, 8, 8), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * BLOCK_NB, 8, 8), jnp.float32),
        interpret=interpret,
    )(cp, qtable.astype(jnp.float32), m)
    return out[:nb_total]
