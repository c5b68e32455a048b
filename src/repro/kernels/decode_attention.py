"""Flash-decode GQA attention kernel (beyond-paper serving hot-spot).

One query token per sequence against a long KV cache: the per-chip cost is
HBM-bound cache reads, so the kernel streams KV blocks through VMEM with an
online-softmax accumulator held in VMEM scratch. Grid = (batch, kv_head,
S/BLOCK_S); for GQA all G query heads of a kv head ride in one [G, D] tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_S = 512


def _decode_attn_kernel(scale, vlen_ref, q_ref, k_ref, v_ref, o_ref,
                        m_ref, l_ref, acc_ref):
    s_idx = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)         # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)         # [BLOCK_S, D]
    v = v_ref[0, 0].astype(jnp.float32)         # [BLOCK_S, D]
    vlen = vlen_ref[pl.program_id(0)]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                   # [G, BLOCK_S]
    pos = s_idx * BLOCK_S + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < vlen, s, -1e30)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(s_idx == ns - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            valid_len: jax.Array, *, interpret: bool) -> jax.Array:
    """q: [B, H, D]; k,v: [B, S, KH, D]; valid_len: [B] -> out [B, H, D].

    Prefix-valid cache layout (slots [0, valid_len) hold keys). The kernel
    reads the cache as [B, KH, S, D], so each K/V block is a
    (BLOCK_S, D) tile; valid_len rides in SMEM via scalar prefetch."""
    B, H, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    G = H // KH
    qg = q.reshape(B, KH, G, D)
    ns = pl.cdiv(S, BLOCK_S)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if ns * BLOCK_S != S:
        pad = ((0, 0), (0, 0), (0, ns * BLOCK_S - S), (0, 0))
        kt = jnp.pad(kt, pad)
        vt = jnp.pad(vt, pad)
    scale = 1.0 / (D ** 0.5)

    out = pl.pallas_call(
        functools.partial(_decode_attn_kernel, scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KH, ns),
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, s, vl: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, BLOCK_S, D), lambda b, h, s, vl: (b, h, s, 0)),
                pl.BlockSpec((1, 1, BLOCK_S, D), lambda b, h, s, vl: (b, h, s, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, s, vl: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        interpret=interpret,
    )(valid_len.astype(jnp.int32), qg, kt, vt)
    return out.reshape(B, H, D)
