"""Per-utterance normalize DPU kernel — the paper's separate 'Normalize' CU.

Three-phase (mean -> variance -> scale) over the whole utterance: the global
reduction is why the paper gives it its own CU type (Fig. 11b/12c) instead of
fusing it into the streaming Resample+Mel unit. Implemented as a stats sweep
(grid-accumulated VMEM partials) followed by a scale sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_T = 128


def _stats_kernel(t_total, feats_ref, sum_out, sq_out):
    i = pl.program_id(0)
    x = feats_ref[...].astype(jnp.float32)
    base = i * BLOCK_T
    valid = (base + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)) < t_total
    xv = jnp.where(valid, x, 0.0)

    @pl.when(i == 0)
    def _init():
        sum_out[...] = jnp.zeros_like(sum_out)
        sq_out[...] = jnp.zeros_like(sq_out)

    sum_out[...] += jnp.sum(xv, axis=0, keepdims=True)
    sq_out[...] += jnp.sum(xv * xv, axis=0, keepdims=True)


def _scale_kernel(feats_ref, mu_ref, inv_ref, out_ref):
    x = feats_ref[...].astype(jnp.float32)
    out_ref[...] = (x - mu_ref[...]) * inv_ref[...]


def _stats_kernel_b(t_total, feats_ref, sum_out, sq_out):
    i = pl.program_id(1)
    x = feats_ref[0].astype(jnp.float32)
    base = i * BLOCK_T
    valid = (base + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)) < t_total
    xv = jnp.where(valid, x, 0.0)

    @pl.when(i == 0)
    def _init():
        sum_out[...] = jnp.zeros_like(sum_out)
        sq_out[...] = jnp.zeros_like(sq_out)

    sum_out[...] += jnp.sum(xv, axis=0, keepdims=True)[None]
    sq_out[...] += jnp.sum(xv * xv, axis=0, keepdims=True)[None]


def _scale_kernel_b(feats_ref, mu_ref, inv_ref, out_ref):
    x = feats_ref[0].astype(jnp.float32)
    out_ref[0] = (x - mu_ref[0]) * inv_ref[0]


def audio_normalize_batch_pallas(feats: jax.Array, *, eps: float = 1e-5,
                                 interpret: bool) -> jax.Array:
    """feats: [N, T, F] stack of same-shape utterances -> per-utterance
    mean/var normalized [N, T, F]. One stats launch + one scale launch for
    the whole stack (grid (N, T-tiles)) instead of 2N per-request launches."""
    n, t, f = feats.shape
    nb = pl.cdiv(t, BLOCK_T)
    pad = nb * BLOCK_T - t
    fp = jnp.pad(feats, ((0, 0), (0, pad), (0, 0))) if pad else feats

    sums, sqs = pl.pallas_call(
        functools.partial(_stats_kernel_b, t),
        grid=(n, nb),
        in_specs=[pl.BlockSpec((1, BLOCK_T, f), lambda b, i: (b, i, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, f), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, f), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1, f), jnp.float32),
            jax.ShapeDtypeStruct((n, 1, f), jnp.float32),
        ],
        interpret=interpret,
    )(fp)
    mu = sums / t
    var = jnp.maximum(sqs / t - mu * mu, 0.0)
    inv = jax.lax.rsqrt(var + eps)

    out = pl.pallas_call(
        _scale_kernel_b,
        grid=(n, nb),
        in_specs=[
            pl.BlockSpec((1, BLOCK_T, f), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, f), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, f), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_T, f), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, nb * BLOCK_T, f), jnp.float32),
        interpret=interpret,
    )(fp, mu, inv)
    return out[:, :t]


def audio_normalize_pallas(feats: jax.Array, *, eps: float = 1e-5,
                           interpret: bool) -> jax.Array:
    """feats: [T, F] -> per-utterance mean/var normalized [T, F]."""
    t, f = feats.shape
    nb = pl.cdiv(t, BLOCK_T)
    pad = nb * BLOCK_T - t
    fp = jnp.pad(feats, ((0, pad), (0, 0))) if pad else feats

    sums, sqs = pl.pallas_call(
        functools.partial(_stats_kernel, t),
        grid=(nb,),
        in_specs=[pl.BlockSpec((BLOCK_T, f), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((1, f), lambda i: (0, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, f), jnp.float32),
            jax.ShapeDtypeStruct((1, f), jnp.float32),
        ],
        interpret=interpret,
    )(fp)
    mu = sums / t
    # E[x^2]-mu^2 can go slightly negative for constant features (catastrophic
    # cancellation on empty mel bands) — clamp before rsqrt
    var = jnp.maximum(sqs / t - mu * mu, 0.0)
    inv = jax.lax.rsqrt(var + eps)

    out = pl.pallas_call(
        _scale_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((BLOCK_T, f), lambda i: (i, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_T, f), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * BLOCK_T, f), jnp.float32),
        interpret=interpret,
    )(fp, mu, inv)
    return out[:t]
