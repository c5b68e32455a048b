"""Pieces shared by the plain references: float32 math at the highest
matmul precision, and the fp8 rounding that the correctness control puts
in the reference's place (see PERF.md, "How correct is decided")."""
from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def fp8(x, axis=None):
    """Round to float8_e4m3fn with an absmax scale (per tensor, or per
    slice along `axis`), returned in float32."""
    a = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(a, 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a, w, quant: bool, spec: str):
    """einsum of an activation with a weight; with `quant` both operands
    are rounded to fp8 first (activations per row, weights per tensor)."""
    if quant:
        a, w = fp8(a, axis=-1), fp8(w)
    return jnp.einsum(spec, a, w, precision=jax.lax.Precision.HIGHEST)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w
