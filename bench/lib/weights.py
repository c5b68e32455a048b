"""Seeded weights, made on the device in one jitted call.

The tree has the serving program's parameter layout (the shapes and init
kinds of `repro.models.lm.model_defs`), each leaf drawn from its own key
folded out of the run's seed and written straight in the type it is
served in: no float32 copy of the tree and no second tree. A large leaf
is drawn in slices along its first axis, so the float32 draw of one slice
(at most 2**26 elements) is the only transient.
"""
from __future__ import annotations

import math

import numpy as np


def key_int(seed: int) -> int:
    """A 31-bit PRNG seed from any whole number (a run's --seed may
    exceed 32 bits)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) \
        & 0x7FFFFFFF


def init_params(cfg, seed: int, device):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.models import lm

    defs = lm.model_defs(cfg)
    flat, treedef = jax.tree_util.tree_flatten(defs, is_leaf=lm.is_param_def)
    serve_dt = jnp.dtype(cfg.dtype)

    def draw(k, pd, shape, dt):
        if pd.init == "zeros":
            return jnp.zeros(shape, dt)
        if pd.init == "ones":
            return jnp.ones(shape, dt)
        u = jax.random.uniform(k, shape, jnp.float32)
        if pd.init == "dt_bias":  # softplus^-1 of dt ~ logU(1e-3, 1e-1)
            d = jnp.exp(u * (math.log(0.1) - math.log(0.001))
                        + math.log(0.001))
            return (d + jnp.log(-jnp.expm1(-d))).astype(dt)
        if pd.init == "a_log":    # A = -exp(A_log), A_log ~ log U(1, 16)
            return jnp.log(1.0 + 15.0 * u).astype(dt)
        return (jax.random.normal(k, shape, jnp.float32)
                * pd.scale).astype(dt)

    def one(k, pd):
        dt = jnp.dtype(pd.dtype) if pd.dtype else serve_dt
        n, lead = math.prod(pd.shape), pd.shape[0] if pd.shape else 1
        c = next((d for d in range(1, lead + 1)
                  if lead % d == 0 and n // d <= 2**26), lead)
        if c == 1 or pd.init in ("zeros", "ones"):
            return draw(k, pd, pd.shape, dt)
        part = (lead // c,) + tuple(pd.shape[1:])
        out = jax.lax.map(lambda kk: draw(kk, pd, part, dt),
                          jax.random.split(k, c))
        return out.reshape(pd.shape)

    def make(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [one(keys[i], pd) for i, pd in enumerate(flat)])

    out = jax.jit(make, out_shardings=SingleDeviceSharding(device))(
        jax.random.PRNGKey(key_int(seed)))
    return jax.block_until_ready(out)
