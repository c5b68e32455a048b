"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Completes the parallelism menu (DP/TP/EP/SP elsewhere; PP here): layer
stages are sharded over a 'pipe' mesh axis, microbatches stream through the
classic (n_micro + n_stages - 1)-step schedule with a ppermute shift per
step. Exact-equivalence against sequential apply is tested on an 8-device
host mesh (tests/test_pipeline.py).

At pod scale this composes with the production mesh by reshaping the 'data'
axis into ('pipe', 'data'): e.g. a 2x16x16 multi-pod mesh can run 4 pipeline
stages of 128 chips each. Bubble fraction = (S-1)/(M+S-1); the dry-run
machinery (roofline terms per stage) applies unchanged to the stage step
function.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    mesh,
    axis: str = "pipe",
):
    """Run `n_stages` copies of stage_fn as a pipeline.

    stage_params: pytree with leading dim n_stages (sharded over `axis`).
    microbatches: [n_micro, mb, ...] inputs (replicated; stage 0 ingests).
    Returns [n_micro, mb, ...] outputs of the final stage (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    steps = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local_fn(params_local, mbs):
        # params_local: stage slice (leading dim 1); mbs: [n_micro, mb, ...]
        params = jax.tree.map(lambda x: x[0], params_local)
        stage = jax.lax.axis_index(axis)
        state = jnp.zeros_like(mbs[0])
        outs = jnp.zeros_like(mbs)

        def body(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (clamped; masked later)
            mb_in = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
            )
            inp = jnp.where(stage == 0, mb_in, state)
            out = stage_fn(params, inp)
            # emit from the last stage at t >= n_stages-1
            emit_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            do_emit = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, emit_idx, 0, keepdims=False)
            new = jnp.where(do_emit, out, cur)
            outs = jax.lax.dynamic_update_index_in_dim(outs, new, emit_idx, 0)
            # shift activations to the next stage
            state = jax.lax.ppermute(out, axis, perm)
            return (state, outs), None

        (state, outs), _ = jax.lax.scan(body, (state, outs), jnp.arange(steps))
        # replicate the last stage's outputs to every shard
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, microbatches)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
