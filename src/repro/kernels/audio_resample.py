"""FIR decimation DPU kernel (paper 'Resample' functional unit).

y[i] = sum_k h[k] x[i*down + k] over a pre-padded signal. The FPGA's
polyphase MAC array maps to the MXU as a banded matmul: output block b
(BLOCK_OUT samples) reads the contiguous input window
x[b*W : b*W + W + HEAD] (W = BLOCK_OUT*down), so

    y_b = x[b*W : (b+1)*W] @ T0 + x[(b+1)*W : (b+1)*W + HEAD] @ T1

with T0[j, r] = h[j - r*down] and T1[j, r] = h[W + j - r*down] (zero off
the band). The wrapper lays the signal out as tile-aligned rows
(one row per output block, plus the HEAD-sample head of the next block), so
every grid step reads plain Blocked tiles: no overlapping windows, no
strided lane slices and no per-step DMA. The stride `down` lives only in
the constant band matrices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

BLOCK_OUT = 256   # output samples per row (lane-aligned)
ROW_BLOCK = 128   # rows (output blocks, all signals flattened) per grid step
_HI = jax.lax.Precision.HIGHEST


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.lru_cache(maxsize=16)
def _band_matrices(hs: tuple, down: int):
    """(T0 [W, BLOCK_OUT], T1 [HEAD, BLOCK_OUT]) for taps `hs`."""
    h = np.asarray(hs, np.float32)
    taps = h.shape[0]
    w = BLOCK_OUT * down
    head = _round_up(max(taps - down, 1), 128)
    t = np.zeros((w + head, BLOCK_OUT), np.float32)
    for r in range(BLOCK_OUT):
        t[r * down: r * down + taps, r] = h
    t.setflags(write=False)  # cached: shared by every caller
    return t[:w], t[w:]


def _resample_kernel(x_ref, nxt_ref, t0_ref, t1_ref, out_ref):
    out_ref[...] = (
        jnp.dot(x_ref[...], t0_ref[...], precision=_HI,
                preferred_element_type=jnp.float32)
        + jnp.dot(nxt_ref[...], t1_ref[...], precision=_HI,
                  preferred_element_type=jnp.float32)
    )


def audio_resample_batch_pallas(x: jax.Array, h: jax.Array, down: int, *,
                                interpret: bool) -> jax.Array:
    """x: [N, L] stack of pre-padded same-length signals -> [N, n_out]
    decimated outputs, y[n, i] = sum_k h[k] x[n, i*down + k] for
    i < (L - taps)//down + 1, in a single kernel launch."""
    nsig, length = x.shape
    # taps are trace-time constants: the band matrices are built on the host
    # (ops.py always passes a concrete filter)
    hs = tuple(float(v) for v in np.asarray(h, np.float32))
    taps = len(hs)
    n_out = (length - taps) // down + 1
    t0, t1 = _band_matrices(hs, down)
    w, head = t0.shape[0], t1.shape[0]
    nb = pl.cdiv(n_out, BLOCK_OUT)
    # one extra block so block nb-1 has a "next" head to read
    xp = jnp.pad(x.astype(jnp.float32),
                 ((0, 0), (0, max(0, (nb + 1) * w - length))))[:, :(nb + 1) * w]
    blocks = xp.reshape(nsig, nb + 1, w)
    cur = blocks[:, :nb].reshape(nsig * nb, w)
    nxt = blocks[:, 1:, :head].reshape(nsig * nb, head)
    rows = nsig * nb
    rb = min(ROW_BLOCK, _round_up(rows, 8))
    rows_p = _round_up(rows, rb)
    if rows_p != rows:
        cur = jnp.pad(cur, ((0, rows_p - rows), (0, 0)))
        nxt = jnp.pad(nxt, ((0, rows_p - rows), (0, 0)))
    out = pl.pallas_call(
        _resample_kernel,
        grid=(rows_p // rb,),
        in_specs=[
            pl.BlockSpec((rb, w), lambda i: (i, 0)),
            pl.BlockSpec((rb, head), lambda i: (i, 0)),
            pl.BlockSpec((w, BLOCK_OUT), lambda i: (0, 0)),
            pl.BlockSpec((head, BLOCK_OUT), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rb, BLOCK_OUT), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, BLOCK_OUT), jnp.float32),
        interpret=interpret,
    )(cur, nxt, jnp.asarray(t0), jnp.asarray(t1))
    return out[:rows].reshape(nsig, nb * BLOCK_OUT)[:, :n_out]
