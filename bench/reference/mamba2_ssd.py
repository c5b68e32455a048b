"""Plain float32 reference of a Mamba-2 (SSD) language model.

Per layer, on n(x) = RMSNorm(x): z = n·Wz, xBC = n·Wxbc, dt_raw = n·Wdt;
xBC <- silu(causal depthwise conv_k(xBC) + b); split xBC into x (heads of
`ssm_headdim`), B and C (one group of `ssm_state`); dt = softplus(dt_raw +
dt_bias), A = -exp(A_log); then the recurrence, one token at a time:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t

y <- RMSNorm(y * silu(z)) * norm_w; x += y·Wout. No feed-forward block.
RMSNorm on the last hidden, logits against the tied embedding (or an
untied `lm_head`). Written as
the sequential scan (not the chunked dual form the program computes), in
float32 at the highest matmul precision, one layer at a time.

Weights are read by name from the tree the benchmark made: embed, final_ln
and per layer (stacked on axis 0) mixer {ln, in_z, in_xbc, in_dt, conv_w
[cd, k], conv_b, dt_bias, A_log, D, norm_w, out_proj}. It shares no code
with the program.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import mm, rms

PAD = 128


@functools.partial(jax.jit, static_argnames=("eps", "headdim", "state",
                                             "quant"))
def _layer(x, body, i, eps, headdim, state, quant):
    p = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        body)["mixer"]
    f32 = lambda a: a.astype(jnp.float32)
    B, S, _ = x.shape
    h = rms(x, f32(p["ln"]), eps)
    z = mm(h, f32(p["in_z"]), quant, "bsd,di->bsi")
    xbc = mm(h, f32(p["in_xbc"]), quant, "bsd,dc->bsc")
    dtr = mm(h, f32(p["in_dt"]), quant, "bsd,dh->bsh")
    w = f32(p["conv_w"])                      # [channels, k]
    k = w.shape[1]
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(xp[:, j:j + S] * w[:, j] for j in range(k)) + f32(p["conv_b"])
    xbc = jax.nn.silu(conv)
    di = z.shape[-1]
    H = di // headdim
    xs = xbc[..., :di].reshape(B, S, H, headdim)
    Bm = xbc[..., di:di + state]
    Cm = xbc[..., di + state:di + 2 * state]
    dt = jax.nn.softplus(dtr + f32(p["dt_bias"]))          # [B, S, H]
    A = -jnp.exp(f32(p["A_log"]))                           # [H]

    def step(hs, inp):
        xt, bt, ct, dtt = inp      # [B,H,P], [B,N], [B,N], [B,H]
        hs = hs * jnp.exp(dtt * A)[..., None, None] + \
            (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        return hs, jnp.einsum("bhpn,bn->bhp", hs, ct,
                              precision=jax.lax.Precision.HIGHEST)

    h0 = jnp.zeros((B, H, headdim, state), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (jnp.moveaxis(xs, 1, 0),
                                    jnp.moveaxis(Bm, 1, 0),
                                    jnp.moveaxis(Cm, 1, 0),
                                    jnp.moveaxis(dt, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1) + f32(p["D"])[None, None, :, None] * xs
    y = rms(y.reshape(B, S, di) * jax.nn.silu(z), f32(p["norm_w"]), eps)
    return x + mm(y, f32(p["out_proj"]), quant, "bsi,id->bsd")


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "quant"))
def _head(x, final_ln, head, eps, vocab, quant):
    """Logits against the tied embedding [V, d], or an untied head [d, V]."""
    h = rms(x, final_ln.astype(jnp.float32), eps)
    if head.shape[0] == h.shape[-1] and head.shape[1] != h.shape[-1]:
        return mm(h, head[:, :vocab].astype(jnp.float32), quant, "sd,dv->sv")
    return mm(h, head[:vocab].astype(jnp.float32), quant, "sd,vd->sv")


def logits(params: Dict, seqs: List[np.ndarray], rows: List[np.ndarray],
           spec: Dict, quant: bool = False) -> List[np.ndarray]:
    """Float32 logits [len(rows[i]), vocab] of each token sequence at the
    given positions (the position predicting token t+1 is t). All
    sequences run as one right-padded batch (padding after a sequence
    cannot reach it: the scan is causal)."""
    body = params["body"]
    n_layers = jax.tree.leaves(body)[0].shape[0]
    S = -(-max(len(s) for s in seqs) // PAD) * PAD
    t = np.zeros((len(seqs), S), np.int32)
    for b, s in enumerate(seqs):
        t[b, :len(s)] = s
    x = jnp.take(params["embed"], jnp.asarray(t), axis=0).astype(jnp.float32)
    for i in range(n_layers):
        x = _layer(x, body["l0"], jnp.int32(i), float(spec["norm_eps"]),
                   int(spec["ssm_headdim"]), int(spec["ssm_state"]), quant)
    out = []
    for b, rr in enumerate(rows):
        sel = x[b, jnp.asarray(np.asarray(rr, np.int32))]
        out.append(np.asarray(_head(sel, params["final_ln"], params.get("lm_head", params["embed"]),
                                    float(spec["norm_eps"]),
                                    int(spec["vocab"]), quant)))
    return out
