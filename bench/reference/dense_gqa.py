"""Plain float32 reference of a dense grouped-query-attention decoder.

Pre-norm blocks: x += Wo·attn(RoPE(Wq·n1(x)), RoPE(Wk·n1(x)), Wv·n1(x));
x += Wd·(silu(Wg·n2(x)) * Wu·n2(x)); RMSNorm on the last hidden; logits
against the tied embedding (or an untied `lm_head` [d, V]). RoPE rotates the two halves of each head
(base `rope_theta`); query head (k, g) reads key/value head k. Causal
softmax over true positions 0..t, all in float32 at the highest matmul
precision, computed one layer at a time so only that layer's weights are
ever widened to float32.

It reads the weights by name from the tree the benchmark made: embed
[Vp, d], final_ln [d], and per layer (stacked on axis 0) mixer {ln, wq
[d, kh, g, hd], wk/wv [d, kh, hd], wo [kh, g, hd, d]} and ffn {ln,
wi_gate/wi_up [d, f], wo [f, d]}. It shares no code with the program.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import fp8, mm, rms

PAD = 512  # sequence lengths round up to this, so few programs compile


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    f = pos.astype(jnp.float32)[:, None] * inv
    c, s = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "quant"))
def _layer(x, body, i, theta, eps, quant):
    lw = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), body)
    f32 = lambda a: a.astype(jnp.float32)
    S = x.shape[0]
    pos = jnp.arange(S)
    at, ff = lw["mixer"], lw["ffn"]
    h = rms(x, f32(at["ln"]), eps)
    q = mm(h, f32(at["wq"]), quant, "sd,dkgh->skgh")
    k = mm(h, f32(at["wk"]), quant, "sd,dkh->skh")
    v = mm(h, f32(at["wv"]), quant, "sd,dkh->skh")
    kh, g, hd = q.shape[1], q.shape[2], q.shape[3]
    q = _rope(q.reshape(S, kh * g, hd), pos, theta).reshape(S, kh, g, hd)
    k = _rope(k, pos, theta)
    if quant:
        q, k, v = fp8(q, -1), fp8(k, -1), fp8(v, -1)
    s = jnp.einsum("qkgh,jkh->kgqj", q, k,
                   precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                       jnp.float32(hd))
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if quant:
        p = fp8(p, -1)
    o = jnp.einsum("kgqj,jkh->qkgh", p, v,
                   precision=jax.lax.Precision.HIGHEST)
    x = x + mm(o, f32(at["wo"]), quant, "qkgh,kghd->qd")
    h = rms(x, f32(ff["ln"]), eps)
    a = jax.nn.silu(mm(h, f32(ff["wi_gate"]), quant, "sd,df->sf"))
    u = mm(h, f32(ff["wi_up"]), quant, "sd,df->sf")
    return x + mm(a * u, f32(ff["wo"]), quant, "sf,fd->sd")


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
    given positions (the position predicting token t+1 is t)."""
    body = params["body"]["l0"]
    n_layers = jax.tree.leaves(body)[0].shape[0]
    out = []
    for toks, rr in zip(seqs, rows):
        S = -(-len(toks) // PAD) * PAD
        t = np.zeros(S, np.int32)
        t[:len(toks)] = toks
        x = jnp.take(params["embed"], jnp.asarray(t), axis=0).astype(
            jnp.float32)
        for i in range(n_layers):
            x = _layer(x, body, jnp.int32(i), float(spec["rope_theta"]),
                       float(spec["norm_eps"]), quant)
        sel = x[jnp.asarray(np.asarray(rr, np.int32))]
        out.append(np.asarray(_head(sel, params["final_ln"], params.get("lm_head", params["embed"]),
                                    float(spec["norm_eps"]),
                                    int(spec["vocab"]), quant)))
    return out
