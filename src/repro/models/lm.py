"""Unified scan-based LM covering all assigned families.

One parameter-definition tree drives init / abstract specs / shardings; the
forward pass interprets per-layer *kinds* from the config (attn/ssm mixer,
mlp/moe ffn, optional cross-attention for enc-dec). Layer stacks are grouped
into a scanned `body` of identical blocks (period = lcm of the kind pattern)
plus an unrolled `prefix` (e.g. moonshot's leading dense layer), which keeps
the lowered HLO small enough to compile 512-way GSPMD programs quickly.

GQA tensors are factored as [kv_heads, q_per_kv, head_dim] throughout so the
kv_heads axis can be model-sharded without reshapes (DESIGN.md §4).
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones | dt_bias | a_log
    scale: float = 0.02
    dtype: Optional[str] = None
    tags: Tuple[str, ...] = ()

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_param_def(x) -> bool:
    return isinstance(x, ParamDef)


def block_period(cfg: ModelConfig) -> int:
    p = 1
    if cfg.family == "hybrid" and cfg.attn_every:
        p = cfg.attn_every
    if cfg.n_experts and cfg.moe_every > 1:
        p = int(p * cfg.moe_every // math.gcd(p, cfg.moe_every))
    return p


def _attn_defs(cfg: ModelConfig) -> Dict[str, Any]:
    d, kh, hd = cfg.d_model, cfg.n_kv_heads, cfg.hd
    g = cfg.n_heads // kh
    out_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    return {
        "ln": ParamDef((d,), (None,), "ones"),
        "wq": ParamDef((d, kh, g, hd), ("embed", "kv_heads", None, "head_dim")),
        "wk": ParamDef((d, kh, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, kh, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((kh, g, hd, d), ("kv_heads", None, "head_dim", "embed"), scale=out_scale),
    }


def _mlp_defs(cfg: ModelConfig, d_ff: int) -> Dict[str, Any]:
    d = cfg.d_model
    out_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    return {
        "ln": ParamDef((d,), (None,), "ones"),
        "wi_gate": ParamDef((d, d_ff), ("embed", "mlp")),
        "wi_up": ParamDef((d, d_ff), ("embed", "mlp")),
        "wo": ParamDef((d_ff, d), ("mlp", "embed"), scale=out_scale),
    }


def _moe_defs(cfg: ModelConfig) -> Dict[str, Any]:
    d, e = cfg.d_model, cfg.n_experts
    f = cfg.moe_d_ff or cfg.d_ff
    out_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    defs = {
        "ln": ParamDef((d,), (None,), "ones"),
        "router": ParamDef((d, e), ("embed", None)),
        "wi_gate": ParamDef((e, d, f), ("expert", "expert_embed", "expert_mlp"), tags=("expert",)),
        "wi_up": ParamDef((e, d, f), ("expert", "expert_embed", "expert_mlp"), tags=("expert",)),
        "wo": ParamDef((e, f, d), ("expert", "expert_mlp", "expert_embed"), scale=out_scale, tags=("expert",)),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        defs["shared"] = {
            "wi_gate": ParamDef((d, fs), ("embed", "mlp")),
            "wi_up": ParamDef((d, fs), ("embed", "mlp")),
            "wo": ParamDef((fs, d), ("mlp", "embed"), scale=out_scale),
        }
    return defs


def _ssm_defs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    di, cd, h = cfg.d_inner, cfg.conv_dim, cfg.n_ssm_heads
    out_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    return {
        "ln": ParamDef((d,), (None,), "ones"),
        "in_z": ParamDef((d, di), ("embed", "ssm_inner")),
        "in_xbc": ParamDef((d, cd), ("embed", "ssm_conv")),
        "in_dt": ParamDef((d, h), ("embed", "ssm_heads")),
        "conv_w": ParamDef((cd, cfg.ssm_conv), ("ssm_conv", None), scale=0.1),
        "conv_b": ParamDef((cd,), ("ssm_conv",), "zeros"),
        "dt_bias": ParamDef((h,), ("ssm_heads",), "dt_bias"),
        "A_log": ParamDef((h,), ("ssm_heads",), "a_log"),
        "D": ParamDef((h,), ("ssm_heads",), "ones"),
        "norm_w": ParamDef((di,), ("ssm_inner",), "ones"),
        "out_proj": ParamDef((di, d), ("ssm_inner", "embed"), scale=out_scale),
    }


def _sublayer_defs(cfg: ModelConfig, kind: Tuple[str, str], with_xattn: bool) -> Dict[str, Any]:
    mixer, ffn = kind
    sub: Dict[str, Any] = {}
    sub["mixer"] = _attn_defs(cfg) if mixer == "attn" else _ssm_defs(cfg)
    if with_xattn:
        sub["xattn"] = _attn_defs(cfg)
    if ffn == "mlp":
        sub["ffn"] = _mlp_defs(cfg, cfg.d_ff)
    elif ffn == "moe":
        sub["ffn"] = _moe_defs(cfg)
    return sub


def _block_defs(cfg: ModelConfig, kinds, with_xattn) -> Dict[str, Any]:
    return {f"l{i}": _sublayer_defs(cfg, k, with_xattn) for i, k in enumerate(kinds)}


def _stack(tree, n: int):
    return jax.tree.map(
        lambda pd: ParamDef(
            (n,) + pd.shape, ("layers",) + pd.axes, pd.init, pd.scale, pd.dtype, pd.tags
        ),
        tree,
        is_leaf=is_param_def,
    )


def model_defs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    kinds = cfg.layer_kinds()
    p = block_period(cfg)
    npre = cfg.first_k_dense
    body_kinds = kinds[npre:]
    assert len(body_kinds) % p == 0, (cfg.name, len(body_kinds), p)
    nb = len(body_kinds) // p
    with_xattn = cfg.enc_layers > 0

    defs: Dict[str, Any] = {
        "embed": ParamDef((cfg.padded_vocab, d), ("vocab", "embed")),
        "final_ln": ParamDef((d,), (None,), "ones"),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, cfg.padded_vocab), ("embed", "vocab"))
    if npre:
        defs["prefix"] = {
            f"l{i}": _sublayer_defs(cfg, kinds[i], with_xattn) for i in range(npre)
        }
    defs["body"] = _stack(_block_defs(cfg, body_kinds[:p], with_xattn), nb)
    if cfg.enc_layers:
        enc_block = {
            "mixer": _attn_defs(cfg),
            "ffn": _mlp_defs(cfg, cfg.d_ff),
        }
        defs["encoder"] = {
            "blocks": _stack(enc_block, cfg.enc_layers),
            "ln": ParamDef((d,), (None,), "ones"),
        }
    return defs


# ---------------------------------------------------------------------------
# Init / specs / counting
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array, dtype: Optional[str] = None):
    defs = model_defs(cfg)
    dt = jnp.dtype(dtype or cfg.param_dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(defs, is_leaf=is_param_def)

    def one(path, pd: ParamDef):
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % (2**31)
        )
        d = jnp.dtype(pd.dtype) if pd.dtype else dt
        if pd.init == "zeros":
            return jnp.zeros(pd.shape, d)
        if pd.init == "ones":
            return jnp.ones(pd.shape, d)
        if pd.init == "dt_bias":
            dt_ = jnp.exp(
                jax.random.uniform(k, pd.shape, jnp.float32)
                * (math.log(0.1) - math.log(0.001))
                + math.log(0.001)
            )
            return (dt_ + jnp.log(-jnp.expm1(-dt_))).astype(d)
        if pd.init == "a_log":
            return jnp.log(
                jax.random.uniform(k, pd.shape, jnp.float32, 1.0, 16.0)
            ).astype(d)
        return (jax.random.normal(k, pd.shape, jnp.float32) * pd.scale).astype(d)

    leaves = [one(p, pd) for p, pd in flat]
    return jax.tree.unflatten(treedef, leaves)


def param_specs(cfg: ModelConfig, dtype: Optional[str] = None):
    dt = jnp.dtype(dtype or cfg.param_dtype)
    return jax.tree.map(
        lambda pd: jax.ShapeDtypeStruct(pd.shape, jnp.dtype(pd.dtype) if pd.dtype else dt),
        model_defs(cfg),
        is_leaf=is_param_def,
    )


def param_axes(cfg: ModelConfig):
    return jax.tree.map(lambda pd: pd.axes, model_defs(cfg), is_leaf=is_param_def)


def count_params_analytical(cfg: ModelConfig, active_only: bool = False) -> int:
    total = 0
    for pd in jax.tree.leaves(model_defs(cfg), is_leaf=is_param_def):
        n = math.prod(pd.shape)
        if active_only and "expert" in pd.tags:
            n = int(n * cfg.top_k / cfg.n_experts)
        total += n
    return total


# ---------------------------------------------------------------------------
# Cache definitions (must mirror what prefill emits / decode consumes;
# enforced by tests against jax.eval_shape of prefill)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CacheDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: str = "bfloat16"


def is_cache_def(x) -> bool:
    return isinstance(x, CacheDef)


def ring_len(cfg: ModelConfig, cache_len: int) -> int:
    return min(cfg.sliding_window, cache_len) if cfg.sliding_window else cache_len


def _sublayer_cache_defs(cfg, kind, with_xattn, batch, cache_len):
    mixer, _ = kind
    wc = ring_len(cfg, cache_len)
    kh, hd = cfg.n_kv_heads, cfg.hd
    sub: Dict[str, Any] = {}
    if mixer == "attn":
        sub["mixer"] = {
            "k": CacheDef((batch, wc, kh, hd), ("batch", "seq", "kv_heads", "head_dim"), cfg.dtype),
            "v": CacheDef((batch, wc, kh, hd), ("batch", "seq", "kv_heads", "head_dim"), cfg.dtype),
        }
    else:
        sub["mixer"] = {
            "conv": CacheDef(
                (batch, cfg.ssm_conv - 1, cfg.conv_dim), ("batch", None, "ssm_conv"), cfg.dtype
            ),
            "state": CacheDef(
                (batch, cfg.n_ssm_heads, cfg.ssm_headdim, cfg.ssm_state),
                ("batch", "ssm_heads", None, None),
                "float32",
            ),
        }
    if with_xattn:
        sub["xattn"] = {
            "ck": CacheDef(
                (batch, cfg.n_audio_ctx, kh, hd), ("batch", "seq", "kv_heads", "head_dim"), cfg.dtype
            ),
            "cv": CacheDef(
                (batch, cfg.n_audio_ctx, kh, hd), ("batch", "seq", "kv_heads", "head_dim"), cfg.dtype
            ),
        }
    return sub


def _stack_cache(tree, n):
    return jax.tree.map(
        lambda cd: CacheDef((n,) + cd.shape, ("layers",) + cd.axes, cd.dtype),
        tree,
        is_leaf=is_cache_def,
    )


def cache_defs(cfg: ModelConfig, batch: int, cache_len: int):
    kinds = cfg.layer_kinds()
    p = block_period(cfg)
    npre = cfg.first_k_dense
    nb = (len(kinds) - npre) // p
    with_xattn = cfg.enc_layers > 0
    defs: Dict[str, Any] = {}
    if npre:
        defs["prefix"] = {
            f"l{i}": _sublayer_cache_defs(cfg, kinds[i], with_xattn, batch, cache_len)
            for i in range(npre)
        }
    block = {
        f"l{i}": _sublayer_cache_defs(cfg, kinds[npre + i], with_xattn, batch, cache_len)
        for i in range(p)
    }
    defs["body"] = _stack_cache(block, nb)
    return defs


def cache_specs(cfg: ModelConfig, batch: int, cache_len: int):
    return jax.tree.map(
        lambda cd: jax.ShapeDtypeStruct(cd.shape, jnp.dtype(cd.dtype)),
        cache_defs(cfg, batch, cache_len),
        is_leaf=is_cache_def,
    )


def cache_axes(cfg: ModelConfig, batch: int, cache_len: int):
    return jax.tree.map(
        lambda cd: cd.axes, cache_defs(cfg, batch, cache_len), is_leaf=is_cache_def
    )


# ---------------------------------------------------------------------------
# KV slot pool (continuous batching)
#
# The pool is one fixed-shape cache tree [max_slots, cache_len] shared by all
# in-flight requests; requests join by having their prefill cache scattered
# into a row slot and leave by simply being ignored (stale rows are masked,
# overwritten on slot reuse). A single global scalar `clock` is the shared
# padded write position: a request admitted at clock P with true prompt
# length n gets pos_offset = P - n, and each row's cache is TRUE-POSITION
# indexed — its prompt KV lands on ring slots 0..n-1, and every decode step
# writes row b's slot (clock - pos_offset[b]) mod cache_len (a per-row
# scatter of one shared fixed-shape op) — so the decode executable never
# changes shape as requests come and go, and a row's KV layout is exactly
# the layout of an isolated per-request cache no matter WHEN it joined.
# Clock-independent layout is what makes outputs bit-identical across
# compositions/timings (see _attn_decode); a row's live span never exceeds
# the ring (cache_len >= max_prompt + max_new + segment), so slot t of a
# live row is always its own token at true position t.
# ---------------------------------------------------------------------------


def alloc_slot_pool(cfg: ModelConfig, max_slots: int, cache_len: int,
                    device=None):
    """Zero-initialized slot-pool cache tree (shape [max_slots, cache_len])
    on `device` (a Device or Sharding; None = the default device)."""
    return jax.tree.map(
        lambda sd: jnp.zeros(sd.shape, sd.dtype, device=device),
        cache_specs(cfg, max_slots, cache_len),
    )


def _scatter_slot_tree(pool, pre, slot_ids, lp: int, stacked: bool):
    """Scatter prefill-cache rows into pool row slots. The prefill cache is
    TRUE-POSITION indexed (left-padded rows are shifted at cache build, see
    _attn_forward), so attention k/v leaves land on pool ring slots 0..lp-1
    directly — slot t of a row always holds its token at true position t,
    and decode continues writing slot (clock - offset) mod ring (see
    _attn_decode). Everything else (ssm conv/state, cross-attn ck/cv) is a
    plain row copy. slot_ids out of range (>= max_slots) mark padding rows
    and are dropped."""
    out = {}
    for name, pv in pool.items():
        qv = pre[name]
        if isinstance(pv, dict):
            out[name] = _scatter_slot_tree(pv, qv, slot_ids, lp, stacked)
            continue
        axis0 = 1 if stacked else 0  # body leaves carry a leading layer dim
        if name in ("k", "v"):
            wc = pv.shape[axis0 + 1]
            assert qv.shape[axis0 + 1] == lp, (
                "slot-pool admission needs the prefill ring to hold the whole "
                "padded prompt (sliding_window must be 0 or >= prompt bucket)",
                qv.shape, lp,
            )
            tgt = jnp.mod(jnp.arange(lp, dtype=jnp.int32), wc)
            idx = (slot_ids[:, None], tgt[None, :])
        else:
            idx = (slot_ids,)
        if stacked:
            idx = (slice(None),) + idx
        out[name] = pv.at[idx].set(qv.astype(pv.dtype), mode="drop")
    return out


def scatter_into_slots(pool_cache, prefill_cache, slot_ids, clock, lp: int):
    """Admit a prefilled batch into pool row slots (see module comment).
    prefill_cache rows i land in pool slot slot_ids[i]; rows whose slot id is
    out of range (admission padding) are dropped."""
    slot_ids = slot_ids.astype(jnp.int32)
    del clock  # placement is true-position indexed; clock no longer matters
    out = {}
    if "prefix" in pool_cache:
        out["prefix"] = _scatter_slot_tree(
            pool_cache["prefix"], prefill_cache["prefix"], slot_ids, lp, False
        )
    out["body"] = _scatter_slot_tree(
        pool_cache["body"], prefill_cache["body"], slot_ids, lp, True
    )
    return out


def scatter_prefix_into_slots(pool_cache, prefix_cache, slot_ids, lp: int):
    """Admit CACHED prefix K/V (radix prefix-store hits) into pool rows.

    prefix_cache is shaped exactly like a prefill cache for bucket lp
    (leaves [B, lp, kh, hd] / stacked [nb, B, lp, kh, hd]) but its rows are
    assembled host-side from the prefix store: true positions [0, m) carry
    a previous request's extracted K/V (bit-identical to what this
    request's own prefill would write there, by the canonical true-position
    read — see _attn_chunk), positions [m, lp) are zero. The engine then
    resumes chunked prefill at the row's aligned column off + m via
    prefill_chunk_into_slots' per-row start operand, so the suffix chunks
    overwrite [m, n) and everything past n stays masked — no new executable
    shapes beyond one scatter program per bucket. Rows whose slot id is out
    of range (non-hit rows of the admission) are dropped."""
    return scatter_into_slots(pool_cache, prefix_cache, slot_ids,
                              jnp.int32(0), lp)


def prefill_into_slots(params, tokens, pool_cache, slot_ids, clock,
                       cfg: ModelConfig, *, pos_offset=None):
    """Fused admission: prefill a left-padded (batch, lp) prompt bucket and
    scatter its KV/state into slot-pool rows, one executable per prompt
    bucket (the compile-once prefill half of continuous batching). Row i
    lands in pool row slot_ids[i]; a slot id >= max_slots marks a padding
    row, whose write is dropped, so pool rows not named are untouched.

    Returns (first greedy tokens [B, 1] int32, new pool cache). The caller
    sets each admitted slot's pos_offset to clock - true_prompt_len so decode
    positions continue seamlessly from the prompt."""
    lp = tokens.shape[1]
    logits, pcache = prefill(params, tokens, cfg, pos_offset=pos_offset, cache_len=lp)
    new_pool = scatter_into_slots(pool_cache, pcache, slot_ids, clock, lp)
    return jnp.argmax(logits, -1).astype(jnp.int32), new_pool


# ---------------------------------------------------------------------------
# Chunked prefill (continuous batching without long-prompt head-of-line)
#
# A monolithic prefill_into_slots freezes every resident decoder for the
# whole prompt pass. The chunked variant splits a prompt bucket's KV
# construction into fixed-length column chunks: each chunk runs the model
# over C padded prompt positions, writes their K/V at TRUE-POSITION ring
# slots of the pool (the same layout contract as _attn_decode /
# scatter_into_slots, so slot t of a live row is always its own token at
# true position t), and attends the chunk's queries over the updated ring.
# The engine interleaves one decode segment between chunks, so resident
# rows keep producing tokens while a long prompt admits. The chunk program
# reads and writes only the ring PREFIX [0, lp) (lp = the padded prompt
# bucket, a static shape) of the pool rows it is given (slot ids; a fixed
# row count per program): chunk attention costs what the bucket's
# monolithic prefill costs — NOT a full-ring scan per chunk — so the
# executable set is one per (chunk length, prompt bucket), the compile-once
# bound the engine reports as #chunk buckets + 1 segment. Outputs stay
# bit-identical to monolithic admission (the valid key set for a query at
# true position t is the same true positions 0..t in the same axis order;
# masked slots carry exactly-zero probabilities).
# ---------------------------------------------------------------------------


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Chunked prefill is implemented for pure attention+MLP decoder stacks.

    Excluded (engines fall back to monolithic admission): SSM/hybrid mixers
    (ssm_block has no chunk-resume path for the sequential conv/state),
    MoE ffns (expert capacity is computed over the WHOLE prefill token
    count, so chunking changes which tokens drop and therefore the
    outputs), sliding-window attention (a ring smaller than the prompt
    cannot hold the chunk history), and enc-dec / image-prefix models
    (non-token context precedes the prompt)."""
    return (
        cfg.enc_layers == 0
        and cfg.sliding_window == 0
        and cfg.n_img_tokens == 0
        and all(m == "attn" and f == "mlp" for m, f in cfg.layer_kinds())
    )


def _attn_chunk(x, p, cfg, rows, qpos, valid, lp: int):
    """Multi-token cache-extending attention for one prefill chunk.

    x: [B, C, D] chunk hidden states; rows: {'k','v'} the ring PREFIX
    [0, lp) of this call's pool rows, [B, lp, ...]; qpos: [B, C] TRUE
    positions (negative = left-pad or a padding row of the call); valid =
    qpos >= 0; lp: the padded prompt bucket (static). All prompt positions
    live in the ring prefix [0, lp) (ring slot == true position; no wrap:
    the ring holds the whole bucket by pool sizing), so only that prefix is
    read, written, and attended — chunk attention costs what the bucket's
    monolithic prefill costs, not a full-ring scan. Returns the chunk's
    output and the updated prefix.

    Bit-identity detail — the CANONICAL TRUE-POSITION read contract: the
    attention READ presents the pool rows directly, axis column t = the
    roped key at true position t (exactly how the pool stores them), with
    kpos = t for columns up to the row's current chunk end and -1 beyond.
    Monolithic serving prefill (_attn_forward with pos_offset) presents the
    SAME layout — keys shifted to true-position columns over the same axis
    length lp — so XLA's reduction pairing over the key axis matches bit
    for bit between chunked and monolithic admission. Because the layout no
    longer encodes the row's left-pad offset, the K/V bits a prefill writes
    at true position t are a function of (tokens[0..t], lp) ONLY — the
    prefix-shareability invariant the radix prefix cache relies on: K/V
    extracted from one request's pool row can be scattered into another
    request's row (any prompt length within the bucket) and the resumed
    suffix chunks reproduce the cold prefill bit for bit. Columns past the
    chunk end carry stale pool bytes or zeros — masked to exact-zero
    probabilities (scores replaced by NEG_INF before the max), never
    read."""
    dt = x.dtype
    B, C, _ = x.shape
    q = jnp.einsum("bsd,dkgh->bskgh", x, p["wq"].astype(dt))
    k1 = jnp.einsum("bsd,dkh->bskh", x, p["wk"].astype(dt))
    v1 = jnp.einsum("bsd,dkh->bskh", x, p["wv"].astype(dt))
    q = _rope4(q, qpos, cfg.rope_theta)
    k1 = L.apply_rope(k1, qpos, cfg.rope_theta)
    b = jnp.arange(B, dtype=jnp.int32)[:, None]
    slot = jnp.where(valid, qpos, lp)  # invalid -> out of range -> dropped
    ckp = rows["k"].at[b, slot].set(k1.astype(rows["k"].dtype), mode="drop")
    cvp = rows["v"].at[b, slot].set(v1.astype(rows["v"].dtype), mode="drop")
    # canonical true-position read: axis col t IS true position t; valid up
    # to the row's last query this chunk, stale/future columns masked
    lp_idx = jnp.arange(lp, dtype=jnp.int32)
    kpos = jnp.where(lp_idx[None, :] <= qpos[:, -1:], lp_idx[None, :], -1)
    kh, g, hd = q.shape[2], q.shape[3], q.shape[4]
    o = L.attention_dense(
        q.reshape(B, C, kh * g, hd), ckp, cvp, qpos, kpos,
        causal=True, window=0
    )
    out = jnp.einsum("bskgh,kghd->bsd", o.reshape(B, C, kh, g, hd),
                     p["wo"].astype(dt))
    return out, {"k": ckp, "v": cvp}


def prefill_chunk_into_slots(params, tokens, pool_cache, start, slot_ids,
                             cfg: ModelConfig, *, pos_offset, lp: int):
    """Run ONE chunk of a left-padded prompt bucket for the pool rows
    `slot_ids` and extend their KV in place (see the chunked-prefill module
    comment above).

    tokens: [R, C] — call row i is pool row slot_ids[i]; its columns are
    padded prompt positions start[i] .. start[i]+C-1. `start` is PER ROW
    ([R] int32, traced), so one call advances rows of several in-flight
    chunked admissions of this (C, lp) class at once, each at its own chunk
    position. lp: the class's padded prompt bucket, a STATIC shape (the
    executable key is (C, lp)). The program gathers the ring prefix
    [0, lp) of the call's rows from the pool, runs the chunk over it, and
    scatters it back with the pool donated: pool rows not in slot_ids are
    neither read nor written. pos_offset: [R] left-pad amounts. Padding
    rows of a call carry a slot id >= max_slots (gathered as zeros, their
    write dropped) and the offset lp with start 0, which makes every
    column's true position negative: embeddings zeroed, attention fully
    masked.

    Returns (greedy next token [R, 1] int32 from the LAST column's logits —
    meaningful only for rows on their bucket's final chunk, where column
    lp-1 is the row's last true prompt position — and the new pool)."""
    if not supports_chunked_prefill(cfg):
        raise ValueError(
            f"chunked prefill unsupported for {cfg.name} "
            "(see lm.supports_chunked_prefill)"
        )
    dt = _cdt(cfg)
    C = tokens.shape[1]
    sids = jnp.asarray(slot_ids, jnp.int32)
    off = pos_offset.astype(jnp.int32)
    qpos = (jnp.asarray(start, jnp.int32)[:, None]
            + jnp.arange(C, dtype=jnp.int32)[None, :]) - off[:, None]
    valid = qpos >= 0
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    x = x * valid[..., None].astype(dt)
    pre_kinds, body_kinds = _kinds_for(cfg)

    def rows_of(tree, ax):
        """The call's rows of a cache subtree, ring prefix [0, lp) only
        (ax: the slot axis, 1 for the stacked body)."""
        idx = (slice(None),) * ax + (sids, slice(0, lp))
        return jax.tree.map(
            lambda c: c.at[idx].get(mode="fill", fill_value=0), tree)

    def put_rows(tree, rows, ax):
        idx = (slice(None),) * ax + (sids, slice(0, lp))
        return jax.tree.map(
            lambda c, r: c.at[idx].set(r.astype(c.dtype), mode="drop"),
            tree, rows)

    def sub_step(x, sub, rows):
        h = L.rmsnorm(x, sub["mixer"]["ln"], cfg.norm_eps)
        o, nrows = _attn_chunk(h, sub["mixer"], cfg, rows, qpos, valid, lp)
        x = x + o
        x, _ = _ffn_forward(x, sub, cfg, ("attn", "mlp"))
        return x, nrows

    new_cache: Dict[str, Any] = {}
    if pre_kinds:
        new_prefix = {}
        for i in range(len(pre_kinds)):
            mix = pool_cache["prefix"][f"l{i}"]["mixer"]
            x, nr = sub_step(x, params["prefix"][f"l{i}"], rows_of(mix, 0))
            new_prefix[f"l{i}"] = {"mixer": put_rows(mix, nr, 0)}
        new_cache["prefix"] = new_prefix

    body = {f"l{li}": pool_cache["body"][f"l{li}"]["mixer"]
            for li in range(len(body_kinds))}

    def block_fn(x, xs):
        bp, rows = xs
        nrs = {}
        for li in range(len(body_kinds)):
            x, nrs[f"l{li}"] = sub_step(x, bp[f"l{li}"], rows[f"l{li}"])
        return x, nrs

    x, new_rows = jax.lax.scan(block_fn, x, (params["body"], rows_of(body, 1)))
    new_cache["body"] = {k: {"mixer": put_rows(body[k], new_rows[k], 1)}
                         for k in body}
    x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    logits = logits_from_hidden(params, x[:, -1:], cfg)
    return jnp.argmax(logits, -1).astype(jnp.int32), new_cache


# ---------------------------------------------------------------------------
# Forward pieces
# ---------------------------------------------------------------------------


def _cdt(cfg):
    return jnp.dtype(cfg.dtype)


def _attn_forward(x, p, cfg, *, causal=True, window=0, pos0=0, kv_x=None, kpos=None,
                  make_cache=False, cache_len=0, pos_offset=None):
    """Self- or cross-attention sublayer (pre-norm residual added by caller).

    x: [B,S,D] normed input; kv_x: encoder output for cross-attn (no rope).
    pos_offset: [B] int32 left-pad amounts for ragged serving batches — row b's
    token at padded index j has true position j - pos_offset[b]; negative
    positions are padding, masked out of attention (and the emitted cache
    slots carry invalid positions for decode).
    Returns (out, cache_entry|None).
    """
    dt = x.dtype
    B, S, D = x.shape
    q = jnp.einsum("bsd,dkgh->bskgh", x, p["wq"].astype(dt))
    src = kv_x if kv_x is not None else x
    k = jnp.einsum("bsd,dkh->bskh", src, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dkh->bskh", src, p["wv"].astype(dt))
    q = L.constrain_batch_dp(q, cfg.attn_dp_axes)
    k = L.constrain_batch_dp(k, cfg.attn_dp_axes)
    v = L.constrain_batch_dp(v, cfg.attn_dp_axes)
    if kv_x is None:
        qpos = pos0 + jnp.arange(S, dtype=jnp.int32)
        if pos_offset is not None:
            qpos = qpos[None, :] - pos_offset[:, None].astype(jnp.int32)
        kpos_ = qpos
        q = _rope4(q, qpos, cfg.rope_theta)
        k = L.apply_rope(k, qpos, cfg.rope_theta)
        if pos_offset is not None:
            # Canonical TRUE-POSITION presentation for serving prefill:
            # shift each row left by its pad amount so axis col t holds the
            # roped key/value at true position t (cols >= true length are
            # zero, kpos -1). This is the same layout the slot pool stores
            # and _attn_chunk reads, so chunked resume stays bit-identical
            # to monolithic admission — and because the layout no longer
            # encodes the row's left-pad offset, K/V bits at position t
            # depend on (tokens[0..t], S) only: the prefix-shareability
            # invariant behind the radix prefix cache. The shifted tensors
            # double as the emitted cache below (one gather, not two).
            off = pos_offset[:, None].astype(jnp.int32)
            s_idx = jnp.arange(S, dtype=jnp.int32)
            gi = s_idx[None, :] + off
            keep = (gi < S)[..., None, None]
            gidx = jnp.minimum(gi, S - 1)

            def _to_true(a):
                g = jnp.take_along_axis(
                    a, jnp.broadcast_to(gidx[..., None, None], a.shape), axis=1
                )
                return jnp.where(keep, g, jnp.zeros((), a.dtype))

            k, v = _to_true(k), _to_true(v)
            kpos_ = jnp.where(gi < S, s_idx[None, :], -1)
    else:
        qpos = jnp.arange(S, dtype=jnp.int32)
        if pos_offset is not None:
            qpos = qpos[None, :] - pos_offset[:, None].astype(jnp.int32)
        kpos_ = kpos if kpos is not None else jnp.arange(k.shape[1], dtype=jnp.int32)
    kh, g, hd = q.shape[2], q.shape[3], q.shape[4]
    qf = q.reshape(B, S, kh * g, hd)
    o = L.attention(
        qf, k, v, qpos, kpos_, causal=(causal and kv_x is None), window=window, pos0=pos0
    )
    o = L.constrain_batch_dp(o.reshape(B, S, kh, g, hd), cfg.attn_dp_axes)
    out = jnp.einsum("bskgh,kghd->bsd", o, p["wo"].astype(dt))
    cache = None
    if make_cache:
        if kv_x is not None:
            cache = {"ck": k, "cv": v}
        else:
            wc = ring_len(cfg, cache_len)
            if wc >= S:
                # decode headroom: slots S..wc-1 stay empty (ring positions
                # j - wc < 0 => masked invalid until decode writes them)
                # with pos_offset the serving read above already shifted k/v
                # to TRUE-POSITION layout (cache slot t = token at true
                # position t, slot >= true length zero), so the emitted
                # cache is a plain pad — decode reads/writes the same axis
                # layout as an unpadded per-request cache (see _attn_decode)
                ck = jnp.pad(k, ((0, 0), (0, wc - S), (0, 0), (0, 0))).astype(dt)
                cv = jnp.pad(v, ((0, 0), (0, wc - S), (0, 0), (0, 0))).astype(dt)
            else:
                if pos_offset is not None:
                    raise ValueError(
                        "pos_offset with a sliding-window ring smaller than "
                        "the padded prompt is unsupported (size the ring to "
                        "cover the prompt bucket)"
                    )
                slots = jnp.arange(S - wc, S, dtype=jnp.int32) % wc
                ck = jnp.zeros((B, wc, k.shape[2], hd), dt).at[:, slots].set(k[:, S - wc :])
                cv = jnp.zeros((B, wc, k.shape[2], hd), dt).at[:, slots].set(v[:, S - wc :])
            cache = {"k": ck, "v": cv}
    return out, cache


def _rope4(q, pos, theta):
    """RoPE on [B,S,KH,G,D] (factored GQA heads)."""
    b, s, kh, g, d = q.shape
    out = L.apply_rope(q.reshape(b, s, kh * g, d), pos, theta)
    return out.reshape(b, s, kh, g, d)


def _attn_decode(x, p, cfg, cache, pos, pos_offset=None):
    """Single-token attention. x: [B,1,D]; cache: {'k','v'} ring buffers.

    `pos` is the scalar *padded* write position; with pos_offset [B] the
    cache is TRUE-POSITION indexed per row: row b's step writes ring slot
    (pos - offset_b) mod wc, so slot t always holds the row's token at true
    position t (within the live window), exactly like an unpadded
    per-request cache. That axis alignment — not just the masking — is what
    makes slot-pool / padded decode bit-identical to isolated decode: XLA's
    blocked reductions pair softmax/PV summands by axis placement, so a
    clock-rotated layout (the old shared-ring-slot scheme) wobbled logits in
    the last ulp whenever a row's window wrapped the ring boundary, and
    occasionally flipped an argmax (regression: tests/test_engine_hotpath
    .py::test_continuous_admission_near_ring_wrap_is_bit_identical).
    Validity needs no slot bookkeeping: within [0, qpos] every slot is the
    row's own most recent write (a row's live span never exceeds wc, by pool
    sizing), and anything past qpos — stale epochs, admission-pad zeros,
    unwritten slots — is cut by the causal mask, while window rings keep the
    exact wrapped-position semantics via per-row ring_slot_positions.
    """
    dt = x.dtype
    B = x.shape[0]
    q = jnp.einsum("bsd,dkgh->bskgh", x, p["wq"].astype(dt))
    k1 = jnp.einsum("bsd,dkh->bskh", x, p["wk"].astype(dt))
    v1 = jnp.einsum("bsd,dkh->bskh", x, p["wv"].astype(dt))
    wc = cache["k"].shape[1]
    wc_idx = jnp.arange(wc, dtype=jnp.int32)
    if pos_offset is None:
        qpos = pos[None].astype(jnp.int32)
        slot_pos = L.ring_slot_positions(pos, wc)
        kpos = jnp.where(slot_pos >= 0, slot_pos, -1)
    else:
        off = pos_offset.astype(jnp.int32)
        qpos = (pos - off)[:, None]                      # [B,1] true positions
        # per-row true-position ring: slot t holds the most recent true
        # position <= qpos congruent to t (mod wc); negatives are invalid
        kpos = qpos - jnp.mod(qpos - wc_idx[None, :], wc)  # [B, wc]
        kpos = jnp.where(kpos >= 0, kpos, -1)
    q = _rope4(q, qpos, cfg.rope_theta)
    k1 = L.apply_rope(k1, qpos, cfg.rope_theta)
    if pos_offset is None:
        idx = (pos % wc).astype(jnp.int32)
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k1.astype(cache["k"].dtype), idx, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v1.astype(cache["v"].dtype), idx, 1)
    else:
        # per-row slot write as a dense select (not a scatter): XLA keeps
        # the donated cache update in-place inside the segment scan, where a
        # gather/scatter would copy the pool every step
        widx = jnp.mod(pos - off, wc).astype(jnp.int32)  # [B] per-row slots
        hit = (wc_idx[None, :] == widx[:, None])[:, :, None, None]
        ck = jnp.where(hit, k1.astype(cache["k"].dtype), cache["k"])
        cv = jnp.where(hit, v1.astype(cache["v"].dtype), cache["v"])
    kh, g, hd = q.shape[2], q.shape[3], q.shape[4]
    o = L.attention_dense(
        q.reshape(B, 1, kh * g, hd), ck, cv, qpos, kpos, causal=True, window=0
    )
    out = jnp.einsum("bskgh,kghd->bsd", o.reshape(B, 1, kh, g, hd), p["wo"].astype(dt))
    return out, {"k": ck, "v": cv}


def _xattn_decode(x, p, cfg, cache):
    dt = x.dtype
    B = x.shape[0]
    q = jnp.einsum("bsd,dkgh->bskgh", x, p["wq"].astype(dt))
    ck, cv = cache["ck"], cache["cv"]
    kh, g, hd = q.shape[2], q.shape[3], q.shape[4]
    kpos = jnp.arange(ck.shape[1], dtype=jnp.int32)
    qpos = jnp.zeros((1,), jnp.int32)
    o = L.attention_dense(
        q.reshape(B, 1, kh * g, hd), ck, cv, qpos, kpos, causal=False, window=0
    )
    out = jnp.einsum("bskgh,kghd->bsd", o.reshape(B, 1, kh, g, hd), p["wo"].astype(dt))
    return out


def _ffn_forward(x, sub, cfg, kind):
    _, ffn = kind
    if ffn == "none":
        return x, 0.0
    h = L.rmsnorm(x, sub["ffn"]["ln"], cfg.norm_eps)
    if ffn == "moe":
        if cfg.moe_shard_constraints and cfg.moe_group_axes:
            # explicit batch->'data' reshard at MoE entry: GSPMD otherwise
            # lowers the (data,model)->(data) transition at the shard_map
            # boundary as permute+all-reduce chains (~480 GiB on mixtral)
            from jax.sharding import PartitionSpec as P

            h = jax.lax.with_sharding_constraint(
                h, P(tuple(cfg.moe_group_axes), None, None)
            )
        y, aux = L.moe(h, sub["ffn"], cfg)
        return x + y, aux
    return x + L.mlp(h, sub["ffn"]), 0.0


def _sublayer_forward(x, sub, cfg, kind, *, enc_out, mode, cache_len, pos_offset=None):
    """Full-sequence sublayer. Returns (x, aux, cache_entry)."""
    mixer, _ = kind
    cache_entry: Dict[str, Any] = {}
    make_cache = mode == "prefill"
    if mixer == "attn":
        h = L.rmsnorm(x, sub["mixer"]["ln"], cfg.norm_eps)
        o, c = _attn_forward(
            h, sub["mixer"], cfg, causal=True, window=cfg.sliding_window,
            make_cache=make_cache, cache_len=cache_len, pos_offset=pos_offset,
        )
        x = x + o
        if make_cache:
            cache_entry["mixer"] = c
    else:
        h = L.rmsnorm(x, sub["mixer"]["ln"], cfg.norm_eps)
        if make_cache:
            o, (conv_tail, fstate) = L.ssm_block(
                h, sub["mixer"], cfg, return_state=True, pos_offset=pos_offset
            )
            cache_entry["mixer"] = {"conv": conv_tail, "state": fstate}
        else:
            o = L.ssm_block(h, sub["mixer"], cfg, pos_offset=pos_offset)
        x = x + o
    if "xattn" in sub:
        h = L.rmsnorm(x, sub["xattn"]["ln"], cfg.norm_eps)
        o, c = _attn_forward(
            h, sub["xattn"], cfg, causal=False, kv_x=enc_out, make_cache=make_cache,
            pos_offset=pos_offset,
        )
        x = x + o
        if make_cache:
            cache_entry["xattn"] = c
    x, aux = _ffn_forward(x, sub, cfg, kind)
    return x, aux, cache_entry


def _sublayer_decode(x, sub, cache_sub, cfg, kind, pos, pos_offset=None):
    mixer, _ = kind
    new_cache: Dict[str, Any] = {}
    if mixer == "attn":
        h = L.rmsnorm(x, sub["mixer"]["ln"], cfg.norm_eps)
        o, c = _attn_decode(h, sub["mixer"], cfg, cache_sub["mixer"], pos, pos_offset)
        x = x + o
        new_cache["mixer"] = c
    else:
        h = L.rmsnorm(x, sub["mixer"]["ln"], cfg.norm_eps)
        o, (conv, state) = L.ssm_block_decode(
            h, sub["mixer"], cfg, cache_sub["mixer"]["conv"], cache_sub["mixer"]["state"]
        )
        x = x + o
        new_cache["mixer"] = {"conv": conv, "state": state}
    if "xattn" in sub:
        h = L.rmsnorm(x, sub["xattn"]["ln"], cfg.norm_eps)
        x = x + _xattn_decode(h, sub["xattn"], cfg, cache_sub["xattn"])
        new_cache["xattn"] = cache_sub["xattn"]
    x, _ = _ffn_forward(x, sub, cfg, kind)
    return x, new_cache


# ---------------------------------------------------------------------------
# Whisper encoder
# ---------------------------------------------------------------------------


def encode_audio(params, audio_frames, cfg):
    """audio_frames: [B, n_audio_ctx, d_model] stub embeddings (post-conv)."""
    x = audio_frames.astype(_cdt(cfg))
    enc = params["encoder"]

    def body(x, bp):
        h = L.rmsnorm(x, bp["mixer"]["ln"], cfg.norm_eps)
        o, _ = _attn_forward(h, bp["mixer"], cfg, causal=False)
        x = x + o
        h = L.rmsnorm(x, bp["ffn"]["ln"], cfg.norm_eps)
        x = x + L.mlp(h, bp["ffn"])
        return x, None

    x, _ = jax.lax.scan(body, x, enc["blocks"])
    return L.rmsnorm(x, enc["ln"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Top-level forward / decode
# ---------------------------------------------------------------------------


def _kinds_for(cfg):
    kinds = cfg.layer_kinds()
    p = block_period(cfg)
    npre = cfg.first_k_dense
    return kinds[:npre], tuple(kinds[npre : npre + p])


def forward(params, tokens, cfg: ModelConfig, *, mode: str = "train",
            img_embeds=None, audio_frames=None, cache_len: int = 0,
            pos_offset=None):
    """mode: 'train' -> (hidden, aux); 'prefill' -> (hidden_last, cache).

    pos_offset: optional [B] int32 left-pad amounts (bucketed serving): row b's
    first pos_offset[b] token slots are padding. Their embeddings are zeroed
    and they are masked out of attention/SSM state, so each row computes
    exactly what it would at its true length (padding slots stay identically
    zero through every layer).
    """
    assert mode in ("train", "prefill")
    if pos_offset is not None and cfg.n_img_tokens and img_embeds is not None:
        raise ValueError(
            "pos_offset (left-padded bucketing) is not supported with image "
            "prefixes: the left-pad mask would zero the leading img_embeds "
            "slots. Pad such batches on the right by length bucket instead."
        )
    dt = _cdt(cfg)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    if cfg.n_img_tokens and img_embeds is not None:
        n = cfg.n_img_tokens
        x = jnp.concatenate([img_embeds.astype(dt), x[:, n:]], axis=1)
    if pos_offset is not None:
        valid = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :] >= (
            pos_offset[:, None].astype(jnp.int32)
        )
        x = x * valid[..., None].astype(dt)
    enc_out = None
    if cfg.enc_layers:
        enc_out = encode_audio(params, audio_frames, cfg)

    pre_kinds, body_kinds = _kinds_for(cfg)
    aux = jnp.zeros((), jnp.float32)
    prefix_cache: Dict[str, Any] = {}
    for i, kind in enumerate(pre_kinds):
        sub = params["prefix"][f"l{i}"]
        x, a, ce = _sublayer_forward(
            x, sub, cfg, kind, enc_out=enc_out, mode=mode, cache_len=cache_len,
            pos_offset=pos_offset,
        )
        aux = aux + a
        if mode == "prefill":
            prefix_cache[f"l{i}"] = ce

    def _make_sub(kind):
        def sub_fn(x, sub, enc):
            return _sublayer_forward(
                x, sub, cfg, kind, enc_out=enc, mode=mode, cache_len=cache_len,
                pos_offset=pos_offset,
            )

        if cfg.remat and mode == "train":
            # Per-sublayer remat: hybrid blocks hold several MoE sublayers
            # per scan iteration; without this the backward keeps all their
            # dispatched-slot tensors alive at once (jamba: ~90 GB/chip).
            return jax.checkpoint(sub_fn, prevent_cse=False)
        return sub_fn

    sub_fns = [_make_sub(kind) for kind in body_kinds]

    def block_fn(carry, bp):
        x, aux = carry
        cache_block = {}
        for i, kind in enumerate(body_kinds):
            x, a, ce = sub_fns[i](x, bp[f"l{i}"], enc_out)
            aux = aux + a
            cache_block[f"l{i}"] = ce
        # Remat saves the scan carry per block; constraining it to
        # batch x (all mesh axes) shards the saved activations 256-way
        # instead of 16-way (yi train_4k: 56 GB -> 3.5 GB per chip).
        x = L.constrain_batch_dp(x, cfg.attn_dp_axes)
        return (x, aux), (cache_block if mode == "prefill" else None)

    body = jax.checkpoint(block_fn, prevent_cse=False) if (cfg.remat and mode == "train") else block_fn
    (x, aux), body_cache = jax.lax.scan(body, (x, aux), params["body"])
    x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)

    if mode == "train":
        return x, aux
    cache = {}
    if pre_kinds:
        cache["prefix"] = prefix_cache
    cache["body"] = body_cache
    return x, cache


def logits_from_hidden(params, x, cfg):
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "bsd,vd->bsv", x, params["embed"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
    else:
        logits = jnp.einsum(
            "bsd,dv->bsv", x, params["lm_head"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
    return logits[..., : cfg.vocab]  # strip sharding-pad vocab slots


def decode(params, cache, tokens, pos, cfg: ModelConfig, *, pos_offset=None):
    """One decode step. tokens: [B,1] int32; pos: scalar int32 (current
    absolute *padded* position being written); pos_offset: optional [B] int32
    left-pad amounts (row b's true position is pos - pos_offset[b]).
    Returns (logits [B,1,V], new_cache)."""
    dt = _cdt(cfg)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    pre_kinds, body_kinds = _kinds_for(cfg)

    new_cache: Dict[str, Any] = {}
    if pre_kinds:
        new_prefix = {}
        for i, kind in enumerate(pre_kinds):
            x, nc = _sublayer_decode(
                x, params["prefix"][f"l{i}"], cache["prefix"][f"l{i}"], cfg, kind,
                pos, pos_offset,
            )
            new_prefix[f"l{i}"] = nc
        new_cache["prefix"] = new_prefix

    # The body cache rides in the scan *carry* and is updated in place with
    # dynamic_update_index (scan xs->ys would double-buffer the whole KV
    # cache: +2 copies, e.g. +16 GB/chip on yi decode_32k).
    nb = jax.tree.leaves(params["body"])[0].shape[0]

    def block_fn(carry, xs):
        x, cbody = carry
        bp, i = xs
        cb = jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, keepdims=False), cbody
        )
        ncb = {}
        for li, kind in enumerate(body_kinds):
            x, nc = _sublayer_decode(
                x, bp[f"l{li}"], cb[f"l{li}"], cfg, kind, pos, pos_offset
            )
            ncb[f"l{li}"] = nc
        cbody = jax.tree.map(
            lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, i, 0), cbody, ncb
        )
        return (x, cbody), None

    (x, new_body), _ = jax.lax.scan(
        block_fn, (x, cache["body"]), (params["body"], jnp.arange(nb))
    )
    new_cache["body"] = new_body
    x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    return logits_from_hidden(params, x, cfg), new_cache


def prefill(params, tokens, cfg: ModelConfig, *, img_embeds=None, audio_frames=None,
            pos_offset=None, cache_len: Optional[int] = None):
    """Full-sequence prefill. Returns (last-position logits [B,1,V], cache).

    pos_offset: [B] left-pad amounts for ragged bucketed batches (see forward).
    cache_len: total KV-cache slots to allocate; pass prompt_len + max_new_tokens
    so the decode ring never wraps over live prompt slots. Defaults to the
    prompt length (legacy behavior, headroom-free).
    """
    x, cache = forward(
        params, tokens, cfg, mode="prefill",
        img_embeds=img_embeds, audio_frames=audio_frames,
        cache_len=cache_len if cache_len is not None else tokens.shape[1],
        pos_offset=pos_offset,
    )
    logits = logits_from_hidden(params, x[:, -1:], cfg)
    return logits, cache


def generate(params, cache, last_logits, pos0: int, cfg: ModelConfig, *,
             steps: int, pos_offset=None):
    """Greedy-decode `steps` tokens as one fused `lax.scan` (compile-once
    serving hot path): no per-step host sync, no per-step dispatch, and —
    when the caller jits with the cache donated — no per-step cache copies.

    last_logits: [B,1,V] prefill output; pos0: first padded write position
    (the padded prompt length). Returns (tokens [B, steps] int32, final cache);
    tokens are bit-identical to argmax(last_logits) followed by steps-1
    sequential decode() calls. The final cache is returned so a donated input
    cache has an output to alias with (true in-place update, zero copies).
    """
    tok0 = jnp.argmax(last_logits, -1).astype(jnp.int32)  # [B,1]
    if steps == 1:
        return tok0, cache
    rest, cache = decode_segment(
        params, cache, tok0, pos0, cfg, steps=steps - 1, pos_offset=pos_offset
    )
    return jnp.concatenate([tok0, rest], axis=1), cache


def decode_segment(params, cache, tok, pos0, cfg: ModelConfig, *,
                   steps: int, pos_offset=None):
    """Segment mode of the fused generate scan (continuous batching): greedy-
    decode `steps` tokens starting *after* the last emitted token `tok`
    [B, 1], as one jitted lax.scan. Between segments the caller may retire
    finished rows and admit new requests into free slots (prefill_into_slots)
    — the segment executable itself never changes shape, so steady-state
    serving stays at two traced programs (one prefill bucket + one segment).

    pos0: the shared padded write position of the first decoded step (the
    slot-pool clock); pos_offset: [B] per-slot offsets (true position =
    padded position - offset). Returns (tokens [B, steps] int32, new cache);
    chaining segments is bit-identical to one longer segment or to the
    sequential decode() loop."""

    def step(carry, _):
        c, t, pos = carry
        logits, c = decode(params, c, t, pos, cfg, pos_offset=pos_offset)
        ntok = jnp.argmax(logits, -1).astype(jnp.int32)
        return (c, ntok, pos + 1), ntok

    (cache, _, _), toks = jax.lax.scan(
        step, (cache, tok, jnp.asarray(pos0, jnp.int32)), length=steps
    )
    # toks: [steps, B, 1] -> [B, steps]
    return jnp.moveaxis(toks[..., 0], 0, 1), cache


# ---------------------------------------------------------------------------
# Loss (chunked over sequence to bound logits memory)
# ---------------------------------------------------------------------------


def softmax_xent_chunked(params, x, labels, cfg, chunk: int = 2048):
    """x: [B,S,D] final hidden; labels int32 [B,S] (-100 = ignore).
    Computes CE + z-loss scanning over sequence chunks (logits for a 163k
    vocab at 1M tokens would otherwise need ~0.7 TB)."""
    B, S, D = x.shape
    c = min(chunk, S)
    while S % c:
        c -= 1
    nc = S // c
    xs = jnp.moveaxis(x.reshape(B, nc, c, D), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, nc, c), 1, 0)

    def body(carry, inp):
        nll_sum, z_sum, count = carry
        xc, lc = inp
        logits = logits_from_hidden(params, xc, cfg)  # fp32 [B,c,V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1
        )[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        nll = (lse - gold) * mask
        return (
            nll_sum + jnp.sum(nll),
            z_sum + jnp.sum(lse * lse * mask),
            count + jnp.sum(mask),
        ), None

    body = jax.checkpoint(body, prevent_cse=False)
    (nll_sum, z_sum, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32),) * 3, (xs, ls)
    )
    count = jnp.maximum(count, 1.0)
    return nll_sum / count, z_sum / count


def train_loss(params, batch, cfg: ModelConfig, z_loss_weight: float = 1e-4):
    x, aux = forward(
        params, batch["tokens"], cfg, mode="train",
        img_embeds=batch.get("img_embeds"), audio_frames=batch.get("audio_frames"),
    )
    nll, z2 = softmax_xent_chunked(params, x, batch["labels"], cfg)
    loss = nll + z_loss_weight * z2 + aux
    return loss, {"loss": loss, "nll": nll, "aux": aux}
