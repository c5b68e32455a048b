"""Layer primitives: norms, RoPE, attention (dense / blockwise-online-softmax /
sliding-window-banded / decode), gated MLP, GShard MoE (einsum baseline +
sort-based variant), causal depthwise conv, Mamba2 SSD (chunked) + single-step.

All functions are pure; parameters arrive as dict subtrees. Compute dtype is
the activation dtype; softmax/statistics accumulate in fp32.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def constrain_batch_dp(x: jax.Array, axes) -> jax.Array:
    """Constrain dim0 (batch) to shard over `axes` (e.g. ('data','model')) so
    attention score compute is pure-DP across the whole mesh — sidesteps
    head-count divisibility and removes model-axis redundancy (DESIGN.md §4).
    No-op when axes is empty (requires an active mesh context otherwise)."""
    if not axes:
        return x
    from jax.sharding import PartitionSpec as P

    spec = P(tuple(axes), *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def rmsnorm_gated(x: jax.Array, z: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Mamba2-style gated RMSNorm: norm(x * silu(z)) * w."""
    return rmsnorm(x * jax.nn.silu(z.astype(x.dtype)), w, eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def apply_rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D]; pos: int32 [S] absolute positions, or [B, S] per-row
    positions (ragged left-padded serving batches)."""
    d = x.shape[-1]
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    freqs = pos.astype(jnp.float32)[..., :, None] * inv  # [S, half] or [B, S, half]
    if freqs.ndim == 2:
        freqs = freqs[None]
    cos = jnp.cos(freqs)[:, :, None, :]
    sin = jnp.sin(freqs)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x1f * sin + x2f * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def ring_slot_positions(pos: jax.Array, wc: int) -> jax.Array:
    """Position of the most recent write to each KV ring slot: slot j holds
    position pos - ((pos - j) mod wc), the largest value <= pos congruent to
    j (mod wc); negative values (slots not yet written, or other epochs'
    stale data) are masked invalid. Used by the shared-position decode ring
    (lm._attn_decode, pos_offset=None); slot-pool rows use the same formula
    per row in TRUE coordinates (qpos - mod(qpos - j, wc)) — each row's
    cache is true-position indexed, so slot t of a live row is its own token
    at position t and the layout is independent of the admission clock."""
    j = jnp.arange(wc, dtype=jnp.int32)
    return pos - jnp.mod(pos - j, wc)


def _pair_mask(qpos: jax.Array, kpos: jax.Array, causal: bool, window: int) -> jax.Array:
    """[Sq, Skv] bool mask (or [B, Sq, Skv] when either pos is per-row [B, S]).
    Negative positions mark invalid slots: kpos < 0 excludes a cache slot,
    qpos < 0 fully masks a padding query row."""
    q = qpos[..., :, None]
    k = kpos[..., None, :]
    m = (k >= 0) & (q >= 0)
    if causal:
        m = m & (q >= k)
    if window:
        m = m & ((q - k) < window)
    return m


def _batch_mask(mask: jax.Array) -> jax.Array:
    """Normalize a _pair_mask result to [B|1, 1, 1, Sq, Skv] for [B,KH,G,Sq,Skv]
    score tensors."""
    if mask.ndim == 2:
        mask = mask[None]
    return mask[:, None, None]


def attention_dense(q, k, v, qpos, kpos, *, causal=True, window=0):
    """Direct-softmax attention. q: [B,Sq,H,D]; k,v: [B,Skv,KH,D] (GQA)."""
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    qg = q.reshape(B, Sq, KH, G, D)
    s = jnp.einsum("bqkgd,bjkd->bkgqj", qg, k, preferred_element_type=jnp.float32)
    s = s * (1.0 / math.sqrt(D))
    mask = _batch_mask(_pair_mask(qpos, kpos, causal, window))
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked query rows (left-pad slots) emit exactly 0, not a uniform
    # average; a no-op elsewhere since masked probs are already exactly 0
    p = jnp.where(mask, p, 0.0)
    o = jnp.einsum("bkgqj,bjkd->bqkgd", p.astype(v.dtype), v)
    return o.reshape(B, Sq, H, D)


def attention_blockwise(q, k, v, qpos, kpos, *, causal=True, window=0, kv_block=512):
    """Online-softmax (flash-style) attention via lax.scan over KV blocks.

    Peak memory is O(Sq * kv_block) scores instead of O(Sq * Skv); this is
    what lets 32k prefill fit in HBM (DESIGN.md §4).
    """
    B, Sq, H, D = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    G = H // KH
    if Skv % kv_block:
        pad = kv_block - Skv % kv_block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(
            kpos, [(0, 0)] * (kpos.ndim - 1) + [(0, pad)], constant_values=-1
        )
        Skv += pad
    nb = Skv // kv_block
    qg = (q.reshape(B, Sq, KH, G, D)).astype(jnp.float32)
    ks = jnp.moveaxis(k.reshape(B, nb, kv_block, KH, D), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, nb, kv_block, KH, D), 1, 0)
    if kpos.ndim == 2:  # per-row positions: scan over [nb, B, kv_block]
        kps = jnp.moveaxis(kpos.reshape(B, nb, kv_block), 1, 0)
    else:
        kps = kpos.reshape(nb, kv_block)
    scale = 1.0 / math.sqrt(D)

    m0 = jnp.full((B, KH, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KH, G, Sq, D), jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, kp = xs
        s = jnp.einsum("bqkgd,bjkd->bkgqj", qg, kb.astype(jnp.float32)) * scale
        mask = _batch_mask(_pair_mask(qpos, kp, causal, window))
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)  # fully-masked pad queries stay exactly 0
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqj,bjkd->bkgqd", p, vb.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    # Nested remat: without it the backward pass stacks per-KV-block scores
    # across the scan ([nb,B,KH,G,Sq,blk] f32 — ~20 GB/chip on yi train_4k).
    body = jax.checkpoint(body, prevent_cse=False)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, kps))
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B,KH,G,Sq,D]
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, D)
    return out.astype(q.dtype)


def attention_swa_banded(q, k, v, pos0: int, window: int, *, kv_block=512):
    """Sliding-window attention with banded blocking: each W-sized query block
    attends only to its own and the previous key block => O(S*2W) not O(S^2).
    Requires S % window == 0. q,k,v: [B,S,{H|KH},D] aligned positions.
    """
    B, S, H, D = q.shape
    KH = k.shape[2]
    W = window
    assert S % W == 0, (S, W)
    nb = S // W
    qb = jnp.moveaxis(q.reshape(B, nb, W, H, D), 1, 0)
    kb = k.reshape(B, nb, W, KH, D)
    vb = v.reshape(B, nb, W, KH, D)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    kcat = jnp.moveaxis(jnp.concatenate([kprev, kb], axis=2), 1, 0)  # [nb,B,2W,KH,D]
    vcat = jnp.moveaxis(jnp.concatenate([vprev, vb], axis=2), 1, 0)
    blk_idx = jnp.arange(nb)

    def body(_, xs):
        qj, kj, vj, j = xs
        qpos = pos0 + j * W + jnp.arange(W)
        kpos = pos0 + (j - 1) * W + jnp.arange(2 * W)
        kpos = jnp.where(kpos >= pos0, kpos, -1)  # first block has no prev
        out = attention_blockwise(
            qj, kj, vj, qpos, kpos, causal=True, window=W, kv_block=min(kv_block, 2 * W)
        )
        return None, out

    _, outs = jax.lax.scan(body, None, (qb, kcat, vcat, blk_idx))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, D)


def attention(q, k, v, qpos, kpos, *, causal=True, window=0, pos0=0, kv_block=512):
    """Dispatcher: picks banded-SWA / blockwise / dense by shape. Per-row
    [B, S] positions (ragged serving batches) route to dense/blockwise, which
    handle batched masks; the banded path assumes shared positions."""
    Sq, Skv = q.shape[1], k.shape[1]
    shared_pos = qpos.ndim == 1 and kpos.ndim == 1
    if window and shared_pos and Sq == Skv and Sq % window == 0 and Sq // window >= 2 and causal:
        return attention_swa_banded(q, k, v, pos0, window, kv_block=kv_block)
    if Sq * Skv <= 4096 * 1024 or Sq == 1:
        return attention_dense(q, k, v, qpos, kpos, causal=causal, window=window)
    return attention_blockwise(q, k, v, qpos, kpos, causal=causal, window=window, kv_block=kv_block)


# ---------------------------------------------------------------------------
# Gated MLP
# ---------------------------------------------------------------------------


def mlp(x, p, *, act=jax.nn.silu):
    dt = x.dtype
    g = jnp.einsum("...d,df->...f", x, p["wi_gate"].astype(dt))
    u = jnp.einsum("...d,df->...f", x, p["wi_up"].astype(dt))
    return jnp.einsum("...f,fd->...d", act(g) * u, p["wo"].astype(dt))


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------


def _router(x2d, p, n_experts, top_k):
    """x2d: [T, D] -> (gate_vals [T,k], gate_idx [T,k], probs [T,E], aux)."""
    logits = jnp.einsum(
        "td,de->te", x2d.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    # load-balance aux (Switch): E * sum_e f_e * P_e
    E = n_experts
    f = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0)
    P = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * P)
    return gate_vals, gate_idx, probs, aux


def _expert_ffn(xe, p, cfg=None):
    """xe: [G, E, C, D] dispatched token slots (group dim G stays intact —
    reshaping it away would mix a sharded dim and force GSPMD to replicate).

    With cfg.moe_shard_constraints, pins the compute strategy GSPMD must use
    (it otherwise falls back to gathering FULL f32 expert weights per layer —
    ~90 GB/chip on jamba train_4k):
      * EP (E % data == 0): tokens all-to-all to expert shards (g replicated,
        e sharded over 'data'); weights stay put.
      * else: token groups stay data-sharded; weights are gathered over
        'data' only, ffn dim stays model-sharded (Megatron column/row pair).
    """
    dt = xe.dtype
    if cfg is not None and cfg.moe_shard_constraints:
        from jax.sharding import PartitionSpec as P

        con = jax.lax.with_sharding_constraint
        ep = cfg.moe_ep_axis or None
        wg = con(p["wi_gate"].astype(dt), P(ep, None, "model"))
        wu = con(p["wi_up"].astype(dt), P(ep, None, "model"))
        wo = con(p["wo"].astype(dt), P(ep, "model", None))
    else:
        wg = p["wi_gate"].astype(dt)
        wu = p["wi_up"].astype(dt)
        wo = p["wo"].astype(dt)
    g = jnp.einsum("gecd,edf->gecf", xe, wg)
    u = jnp.einsum("gecd,edf->gecf", xe, wu)
    return jnp.einsum("gecf,efd->gecd", jax.nn.silu(g) * u, wo)


def moe_gshard_einsum(x, p, cfg):
    """GShard-style grouped einsum dispatch with capacity (faithful baseline).

    x: [B, S, D]. Returns (y, aux_loss). Tokens beyond per-expert capacity in
    their group are dropped (residual passes through), capacity_factor 1.25.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    group = min(cfg.moe_group_size, T)
    G = T // group
    assert G * group == T, (T, group)

    # Staged sharding constraints (see _expert_ffn docstring): keep the
    # gate/dispatch math local to the token-group sharding, then perform a
    # single canonical reshard into the expert-compute layout. Without the
    # staging GSPMD falls back to full replication of xg (jamba train:
    # ~119 GB/chip of f32 token copies).
    ga = tuple(cfg.moe_group_axes) or None
    con = (
        jax.lax.with_sharding_constraint
        if (cfg.moe_shard_constraints and ga and G > 1)
        else (lambda t, s: t)
    )
    from jax.sharding import PartitionSpec as P

    xg = con(x.reshape(G, group, D), P(ga, None, None))
    gate_vals, gate_idx, _, aux = _router(xg.reshape(T, D), p, E, K)
    gate_vals = gate_vals.reshape(G, group, K)
    gate_idx = gate_idx.reshape(G, group, K)
    C = max(4, int(math.ceil(cfg.capacity_factor * group * K / E)))
    dispatch, wte = _dispatch_mask(gate_idx, gate_vals, E, C, x.dtype)
    combine = dispatch * wte[..., None].astype(x.dtype)

    xe = jnp.einsum("gtec,gtd->gecd", dispatch, xg)
    xe = con(xe, P(ga, None, None, None))  # dispatch product stays group-local
    if cfg.moe_ep_axis:
        xe = con(xe, P(None, cfg.moe_ep_axis, None, None))  # EP all-to-all
    else:
        xe = con(xe, P("data", None, None, None))
    ye = _expert_ffn(xe, p, cfg)
    ye = con(ye, P(ga, None, None, None))  # all-to-all back before combine
    y = jnp.einsum("gtec,gecd->gtd", combine, ye)
    return y.reshape(B, S, D), aux * cfg.router_aux_weight


def moe_sort(x, p, cfg):
    """Sort-based dispatch (beyond-paper §Perf): tokens are sorted by expert
    id and sliced into equal per-expert buffers; dispatch/combine one-hot
    matmuls are eliminated (gather/scatter only)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    x2 = x.reshape(T, D)
    gate_vals, gate_idx, _, aux = _router(x2, p, E, K)
    C = max(4, int(math.ceil(cfg.capacity_factor * T * K / E)))

    flat_e = gate_idx.reshape(-1)  # [T*K]
    order = jnp.argsort(flat_e, stable=True)
    tok_of = order // K  # token id feeding each sorted slot
    sorted_e = flat_e[order]
    # rank within expert = idx - first idx of that expert
    idx = jnp.arange(T * K)
    first = jnp.searchsorted(sorted_e, jnp.arange(E))  # [E]
    rank = idx - first[sorted_e]
    slot = sorted_e * C + rank
    ok = rank < C
    buf = jnp.zeros((E * C, D), x.dtype)
    buf = buf.at[jnp.where(ok, slot, E * C - 1)].set(
        jnp.where(ok[:, None], x2[tok_of], 0), mode="drop"
    )
    ye = _expert_ffn(buf.reshape(1, E, C, D), p, cfg).reshape(E * C, D)
    w = gate_vals.reshape(-1)[order].astype(x.dtype)
    contrib = jnp.where(ok[:, None], ye[slot] * w[:, None], 0)
    y = jnp.zeros((T, D), x.dtype).at[tok_of].add(contrib)
    return y.reshape(B, S, D), aux * cfg.router_aux_weight


def moe_shard_map(x, p, cfg, mesh):
    """Expert FFN with explicit collectives via shard_map (DESIGN.md §4).

    GSPMD's auto-partitioner repeatedly falls back to gathering FULL expert
    weight stacks for the GShard einsums under autodiff (jamba train: ~77-110
    GB/chip). shard_map makes the layout contract explicit:

      * EP mode (E % n_data == 0): weights stay [E/'data', D, F/'model'];
        dispatched token slots all-to-all over 'data' (g <-> e), expert
        compute local, psum over 'model' for the row-parallel wo.
      * weight-gather mode (mixtral, E=8 < 16): tokens stay put; the layer's
        weight shard is all-gathered over 'data' in bf16 (~100 MB) — gather
        placement is now ours, per-layer, never hoisted.
    """
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    n_data = mesh.shape["data"]
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    group = min(cfg.moe_group_size, T // n_dp)
    G = T // group
    ep = E % n_data == 0

    def local_fn(xl, router, wg, wu, wo):
        # xl: [G/n_dp, t, D]; router replicated; weights local shards.
        g_loc, t, _ = xl.shape
        gate_vals, gate_idx, _, aux = _router(
            xl.reshape(g_loc * t, D), {"router": router}, E, K
        )
        gate_vals = gate_vals.reshape(g_loc, t, K)
        gate_idx = gate_idx.reshape(g_loc, t, K)
        C = max(4, int(math.ceil(cfg.capacity_factor * t * K / E)))
        dispatch, wte = _dispatch_mask(gate_idx, gate_vals, E, C, xl.dtype)
        combine = dispatch * wte[..., None].astype(xl.dtype)
        xe = jnp.einsum("gtec,gtd->gecd", dispatch, xl)  # [g_loc, E, C, D]
        if ep:
            # tokens to expert shards: split E, concat g  -> [G, E/n, C, D]
            xe = jax.lax.all_to_all(xe, "data", split_axis=1, concat_axis=0, tiled=True)
            h1 = jnp.einsum("gecd,edf->gecf", xe, wg)
            h2 = jnp.einsum("gecd,edf->gecf", xe, wu)
            ye = jnp.einsum("gecf,efd->gecd", jax.nn.silu(h1) * h2, wo)
            # Row-parallel wo epilogue (§Perf B): reduce-SCATTER the capacity
            # tensor over d instead of psum'ing it whole (the full-ye psum was
            # ~8 GB/layer on moonshot prefill), send the d-shard back through
            # the a2a (16x smaller), combine locally, and all-gather only the
            # final [g,t,d] output.
            ye = jax.lax.psum_scatter(ye, "model", scatter_dimension=3, tiled=True)
            ye = jax.lax.all_to_all(ye, "data", split_axis=0, concat_axis=1, tiled=True)
        else:
            # gather the d-shard of this layer's weights (bf16, ~100 MB)
            wg_f = jax.lax.all_gather(wg, dp, axis=1, tiled=True)
            wu_f = jax.lax.all_gather(wu, dp, axis=1, tiled=True)
            wo_f = jax.lax.all_gather(wo, dp, axis=2, tiled=True)
            h1 = jnp.einsum("gecd,edf->gecf", xe, wg_f)
            h2 = jnp.einsum("gecd,edf->gecf", xe, wu_f)
            ye = jnp.einsum("gecf,efd->gecd", jax.nn.silu(h1) * h2, wo_f)
            ye = jax.lax.psum_scatter(ye, "model", scatter_dimension=3, tiled=True)
        y = jnp.einsum("gtec,gecd->gtd", combine, ye)  # [g_loc, t, d/16]
        y = jax.lax.all_gather(y, "model", axis=2, tiled=True)
        aux = jax.lax.pmean(aux, dp)
        return y, aux

    ep_spec = P("data", None, "model") if ep else P(None, dp, "model")
    ep_spec_o = P("data", "model", None) if ep else P(None, "model", dp)
    dt = x.dtype
    # on a mesh with Explicit axes the token<->group reshapes must name
    # their output sharding (rows split over the data axes); Auto axes
    # refuse one and let the partitioner choose
    explicit = any(
        t == jax.sharding.AxisType.Explicit
        for a, t in zip(mesh.axis_names, mesh.axis_types) if a in dp
    )
    rows = jax.sharding.NamedSharding(mesh, P(dp, None, None)) \
        if explicit else None
    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None), ep_spec, ep_spec, ep_spec_o),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(
        jnp.reshape(x, (G, group, D), out_sharding=rows),
        p["router"].astype(jnp.float32),
        p["wi_gate"].astype(dt),
        p["wi_up"].astype(dt),
        p["wo"].astype(dt),
    )
    y = jnp.reshape(y, (B, S, D), out_sharding=rows)
    return y, aux * cfg.router_aux_weight


def _dispatch_mask(gate_idx, gate_vals, E, C, dtype):
    """[g,t,K] top-k assignments -> ([g,t,E,C] 0/1 dispatch, [g,t,E] weights)."""
    g_loc, t, K = gate_idx.shape
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # [g,t,K,E]
    flat = jnp.moveaxis(onehot, 2, 1).reshape(g_loc, K * t, E)
    pos = jnp.cumsum(flat, axis=1) - flat
    keep = (pos < C) * flat
    pos = pos.reshape(g_loc, K, t, E)
    keep = keep.reshape(g_loc, K, t, E)
    dispatch = jnp.zeros((g_loc, t, E, C), dtype)
    for kk in range(K):
        disp_k = jax.nn.one_hot(pos[:, kk].astype(jnp.int32), C, dtype=dtype)
        dispatch = dispatch + disp_k * keep[:, kk][..., None].astype(dtype)
    wte = jnp.einsum("gtke,gtk->gte", onehot, gate_vals)
    return dispatch, wte


def moe(x, p, cfg):
    from repro.distributed import ctx

    mesh = ctx.get_mesh()
    B, S, _ = x.shape
    T = B * S
    use_shmap = False
    if mesh is not None and mesh.devices.size > 1 and cfg.moe_impl == "einsum":
        n_dp = mesh.devices.size // mesh.shape["model"]
        use_shmap = T % n_dp == 0 and T // n_dp >= 4
    if use_shmap:
        y, aux = moe_shard_map(x, p, cfg, mesh)
    else:
        impl = moe_sort if cfg.moe_impl == "sort" else moe_gshard_einsum
        y, aux = impl(x, p, cfg)
    if cfg.n_shared_experts:
        y = y + mlp(x, p["shared"])
    return y, aux


# ---------------------------------------------------------------------------
# Causal depthwise conv (mamba2 frontend)
# ---------------------------------------------------------------------------


def conv1d_causal(x, w, b):
    """x: [B, L, C]; w: [C, k]; depthwise causal conv, k small (unrolled)."""
    k = w.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    L = x.shape[1]
    wc = w.astype(x.dtype)
    y = sum(xp[:, i : i + L] * wc[None, None, :, i] for i in range(k))
    return y + b.astype(x.dtype)


def conv1d_step(x1, conv_state, w, b):
    """x1: [B, C] new input; conv_state: [B, k-1, C] history."""
    k = w.shape[1]
    full = jnp.concatenate([conv_state, x1[:, None]], axis=1)  # [B,k,C]
    y = jnp.einsum("bkc,ck->bc", full, w.astype(x1.dtype)) + b.astype(x1.dtype)
    new_state = full[:, 1:]
    return y, new_state


# ---------------------------------------------------------------------------
# Mamba2 / SSD
# ---------------------------------------------------------------------------


def segsum(x):
    """x: [..., T] -> [..., T, T] with out[i,j] = sum_{s=j+1..i} x[s] (else -inf)."""
    T = x.shape[-1]
    lower = jnp.tril(jnp.ones((T, T), bool), k=0)  # j <= i
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]  # cs[i] - cs[j]
    return jnp.where(lower, out, -jnp.inf)


def ssd_chunked(xh, dt, A, Bm, Cm, chunk, init_state=None):
    """Chunked state-space-duality scan (Mamba2).

    xh: [b,l,h,p]; dt: [b,l,h] (>0, post-softplus); A: [h] (<0);
    Bm, Cm: [b,l,g,n]. Returns (y [b,l,h,p], final_state [b,h,p,n]).
    """
    b, l, h, p = xh.shape
    g, n = Bm.shape[2], Bm.shape[3]
    rep = h // g
    c = min(chunk, l)
    if l % c:  # pad to a chunk multiple; dt=0 padding is state-neutral
        pad = c - l % c
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    l_orig, l = l, xh.shape[1]
    Bh = jnp.repeat(Bm, rep, axis=2)
    Ch = jnp.repeat(Cm, rep, axis=2)
    nc = l // c

    f32 = jnp.float32
    dA = (dt.astype(f32) * A.astype(f32)).reshape(b, nc, c, h)
    dAc = jnp.transpose(dA, (0, 3, 1, 2))  # [b,h,nc,c]
    A_cs = jnp.cumsum(dAc, axis=-1)

    xdt = (xh.astype(f32) * dt.astype(f32)[..., None]).reshape(b, nc, c, h, p)
    Bc = Bh.astype(f32).reshape(b, nc, c, h, n)
    Cc = Ch.astype(f32).reshape(b, nc, c, h, n)

    L = jnp.exp(segsum(dAc))  # [b,h,nc,c,c]
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", Cc, Bc, L, xdt)

    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)  # [b,h,nc,c]
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", Bc, decay_states, xdt)

    chunk_decay = jnp.exp(A_cs[..., -1])  # [b,h,nc]
    st0 = (
        init_state.astype(f32)
        if init_state is not None
        else jnp.zeros((b, h, p, n), f32)
    )

    def scan_body(st, inp):
        s_c, d_c = inp
        new = st * d_c[..., None, None] + s_c
        return new, st  # emit state at chunk *entry*

    states_s = jnp.moveaxis(states, 1, 0)
    decay_s = jnp.moveaxis(chunk_decay, -1, 0)
    final, prev_states = jax.lax.scan(scan_body, st0, (states_s, decay_s))
    prev = jnp.moveaxis(prev_states, 0, 1)  # [b,nc,h,p,n]

    state_decay_out = jnp.exp(A_cs)  # [b,h,nc,c]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", Cc, prev, state_decay_out)
    y = (y_diag + y_off).reshape(b, l, h, p)[:, :l_orig]
    return y.astype(xh.dtype), final


def ssm_step(x1, dt1, A, B1, C1, state):
    """Single-token SSM recurrence. x1: [b,h,p]; dt1: [b,h]; B1,C1: [b,g,n];
    state: [b,h,p,n] (fp32). Returns (y [b,h,p], new_state)."""
    b, h, p = x1.shape
    g, n = B1.shape[1], B1.shape[2]
    rep = h // g
    f32 = jnp.float32
    Bh = jnp.repeat(B1, rep, axis=1).astype(f32)  # [b,h,n]
    Ch = jnp.repeat(C1, rep, axis=1).astype(f32)
    dA = jnp.exp(dt1.astype(f32) * A.astype(f32))  # [b,h]
    inc = jnp.einsum("bhp,bhn->bhpn", x1.astype(f32) * dt1.astype(f32)[..., None], Bh)
    new_state = state * dA[..., None, None] + inc
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    return y.astype(x1.dtype), new_state


def _ssm_split(xBC, cfg):
    di, gn = cfg.d_inner, cfg.ssm_ngroups * cfg.ssm_state
    xs = xBC[..., :di]
    Bm = xBC[..., di : di + gn]
    Cm = xBC[..., di + gn :]
    return xs, Bm, Cm


def ssm_block(x, p, cfg, init_state=None, return_state=False, pos_offset=None):
    """Full-sequence Mamba2 block. x: [B, L, D].

    pos_offset: [B] left-pad amounts (bucketed serving). The conv/dt biases
    make padding slots nonzero even when their inputs are zero, so with an
    offset the pad slots' dt is forced to 0 (state-neutral: dA = 1, zero
    increment) and the block output is zeroed there, keeping the padded rows'
    state and residual stream exactly equal to unpadded execution.
    """
    B, L, D = x.shape
    dt_ = x.dtype
    z = jnp.einsum("bld,di->bli", x, p["in_z"].astype(dt_))
    xBC = jnp.einsum("bld,dc->blc", x, p["in_xbc"].astype(dt_))
    dtr = jnp.einsum("bld,dh->blh", x, p["in_dt"].astype(dt_))
    xBC = jax.nn.silu(conv1d_causal(xBC, p["conv_w"], p["conv_b"]))
    valid = None
    if pos_offset is not None:
        valid = (
            jnp.arange(L, dtype=jnp.int32)[None, :]
            >= pos_offset[:, None].astype(jnp.int32)
        )
        xBC = xBC * valid[..., None].astype(xBC.dtype)
    xs, Bm, Cm = _ssm_split(xBC, cfg)
    h, pd = cfg.n_ssm_heads, cfg.ssm_headdim
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    dt = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xs.reshape(B, L, h, pd)
    y, fstate = ssd_chunked(
        xh, dt, A, Bm.reshape(B, L, g, n), Cm.reshape(B, L, g, n), cfg.ssm_chunk,
        init_state=init_state,
    )
    y = y + p["D"].astype(dt_)[None, None, :, None] * xh
    y = rmsnorm_gated(y.reshape(B, L, cfg.d_inner), z, p["norm_w"], cfg.norm_eps)
    out = jnp.einsum("bli,id->bld", y, p["out_proj"].astype(dt_))
    if valid is not None:
        out = out * valid[..., None].astype(out.dtype)
    if return_state:
        conv_tail = _conv_tail(x, p, cfg)
        return out, (conv_tail, fstate)
    return out


def _conv_tail(x, p, cfg):
    """Last k-1 pre-conv inputs (for decode handoff after prefill)."""
    dt_ = x.dtype
    xBC = jnp.einsum("bld,dc->blc", x, p["in_xbc"].astype(dt_))
    k = cfg.ssm_conv
    return xBC[:, -(k - 1) :, :]


def ssm_block_decode(x1, p, cfg, conv_state, state):
    """Single-token Mamba2 block. x1: [B, 1, D]; returns (y, new_caches)."""
    B = x1.shape[0]
    dt_ = x1.dtype
    xf = x1[:, 0]
    z = jnp.einsum("bd,di->bi", xf, p["in_z"].astype(dt_))
    xBC = jnp.einsum("bd,dc->bc", xf, p["in_xbc"].astype(dt_))
    dtr = jnp.einsum("bd,dh->bh", xf, p["in_dt"].astype(dt_))
    xBC, new_conv = conv1d_step(xBC, conv_state, p["conv_w"], p["conv_b"])
    xBC = jax.nn.silu(xBC)
    xs, Bm, Cm = _ssm_split(xBC, cfg)
    h, pd = cfg.n_ssm_heads, cfg.ssm_headdim
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    dt = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, new_state = ssm_step(
        xs.reshape(B, h, pd), dt, A, Bm.reshape(B, g, n), Cm.reshape(B, g, n), state
    )
    y = y + p["D"].astype(dt_)[None, :, None] * xs.reshape(B, h, pd)
    y = rmsnorm_gated(y.reshape(B, cfg.d_inner), z, p["norm_w"], cfg.norm_eps)
    out = jnp.einsum("bi,id->bd", y, p["out_proj"].astype(dt_))
    return out[:, None], (new_conv, new_state)
