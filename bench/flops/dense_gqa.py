"""Model FLOPs of a dense GQA decoder, per token, as the algorithm needs
them (2 per multiply-add): the q/k/v/o projections, the gated MLP, and
attention over the token's own causal context (q.k and p.v, 2 x 2 x
heads x head_dim per key). The logits head counts once per token whose
logits are used: each decode token, and the last position of a prompt.
Embedding lookups, norms and softmax are not counted."""
from __future__ import annotations


def _dims(cfg: dict):
    d, hd = cfg["d_model"], cfg["head_dim"]
    return d, cfg["n_heads"], cfg["n_kv_heads"], hd, cfg["d_ff"]


def token_flops(cfg: dict, context: int) -> float:
    """FLOPs of all layers for one token that attends to `context` keys."""
    d, H, KH, hd, f = _dims(cfg)
    proj = 2 * d * (H * hd + 2 * KH * hd) + 2 * H * hd * d
    mlp = 2 * 3 * d * f
    attn = 4 * H * hd * context
    return float(cfg["n_layers"] * (proj + mlp + attn))


def head_flops(cfg: dict) -> float:
    return float(2 * cfg["d_model"] * cfg["vocab"])


def prompt_flops(cfg: dict, start: int, end: int) -> float:
    """FLOPs of prompt positions [start, end) (contexts start+1 .. end)."""
    n = end - start
    ctx_sum = (start + 1 + end) * n / 2.0
    d, H, KH, hd, f = _dims(cfg)
    proj = 2 * d * (H * hd + 2 * KH * hd) + 2 * H * hd * d
    mlp = 2 * 3 * d * f
    return float(cfg["n_layers"] * ((proj + mlp) * n + 4 * H * hd * ctx_sum))
