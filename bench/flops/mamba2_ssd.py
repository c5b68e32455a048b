"""Model FLOPs of a Mamba-2 (SSD) decoder, per token, as the recurrence
needs them (2 per multiply-add): the in-projections (z, xBC, dt), the
depthwise causal conv, the state update h = exp(dt A) h + dt x B^T
(3 per state element: decay, outer product, add) and the read-out y = h C
(2 per state element), the D skip and the out-projection. The cost does
not depend on the context. The logits head counts as for dense models."""
from __future__ import annotations


def token_flops(cfg: dict, context: int = 0) -> float:
    d = cfg["d_model"]
    di = cfg["ssm_expand"] * d
    N, P = cfg["ssm_state"], cfg["ssm_headdim"]
    H = di // P
    cd = di + 2 * N
    proj = 2 * d * (di + cd + H) + 2 * di * d
    conv = 2 * cd * cfg["ssm_conv"]
    ssm = 5 * H * P * N + 2 * di
    return float(cfg["n_layers"] * (proj + conv + ssm))


def head_flops(cfg: dict) -> float:
    return float(2 * cfg["d_model"] * cfg["vocab"])


def prompt_flops(cfg: dict, start: int, end: int) -> float:
    return token_flops(cfg) * (end - start)
