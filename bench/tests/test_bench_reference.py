"""The plain float32 references against the program's own forward at a
reduced size on the CPU.

Tolerance: both sides compute in float32 on the CPU, where XLA's matmuls
are full float32; they differ only in the order of additions (the
program's blocked attention and chunked SSD scan against the references'
direct softmax and sequential recurrence). That leaves float32 rounding
of logits of size ~1 through a few layers: 1e-4 absolute covers it with
two orders of magnitude to spare, and is far below the bfloat16 rounding
(~1e-2) a served model shows."""
from dataclasses import replace

import bench_fixture  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib.weights import init_params
from bench.reference import dense_gqa, mamba2_ssd
from repro.configs.base import get_config
from repro.models import lm

CASES = {
    "dense_gqa": ("granite-3-8b", dense_gqa,
                  dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                       head_dim=32, d_ff=256, vocab=500)),
    "mamba2_ssd": ("mamba2-370m", mamba2_ssd,
                   dict(n_layers=2, d_model=128, vocab=500, ssm_state=16,
                        ssm_headdim=16, ssm_chunk=16)),
}


@pytest.mark.parametrize("family", sorted(CASES))
def test_reference_matches_program_forward(family):
    name, ref, over = CASES[family]
    cfg = replace(get_config(name), dtype="float32", **over)
    params = init_params(cfg, 2**35 + 3, jax.devices()[0])
    toks = np.random.default_rng(1).integers(0, cfg.vocab, 70).astype(np.int32)
    x, _ = lm.forward(params, jnp.asarray(toks)[None], cfg, mode="train")
    want = np.asarray(lm.logits_from_hidden(params, x, cfg))[0]
    spec = dict(rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                vocab=cfg.vocab, ssm_headdim=cfg.ssm_headdim,
                ssm_state=cfg.ssm_state)
    rows = np.arange(3, 70, 5)
    got = ref.logits(params, [toks], [rows], spec)[0]
    assert got.shape == (len(rows), cfg.vocab)
    np.testing.assert_allclose(got, want[rows], atol=1e-4, rtol=0)
    # the fp8 control moves the logits by far more than that
    q = ref.logits(params, [toks], [rows], spec, quant=True)[0]
    assert np.abs(q - got).max() > 1e-2
