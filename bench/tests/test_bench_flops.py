"""FLOP and ops/byte counters against hand counts at one small shape."""
import math

import bench_fixture  # noqa: F401
import pytest

from bench.lib.spec import BENCH_DIR, load_module

dense = load_module(BENCH_DIR, "flops", "dense_gqa")
ssd = load_module(BENCH_DIR, "flops", "mamba2_ssd")
dpu = load_module(BENCH_DIR, "flops", "dpu_audio")

CFG = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
       "head_dim": 2, "d_ff": 16, "vocab": 10}


def test_dense_token_flops_by_hand():
    # per layer: q 2*8*8=128, k,v 2*8*4=64 each, o 2*8*8=128 -> 384;
    # mlp 3 * 2*8*16 = 768; attention over 5 keys: 2*2*4*2*5 = 160
    assert dense.token_flops(CFG, 5) == 2 * (384 + 768 + 160)
    assert dense.head_flops(CFG) == 2 * 8 * 10


def test_dense_prompt_flops_is_the_sum_of_tokens():
    want = sum(dense.token_flops(CFG, t + 1) for t in range(3, 9))
    assert dense.prompt_flops(CFG, 3, 9) == pytest.approx(want)


def test_ssd_token_flops_by_hand():
    cfg = {"n_layers": 1, "d_model": 4, "ssm_expand": 2, "ssm_state": 3,
           "ssm_headdim": 4, "ssm_conv": 4, "vocab": 10}
    di, N, P = 8, 3, 4
    H, cd = di // P, di + 2 * N
    want = (2 * 4 * (di + cd + H) + 2 * di * 4 + 2 * cd * 4
            + 5 * H * P * N + 2 * di)
    assert ssd.token_flops(cfg) == want
    assert ssd.prompt_flops(cfg, 2, 7) == 5 * want


def test_dpu_clip_cost_by_hand():
    samples = 48000  # one second at 48 kHz -> 16000 at 16 kHz
    ops, nbytes = dpu.clip_cost(samples)
    frames = 1 + (16000 - 400) // 160                        # 98
    fir = 2 * 144 * 16000
    stft = frames * (400 + 2.5 * 512 * 9 + 3 * 257)
    from bench.reference.audio_frontend import mel_filterbank
    import numpy as np
    nz = int(np.count_nonzero(mel_filterbank(80, 512, 16000)))
    mel = frames * (2 * nz + 80)
    norm = 5 * frames * 80
    assert ops == pytest.approx(fir + stft + mel + norm)
    assert nbytes == 4 * samples + 4 * frames * 80
    assert math.isclose(2.5 * 512 * math.log2(512), 2.5 * 512 * 9)
