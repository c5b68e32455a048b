"""Per-kernel shape/dtype sweeps on the CPU: pallas (interpret mode) vs
pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.data import preprocess_cpu as pp
from repro.kernels import ops, ref
from repro.kernels.audio_normalize import audio_normalize_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.jpeg_idct import jpeg_idct_pallas
from repro.kernels.mel_spectrogram import mel_spectrogram_pallas

rng = np.random.default_rng(42)


@pytest.mark.parametrize("n_frames", [1, 64, 128, 257])
@pytest.mark.parametrize("n_mels", [40, 80])
def test_mel_spectrogram_sweep(n_frames, n_mels):
    n_fft = 512
    frames = rng.standard_normal((n_frames, n_fft)).astype(np.float32)
    cr, ci = pp.dft_matrices(n_fft)
    fb = pp.mel_filterbank(n_mels, n_fft, 16000).T
    got = mel_spectrogram_pallas(
        jnp.asarray(frames), jnp.asarray(cr), jnp.asarray(ci), jnp.asarray(fb),
        interpret=True,
    )
    want = ref.mel_spectrogram_ref(
        jnp.asarray(frames), jnp.asarray(cr), jnp.asarray(ci), jnp.asarray(fb)
    )
    assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t", [5, 128, 300])
@pytest.mark.parametrize("f", [80, 128])
def test_audio_normalize_sweep(t, f):
    feats = (rng.standard_normal((t, f)) * 3 + 1).astype(np.float32)
    got = audio_normalize_pallas(jnp.asarray(feats), interpret=True)
    want = ref.audio_normalize_ref(jnp.asarray(feats))
    assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("down", [2, 3])
@pytest.mark.parametrize("n", [1600, 4800])
def test_audio_resample_sweep(down, n):
    x = rng.standard_normal(n).astype(np.float32)
    got = np.asarray(ops.audio_resample(jnp.asarray(x), 1, down))
    want = pp.resample_poly(x, 1, down)
    m = min(len(got), len(want))
    assert_allclose(got[:m], want[:m], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("nb", [1, 100, 513])
def test_jpeg_idct_sweep(nb):
    co = rng.integers(-64, 64, (nb, 8, 8)).astype(np.float32)
    qt = rng.integers(1, 32, (8, 8)).astype(np.float32)
    got = jpeg_idct_pallas(jnp.asarray(co), jnp.asarray(qt), interpret=True)
    want = ref.jpeg_idct_ref(jnp.asarray(co), jnp.asarray(qt))
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("hw", [(256, 256), (320, 200)])
@pytest.mark.parametrize("out", [(256, 256), (112, 96)])
def test_image_resize_sweep(hw, out):
    img = rng.standard_normal(hw).astype(np.float32)
    got = np.asarray(ops.image_resize(jnp.asarray(img), *out))
    want = pp.resize_bilinear(img, *out)
    assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_image_pipeline_end_to_end():
    co = rng.integers(-32, 32, (32, 32, 8, 8)).astype(np.float32)
    qt = rng.integers(1, 16, (8, 8)).astype(np.float32)
    img = ops.jpeg_decode(jnp.asarray(co), jnp.asarray(qt))
    img = ops.image_resize(img, 256, 256)
    img = ops.center_crop(img, 224, 224)
    got = np.asarray(ops.image_normalize(img, 127.5, 64.0))
    want = pp.image_pipeline(co, qt)
    assert_allclose(got, want, rtol=1e-3, atol=1e-2)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(2, 8, 4, 64, 512), (1, 7, 7, 128, 300), (4, 16, 16, 64, 1024)])
def test_decode_attention_sweep(shape, dtype):
    b, h, kh, d, s = shape
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, kh, d)).astype(np.float32)
    v = rng.standard_normal((b, s, kh, d)).astype(np.float32)
    vl = rng.integers(1, s + 1, (b,)).astype(np.int32)
    qj, kj, vj = (jnp.asarray(a, dtype) for a in (q, k, v))
    got = decode_attention_pallas(qj, kj, vj, jnp.asarray(vl),
                                  interpret=True)
    want = ref.decode_attention_ref(qj, kj, vj, jnp.asarray(vl))
    tol = 2e-4 if dtype == np.float32 else 3e-2
    assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_decode_attention_matches_model_attention():
    """The Pallas decode kernel agrees with the model's jnp decode path."""
    from repro.models import layers as L

    b, kh, g, d, s = 2, 4, 2, 64, 256
    q = jnp.asarray(rng.standard_normal((b, 1, kh * g, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kh, d)), jnp.float32)
    vl = jnp.asarray([s, 100], jnp.int32)
    kpos = jnp.arange(s)
    # model path (single batch entry at a time to honor per-seq valid lens)
    outs = []
    for i in range(b):
        kp = jnp.where(kpos < vl[i], kpos, -1)
        outs.append(
            L.attention_dense(q[i : i + 1], k[i : i + 1], v[i : i + 1],
                              jnp.array([s]), kp, causal=True, window=0)
        )
    want = jnp.concatenate(outs, 0)[:, 0]
    got = decode_attention_pallas(q[:, 0], k, v, vl, interpret=True)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
