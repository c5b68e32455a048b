"""Jitted public wrappers for the DPU kernels. On the CPU backend the
kernels run in Pallas interpret mode (the tests validate them there); on a
TPU they compile to Mosaic. Any other backend is refused."""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import preprocess_cpu as pp
from repro.kernels.audio_normalize import audio_normalize_batch_pallas, audio_normalize_pallas
from repro.kernels.audio_resample import audio_resample_batch_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.image_normalize import image_crop_normalize_pallas
from repro.kernels.image_resize import image_resize_batch_pallas, image_resize_pallas
from repro.kernels.jpeg_idct import jpeg_idct_pallas
from repro.kernels.mel_spectrogram import mel_spectrogram_pallas


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"DPU kernels run on a TPU (or interpreted on the CPU), "
            f"not on backend {backend!r}")
    return backend == "cpu"


# --- audio ------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("sr", "n_fft", "frame", "hop", "n_mels"))
def mel_spectrogram(x: jax.Array, *, sr: int = 16000, n_fft: int = 512,
                    frame: int = 400, hop: int = 160, n_mels: int = 80) -> jax.Array:
    """x: [L] mono audio -> log-mel [n_frames, n_mels]."""
    return mel_spectrogram_batch(
        x[None], sr=sr, n_fft=n_fft, frame=frame, hop=hop, n_mels=n_mels
    )[0]


@jax.jit
def audio_normalize(feats: jax.Array) -> jax.Array:
    return audio_normalize_pallas(feats, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("up", "down", "num_taps"))
def audio_resample(x: jax.Array, up: int, down: int, num_taps: int = 48) -> jax.Array:
    """Rational resample; up==1 path runs the FIR-decimate kernel."""
    return audio_resample_batch(x[None], up, down, num_taps)[0]


# --- batched audio (one kernel launch per same-shape request stack) ----------


@functools.partial(jax.jit, static_argnames=("sr", "n_fft", "frame", "hop", "n_mels"))
def mel_spectrogram_batch(x: jax.Array, *, sr: int = 16000, n_fft: int = 512,
                          frame: int = 400, hop: int = 160,
                          n_mels: int = 80) -> jax.Array:
    """x: [N, L] same-length mono stack -> log-mel [N, n_frames, n_mels].
    The framed stack flattens to [N*n_frames, n_fft] so the whole batch is a
    single kernel launch instead of one per request."""
    nsig = x.shape[0]
    n = 1 + max(0, (x.shape[1] - frame)) // hop
    idx = jnp.arange(frame)[None, :] + hop * jnp.arange(n)[:, None]
    frames = x[:, idx] * jnp.asarray(pp.hann(frame))[None, None, :]
    frames = jnp.pad(frames, ((0, 0), (0, 0), (0, n_fft - frame)))
    cr, ci = pp.dft_matrices(n_fft)
    fb = pp.mel_filterbank(n_mels, n_fft, sr).T
    out = mel_spectrogram_pallas(
        frames.reshape(nsig * n, n_fft), jnp.asarray(cr), jnp.asarray(ci),
        jnp.asarray(fb), interpret=_interpret(),
    )
    return out.reshape(nsig, n, n_mels)


@jax.jit
def audio_normalize_batch(feats: jax.Array) -> jax.Array:
    """feats: [N, T, F] -> per-utterance normalized, one launch per pass."""
    return audio_normalize_batch_pallas(feats, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("up", "down", "num_taps"))
def audio_resample_batch(x: jax.Array, up: int, down: int,
                         num_taps: int = 48) -> jax.Array:
    """x: [N, L] same-length stack; rational resample in one kernel launch."""
    g = math.gcd(up, down)
    up, down = up // g, down // g
    if up == 1 and down == 1:
        return x.astype(jnp.float32)
    h = pp.fir_lowpass(num_taps * max(up, down), 1.0 / max(up, down)) * up
    if up > 1:
        xu = jnp.zeros((x.shape[0], x.shape[1] * up), jnp.float32).at[:, ::up].set(x)
    else:
        xu = x.astype(jnp.float32)
    taps = h.shape[0]
    xp = jnp.pad(xu, ((0, 0), (taps // 2, taps)))
    n_out = (xu.shape[1] + down - 1) // down
    return audio_resample_batch_pallas(xp, h, down, interpret=_interpret())[:, :n_out]


@functools.partial(jax.jit, static_argnames=("up", "down"))
def audio_pipeline_batch(x: jax.Array, up: int = 1, down: int = 3) -> jax.Array:
    """Whole audio front-end — resample -> mel -> normalize — for a
    same-length stack [N, L] as ONE jitted program (the DPU service's fused
    CU launch): a single XLA call per request group, so the service worker
    holds the GIL only at dispatch, not per functional unit, and decode on
    the event-loop thread genuinely overlaps preprocessing."""
    y = audio_resample_batch(x, up, down)
    feats = mel_spectrogram_batch(y)
    return audio_normalize_batch(feats)


# --- image ------------------------------------------------------------------


@jax.jit
def jpeg_decode(coeffs: jax.Array, qtable: jax.Array) -> jax.Array:
    """coeffs: [H/8, W/8, 8, 8] -> pixels [H, W]."""
    return jpeg_decode_batch(coeffs[None], qtable)[0]


@functools.partial(jax.jit, static_argnames=("out_h", "out_w"))
def image_resize(img: jax.Array, out_h: int, out_w: int) -> jax.Array:
    ry = jnp.asarray(pp._resize_matrix(img.shape[0], out_h))
    rx = jnp.asarray(pp._resize_matrix(img.shape[1], out_w))
    return image_resize_pallas(img, ry, rx, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("ch", "cw"))
def center_crop(img: jax.Array, ch: int, cw: int) -> jax.Array:
    y0 = (img.shape[0] - ch) // 2
    x0 = (img.shape[1] - cw) // 2
    return jax.lax.slice(img, (y0, x0), (y0 + ch, x0 + cw))


@functools.partial(jax.jit, static_argnames=("mean", "std"))
def image_normalize(img: jax.Array, mean: float, std: float) -> jax.Array:
    h, w = img.shape
    return image_crop_normalize_pallas(
        img, h, w, mean, std, interpret=_interpret()
    )


# --- batched image (one kernel launch per same-shape request stack) ----------


@jax.jit
def jpeg_decode_batch(coeffs: jax.Array, qtable: jax.Array) -> jax.Array:
    """coeffs: [N, H/8, W/8, 8, 8] same-shape stack -> pixels [N, H, W];
    all N*H/8*W/8 blocks go through one IDCT launch."""
    n, by, bx = coeffs.shape[0], coeffs.shape[1], coeffs.shape[2]
    blocks = jpeg_idct_pallas(
        coeffs.reshape(n * by * bx, 8, 8), qtable, interpret=_interpret()
    )
    return (
        blocks.reshape(n, by, bx, 8, 8)
        .transpose(0, 1, 3, 2, 4)
        .reshape(n, by * 8, bx * 8)
    )


@functools.partial(jax.jit, static_argnames=("out_h", "out_w"))
def image_resize_batch(imgs: jax.Array, out_h: int, out_w: int) -> jax.Array:
    """imgs: [N, H, W] -> [N, out_h, out_w] in two launches for the stack."""
    ry = jnp.asarray(pp._resize_matrix(imgs.shape[1], out_h))
    rx = jnp.asarray(pp._resize_matrix(imgs.shape[2], out_w))
    return image_resize_batch_pallas(imgs, ry, rx, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("ch", "cw"))
def center_crop_batch(imgs: jax.Array, ch: int, cw: int) -> jax.Array:
    y0 = (imgs.shape[1] - ch) // 2
    x0 = (imgs.shape[2] - cw) // 2
    return jax.lax.slice(
        imgs, (0, y0, x0), (imgs.shape[0], y0 + ch, x0 + cw)
    )


@functools.partial(jax.jit, static_argnames=("mean", "std"))
def image_normalize_batch(imgs: jax.Array, mean: float, std: float) -> jax.Array:
    """imgs: [N, H, W] -> normalized stack; rows flatten to [N*H, W] so the
    element-wise kernel runs once for the whole stack."""
    n, h, w = imgs.shape
    out = image_crop_normalize_pallas(
        imgs.reshape(n * h, w), n * h, w, mean, std, interpret=_interpret()
    )
    return out.reshape(n, h, w)


@functools.partial(jax.jit,
                   static_argnames=("resize_to", "crop_to", "mean", "std"))
def image_pipeline_batch(coeffs: jax.Array, qtable: jax.Array, *,
                         resize_to: int = 256, crop_to: int = 224,
                         mean: float = 127.5, std: float = 64.0) -> jax.Array:
    """Whole JPEG front-end — dequantize+IDCT decode -> resize -> center
    crop -> normalize — for a same-shape coefficient stack [N, H/8, W/8, 8,
    8] with one shared qtable, as ONE jitted program (the DPU service's
    fused CU launch, mirroring audio_pipeline_batch): a single XLA call per
    request group instead of one launch per functional unit, so the service
    worker holds the GIL only at dispatch and decode on the event-loop
    thread genuinely overlaps preprocessing."""
    imgs = jpeg_decode_batch(coeffs, qtable)
    imgs = image_resize_batch(imgs, resize_to, resize_to)
    imgs = center_crop_batch(imgs, crop_to, crop_to)
    return image_normalize_batch(imgs, mean, std)


# --- serving -----------------------------------------------------------------


@jax.jit
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid_len: jax.Array) -> jax.Array:
    return decode_attention_pallas(q, k, v, valid_len, interpret=_interpret())
