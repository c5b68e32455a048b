"""Plain numpy reference of the audio front end the DPU runs: polyphase
resample (Hamming-windowed sinc FIR) -> framed, Hann-windowed |DFT|^2 ->
mel filterbank -> log -> per-utterance mean/variance normalization.

A copy of the program's CPU path (data/preprocess_cpu.py), kept here so a
program change cannot move the yardstick. `bf16=True` is the correctness
control: every matmul operand rounded to bfloat16, the precision of one
MXU pass, the step below the float32 the front end states.
"""
from __future__ import annotations

import math
from functools import lru_cache

import numpy as np


def _bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    b = np.ascontiguousarray(a, np.float32).view(np.uint32)
    r = ((b >> 16) & 1) + 0x7FFF
    return ((b + r) & 0xFFFF0000).view(np.float32)


@lru_cache(maxsize=None)
def fir_lowpass(num_taps: int, cutoff: float) -> np.ndarray:
    n = np.arange(num_taps) - (num_taps - 1) / 2.0
    h = np.sinc(cutoff * n) * cutoff
    h *= np.hamming(num_taps)
    return (h / h.sum()).astype(np.float32)


def resample_poly(x, up: int, down: int, num_taps: int = 48, bf16=False):
    g = math.gcd(up, down)
    up, down = up // g, down // g
    h = fir_lowpass(num_taps * max(up, down), 1.0 / max(up, down)) * up
    xu = np.zeros(len(x) * up, np.float32)
    xu[::up] = x
    if bf16:
        xu, h = _bf16(xu), _bf16(h)
    y = np.convolve(xu, h, mode="same")
    return y[::down].astype(np.float32)


@lru_cache(maxsize=None)
def hann(n: int) -> np.ndarray:
    return (0.5 - 0.5 * np.cos(2 * np.pi * np.arange(n) / n)).astype(
        np.float32)


@lru_cache(maxsize=None)
def mel_filterbank(n_mels: int, n_fft: int, sr: int) -> np.ndarray:
    def to_mel(f):
        return 2595.0 * np.log10(1.0 + f / 700.0)

    def from_mel(m):
        return 700.0 * (10.0 ** (m / 2595.0) - 1.0)

    pts = from_mel(np.linspace(to_mel(0.0), to_mel(sr / 2), n_mels + 2))
    bins = np.floor((n_fft + 1) * pts / sr).astype(int)
    fb = np.zeros((n_mels, n_fft // 2 + 1), np.float32)
    for i in range(n_mels):
        l, c, r = bins[i], bins[i + 1], bins[i + 2]
        for j in range(l, c):
            fb[i, j] = (j - l) / (c - l)
        for j in range(c, r):
            fb[i, j] = (r - j) / (r - c)
    return fb


@lru_cache(maxsize=None)
def dft(n_fft: int):
    k = np.arange(n_fft // 2 + 1)[None, :]
    t = np.arange(n_fft)[:, None]
    ang = -2.0 * np.pi * t * k / n_fft
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def log_mel(x, *, sr=16000, n_fft=512, frame=400, hop=160, n_mels=80,
            bf16=False):
    n = 1 + max(0, len(x) - frame) // hop
    idx = np.arange(frame)[None, :] + hop * np.arange(n)[:, None]
    frames = x[idx] * hann(frame)[None, :]
    fp = np.concatenate(
        [frames, np.zeros((n, n_fft - frame), np.float32)], axis=1)
    cr, ci = dft(n_fft)
    fb = mel_filterbank(n_mels, n_fft, sr).T
    r = _bf16 if bf16 else (lambda a: a)
    re, im = r(fp) @ r(cr), r(fp) @ r(ci)
    power = re * re + im * im
    return np.log(r(power) @ r(fb) + 1e-6).astype(np.float32)


def normalize(feats, eps=1e-5):
    mu = feats.mean(axis=0, keepdims=True)
    var = ((feats - mu) ** 2).mean(axis=0, keepdims=True)
    return ((feats - mu) / np.sqrt(var + eps)).astype(np.float32)


def features(x: np.ndarray, *, in_sr: int = 48000, sr: int = 16000,
             bf16: bool = False) -> np.ndarray:
    """Raw mono clip at `in_sr` -> normalized log-mel [frames, 80]."""
    y = resample_poly(np.asarray(x, np.float32), sr, in_sr, bf16=bf16)
    return normalize(log_mel(y, sr=sr, bf16=bf16))
