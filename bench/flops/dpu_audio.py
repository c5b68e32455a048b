"""Operations and bytes the audio front end needs for one clip, the same
whatever implements it (the DPU's banded-matmul resample and DFT-matmul
mel transform do more):

  resample  FIR taps per output sample: 2 x taps per sample at 16 kHz
            (taps = 48 x max(up, down) of the polyphase filter)
  STFT      per frame: Hann window (frame mults) + a real FFT of n_fft
            counted as 2.5 n_fft log2 n_fft + |X|^2 (3 per bin)
  mel       the filterbank's nonzero weights, 2 per weight, + log per mel
  normalize 5 per feature (mean, variance, scale)
  bytes     the float32 clip read once and the features written once
"""
from __future__ import annotations

import math

import numpy as np

from bench.reference.audio_frontend import mel_filterbank


def _mel_nonzero(n_mels: int, n_fft: int, sr: int) -> int:
    return int(np.count_nonzero(mel_filterbank(n_mels, n_fft, sr)))


def clip_cost(samples: int, *, in_sr: int = 48000, sr: int = 16000,
              n_fft: int = 512, frame: int = 400, hop: int = 160,
              n_mels: int = 80, num_taps: int = 48):
    """(operations, bytes) for one raw clip of `samples` at `in_sr`."""
    g = math.gcd(sr, in_sr)
    up, down = sr // g, in_sr // g
    taps = num_taps * max(up, down)
    out = (samples * up + down - 1) // down
    frames = 1 + max(0, out - frame) // hop
    ops = 2.0 * taps * out
    ops += frames * (frame + 2.5 * n_fft * math.log2(n_fft)
                     + 3 * (n_fft // 2 + 1))
    ops += frames * (2 * _mel_nonzero(n_mels, n_fft, sr) + n_mels)
    ops += 5.0 * frames * n_mels
    nbytes = 4.0 * samples + 4.0 * frames * n_mels
    return ops, nbytes
