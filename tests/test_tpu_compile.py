"""The DPU kernels compile for a TPU v5e at serving widths.

No chip is needed: the TPU compiler compiles for a described v5e:2x2 and
refuses what the chip would refuse (tile-illegal blocks, too much VMEM),
which interpret mode cannot show. Each test asserts that the kernel reached
Mosaic (`tpu_custom_call` in the compiled program), so nothing silently ran
as plain XLA. The topology is described only inside fixtures: only the
worker that runs this file loads the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the ops wrappers to compile their kernels (they interpret on
    the CPU backend); jit caches are cleared on both sides so no
    interpreted trace is reused here and no Mosaic trace leaks out."""
    jax.clear_caches()
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    yield
    jax.clear_caches()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_audio_pipeline_compiles_for_v5e(one_chip, mosaic):
    """16 one-second 48 kHz utterances: resample 48k->16k, mel, normalize."""
    x = jax.ShapeDtypeStruct((16, 48000), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        functools.partial(ops.audio_pipeline_batch, up=1, down=3), x)
    # resample + mel + two normalize passes, all Mosaic
    assert text.count("tpu_custom_call") >= 4, text[-2000:]


def test_image_pipeline_compiles_for_v5e(one_chip, mosaic):
    """8 256x256 JPEG block stacks: IDCT, resize, crop-normalize to 224."""
    co = jax.ShapeDtypeStruct((8, 32, 32, 8, 8), jnp.float32,
                              sharding=one_chip)
    qt = jax.ShapeDtypeStruct((8, 8), jnp.float32, sharding=one_chip)
    text = _compiled_text(ops.image_pipeline_batch, co, qt)
    # IDCT + two resize passes + crop-normalize, all Mosaic
    assert text.count("tpu_custom_call") >= 4, text[-2000:]


_SINGLE = {
    "audio_resample": (functools.partial(ops.audio_resample, up=1, down=3),
                       [(48000,)]),
    "mel_spectrogram": (ops.mel_spectrogram, [(16000,)]),
    "audio_normalize": (ops.audio_normalize, [(98, 80)]),
    "jpeg_decode": (ops.jpeg_decode, [(32, 32, 8, 8), (8, 8)]),
    "image_resize": (functools.partial(ops.image_resize, out_h=256,
                                       out_w=256), [(320, 240)]),
    "image_normalize": (functools.partial(ops.image_normalize, mean=127.5,
                                          std=64.0), [(224, 224)]),
}


@pytest.mark.parametrize("name", sorted(_SINGLE))
def test_single_request_kernels_compile_for_v5e(one_chip, mosaic, name):
    """The per-request wrappers (one kernel variant each) reach Mosaic too."""
    fn, shapes = _SINGLE[name]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_decode_attention_compiles_for_v5e(one_chip, dtype):
    """tinyllama-1.1b decode: 32 query heads over 4 KV heads, head_dim 64,
    an 8-slot pool with a 2048-token cache."""
    b, h, kh, d, s = 8, 32, 4, 64, 2048
    q = jax.ShapeDtypeStruct((b, h, d), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kh, d), dtype, sharding=one_chip)
    vl = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    text = _compiled_text(
        functools.partial(decode_attention_pallas, interpret=False),
        q, kv, kv, vl)
    assert "tpu_custom_call" in text, text[-2000:]


def test_interpret_only_on_cpu(monkeypatch):
    """Interpret mode is the CPU's; a TPU compiles; anything else raises."""
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()


def test_cpu_path_still_runs_after_mosaic_steering():
    """The jit caches cleared by `mosaic` leave no Mosaic trace behind: the
    CPU backend interprets the pipeline again."""
    x = np.random.default_rng(0).standard_normal((2, 4800)).astype(np.float32)
    y = np.asarray(ops.audio_pipeline_batch(jnp.asarray(x)))
    assert y.shape[0] == 2 and np.isfinite(y).all()
