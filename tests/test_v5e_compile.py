"""Compile the main path's kernels and the KWT-1 local update for a v5e.

The TPU compiler compiles for a described, unattached ``v5e:2x2``
topology, so these tests catch what interpret mode cannot (a lowering the
chip refuses, a tile over the fast-memory limit) at no chip time. Nothing
runs: each test only asserts that the compile succeeds and that the
kernel is a Mosaic custom call.

The topology is described in a fixture, never at import, and only the
worker that runs this file loads the TPU compiler. The persistent
compilation cache is off around the compiles: an executable compiled for
a described chip cannot be read back without one.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("window", [0, 1024])
def test_flash_attention_compiles(one_chip, window):
    # smollm-360m widths: 16 query / 8 kv heads (padded), head dim 64
    B, H, KV, S, dh = 8, 16, 8, 2048, 64
    q = _spec(one_chip, (B, H, S, dh), jnp.bfloat16)
    kv = _spec(one_chip, (B, KV, S, dh), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            window=window, interpret=False),
        q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_moe_gemm_compiles(one_chip):
    E, C, d, f = 8, 512, 6144, 2048
    compiled = _compile(lambda x, w: ops.moe_gemm(x, w, interpret=False),
                        _spec(one_chip, (E, C, d), jnp.bfloat16),
                        _spec(one_chip, (E, d, f), jnp.bfloat16))
    assert "tpu_custom_call" in compiled.as_text()


def test_rwkv_scan_compiles(one_chip):
    # rwkv6-1.6b widths: 32 heads of 64
    B, S, H, dh = 2, 1024, 32, 64
    x = _spec(one_chip, (B, S, H, dh), jnp.bfloat16)
    compiled = _compile(
        lambda r, k, v, w, u: ops.rwkv_scan(r, k, v, w, u, interpret=False),
        x, x, x, x, _spec(one_chip, (H, dh), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_kwt1_local_step_compiles(one_chip):
    """The FedProx local update of a KWT-1 JaxTrainer at its published
    width (d 64, 12 layers, 1 head, MLP 256, 98 MFCC patches, 35 classes),
    as the one program the chip runs per update: the loop of local steps
    over the staged ``[max_steps, B, ...]`` batches, then the probe."""
    from repro.core import JaxTrainer
    from repro.data.federated import synthetic_speech
    from repro.models import KWTModel

    names = [f"c{i}" for i in range(4)]
    data = synthetic_speech(4, names, n_classes=35, n_samples=64,
                            n_patches=98, n_test=16)
    tr = JaxTrainer(KWTModel(n_classes=35, n_patches=98), data)
    on_chip = lambda t: jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), t)
    rng = np.random.default_rng(0)
    draws = [data.sample_batch("c0", tr.batch_size, rng) for _ in range(3)]
    probe = data.sample_batch("c0", 4 * tr.batch_size, rng)
    batches = tr._stage(draws)
    assert batches["mfcc"].shape == (tr.max_steps, tr.batch_size, 98, 40)
    compiled = tr._local_update.lower(
        on_chip(tr.params), on_chip(batches),
        _spec(one_chip, (), jnp.int32), on_chip(probe)).compile()
    assert compiled.memory_analysis() is not None
    assert "while" in compiled.as_text()
