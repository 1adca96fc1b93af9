"""Pallas counter-hash synthesis kernels: interpreter-mode bit-parity.

Ground truth is the NumPy counter-hash reference in ``repro.backend.base``
(the same contract the jit backend is pinned against), so every
comparison here is ``assert_array_equal`` — no tolerances. The kernels
mix uint64 and therefore run in **interpreter mode** on CPU CI
(``ops.piece_window``/``ops.forecast_z`` default to it off the TPU); the
``pallas`` registry backend layers them over the JAX backend, and the
70k-row case exercises its shape-bucket padding across the 65536
power-of-two boundary exactly like the acceptance fleet does.

The (seed, row, segment) sweep is a hypothesis property when hypothesis
is installed, with a seeded fallback sweep otherwise.
"""
import numpy as np
import pytest

import jax

from repro.backend import available_backends, get_backend
from repro.backend.jax_backend import JaxBackend
from repro.kernels import ops, ref

pytestmark = pytest.mark.slow  # deselect via -m 'not slow'

NP = get_backend("numpy")
_U64 = np.uint64
_FOLD = _U64(0x9E3779B97F4A7C15)


def _grid_case(rng, R, S, W):
    levels = rng.random((R, S), dtype=np.float32)
    slot = rng.integers(0, S, (R, W)).astype(np.int64)
    rows = np.sort(rng.choice(10 ** 7, R, replace=False)).astype(np.uint64)
    return levels, slot, rows


@pytest.mark.parametrize("R,S,W,br,bw", [
    (16, 3, 16, 16, 16),        # single tile
    (256, 5, 96, 64, 32),       # multi-tile both axes
    (512, 8, 64, 256, 64),      # uneven tiling, levels wider than slots
])
def test_piece_window_interpreter_parity(R, S, W, br, bw, rng):
    levels, slot, rows = _grid_case(rng, R, S, W)
    fold = _U64(rng.integers(0, 2 ** 62))
    amp = np.float32(0.05 * np.sqrt(12.0))
    want = ref.piece_window_ref(levels, slot, fold, rows, 10_000, amp)
    with jax.enable_x64(True):
        got = np.asarray(ops.piece_window(
            levels, slot, fold, rows, np.int64(10_000), amp,
            block_r=br, block_w=bw))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("R,W,br,bw", [(64, 16, 64, 16), (512, 64, 128, 32)])
def test_forecast_z_interpreter_parity(R, W, br, bw, rng):
    rows = rng.integers(0, 2 ** 40, R, dtype=np.int64).astype(np.uint64)
    fold = _U64(rng.integers(0, 2 ** 62))
    std = (0.05 + 0.2 * np.minimum(np.arange(1, W + 1) / 1440.0, 1.0)
           ).astype(np.float32)
    want = ref.forecast_z_ref(fold, rows, 777, std)
    with jax.enable_x64(True):
        got = np.asarray(ops.forecast_z(fold, rows, _U64(777), std,
                                        block_r=br, block_w=bw))
    np.testing.assert_array_equal(want, got)


def test_pallas_backend_registered_and_bucket_boundary_70k(rng):
    """`backend="pallas"` resolves via the registry, inherits the JAX
    fused ops, and its kernel windows are bit-identical to the NumPy
    reference at 70k rows — padding across the 65536 shape bucket."""
    assert "pallas" in available_backends()
    pb = get_backend("pallas")
    assert pb.name == "pallas" and isinstance(pb, JaxBackend)
    assert get_backend("pallas") is pb          # singleton

    R, S, W = 70_000, 6, 12
    levels, slot, rows = _grid_case(rng, R, S, W)
    fold = _U64(rng.integers(0, 2 ** 62))
    a = NP.synth_window(levels.copy(), slot, fold, rows, 4_321, 0.1732)
    b = pb.synth_window(levels.copy(), slot, fold, rows, 4_321, 0.1732)
    np.testing.assert_array_equal(a, b)

    std = (0.05 + 0.2 * np.minimum(np.arange(1, W + 1) / 1440.0, 1.0)
           ).astype(np.float32)
    za = NP.forecast_noise_z(fold, rows, 777, W, std)
    zb = pb.forecast_noise_z(fold, rows, 777, W, std)
    np.testing.assert_array_equal(za, zb)
    assert zb.flags.writeable                   # callers np.exp in place

    # below the device crossover the pallas backend serves host bits
    small = pb.synth_window(levels[:8].copy(), slot[:8], fold, rows[:8],
                            4_321, 0.1732)
    np.testing.assert_array_equal(
        NP.synth_window(levels[:8].copy(), slot[:8], fold, rows[:8],
                        4_321, 0.1732), small)


def _key_sweep_case(seed, row_key, segment):
    """One (seed, row, segment) key triple → both kernels vs reference."""
    rng = np.random.default_rng(seed)
    R, S, W = 32, 4, 16
    levels = rng.random((R, S), dtype=np.float32)
    slot = np.full((R, W), segment % S, dtype=np.int64)
    rows = (np.arange(R, dtype=np.uint64) * _U64(2654435761)
            + _U64(row_key)) & _U64((1 << 40) - 1)
    fold = NP.hash64(seed, 17, np.uint64(segment))
    amp = np.float32(0.1732)
    want = ref.piece_window_ref(levels, slot, fold, rows, segment, amp)
    with jax.enable_x64(True):
        got = np.asarray(ops.piece_window(
            levels, slot, _U64(fold), rows, np.int64(segment), amp,
            block_r=16, block_w=16))
    np.testing.assert_array_equal(want, got)

    std = np.full(W, 0.07, dtype=np.float32)
    wantz = ref.forecast_z_ref(fold, rows, row_key, std)
    with jax.enable_x64(True):
        gotz = np.asarray(ops.forecast_z(_U64(fold), rows, _U64(row_key),
                                         std, block_r=16, block_w=16))
    np.testing.assert_array_equal(wantz, gotz)


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2 ** 31 - 1),
           row_key=st.integers(0, 2 ** 32 - 1),
           segment=st.integers(0, 10 ** 6))
    def test_counter_hash_key_sweep(seed, row_key, segment):
        _key_sweep_case(seed, row_key, segment)

except ImportError:  # pragma: no cover - optional dev dep

    @pytest.mark.parametrize("seed,row_key,segment", [
        (0, 0, 0), (1, 1, 1), (2 ** 31 - 1, 2 ** 32 - 1, 10 ** 6),
        (12345, 99991, 86_400), (7, 2 ** 24, 65_535), (42, 3, 1_000_003),
    ])
    def test_counter_hash_key_sweep(seed, row_key, segment):
        """Seeded fallback sweep when hypothesis is unavailable."""
        _key_sweep_case(seed, row_key, segment)
