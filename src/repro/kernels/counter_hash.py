"""Pallas counter-hash synthesis kernels for the scheduler hot path.

The repo's first *scheduler-facing* Pallas kernels (the others serve the
client training workloads): the sparse-util piece-grid window and the
forecast-error exponent grid, each as ONE kernel tiled over rows × steps.
A cell's value is pure counter hashing — splitmix64 chain for the per-row
premix, the two-round multiply–xorshift "cheap" mixer per cell — so the
kernel reads only its tile's rows/levels and writes its tile of output:
no cross-tile state, both grid axes are ``parallel``.

Bit-exactness contract: output must equal the NumPy counter-hash
reference (:meth:`repro.backend.base.ArrayBackend.synth_window` /
``forecast_noise_z``) bit-for-bit. The float32 multiply seams
(``(u−½)·amp``, ``t·std``) are fenced against FMA contraction and
reassociation with :func:`round24`, the integer rounding fence that the
fused jit backend (:mod:`repro.backend.jax_backend`) imports from here —
the fence is real integer arithmetic inside the kernel body, so it
survives whatever the surrounding compiler does (docs/backends.md, "fused
ops & dispatch budget").

Execution modes: the mixing chain and the fence are uint64/float64
arithmetic. The wrappers in :mod:`repro.kernels.ops` interpret them off
the TPU, like every other kernel. The v5e compiler refuses them (uint64
lanes fail Mosaic lowering), so the scheduler backends that use them do
not run on a TPU; a 32-bit-limb lowering is the open route (ROADMAP S4).
They must be called under ``jax.enable_x64(True)`` (uint64 keys, float64
rounding fence) — the pallas backend does this; tests use the same
scope.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_U64 = np.uint64


def round24(p):
    """float64 → float32 round-to-nearest-even by integer bit arithmetic.

    The fence for float32 multiply→add and multiply→multiply seams
    inside one executable: compute the product exactly in float64 (two
    24-bit mantissas always fit the 53-bit mantissa), then perform the
    float32 rounding *manually* on the bit pattern. XLA cannot contract
    through it — the rounding is real integer arithmetic, not a
    ``convert`` it may elide — so the result is bit-identical to
    NumPy's independently-rounded float32 op chain. Inputs are products
    of finite normal float32 values (plus exact zeros), so subnormal /
    overflow handling is unnecessary; ``p == 0`` keeps its sign.
    """
    U = jnp.uint64
    u = jax.lax.bitcast_convert_type(p, jnp.uint64)
    sign = (u >> U(63)).astype(jnp.uint32) << jnp.uint32(31)
    exp = ((u >> U(52)) & U(0x7FF)).astype(jnp.int64) - 1023
    mant = u & U((1 << 52) - 1)
    keep = (mant >> U(29)).astype(jnp.int64)
    rest = mant & U((1 << 29) - 1)
    half = 1 << 28
    up = (rest > half) | ((rest == half) & ((keep & 1) == 1))
    keep = keep + up.astype(jnp.int64)
    ovf = keep >> 23
    keep = jnp.where(ovf == 1, 0, keep)
    exp32 = (exp + ovf + 127).astype(jnp.uint32) << jnp.uint32(23)
    bits = sign | exp32 | keep.astype(jnp.uint32)
    out = jax.lax.bitcast_convert_type(bits.astype(jnp.uint32), jnp.float32)
    return jnp.where(p == 0.0, jnp.float32(0.0) * p.astype(jnp.float32), out)


def _sm64(x):
    """splitmix64 finalizer over uint64 lanes (traced)."""
    x = x + _U64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)
    return x ^ (x >> _U64(31))


def _mix_cheap(h):
    """two-round multiply–xorshift mixer → float32 uniform in [0, 1)."""
    h = h * _U64(0xFF51AFD7ED558CCD)
    h = h ^ (h >> _U64(32))
    h = h * _U64(0xC4CEB9FE1A85EC53)
    h = h ^ (h >> _U64(29))
    return (h >> _U64(40)).astype(jnp.float32) * np.float32(2.0 ** -24)


def _piece_window_kernel(fold_ref, t0_ref, amp_ref, levels_ref, slot_ref,
                         rows_ref, o_ref, *, block_w: int):
    """One [block_r, block_w] tile: level gather + cell noise + clip."""
    j = pl.program_id(1)
    util = jnp.take_along_axis(levels_ref[...], slot_ref[...], axis=1)
    t = (t0_ref[0, 0] + j.astype(jnp.int64) * block_w
         + jax.lax.broadcasted_iota(jnp.int64, (1, block_w), 1)
         ).astype(jnp.uint64)
    key = (rows_ref[...] << _U64(24)) ^ t
    u = _mix_cheap(key ^ fold_ref[0, 0])
    noise = round24((u - np.float32(0.5)).astype(jnp.float64)
                    * amp_ref[0, 0].astype(jnp.float64))
    o_ref[...] = jnp.clip(util + noise, 0.0, 1.0)


def _forecast_z_kernel(fold_ref, now_ref, rows_ref, std_ref, o_ref, *,
                       block_w: int):
    """One tile of the pre-``exp`` forecast exponent: splitmix64 row
    premix + cheap mixer + the two fenced float32 scale multiplies."""
    j = pl.program_id(1)
    fold = fold_ref[0, 0]
    row_h = _sm64(rows_ref[...] ^ fold)                       # [br, 1]
    leads = (_U64(1) + (j.astype(jnp.int64) * block_w).astype(jnp.uint64)
             + jax.lax.broadcasted_iota(jnp.uint64, (1, block_w), 1))
    key = row_h ^ ((now_ref[0, 0] << _U64(20)) + leads)
    u = _mix_cheap(key ^ fold)
    t = round24((u - np.float32(0.5)).astype(jnp.float64)
                * np.float64(np.float32(np.sqrt(12.0))))
    o_ref[...] = round24(t.astype(jnp.float64)
                         * std_ref[...].astype(jnp.float64))


def _scalar(v, dtype):
    return jnp.asarray(v, dtype).reshape(1, 1)


def piece_window(levels, slot, fold, rows, t0, amp, *, block_r: int = 256,
                 block_w: int = 256, interpret: bool = False):
    """[R, W] sparse-util window (gather + noise + clip) in one kernel.

    levels: [R, S] f32 per-slot levels; slot: [R, W] int64 slot index per
    step; rows: [R] uint64 row keys; fold/t0/amp: scalars. R and W must
    be multiples of the block sizes (callers pad to shape buckets).
    """
    R, S = levels.shape
    W = slot.shape[1]
    br, bw = min(block_r, R), min(block_w, W)
    assert R % br == 0 and W % bw == 0, (R, W, br, bw)
    grid = (R // br, W // bw)
    kernel = functools.partial(_piece_window_kernel, block_w=bw)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),        # fold
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),        # t0
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),        # amp
            pl.BlockSpec((br, S), lambda i, j: (i, 0)),       # levels
            pl.BlockSpec((br, bw), lambda i, j: (i, j)),      # slot
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),       # rows
        ],
        out_specs=pl.BlockSpec((br, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(_scalar(fold, jnp.uint64), _scalar(t0, jnp.int64),
      _scalar(amp, jnp.float32), jnp.asarray(levels),
      jnp.asarray(slot, jnp.int64),
      jnp.asarray(rows, jnp.uint64).reshape(-1, 1))


def forecast_z(fold, rows, now, std, *, block_r: int = 256,
               block_w: int = 256, interpret: bool = False):
    """[R, W] pre-``exp`` forecast-error exponent in one kernel.

    rows: [R] uint64 registry rows; std: [W] f32 per-lead spread;
    fold/now: scalars. R and W must be multiples of the block sizes.
    """
    R = int(rows.shape[0])
    W = int(std.shape[0])
    br, bw = min(block_r, R), min(block_w, W)
    assert R % br == 0 and W % bw == 0, (R, W, br, bw)
    grid = (R // br, W // bw)
    kernel = functools.partial(_forecast_z_kernel, block_w=bw)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),        # fold
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),        # now
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),       # rows
            pl.BlockSpec((1, bw), lambda i, j: (0, j)),       # std
        ],
        out_specs=pl.BlockSpec((br, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(_scalar(fold, jnp.uint64), _scalar(now, jnp.uint64),
      jnp.asarray(rows, jnp.uint64).reshape(-1, 1),
      jnp.asarray(std, jnp.float32).reshape(1, -1))
