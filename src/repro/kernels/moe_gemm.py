"""Pallas TPU grouped (per-expert) matmul for the MoE layer.

Computes out[e] = x[e] @ w[e] for the capacity-packed expert buffer
x: [E, C, d], w: [E, d, f]. The expert dim is the outer (parallel) grid
axis — on an expert-parallel sharding each core loops only over its local
experts. Tiles are MXU-aligned (bc × bd)·(bd × bf) with an fp32 VMEM
accumulator carried across the (sequential, innermost) d-block axis.

This is the TPU-native replacement for the CUDA grouped-GEMM the paper's
clients would use: instead of dynamic per-expert kernels, a static
fixed-capacity grid that the systolic array streams through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_gemm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nd):
    dk = pl.program_id(3)

    @pl.when(dk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)      # [bc, bd]
    w = w_ref[0].astype(jnp.float32)      # [bd, bf]
    acc_ref[...] += jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())))

    @pl.when(dk == nd - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_gemm(x, w, *, block_c: int = 128, block_f: int = 128,
             block_d: int = 128, interpret: bool = False):
    """x: [E, C, d]; w: [E, d, f] -> [E, C, f]."""
    E, C, d = x.shape
    _, _, f = w.shape
    bc, bf, bd = min(block_c, C), min(block_f, f), min(block_d, d)
    assert C % bc == 0 and f % bf == 0 and d % bd == 0
    grid = (E, C // bc, f // bf, d // bd)
    kernel = functools.partial(_moe_gemm_kernel, nd=d // bd)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, i, j, kd: (e, i, kd)),
            pl.BlockSpec((1, bd, bf), lambda e, i, j, kd: (e, kd, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j, kd: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
