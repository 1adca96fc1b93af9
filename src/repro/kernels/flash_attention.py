"""Pallas TPU flash attention (causal / sliding-window), GQA-aware.

TPU adaptation of the blockwise online-softmax algorithm: q/k/v tiles live
in VMEM via BlockSpec; the MXU consumes (bq × dh)·(dh × bk) tiles; running
max/denominator/accumulator sit in VMEM scratch across the (sequential)
key-block grid dimension. Fully-masked key blocks (beyond the causal
frontier or outside the sliding window) are skipped with pl.when — for a
window of W only ~W/bk key blocks per query block do work, which is what
makes the long_500k shapes sub-quadratic.

Block sizes default to MXU-aligned (128, 128); the grid is
(batch, q_heads, q_blocks, k_blocks) with k_blocks innermost ("arbitrary"
semantics — sequential on TPU) so the scratch carry is valid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale, causal, window, bq, bk, seq_k, q_offset):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # global positions of this tile
    q_lo = qi * bq + q_offset          # first query position (key-aligned)
    k_lo = kj * bk

    # block-level skip: entire tile masked out?
    run = True
    if causal:
        run = jnp.logical_and(k_lo <= q_lo + bq - 1, True)
        if window > 0:
            run = jnp.logical_and(run, k_lo + bk - 1 > q_lo - window)

    @pl.when(run if causal else True)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, dh]
        v = v_ref[0, 0].astype(jnp.float32)            # [bk, dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if causal:
            qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            ok = kpos <= qpos
            if window > 0:
                ok = jnp.logical_and(ok, kpos > qpos - window)
            s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale=None, block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: [B, H, S, dh]; k, v: [B, KV, Sk, dh] with H % KV == 0.

    Returns [B, H, S, dh]. Queries are aligned to the END of the key
    sequence (prefill convention when Sk > S).
    """
    B, H, S, dh = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0
    group = H // KV
    bq = min(block_q, S)
    bk = min(block_k, Sk)
    assert S % bq == 0 and Sk % bk == 0, "seq must divide block size"
    scale = float(scale) if scale is not None else 1.0 / (dh ** 0.5)
    q_offset = Sk - S

    grid = (B, H, S // bq, Sk // bk)
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, seq_k=Sk, q_offset=q_offset)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),   # acc
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running denom
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
