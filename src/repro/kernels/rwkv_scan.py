"""Pallas TPU chunked RWKV6 scan (data-dependent-decay linear attention).

The exact recurrence (per head, state S ∈ R^{dh×dh}, key-major):

    out_t = r_t · (S_{t-1} + diag(u) k_t v_t^T)
    S_t   = diag(w_t) S_{t-1} + k_t v_t^T

GPU implementations stream one token per thread-block step; on TPU we use
the *chunked* form so the MXU does the work. For a chunk of T tokens with
inclusive per-channel cumulative decay a_t = Π_{i≤t} w_i:

    out_t = (r_t ⊙ a_{t-1}) · S_in                       (cross-chunk)
          + Σ_{j<t} [(r_t ⊙ a_{t-1}) · (k_j / a_j)] v_j   (intra, matmul)
          + (r_t ⊙ u ⊙ k_t) · v_t                         (diagonal bonus)
    S_out = diag(a_T) S_in + ((a_T / a) ⊙ k)^T @ v

Everything inside a chunk is three (T×dh)·(dh×dh/T) matmuls + a masked
(T×T) correction — MXU food. a_t is exp of the prefix sum of log w, and
that prefix sum is itself a (T×T)·(T×dh) matmul with a lower-triangular
ones matrix: Mosaic has no cumulative-product lowering. The state S (dh×dh fp32) lives in VMEM
scratch and is carried across the sequential chunk grid axis. The k/a
rescaling is numerically safe for chunk sizes ≤64 because w ∈ (0,1) and
fp32 headroom covers 64 steps of the steepest decay used by RWKV6.

Grid: (B·H, S/T) with the chunk axis sequential ("arbitrary").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref, *, T, dh):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)      # [T, dh]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)      # decay in (0, 1)
    u = u_ref[0].astype(jnp.float32)      # [1, dh] bonus

    # inclusive log-decay prefix la_t = Σ_{i≤t} log w_i as a lower-
    # triangular matmul (Mosaic has no cumprod lowering); a_t = exp(la_t)
    logw = jnp.log(w)
    tpos = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    jpos = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    tril = (jpos <= tpos).astype(jnp.float32)
    la = jax.lax.dot_general(tril, logw, (((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST)
    S_in = state_ref[...]                 # [dh, dh]

    rq = r * jnp.exp(la - logw)           # decay-adjusted queries (a_{t-1})
    ks = k * jnp.exp(-la)                 # decay-adjusted keys (k / a)
    # intra-chunk pairwise scores, strictly causal (j < t)
    scores = jax.lax.dot_general(rq, ks, (((1,), (1,)), ((), ())))  # [T, T]
    scores = jnp.where(jpos < tpos, scores, 0.0)
    intra = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())))
    cross = jax.lax.dot_general(rq, S_in, (((1,), (0,)), ((), ())))
    # diagonal bonus term: out_diag_t = ((r_t ⊙ u)·k_t) * v_t
    bonus = ((r * u * k).sum(axis=1, keepdims=True)) * v
    o_ref[0] = (cross + intra + bonus).astype(o_ref.dtype)

    # state update
    laT = la[T - 1:T, :]                  # [1, dh]
    k_scaled = jnp.exp(laT - la) * k      # (a_T / a) ⊙ k, [T, dh]
    state_ref[...] = jnp.exp(laT).T * S_in + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())))


def rwkv_scan(r, k, v, w, u, *, chunk: int = 32, interpret: bool = False):
    """r/k/v/w: [B, S, H, dh]; u: [H, dh]. Returns out [B, S, H, dh] fp32.

    S must be divisible by ``chunk``.
    """
    B, S, H, dh = r.shape
    T = min(chunk, S)
    assert S % T == 0
    # layout: [B*H, S, dh] so each grid row owns one head's stream
    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, dh)

    rb, kb, vb, wb = map(to_bh, (r, k, v, w))
    ub = jnp.broadcast_to(u[None], (B, H, dh)).reshape(B * H, 1, dh)

    grid = (B * H, S // T)
    kernel = functools.partial(_rwkv_kernel, T=T, dh=dh)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, T, dh), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, T, dh), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, T, dh), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, T, dh), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, dh), lambda i, c: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, dh), lambda i, c: (i, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rb, kb, vb, wb, ub)
    return out.reshape(B, H, S, dh).transpose(0, 2, 1, 3)
