"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to :func:`_default_interpret`: True only off the
TPU, so the kernels run (and are tested) on CPU, and on a TPU every
kernel compiles to Mosaic. Nothing here interprets because it runs on a
TPU; a kernel the TPU compiler refuses fails there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .counter_hash import forecast_z as _forecast_z
from .counter_hash import piece_window as _piece_window
from .flash_attention import flash_attention as _flash
from .moe_gemm import moe_gemm as _moe_gemm
from .rwkv_scan import rwkv_scan as _rwkv_scan


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _flash(q, k, v, causal=causal, window=window,
                  block_q=block_q, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d",
                                             "interpret"))
def moe_gemm(x, w, block_c: int = 128, block_f: int = 128, block_d: int = 128,
             interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _moe_gemm(x, w, block_c=block_c, block_f=block_f, block_d=block_d,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv_scan(r, k, v, w, u, chunk: int = 32, interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _rwkv_scan(r, k, v, w, u, chunk=chunk, interpret=interpret)


# the counter-hash synthesis kernels trace uint64/float64 — call under
# jax.enable_x64(True) (the pallas backend and the parity tests do)
@functools.partial(jax.jit, static_argnames=("block_r", "block_w",
                                             "interpret"))
def piece_window(levels, slot, fold, rows, t0, amp, block_r: int = 256,
                 block_w: int = 256, interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _piece_window(levels, slot, fold, rows, t0, amp,
                         block_r=block_r, block_w=block_w,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_r", "block_w",
                                             "interpret"))
def forecast_z(fold, rows, now, std, block_r: int = 256,
               block_w: int = 256, interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _forecast_z(fold, rows, now, std, block_r=block_r,
                       block_w=block_w, interpret=interpret)
