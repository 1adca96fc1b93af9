"""Primitives shared by the plain references.

Every matrix product names its precision, so that one forward pass serves as
the reference and as the control:

- ``highest``: float32 products at ``Precision.HIGHEST``. The reference.
- ``bf16x3``: three bfloat16 passes, hi*hi + hi*lo + lo*hi, which is what
  ``Precision.HIGH`` computes on a TPU, written out so that it computes the
  same on every backend. The control of a float32 configuration.
- ``fp8``: each operand scaled by its largest magnitude onto the range of
  float8_e4m3fn, rounded to it, and scaled back; products summed in
  float32. In the backward pass the gradient that reaches each operand is
  scaled and rounded to float8_e5m2 the same way, as fp8 training does.
  The control of a bfloat16 configuration.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
MODES = ("highest", "bf16x3", "fp8")


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(F32)


def _scaled(x, dtype):
    """``x`` scaled by its largest magnitude onto ``dtype``'s range, rounded
    to it and scaled back."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(
        jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _fp8(x):
    return _scaled(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_scaled(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def mm(spec: str, a, b, mode: str):
    """``jnp.einsum(spec, a, b)`` in float32 with the products of ``mode``."""
    a, b = a.astype(F32), b.astype(F32)
    ein = partial(jnp.einsum, spec, precision=jax.lax.Precision.HIGHEST)
    if mode == "highest":
        return ein(a, b)
    if mode == "bf16x3":
        a_hi, b_hi = _bf16(a), _bf16(b)
        a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
        return ein(a_hi, b_hi) + (ein(a_hi, b_lo) + ein(a_lo, b_hi))
    if mode == "fp8":
        return ein(_fp8(a), _fp8(b))
    raise ValueError(f"unknown precision mode {mode!r}; use one of {MODES}")


def rmsnorm(x, gamma, eps: float = 1e-5):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma.astype(F32)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


def cross_entropy(logits, labels):
    """Mean negative log-likelihood of ``labels`` over every position."""
    logits = logits.astype(F32)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def row_nll(logits, labels):
    """Negative log-likelihood of ``labels`` for each row, the mean over a
    row's positions where it has several."""
    logits = logits.astype(F32)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    nll = jax.nn.logsumexp(logits, -1) - gold
    return nll.reshape(nll.shape[0], -1).mean(-1)


def fedprox_sgd(grad_fn, params, batches, lr: float, mu: float):
    """Plain FedProx local training from ``params``, which is also the
    global model: per step the loss is the task loss plus
    ``mu/2 * |w - w_global|^2``, and SGD moves each weight by ``lr`` times
    its gradient in float32, then stores it in the weight's own dtype.

    ``grad_fn(params, batch) -> (task_loss, float32 grads)``. Returns the
    loss of each step, the parameters after the first step and those after
    the last.
    """
    w_global = params
    losses, first = [], None
    w = params
    for batch in batches:
        task, grads = grad_fn(w, batch)
        losses.append(float(task) + 0.5 * mu * float(_sq_dist(w, w_global)))
        w = _sgd(w, grads, w_global, lr, mu)
        del grads
        if first is None:
            first = w
    return losses, first, w


@jax.jit
def _sq_dist(a, b):
    return sum(jnp.sum(jnp.square(x.astype(F32) - y.astype(F32)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@partial(jax.jit, static_argnums=(3, 4))
def _sgd(w, grads, w_global, lr, mu):
    def one(x, g, x0):
        g = g + mu * (x.astype(F32) - x0.astype(F32))
        return (x.astype(F32) - lr * g).astype(x.dtype)
    return jax.tree.map(one, w, grads, w_global)

