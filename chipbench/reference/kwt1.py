"""KWT-1 as the ``kwt1`` configuration states it, in plain float32.

Keyword Transformer (Berg et al. 2021, arXiv:2104.00769), KWT-1 widths: a
class token and 98 MFCC patches of 40 coefficients projected to d 64, 12
pre-norm blocks of single-head attention and a GELU MLP of 256, and a
linear head on the class token. The configuration file lists where the
model departs from the paper: RMSNorm with a gain and no bias in place of
LayerNorm, no bias in any projection, no norm before the head.

Also here, as they belong to this model: the weights drawn from a key (in
the layout the trained model keeps them), the synthetic speech shards, and
the FLOPs of one trained sample.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.ops import (F32, cross_entropy, gelu_tanh, mm,
                                     rmsnorm)

CONTROL = "bf16x3"  # float32 at ``highest``: the control is ``high``
BLOCK_ROWS = 128    # rows of one forward pass in the check


def _sizes(cfg):
    m = cfg["model"]
    return (m["d"], m["layers"], m["heads"], m["mlp"], m["n_patches"],
            m["n_mfcc"], m["n_classes"])


def init_params(cfg, key):
    """Weights from ``key``: normal with scale 1/sqrt(fan-in), 0.02 for the
    position table and the class token, unit norm gains."""
    d, L, _, mlp, P, F, C = _sizes(cfg)
    ks = jax.random.split(key, 8)

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, F32) / np.sqrt(fan_in)

    return {
        "patch_proj": dense(ks[0], (F, d), F),
        "pos": 0.02 * jax.random.normal(ks[1], (P + 1, d), F32),
        "cls": 0.02 * jax.random.normal(ks[2], (d,), F32),
        "blocks": {
            "ln1": jnp.ones((L, d), F32), "ln2": jnp.ones((L, d), F32),
            "wqkv": dense(ks[3], (L, d, 3 * d), d),
            "wo": dense(ks[4], (L, d, d), d),
            "w1": dense(ks[5], (L, d, mlp), d),
            "w2": dense(ks[6], (L, mlp, d), mlp),
        },
        "head": dense(ks[7], (d, C), d),
    }


def logits(params, batch, cfg, mode: str = "highest"):
    d, L, H, _, _, _, _ = _sizes(cfg)
    dh = d // H
    x = mm("bpf,fd->bpd", batch["mfcc"], params["patch_proj"], mode)
    B = x.shape[0]
    cls = jnp.broadcast_to(params["cls"].astype(F32), (B, 1, d))
    x = jnp.concatenate([cls, x], 1) + params["pos"][None]
    S = x.shape[1]
    for i in range(L):
        p = {k: v[i] for k, v in params["blocks"].items()}
        h = rmsnorm(x, p["ln1"])
        q, k, v = jnp.split(mm("bsd,de->bse", h, p["wqkv"], mode), 3, -1)
        q, k, v = (t.reshape(B, S, H, dh) for t in (q, k, v))
        a = jax.nn.softmax(mm("bshd,bthd->bhst", q, k, mode) / np.sqrt(dh),
                           -1)
        o = mm("bhst,bthd->bshd", a, v, mode).reshape(B, S, d)
        x = x + mm("bsd,de->bse", o, p["wo"], mode)
        h = rmsnorm(x, p["ln2"])
        x = x + mm("bsf,fd->bsd",
                   gelu_tanh(mm("bsd,df->bsf", h, p["w1"], mode)), p["w2"],
                   mode)
    return mm("bd,dc->bc", x[:, 0], params["head"], mode)


def grad_fn(cfg, mode: str = "highest"):
    """``(params, batch) -> (mean cross-entropy, float32 gradients)``."""
    def task(params, batch):
        return cross_entropy(logits(params, batch, cfg, mode),
                             batch["labels"])
    return jax.jit(jax.value_and_grad(task))


def flops_per_sample(cfg) -> float:
    """Forward and backward FLOPs of one trained sample (3x the forward's
    matrix products), at the published widths, with no recompute."""
    d, L, _, mlp, P, F, C = _sizes(cfg)
    S = P + 1
    per_layer = 2 * S * d * 3 * d + 2 * 2 * S * S * d + 2 * S * d * d \
        + 2 * 2 * S * d * mlp
    forward = 2 * P * F * d + L * per_layer + 2 * d * C
    return 3.0 * forward


def shard_sizes(cfg, n_clients: int, rng: np.random.Generator) -> np.ndarray:
    """Samples per client: uniform over the configured range, as the
    paper's fleet (``make_paper_registry``) draws them by default."""
    lo, hi = cfg["data"]["samples_per_client"]
    return rng.integers(lo, hi, n_clients)


def make_data(cfg, sizes, rng: np.random.Generator):
    """Synthetic MFCC patches: a prototype per class plus unit noise.
    Returns one ``{"mfcc", "labels"}`` shard per client and the test set."""
    _, _, _, _, P, F, C = _sizes(cfg)
    n = int(np.sum(sizes)) + cfg["data"]["n_test"]
    protos = rng.standard_normal((C, P, F), dtype=np.float32)
    labels = rng.integers(0, C, n).astype(np.int32)
    x = rng.standard_normal((n, P, F), dtype=np.float32)
    for i in range(0, n, 4096):  # in place, a block at a time
        x[i:i + 4096] += protos[labels[i:i + 4096]]
    edges = np.cumsum(np.concatenate([[0], sizes]))
    shards = [{"mfcc": x[a:b], "labels": labels[a:b]}
              for a, b in zip(edges[:-1], edges[1:])]
    test = {"mfcc": x[edges[-1]:], "labels": labels[edges[-1]:]}
    return shards, test
