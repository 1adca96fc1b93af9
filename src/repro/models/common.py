"""Common building blocks for the pure-JAX model stack.

Everything here is functional: parameter pytrees in, arrays out. No flax.
Layer parameters are stacked along a leading ``L`` axis and consumed via
``jax.lax.scan`` so compiled HLO size is O(1) in depth.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture description. One instance per assigned architecture."""

    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0  # 0 -> d_model // n_heads

    # attention variant: 'full' or 'swa' (sliding window)
    attn_variant: str = "full"
    window: int = 4096

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25

    # KV-cache storage dtype for decode: None -> activation dtype;
    # jnp.float8_e4m3fn halves cache bytes (beyond-paper §Perf option)
    cache_dtype: Any = None

    # MoE dispatch: 'grouped' = GShard-style per-data-shard packing (local
    # scatter + einsum all-to-all, TPU-native); 'flat' = single global
    # capacity buffer (generic scatter — the naive baseline, kept for the
    # §Perf before/after)
    moe_dispatch: str = "grouped"

    # SSM (rwkv6 / mamba branch)
    ssm_state: int = 0

    # hybrid: fraction of compute in the SSM branch handled in ssm.py
    hybrid: bool = False

    # enc-dec
    encoder_layers: int = 0  # >0 -> encoder-decoder model
    encoder_window: int = 0  # local attention window for the (audio) encoder

    # vlm / audio frontend stub: number of embedding positions provided
    # directly as dense vectors by input_specs() instead of token ids.
    n_frontend_embeds: int = 0

    # padding for shardability: physical head counts (logical heads keep the
    # exact numbers above; padding heads are masked to zero contribution).
    n_heads_padded: int = 0
    n_kv_heads_padded: int = 0

    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32       # activation dtype
    param_dtype: Any = jnp.float32
    tie_embeddings: bool = False

    # citation for the source model card / paper
    source: str = ""

    # physical vocab rows (0 -> auto: vocab rounded up to a multiple of 64
    # when not already divisible by 16, so the lm_head/logits shard over
    # the model axis; padded columns are masked to -inf — §Perf finding:
    # unshardable vocabs forced ~1 GiB logits gathers per decode step)
    vocab_padded: int = 0

    def __post_init__(self):
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // max(self.n_heads, 1))
        if self.vocab_padded == 0:
            vp = self.vocab if self.vocab % 16 == 0 else -(-self.vocab // 64) * 64
            object.__setattr__(self, "vocab_padded", vp)
        if self.n_heads_padded == 0:
            object.__setattr__(self, "n_heads_padded", self.n_heads)
        if self.n_kv_heads_padded == 0:
            object.__setattr__(self, "n_kv_heads_padded", self.n_kv_heads)

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    def param_count(self) -> int:
        """Approximate parameter count (for 6ND model-flops accounting)."""
        d, v = self.d_model, self.vocab
        n = v * d  # embedding
        if not self.tie_embeddings:
            n += v * d
        per_layer = 0
        if self.family != "ssm":
            H, KV, dh = self.n_heads_padded, self.n_kv_heads_padded, self.d_head
            per_layer += d * H * dh + 2 * d * KV * dh + H * dh * d
        if self.family == "ssm":
            # rwkv6: r,k,v,g,o projections + decay lora + channel mix
            per_layer += 5 * d * d + 3 * d * self.d_ff
        elif self.hybrid:
            per_layer += 4 * d * d  # mamba branch in/out/gate/dt
            per_layer += 3 * d * self.d_ff
        if self.n_experts > 0:
            per_layer += d * self.n_experts  # router
            per_layer += 3 * self.n_experts * d * self.moe_d_ff
            per_layer += 3 * self.n_shared_experts * d * self.moe_d_ff
        elif self.family != "ssm":
            per_layer += 3 * d * self.d_ff
        per_layer += 2 * d  # norms
        n += self.n_layers * per_layer
        if self.encoder_layers:
            enc_layer = 4 * d * d + 3 * d * self.d_ff + 2 * d
            n += self.encoder_layers * enc_layer
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if self.n_experts == 0:
            return self.param_count()
        full = self.param_count()
        expert_p = 3 * self.n_experts * self.d_model * self.moe_d_ff * self.n_layers
        active_e = 3 * (self.top_k + self.n_shared_experts) * self.d_model * self.moe_d_ff * self.n_layers
        return full - expert_p + active_e


# ---------------------------------------------------------------------------
# initializers


def _normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def dense_init(key, d_in, shape, dtype):
    """Truncated-normal-ish fan-in init."""
    return _normal(key, shape, 1.0 / math.sqrt(d_in), dtype)


def embed_init(key, vocab, d, dtype):
    return _normal(key, (vocab, d), 0.02, dtype)


# ---------------------------------------------------------------------------
# primitive layers


def rmsnorm(x, gamma, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * gamma.astype(jnp.float32)).astype(dt)


def swiglu(x, w1, w3, w2):
    """SwiGLU MLP: silu(x@w1) * (x@w3) @ w2. Hidden activations are
    pinned to the tensor-parallel (model) axis."""
    h = jax.nn.silu(x @ w1) * (x @ w3)
    h = maybe_shard(h, *((BATCH_AXES,) + (None,) * (h.ndim - 2) + ("model",)))
    return h @ w2


def rope_freqs(d_head: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, d_head, 2) / d_head))


def apply_rope(x, positions, theta):
    """x: [..., S, H, dh]; positions: [..., S] int32."""
    dh = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(dh, theta), dtype=jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, dh/2]
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def vocab_mask(cfg: ModelConfig):
    """Static additive mask (-inf on padded vocab columns), or None."""
    if cfg.vocab_padded == cfg.vocab:
        return None
    m = np.zeros((cfg.vocab_padded,), dtype=np.float32)
    m[cfg.vocab:] = -1e30
    return jnp.asarray(m)


def head_mask(cfg: ModelConfig):
    """Static 0/1 mask zeroing the padded attention heads.

    Padded heads exist only so the head dim is divisible by the model mesh
    axis; masking their outputs keeps the math identical to the logical
    (unpadded) architecture.
    """
    if cfg.n_heads_padded == cfg.n_heads:
        return None
    m = np.zeros((cfg.n_heads_padded,), dtype=np.float32)
    m[: cfg.n_heads] = 1.0
    return jnp.asarray(m)


# ---------------------------------------------------------------------------
# activation sharding constraints
#
# GSPMD's propagation alone replicates the attention/FFN inner dimensions on
# the model axis for several of our einsum chains (verified on the compiled
# HLO: score matmuls carried all heads per device). Production frameworks pin
# activation shardings explicitly; ``maybe_shard`` applies a constraint only
# when an ambient mesh with the named axes is present (so the same model code
# runs unsharded in tests/CPU training). The ambient mesh is the one set by
# ``jax.set_mesh``; its axes must be ``Auto`` (launch/train.py builds them
# so), and a constraint that cannot apply raises.

BATCH_AXES = "__batch__"  # role: ('pod','data') when pod exists, else 'data'


def _ambient_mesh():
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def maybe_shard(x, *entries):
    """with_sharding_constraint guarded by ambient-mesh presence,
    axis-name availability, and dimension divisibility."""
    mesh = _ambient_mesh()
    if mesh is None or x is None:
        return x
    names = set(mesh.axis_names)
    sizes = dict(mesh.shape)
    spec = []
    for d, entry in enumerate(entries):
        if entry == BATCH_AXES:
            entry = tuple(a for a in ("pod", "data") if a in names)
            entry = entry if entry else None
        if entry is None:
            spec.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a in names)
        if not axes:
            spec.append(None)
            continue
        size = int(np.prod([sizes[a] for a in axes]))
        if size <= 1 or x.shape[d] % size != 0:
            spec.append(None)
        else:
            spec.append(axes if len(axes) > 1 else axes[0])
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*spec))


def cross_entropy_loss(logits, labels, mask=None):
    """Mean token-level cross entropy. logits [..., V] fp32-cast inside."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
