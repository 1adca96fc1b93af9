"""SmolLM-360M as the ``smollm-360m`` configuration states it, in plain
float32.

HuggingFaceTB/SmolLM-360M (``config.json``): a Llama-style decoder of 32
blocks, d 960, 15 query heads over 5 key/value heads of 64 (each key/value
head serves three query heads), RoPE with theta 10000 on the two halves of
each head, RMSNorm with eps 1e-5, a SwiGLU MLP of 2560, and the output head
tied to the 49152-row embedding. Weights are kept in bfloat16, as trained;
this reference computes in float32 from them.

Also here, as they belong to this model: the weights drawn from a key (in
the layout the trained model keeps them), the packed token shards, and the
FLOPs of one trained sequence. The gradient of a batch is summed one row at
a time, so that the float32 pass fits beside the bfloat16 weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.ops import F32, cross_entropy, mm, rmsnorm

CONTROL = "fp8"  # bfloat16 weights and activations: the control is fp8
BLOCK_ROWS = 1   # rows of one forward pass in the check


def _sizes(cfg):
    m = cfg["model"]
    return (m["num_hidden_layers"], m["hidden_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["hidden_size"] // m["num_attention_heads"],
            m["intermediate_size"], m["vocab_size"])


def init_params(cfg, key):
    """Weights from ``key`` in bfloat16: normal with the published
    ``initializer_range`` for the embedding, 1/sqrt(fan-in) for the
    projections, unit norm gains."""
    L, d, H, KV, dh, f, V = _sizes(cfg)
    ks = jax.random.split(key, 8)
    bf16 = jnp.bfloat16

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32) / np.sqrt(fan_in)).astype(bf16)

    return {
        "embed": (cfg["model"]["initializer_range"]
                  * jax.random.normal(ks[0], (V, d), F32)).astype(bf16),
        "blocks": {
            "ln1": jnp.ones((L, d), bf16), "ln2": jnp.ones((L, d), bf16),
            "attn": {"wq": dense(ks[1], (L, d, H, dh), d),
                     "wk": dense(ks[2], (L, d, KV, dh), d),
                     "wv": dense(ks[3], (L, d, KV, dh), d),
                     "wo": dense(ks[4], (L, H, dh, d), H * dh)},
            "ffn": {"w1": dense(ks[5], (L, d, f), d),
                    "w3": dense(ks[6], (L, d, f), d),
                    "w2": dense(ks[7], (L, f, d), f)},
        },
        "final_norm": jnp.ones((d,), bf16),
    }


def _rope(x, theta):
    """Rotate the two halves of each head by position: x [B, S, H, dh]."""
    S, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2) / dh)
    ang = jnp.asarray(np.arange(S)[:, None] * inv[None, :], F32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits(params, batch, cfg, mode: str = "highest"):
    L, d, H, KV, dh, f, V = _sizes(cfg)
    eps = cfg["model"]["rms_norm_eps"]
    theta = cfg["model"]["rope_theta"]
    x = params["embed"][batch["tokens"]].astype(F32)
    S = x.shape[1]
    causal = jnp.where(np.tril(np.ones((S, S), bool)), 0.0, -jnp.inf)

    def block(x, p):
        a, m = p["attn"], p["ffn"]
        h = rmsnorm(x, p["ln1"], eps)
        q = _rope(mm("bsd,dhk->bshk", h, a["wq"], mode), theta)
        k = _rope(mm("bsd,dhk->bshk", h, a["wk"], mode), theta)
        v = mm("bsd,dhk->bshk", h, a["wv"], mode)
        k, v = jnp.repeat(k, H // KV, 2), jnp.repeat(v, H // KV, 2)
        s = mm("bshk,bthk->bhst", q, k, mode) / np.sqrt(dh) + causal
        o = mm("bhst,bthk->bshk", jax.nn.softmax(s, -1), v, mode)
        x = x + mm("bshk,hkd->bsd", o, a["wo"], mode)
        h = rmsnorm(x, p["ln2"], eps)
        g = jax.nn.silu(mm("bsd,df->bsf", h, m["w1"], mode)) \
            * mm("bsd,df->bsf", h, m["w3"], mode)
        return x + mm("bsf,fd->bsd", g, m["w2"], mode), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = rmsnorm(x, params["final_norm"], eps)
    return mm("bsd,vd->bsv", x, params["embed"], mode)


def grad_fn(cfg, mode: str = "highest"):
    """``(params, batch) -> (mean token cross-entropy, float32 gradients)``,
    summed over the batch one sequence at a time."""
    def task(params, row):
        return cross_entropy(logits(params, row, cfg, mode), row["labels"])

    row_grad = jax.jit(jax.value_and_grad(task))
    upcast = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(F32), t))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    scale = jax.jit(lambda t, s: jax.tree.map(lambda x: x * s, t))

    def fn(params, batch):
        w = upcast(params)
        n = batch["tokens"].shape[0]
        total, acc = 0.0, None
        for i in range(n):
            loss, g = row_grad(w, {k: v[i:i + 1] for k, v in batch.items()})
            total += float(loss)
            acc = g if acc is None else add(acc, g)
        return total / n, scale(acc, 1.0 / n)
    return fn


def flops_per_sample(cfg) -> float:
    """Forward and backward FLOPs of one trained sequence (3x the forward's
    matrix products, the tied head included), at the published widths,
    with no recompute. Causal attention counts the half of the score and
    value products it needs."""
    L, d, H, KV, dh, f, V = _sizes(cfg)
    S = cfg["data"]["seq"]
    n_mm = L * (2 * d * H * dh + 2 * d * KV * dh + 3 * d * f) + V * d
    attention = L * 2 * H * dh * S * S
    return 3.0 * (2 * S * n_mm + attention)


def shard_sizes(cfg, n_clients: int, rng: np.random.Generator) -> np.ndarray:
    """Sequences per client: log-normal, as federated text corpora are
    unevenly split, clipped to the configured range."""
    c = cfg["data"]
    n = rng.lognormal(np.log(c["seqs_median"]), c["seqs_sigma"], n_clients)
    return np.clip(n, c["seqs_min"], c["seqs_max"]).astype(np.int64)


def make_data(cfg, sizes, rng: np.random.Generator):
    """Token shards: documents of log-normal length, each ended by token 0,
    of Zipf-distributed token ids, packed back to back into rows of
    ``seq + 1`` tokens. Returns one ``{"tokens", "labels"}`` shard per
    client (labels are the tokens shifted by one) and the test set."""
    c = cfg["data"]
    S, V = c["seq"], cfg["model"]["vocab_size"]
    rows = int(np.sum(sizes)) + c["n_test"]
    n_tok = rows * (S + 1)
    weights = 1.0 / np.arange(1, V) ** c["zipf"]
    ids = 1 + rng.permutation(V - 1)
    cdf = np.cumsum(weights / weights.sum())
    tokens = ids[np.minimum(np.searchsorted(cdf, rng.random(n_tok)),
                            V - 2)].astype(np.int32)
    docs = rng.lognormal(np.log(c["doc_len_median"]), c["doc_len_sigma"],
                         n_tok // 2 + 1).astype(np.int64) + 1
    ends = np.cumsum(docs) - 1
    tokens[ends[ends < n_tok]] = 0
    tokens = tokens.reshape(rows, S + 1)
    edges = np.cumsum(np.concatenate([[0], sizes]))

    def shard(a, b):
        return {"tokens": tokens[a:b, :-1], "labels": tokens[a:b, 1:]}

    return ([shard(a, b) for a, b in zip(edges[:-1], edges[1:])],
            shard(edges[-1], rows))
