"""The FLOPs per trained sample that ``train_mfu`` counts, against XLA's
own count of the reference's forward and backward pass at a small size.

XLA counts every operation, elementwise ones too, so its count may exceed
the matrix products the formula counts, by a few percent at these sizes
(3.3% for KWT with two layers, 0.2% for the LM); never the other way. XLA
counts the body of a loop once, so the LM is taken with one layer; and the
reference computes the masked half of causal attention, which the formula
leaves out."""
import copy

import jax
import jax.numpy as jnp
import pytest

from chipbench import bench
from chipbench.reference.ops import cross_entropy

SEQ = 128


def _xla_flops(cell, cfg, batch):
    ref = cell.reference
    params = jax.eval_shape(lambda k: ref.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape,
                                                         jnp.float32), params)

    def loss(p, b):
        return cross_entropy(ref.logits(p, b, cfg), b["labels"])

    compiled = jax.jit(jax.value_and_grad(loss)).lower(params,
                                                       batch).compile()
    return compiled.cost_analysis()["flops"]


def test_kwt1_flops_per_sample():
    cell = bench.find_cell("kwt1.paper100")
    cfg = copy.deepcopy(cell.config)
    cfg["model"]["layers"] = 2
    m = cfg["model"]
    batch = {"mfcc": jax.ShapeDtypeStruct((1, m["n_patches"], m["n_mfcc"]),
                                          jnp.float32),
             "labels": jax.ShapeDtypeStruct((1,), jnp.int32)}
    ratio = _xla_flops(cell, cfg, batch) / cell.reference.flops_per_sample(
        cfg)
    assert 1.0 <= ratio <= 1.05, ratio


def test_smollm_flops_per_sequence():
    cell = bench.find_cell("smollm-360m.paper100")
    cfg = copy.deepcopy(cell.config)
    cfg["model"].update(num_hidden_layers=1, vocab_size=4096)
    cfg["data"]["seq"] = SEQ
    m = cfg["model"]
    tokens = jax.ShapeDtypeStruct((1, SEQ), jnp.int32)
    dh = m["hidden_size"] // m["num_attention_heads"]
    masked_half = 3 * 2 * m["num_attention_heads"] * dh * SEQ * SEQ
    want = cell.reference.flops_per_sample(cfg) + masked_half
    ratio = _xla_flops(cell, cfg, {"tokens": tokens,
                                   "labels": tokens}) / want
    assert 1.0 <= ratio <= 1.05, ratio


@pytest.mark.parametrize("name,gflop", [("kwt1.paper100", 0.4422),
                                        ("smollm-360m.paper100", 567.74)])
def test_full_size_flops(name, gflop):
    """The published widths: 0.44 GFLOP per KWT-1 sample, 2.22 GFLOP per
    SmolLM-360M token over 256 tokens."""
    cell = bench.find_cell(name)
    got = cell.reference.flops_per_sample(cell.config) / 1e9
    assert got == pytest.approx(gflop, rel=1e-3)
