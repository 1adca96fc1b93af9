"""The check's arithmetic on the CPU. A gap where the program and the
reference agree exactly reads 0, also where both are 0; a gap where a value
is not finite reads ``inf``; no number reads NaN. Where no value is near 0,
every number reads what it read before zero and non-finite values were
handled, bit for bit."""
import math
from functools import reduce

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import check

DTYPES = [jnp.bfloat16, jnp.float32]
N_UPDATES, N = 10, 1000
# the least scale at which the unit is the dtype's spacing, a normal float32
LEAST_SCALE = {jnp.bfloat16: 2.0 ** -119, jnp.float32: 2.0 ** -103}


@jax.jit
def ulps_before(got, want, updates):
    """``aggregate_ulps`` as it was computed before: an element that is 0
    everywhere read 0/0, since XLA flushes the subnormal unit to 0."""
    def leaf(g, w, *us):
        eps = jnp.finfo(g.dtype).eps
        scale = reduce(jnp.maximum, [jnp.abs(w)] + [
            jnp.abs(u.astype(jnp.float32)) for u in us])
        tiny = jnp.finfo(g.dtype).tiny
        unit = eps * jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(scale, tiny))))
        return jnp.max(jnp.abs(g.astype(jnp.float32) - w) / unit)
    return jnp.max(jnp.stack(jax.tree.leaves(
        jax.tree.map(leaf, got, want, *updates))))


def random_updates(dtype, zeros=(), seed=0):
    """Seeded updates of a two-leaf tree, with the elements ``zeros`` of
    leaf ``a`` set to 0 in every update; and FedAvg weights from steps."""
    rng = np.random.default_rng(seed)
    updates = []
    for _ in range(N_UPDATES):
        a = rng.normal(size=N)
        a[list(zeros)] = 0.0
        updates.append({"a": jnp.asarray(a, dtype),
                        "b": jnp.asarray(1e-3 * rng.normal(size=(20, 50)),
                                         dtype)})
    steps = rng.integers(1, 51, N_UPDATES).astype(np.float32)
    return updates, list(steps / steps.sum())


def aggregates(updates, weights):
    """The reference's float32 FedAvg, and the program's: that sum stored
    in the weights' dtype."""
    want = check._fedavg(updates, weights)
    dtype = updates[0]["a"].dtype
    return jax.tree.map(lambda x: x.astype(dtype), want), want


@pytest.mark.parametrize("dtype", DTYPES)
def test_ulps_read_as_before_where_no_element_is_zero(dtype):
    updates, weights = random_updates(dtype)
    got, want = aggregates(updates, weights)
    ulps, _ = check._ulps(got, want, updates)
    assert float(ulps) == float(ulps_before(got, want, updates))
    assert 0 <= float(ulps) <= 0.5


@pytest.mark.parametrize("dtype", DTYPES)
def test_elements_zero_everywhere_read_what_the_others_read(dtype):
    zeros = [0, 17, 400, 401, 999]
    updates, weights = random_updates(dtype, zeros)
    got, want = aggregates(updates, weights)
    ulps, _ = check._ulps(got, want, updates)
    rest = np.setdiff1d(np.arange(N), zeros)

    def without(tree):
        return {"a": tree["a"][rest], "b": tree["b"]}

    ulps_rest, _ = check._ulps(without(got), without(want),
                               [without(u) for u in updates])
    assert math.isfinite(float(ulps))
    assert float(ulps) == float(ulps_rest)


@pytest.mark.parametrize("scale", ["one", "least"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_one_unit_in_the_last_place_reads_one(dtype, scale):
    """Three equal updates, weights 1/2, 1/4, 1/4: the dtype holds their
    average exactly; one element of the program's moves by one unit. At the
    least scale the unit is as before, but XLA's ``exp2`` is exact only
    near 0, so the reading is 1 to a few millionths."""
    s = 1.0 if scale == "one" else LEAST_SCALE[dtype]
    rng = np.random.default_rng(1)
    u = {"a": jnp.asarray(s * rng.uniform(1.0, 1.5, N), dtype)}
    weights = [np.float32(0.5), np.float32(0.25), np.float32(0.25)]
    got, want = aggregates([u] * 3, weights)
    assert bool(jnp.all(got["a"].astype(jnp.float32) == want["a"]))
    step = jnp.asarray(float(jnp.finfo(dtype).eps) * s, dtype)
    got = {"a": got["a"].at[7].add(step)}
    ulps, _ = check._ulps(got, want, [u] * 3)
    assert float(ulps) == float(ulps_before(got, want, [u] * 3))
    if scale == "one":
        assert float(ulps) == 1.0
    else:
        assert float(ulps) == pytest.approx(1.0, rel=1e-5)


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_nan_in_the_programs_aggregate_reads_inf_and_is_named(dtype):
    updates, weights = random_updates(dtype, zeros=[3])
    got, want = aggregates(updates, weights)
    got = {"a": got["a"], "b": got["b"].at[2, 3].set(jnp.nan)}
    ulps, at = check._ulps(got, want, updates)
    assert float(ulps) == math.inf
    assert int(at) == 1
    assert check.name_leaf(got, want, updates, int(at)) == (
        "['b']: non-finite in program")
    assert check.name_leaf(got, want, updates, 0) == "['a']: all finite"


@pytest.mark.parametrize("dtype", DTYPES)
def test_an_inf_in_one_update_reads_inf(dtype):
    updates, weights = random_updates(dtype)
    updates[4] = {"a": updates[4]["a"].at[5].set(jnp.inf),
                  "b": updates[4]["b"]}
    got, want = aggregates(updates, weights)
    ulps, at = check._ulps(got, want, updates)
    assert float(ulps) == math.inf
    assert check.name_leaf(got, want, updates, int(at)) == (
        "['a']: non-finite in program, reference, update 4")


def test_leaf_gaps_read_as_before_where_every_norm_is_positive():
    rng = np.random.default_rng(2)
    want = rng.uniform(0.1, 2.0, 30).astype(np.float32)
    got = want * (1 + 1e-4 * rng.normal(size=30)).astype(np.float32)
    keep = check.kept(want)
    keep[:3] = False
    floor = np.maximum(want, np.median(want[keep]))
    before = np.where(keep, np.abs(got - want) / floor, 0.0)
    assert np.array_equal(check.leaf_gaps(got, want, keep), before)


def test_leaf_gaps_of_a_zero_leaf_over_a_zero_median():
    zero = np.zeros(3, np.float32)
    assert np.array_equal(check.leaf_gaps(zero, zero), zero)
    got = np.array([0, 1e-9, 0], np.float32)
    assert list(check.leaf_gaps(got, zero)) == [0, math.inf, 0]


def test_leaf_gaps_read_inf_where_a_norm_is_not_finite():
    want = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    keep = np.array([True, True, True, False])
    got = np.array([1.0, np.nan, 3.0, np.inf], np.float32)
    assert list(check.leaf_gaps(got, want, keep)) == [0, math.inf, 0,
                                                      math.inf]
    assert list(check.leaf_gaps(want, got)) == [0, math.inf, 0, math.inf]


def test_rel_reads_inf_on_non_finite_input():
    assert check.rel(math.nan, 1.0) == math.inf
    assert check.rel(1.0, math.nan) == math.inf
    assert check.rel(math.inf, math.inf) == math.inf
    assert check.rel(np.float32(np.nan), np.float32(1.0)) == math.inf
    assert check.rel(2.0, 4.0) == 0.5
    assert check.rel(0.0, 0.0) == 0.0


def test_sample_loss_gap_reads_zero_on_agreement_and_inf_on_nan():
    want = np.array([0.0, 1.0, 2.0], np.float32)
    assert check.sample_loss_gap(want.astype(np.float64), want) == 0.0
    got = np.array([0.0, 1.5, 2.0])
    assert check.sample_loss_gap(got, want) == 0.5
    assert check.sample_loss_gap(np.array([1e-6, 1.0, 2.0]), want) == (
        math.inf)
    assert check.sample_loss_gap(np.array([0.0, np.nan, 2.0]), want) == (
        math.inf)
    assert check.sample_loss_gap(want, np.array([0.0, 1.0, np.nan])) == (
        math.inf)
    assert check.sample_loss_gap(want[:2], want) == math.inf
