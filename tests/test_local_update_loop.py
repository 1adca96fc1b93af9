"""``JaxTrainer.local_update`` runs a client's update as one device program.
It must compute what a plain per-step loop computes: the same batches drawn
in the same order, then the probe, the same FedProx/SGD steps and the same
per-sample losses. The replay below is that loop, written here on its own:
one jitted step per batch, one loss read back per step."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.trainers import JaxTrainer
from repro.data.federated import FederatedData, synthetic_classification
from repro.models import ConvNet

NAMES = ["big", "small"]
MAX_STEPS = 6
BATCH = 10


def make_trainer(momentum: float) -> JaxTrainer:
    data = synthetic_classification(1, NAMES[:1], n_classes=4, n_samples=200,
                                    hw=4, seed=3, n_test=16)
    big = data.client_data["big"]
    # a shard smaller than the batch: every draw is shorter than BATCH
    data.client_data["small"] = {k: v[:6] for k, v in big.items()}
    return JaxTrainer(ConvNet(n_classes=4, channels=(4,), hw=4), data,
                      lr=0.05, batch_size=BATCH, prox_mu=0.1,
                      momentum=momentum, seed=11,
                      max_steps_per_round=MAX_STEPS, client_names=NAMES)


def record_draws(data: FederatedData) -> list:
    calls = []
    inner = data.sample_batch

    def sample_batch(client, batch_size, rng):
        out = inner(client, batch_size, rng)
        calls.append((client, batch_size, out))
        return out

    data.sample_batch = sample_batch
    return calls


def replay(tr: JaxTrainer, row: int, n_batches: float) -> dict:
    """The update as a host loop of jitted steps, from the same state."""
    @jax.jit
    def step(params, opt_state, batch, global_params):
        loss, grads = jax.value_and_grad(tr._local_loss)(
            params, batch, global_params)
        params, opt_state = tr.opt.update(grads, opt_state, params)
        return params, opt_state, loss

    @jax.jit
    def nll(params, batch):
        logits = tr.model.logits_fn(params, batch).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, batch["labels"][:, None],
                                   axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold

    client = tr._names[row]
    steps = int(min(max(1, round(n_batches)), tr.max_steps))
    params, opt_state = tr.params, tr.opt.init(tr.params)
    losses = []
    for _ in range(steps):
        batch = tr.data.sample_batch(client, tr.batch_size, tr.rng)
        params, opt_state, loss = step(params, opt_state, batch, tr.params)
        losses.append(float(loss))
    probe = tr.data.sample_batch(client, 4 * tr.batch_size, tr.rng)
    return {"params": params, "losses": losses,
            "sample_losses": np.asarray(nll(params, probe))}


@pytest.mark.parametrize("momentum", [0.0, 0.9])
@pytest.mark.parametrize("row", [0, 1], ids=NAMES)
@pytest.mark.parametrize("steps", [1, 3, MAX_STEPS])
def test_the_device_loop_matches_a_per_step_replay(steps, row, momentum):
    got_tr, want_tr = make_trainer(momentum), make_trainer(momentum)
    got_calls = record_draws(got_tr.data)
    want_calls = record_draws(want_tr.data)
    got = got_tr.local_update(row, float(steps))
    want = replay(want_tr, row, float(steps))

    assert len(got_calls) == len(want_calls) == steps + 1
    assert [(c, n) for c, n, _ in got_calls] == (
        [(NAMES[row], BATCH)] * steps + [(NAMES[row], 4 * BATCH)])
    for (_, _, a), (_, _, b) in zip(got_calls, want_calls):
        for k in b:
            np.testing.assert_array_equal(a[k], b[k])
    assert (got_tr.rng.bit_generator.state
            == want_tr.rng.bit_generator.state)

    assert got["weight"] == float(steps * BATCH)
    assert got["mean_loss"] == float(np.mean(want["losses"]))
    np.testing.assert_array_equal(got["sample_losses"],
                                  want["sample_losses"])
    # float32 rounding: the loop may fuse the step's ops otherwise
    eps = float(np.finfo(np.float32).eps)
    for a, b in zip(jax.tree.leaves(got["params"]),
                    jax.tree.leaves(want["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=4 * eps, atol=4 * eps)
