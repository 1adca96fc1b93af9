"""Faults planted in a built simulation, for the tests and for
``calibrate.py``; a benchmark run never plants one. Each takes the
simulation and wraps one of the seams the check reads, underneath the
check's own capture: the data object's ``sample_batch``, or the trainer's
``local_update``, ``aggregate`` or ``evaluate``. A one-chip cell exchanges
nothing between chips, so that fault has no place here."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def unchanged(sim):
    """A local update that returns the global model unchanged."""
    tr = sim.trainer
    inner = tr.local_update

    def local_update(row, n_batches):
        out = dict(inner(row, n_batches))
        out["params"] = tr.params
        return out

    tr.local_update = local_update


def half_batch(sim):
    """Half of each batch left out, the mean taken over the rest."""
    data = sim.trainer.data
    inner = data.sample_batch

    def sample_batch(client, batch_size, rng):
        out = inner(client, batch_size, rng)
        n = len(next(iter(out.values())))
        return {k: v[:max(1, n // 2)] for k, v in out.items()}

    data.sample_batch = sample_batch


def altered_update(sim):
    """An answer altered where it is produced: the update of the largest
    parameter leaf applied twice."""
    tr = sim.trainer
    inner = tr.local_update

    def local_update(row, n_batches):
        out = dict(inner(row, n_batches))
        leaves, treedef = jax.tree.flatten(out["params"])
        old = jax.tree.leaves(tr.params)
        i = max(range(len(leaves)), key=lambda j: leaves[j].size)
        leaves[i] = leaves[i] + (leaves[i] - old[i])
        out["params"] = jax.tree.unflatten(treedef, leaves)
        return out

    tr.local_update = local_update


def stale_aggregate(sim):
    """An aggregate that leaves the global model as it was."""
    sim.trainer.aggregate = lambda updates: None


def half_contributors(sim):
    """An aggregate over the first half of the round's updates alone."""
    tr = sim.trainer
    inner = tr.aggregate
    tr.aggregate = lambda updates: inner(updates[:max(1, len(updates) // 2)])


def nonfinite_aggregate(sim):
    """An aggregate that writes a NaN into the first element of its largest
    leaf."""
    tr = sim.trainer
    inner = tr.aggregate

    def aggregate(updates):
        inner(updates)
        leaves, treedef = jax.tree.flatten(tr.params)
        i = max(range(len(leaves)), key=lambda j: leaves[j].size)
        leaves[i] = leaves[i].ravel().at[0].set(jnp.nan).reshape(
            leaves[i].shape)
        tr.params = jax.tree.unflatten(treedef, leaves)

    tr.aggregate = aggregate


def eval_labels_off(sim):
    """An evaluation that scores each prediction against the next label."""
    tr = sim.trainer
    inner = tr.evaluate

    def evaluate():
        test = tr.data.test_data
        labels = test["labels"]
        test["labels"] = labels + 1
        try:
            return inner()
        finally:
            test["labels"] = labels

    tr.evaluate = evaluate


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered_update": altered_update,
          "stale_aggregate": stale_aggregate,
          "half_contributors": half_contributors,
          "nonfinite_aggregate": nonfinite_aggregate,
          "eval_labels_off": eval_labels_off}
