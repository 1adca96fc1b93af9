"""Trainers plugged into the FL simulation.

* ``JaxTrainer``   — real federated training in JAX: per-client FedProx/SGD
  local updates on the client's data shard, each one device program (its
  batches staged once, its losses read back once), FedAvg aggregation
  weighted by samples processed, evaluation on a held-out test set.
* ``ProxyTrainer`` — analytic convergence proxy for scheduler-scale
  experiments (100k clients, 7 simulated days) where real training is not
  the object of study. Calibrated to show diminishing returns per client
  (re-selecting the same clients helps less — the mechanism behind the
  paper's fairness/convergence coupling).

Both take **registry rows** in ``local_update`` (row-ID-first identity).
The JaxTrainer maps row → dataset shard through a positional name list —
the dataset is the one place client names legitimately live — while the
ProxyTrainer is pure flat arrays.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro import telemetry
from repro.data.federated import FederatedData
from repro.optim import fedprox_loss, sgd


class JaxTrainer:
    def __init__(self, model, data: FederatedData, lr: float = 0.05,
                 batch_size: int = 10, prox_mu: float = 0.1,
                 momentum: float = 0.0, weight_decay: float = 0.0,
                 seed: int = 0, max_steps_per_round: int = 50,
                 eval_batch: int = 512,
                 client_names: Optional[List[str]] = None):
        self.model = model
        self.data = data
        # row -> dataset shard key; defaults to dataset insertion order,
        # which builders align with the registry's row order
        self._names = list(client_names if client_names is not None
                           else data.client_data)
        self.batch_size = batch_size
        self.max_steps = max_steps_per_round
        self.eval_batch = eval_batch
        self.rng = np.random.default_rng(seed)
        self.params = model.init(jax.random.PRNGKey(seed))
        self.opt = sgd(lr, momentum=momentum, weight_decay=weight_decay)
        if prox_mu > 0:
            self._local_loss = fedprox_loss(model.loss, prox_mu)
        else:
            self._local_loss = lambda p, b, g: model.loss(p, b)

        @jax.jit
        def local_update(global_params, batches, steps, probe):
            """``steps`` SGD steps from the global model, batch ``i`` of
            the ``[max_steps, B, ...]`` stack at step ``i``, then the
            probe's per-sample NLL on the final parameters. Returns the
            parameters, the ``[max_steps]`` float32 losses (zero past
            ``steps``) and the probe's losses."""
            def step(i, carry):
                params, opt_state, losses = carry
                batch = jax.tree.map(
                    lambda x: lax.dynamic_index_in_dim(x, i, keepdims=False),
                    batches)
                with jax.named_scope("fl.local_step"):
                    loss, grads = jax.value_and_grad(self._local_loss)(
                        params, batch, global_params)
                    params, opt_state = self.opt.update(grads, opt_state,
                                                        params)
                return (params, opt_state,
                        losses.at[i].set(loss.astype(jnp.float32)))

            n = jax.tree.leaves(batches)[0].shape[0]
            params, _, losses = lax.fori_loop(
                0, steps, step, (global_params, self.opt.init(global_params),
                                 jnp.zeros(n, jnp.float32)))
            with jax.named_scope("fl.sample_losses"):
                logits = model.logits_fn(params, probe).astype(jnp.float32)
                logz = jax.nn.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(
                    logits, probe["labels"][..., None], axis=-1)[..., 0]
                nll = logz - gold
                if nll.ndim > 1:  # LM: mean over sequence
                    nll = nll.mean(axis=tuple(range(1, nll.ndim)))
            return params, losses, nll

        self._local_update = local_update

    def _stage(self, draws: List[Dict]) -> Dict:
        """The step batches as one host array per key, ``[max_steps, B,
        ...]``, zero past the last step: one shape per configuration."""
        out = {}
        for k, v in draws[0].items():
            buf = np.zeros((self.max_steps,) + v.shape, v.dtype)
            np.stack([d[k] for d in draws], out=buf[:len(draws)])
            out[k] = buf
        return out

    def local_update(self, row: int, n_batches: float) -> Dict:
        """One client's FedProx update as one device program: its batches
        are drawn and staged at once, and its losses read back at once."""
        client = self._names[row]
        steps = int(min(max(1, round(n_batches)), self.max_steps))
        with telemetry.span("fl.local_update.batch"):
            draws = [self.data.sample_batch(client, self.batch_size,
                                            self.rng) for _ in range(steps)]
            probe = self.data.sample_batch(client, 4 * self.batch_size,
                                           self.rng)
            staged = telemetry.to_device({
                "batches": self._stage(draws), "steps": np.int32(steps),
                "probe": probe})
        with telemetry.span("fl.local_update.step"):
            params, losses, sample_losses = self._local_update(
                self.params, **staged)
        telemetry.count("local_steps", steps)
        telemetry.count("fused_updates")
        telemetry.count("pad_steps", self.max_steps - steps)
        losses, sample_losses = telemetry.to_host((losses, sample_losses))
        return {"row": row, "params": params,
                "weight": float(steps * self.batch_size),
                "sample_losses": sample_losses,
                "mean_loss": float(losses[:steps].astype(np.float64).mean())}

    def aggregate(self, updates: List[Dict]):
        weights = np.array([u["weight"] for u in updates], np.float32)
        weights = weights / weights.sum()
        leaves = [jax.tree.leaves(u["params"]) for u in updates]
        agg = [sum(w * l for w, l in zip(weights, ls))
               for ls in zip(*leaves)]
        treedef = jax.tree.structure(self.params)
        self.params = jax.tree.unflatten(
            treedef, [a.astype(l.dtype) for a, l in
                      zip(agg, jax.tree.leaves(self.params))])

    def evaluate(self) -> float:
        td = self.data.test_data
        n = len(next(iter(td.values())))
        take = min(self.eval_batch, n)
        batch = telemetry.to_device({k: v[:take] for k, v in td.items()})
        with jax.named_scope("fl.evaluate"):
            logits = self.model.logits_fn(self.params, batch)
            pred = jnp.argmax(logits, axis=-1)
            acc = jnp.mean((pred == batch["labels"]).astype(jnp.float32))
        return float(telemetry.to_host(acc))


class ProxyTrainer:
    """Analytic accuracy model: progress grows with sqrt(batches) per
    contributor, discounted for repeatedly-selected clients, so strategies
    that over-select the same energy-rich clients converge slower — the
    effect the paper measures. Per-sample losses fed back to Oort/FedZero
    utility are proportional to the remaining loss with client-specific
    offsets. State is flat arrays indexed by registry row."""

    def __init__(self, n_clients: int, acc_max: float = 0.9,
                 k: float = 0.003, seed: int = 0):
        self.acc_max = acc_max
        self.k = k
        self.progress = 0.0
        self.counts = np.zeros(n_clients, dtype=np.int64)
        rng = np.random.default_rng(seed)
        self.client_hardness = rng.uniform(0.7, 1.3, n_clients)

    def local_update(self, row: int, n_batches: float) -> Dict:
        self.counts[row] += 1
        novelty = 1.0 / np.sqrt(self.counts[row])
        gain = np.sqrt(max(n_batches, 0.0)) * novelty
        acc = self.evaluate()
        loss_level = max(1e-3, -np.log(max(1e-6, acc / self.acc_max + 1e-3)))
        losses = np.full(16, loss_level * self.client_hardness[row])
        return {"row": row, "params": None, "weight": n_batches,
                "sample_losses": losses,
                "mean_loss": float(losses.mean()), "_gain": gain}

    def aggregate(self, updates: List[Dict]):
        self.progress += sum(u["_gain"] for u in updates)

    def evaluate(self) -> float:
        return self.acc_max * (1.0 - np.exp(-self.k * self.progress))
