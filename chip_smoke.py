"""Smoke test of the FedZero stack on TPU chips, through its entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded train step on four chips

With no option it runs, on one chip:

1. the FedZero round loop at the paper's setup (100 clients, the 10-domain
   ``global`` scenario, fedzero with n=10 and d_max=60), built through
   ``build_scenario`` -> ``build_registry`` -> ``build_experiment``. A
   ``JaxTrainer`` trains KWT-1 at its published width on synthetic
   98x40 MFCC patches of 35 classes, at ``highest`` matmul precision so
   that the float32 model computes in float32. The scheduler runs on the
   host NumPy reference; training, aggregation and evaluation run on the
   chip. The first contributor's first local update is replayed on the
   CPU and must agree with the chip's;
2. three train steps of smollm-360m at full width (32 layers, d 960,
   vocab 49152, bf16) through ``launch.steps.make_train_step`` with
   ``launch.train``'s mesh and jit on one device. The first loss must be
   near ln(vocab), as it is for random weights.

``--chips 4`` runs only the smollm-360m steps, on the four-device mesh
``launch.train.fit_mesh`` builds and on a one-device mesh, and checks that
the losses agree and that the parameters are spread over all four devices.

Every phase asserts; a failure ends the run with a non-zero exit. The run
fails when JAX's first device is not a TPU. The last line of standard
output is the JSON object ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import copy
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import (ExperimentConfig, FleetSection, JaxTrainer,
                        RunSection, ScenarioSection, StrategySection,
                        build_experiment, build_registry, build_scenario)
from repro.data.federated import synthetic_speech
from repro.launch.steps import make_train_step
from repro.launch.train import fit_mesh, jit_train_step, synthetic_lm_batch
from repro.models import KWTModel, build_model
from repro.optim import adamw

SEED = 0
# SGD step size of the KWT-1 local updates. This KWT has no norm before its
# head, and at the trainer's default of 0.05 its loss runs to NaN within
# one local update (50 steps); at 0.002 it falls steadily.
KWT_LR = 0.002
# Chip vs CPU replay of one local update of the float32 KWT-1, both at
# ``highest`` matmul precision. At a TPU v5e's default precision, float32
# matrix operands are rounded to bfloat16, and the replay there differed
# by 5.2e-2 per sample: that compares bf16 passes, not the float32 model.
# At ``highest`` both sides compute in float32 and differ only in rounding
# order and transcendental implementations, amplified over up to 50 SGD
# steps through 12 layers.
REPLAY_TOL = dict(rtol=1e-3, atol=1e-3)
# One-device vs four-device smollm-360m steps: bf16 activations whose
# matmul partial sums are reduced in another order. A few bf16 ulps
# (2^-8 relative) of a loss near 11.
SHARD_RTOL = 2.0 ** -6


def _line(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX's first device is "
                         f"{d.platform!r}")
    if len(devices) < n_chips:
        raise SystemExit(f"--chips {n_chips} needs {n_chips} devices; JAX "
                         f"sees {len(devices)}")
    _line(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)} jax={jax.__version__}")
    return devices


# ---------------------------------------------------------------------------
# 1. FedZero round loop with KWT-1 on the chip


class _RecordingTrainer(JaxTrainer):
    """A JaxTrainer that keeps the inputs and the result of its first
    local update, so the update can be replayed elsewhere."""

    first = None

    def local_update(self, row, n_batches):
        start = None
        if self.first is None:
            start = (row, n_batches, copy.deepcopy(self.rng), self.params)
        out = super().local_update(row, n_batches)
        if start is not None:
            self.first = start + (out,)
        return out


def fedzero_phase(device, n_clients: int = 100, n_samples: int = 12000,
                  min_rounds: int = 3, max_rounds: int = 12):
    cfg = ExperimentConfig(
        scenario=ScenarioSection(name="global", days=1, seed=SEED),
        fleet=FleetSection(n_clients=n_clients, workload="kwt", seed=SEED),
        strategy=StrategySection(name="fedzero", n=10, d_max=60, seed=SEED),
        run=RunSection(eval_every=1, seed=SEED))
    sc = build_scenario(cfg)
    reg = build_registry(cfg, sc)
    data = synthetic_speech(n_clients, reg.client_names, n_classes=35,
                            n_samples=n_samples, n_patches=98, seed=SEED)
    for c in reg.client_names:  # retune the fleet to the real shard sizes
        reg.clients[c].n_samples = data.n_samples(c)
        reg.clients[c].batches_per_epoch = max(1, data.n_samples(c) // 10)
    reg.refresh_arrays()
    model = KWTModel(n_classes=35, d=64, layers=12, heads=1, mlp=256,
                     n_patches=98)
    trainer = _RecordingTrainer(model, data, lr=KWT_LR, seed=SEED)
    sim = build_experiment(cfg, scenario=sc, registry=reg, trainer=trainer)

    t0 = time.perf_counter()
    trained = 0
    with jax.default_matmul_precision("highest"):
        while trained < min_rounds:
            before = sim.round_idx
            if before >= max_rounds:
                raise AssertionError(f"only {trained} of {before} rounds "
                                     "had contributors")
            sim.run(max_rounds=before + 1, verbose=True)
            if sim.round_idx == before:
                raise AssertionError("the scenario ended after "
                                     f"{trained} rounds with contributors")
            trained += int(sim.results[-1].contributors.size > 0)
    _line(f"fedzero: {sim.round_idx} rounds, {trained} with contributors, "
          f"{time.perf_counter() - t0:.1f}s host clock (first rounds: "
          "includes compiles or cache loads)")

    for rr in sim.results:
        if rr.contributors.size:
            assert math.isfinite(rr.train_loss), rr
        assert 0.0 <= rr.eval_metric <= 1.0, rr
    leaves = jax.tree.leaves(trainer.params)
    assert all(leaf.devices() == {device} for leaf in leaves), \
        "global parameters are not on the chip"

    row, n_batches, rng, params, got = trainer.first
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        ref = JaxTrainer(model, data, lr=KWT_LR, seed=SEED)
        ref.params = jax.device_put(params, cpu)
        ref.rng = rng
        want = ref.local_update(row, n_batches)
    d_mean = abs(got["mean_loss"] - want["mean_loss"])
    d_samples = float(np.max(np.abs(got["sample_losses"]
                                    - want["sample_losses"])))
    _line(f"fedzero: CPU replay of row {row} ({n_batches:.0f} batches): "
          f"|d mean_loss| {d_mean:.3e}, max |d sample_loss| "
          f"{d_samples:.3e}, tolerance {REPLAY_TOL}")
    np.testing.assert_allclose(got["mean_loss"], want["mean_loss"],
                               **REPLAY_TOL)
    np.testing.assert_allclose(got["sample_losses"], want["sample_losses"],
                               **REPLAY_TOL)


# ---------------------------------------------------------------------------
# 2. smollm-360m train steps


def lm_steps(cfg, mesh, host_params, batches, label: str):
    """Train ``len(batches)`` steps on ``mesh`` from ``host_params``;
    return the per-step losses and the final parameters."""
    model, opt, train_step = make_train_step(
        cfg, optimizer=adamw(3e-4, weight_decay=0.1), remat=True)
    opt_struct = jax.eval_shape(opt.init, host_params)
    jitted, (p_sh, o_sh, _) = jit_train_step(train_step, host_params,
                                             opt_struct, batches[0], mesh)
    params = jax.device_put(host_params, p_sh)
    opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)
    losses = []
    with jax.set_mesh(mesh):
        for i, batch in enumerate(batches):
            t0 = time.perf_counter()
            params, opt_state, loss = jitted(params, opt_state, batch)
            losses.append(float(loss))
            when = "cold: compile or cache load" if i == 0 else "warm"
            _line(f"lm[{label}]: step {i} loss {losses[-1]:.6f} ({when}, "
                  f"{time.perf_counter() - t0:.3f}s host clock)")
    return losses, params


def lm_setup(arch: str, steps: int, batch: int, seq: int, reduced=False):
    cfg = get_config(arch, reduced=reduced)
    model = build_model(cfg)
    host_params = jax.device_get(jax.jit(model.init)(
        jax.random.PRNGKey(SEED)))
    rng = np.random.default_rng(SEED)
    batches = [synthetic_lm_batch(rng, batch, seq, cfg.vocab)
               for _ in range(steps)]
    return cfg, host_params, batches


def lm_phase(device, arch="smollm-360m", steps=3, batch=8, seq=512,
             reduced=False):
    cfg, host_params, batches = lm_setup(arch, steps, batch, seq, reduced)
    losses, _ = lm_steps(cfg, fit_mesh([device]), host_params, batches,
                         "1 device")
    stats = device.memory_stats() or {}
    _line(f"lm: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    assert all(math.isfinite(x) for x in losses), losses
    assert abs(losses[0] - math.log(cfg.vocab)) <= 1.0, \
        (losses[0], math.log(cfg.vocab))


def sharded_phase(devices, arch="smollm-360m", steps=3, batch=8, seq=512,
                  reduced=False, large=1 << 20):
    cfg, host_params, batches = lm_setup(arch, steps, batch, seq, reduced)
    one, _ = lm_steps(cfg, fit_mesh(devices[:1]), host_params, batches,
                      "1 device")
    mesh = fit_mesh(devices)
    _line(f"lm: mesh {dict(mesh.shape)}")
    many, params = lm_steps(cfg, mesh, host_params, batches,
                            f"{len(devices)} devices")
    np.testing.assert_allclose(many, one, rtol=SHARD_RTOL)

    n = len(devices)
    per_device = dict.fromkeys(devices, 0)
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if leaf.size < large:
            continue
        held = dict.fromkeys(devices, 0)
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
        for d, b in held.items():
            assert abs(b / leaf.nbytes - 1.0 / n) < 0.02, \
                (jax.tree_util.keystr(path), d, b, leaf.nbytes)
            per_device[d] += b
        total += leaf.nbytes
    _line("lm: bytes of large tensors per device "
          + ", ".join(f"{d.id}: {b}" for d, b in per_device.items())
          + f" (total {total})")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    enable_compile_cache()
    devices = require_tpu(args.chips)
    if args.chips == 4:
        sharded_phase(devices[:4])
    else:
        fedzero_phase(devices[0])
        lm_phase(devices[0])
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
