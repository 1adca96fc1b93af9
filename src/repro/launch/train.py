"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --reduced --steps 200 --batch 8 --seq 128

Runs the real distributed train_step (same code path the dry-run lowers)
on whatever mesh the current backend offers: the full production mesh on a
pod, a 1×1 mesh on one device. Synthetic LM data (Zipf tokens with
learnable bigram structure) feeds the loss; checkpoints go to --ckpt-dir.
chip_smoke.py drives the same mesh and jit (:func:`fit_mesh`,
:func:`jit_train_step`) on one and on four TPU chips.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.launch.steps import make_train_step
from repro.optim import adamw
from repro.sharding import batch_specs, param_specs, tree_shardings


def synthetic_lm_batch(rng: np.random.Generator, batch: int, seq: int,
                       vocab: int):
    """Bigram-structured token stream: next token = (3·tok + noise) % V."""
    toks = np.zeros((batch, seq + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, batch)
    noise = rng.integers(0, 7, (batch, seq))
    for t in range(seq):
        toks[:, t + 1] = (3 * toks[:, t] + noise[:, t]) % vocab
    return {"tokens": jnp.asarray(toks[:, :-1]),
            "labels": jnp.asarray(toks[:, 1:])}


def fit_mesh(devices=None):
    """("data", "model") mesh over ``devices`` (default: all of them), with
    ``Auto`` axes so the models' activation constraints apply. The model
    axis takes the largest of 16, 8, 4, 2, 1 that divides the count."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    model_par = next(c for c in (16, 8, 4, 2, 1) if n % c == 0)
    return jax.make_mesh((n // model_par, model_par), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)


def jit_train_step(train_step, params, opt_state, batch, mesh):
    """``train_step`` jitted with the repo's parameter, optimizer-state and
    batch shardings on ``mesh``. Returns ``(jitted, in_shardings)``; the
    step returns params and optimizer state in their input shardings and
    a replicated loss, and donates the params and optimizer state it is
    given. Arguments may be arrays or ``jax.ShapeDtypeStruct`` trees.
    Call the step inside ``jax.set_mesh(mesh)``."""
    in_sh = (tree_shardings(param_specs(params, mesh), mesh),
             tree_shardings(param_specs(opt_state, mesh), mesh),
             tree_shardings(batch_specs(batch, mesh), mesh))
    jitted = jax.jit(train_step, in_shardings=in_sh,
                     out_shardings=(in_sh[0], in_sh[1],
                                    NamedSharding(mesh, P())),
                     donate_argnums=(0, 1))
    return jitted, in_sh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    mesh = fit_mesh()
    model, opt, train_step = make_train_step(
        cfg, optimizer=adamw(args.lr, weight_decay=0.1),
        remat=not args.reduced)

    params = model.init(jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)
    rng = np.random.default_rng(args.seed)
    batch0 = synthetic_lm_batch(rng, args.batch, args.seq, cfg.vocab)

    start = 0
    if args.ckpt_dir and (latest := latest_step(args.ckpt_dir)) is not None:
        (params, opt_state), extra = load_checkpoint(
            args.ckpt_dir, (params, opt_state))
        start = (extra or {}).get("step", latest)
        print(f"resumed from step {start}")

    jitted, _ = jit_train_step(train_step, params, opt_state, batch0, mesh)
    t0 = time.time()
    with jax.set_mesh(mesh):
        for step in range(start, args.steps):
            batch = synthetic_lm_batch(rng, args.batch, args.seq, cfg.vocab)
            params, opt_state, loss = jitted(params, opt_state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                tok_s = args.batch * args.seq * (step - start + 1) / (time.time() - t0)
                print(f"step {step:5d} loss {float(loss):.4f} tok/s {tok_s:,.0f}")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, step + 1, (params, opt_state),
                                extra={"step": step + 1, "arch": args.arch})
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, (params, opt_state),
                        extra={"step": args.steps, "arch": args.arch})
    print("done: final loss", float(loss))


if __name__ == "__main__":
    main()
