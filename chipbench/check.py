"""The comparison that decides ``correct``.

It reads the program only at public seams: the rows its data object
(``FederatedData.sample_batch``, made by the benchmark) hands out, what
``JaxTrainer.local_update`` returns, and the updates that ``aggregate``
receives with the global model before and after it.

Two things are compared with the plain float32 reference, once the window
has closed and the memory peak has been read:

- **the first steps**: set-up drives the trainer, the same object the
  window then drives, through ``local_update(row, 1)`` and
  ``local_update(row, 3)`` of a client drawn from the seed. The reference
  regenerates the weights from the seed and follows the same rows;
- **the window's last round with contributors**: every contributor's
  rows, a sample of its updates drawn from the seed replayed by the
  reference from the round's global model (loss, sample losses, change of
  each leaf), the FedAvg of the round's updates with the weights the
  configuration's step rule gives, and the evaluation of that average.

The numbers, of which the configuration file's ``check`` names those
compared, each with its limit:

- ``first_loss_gap``: relative gap of the one-step update's loss, a
  forward pass from the seed's weights;
- ``loss_gap``: the larger of that and the relative gap of the three-step
  update's mean loss;
- ``grad_gap`` and ``change_gap``: of the worst leaf, the gap between the
  program's norm and the reference's, over the reference's norm of that
  leaf or of the median leaf, whichever is larger: the first gradient as
  SGD applied it (``|w0 - w1| / lr``) and the change after three steps.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's move by round-off alone and are left out of the change;
- ``round_loss_gap``, ``round_change_gap``, ``sample_loss_gap``: of the
  replayed updates of the last round, the worst relative gap of the mean
  loss, the worst leaf's change gap as above, and the worst relative gap
  of a sample loss of the update's probe rows;
- ``aggregate_ulps``: the largest gap of an element of the program's
  FedAvg from the reference's float32 FedAvg, in units of the weights'
  dtype spacing there (:func:`_ulps`); an element that is exactly 0 on
  every side reads 0, and the leaf that holds the largest gap is named on
  standard error;
- ``eval_gap``: the program's evaluation against the reference's accuracy
  of the reference FedAvg, stored in the weights' dtype, on the same test
  rows;
- ``foreign_rows``: rows handed to an update that are not rows of its
  client's shard as the benchmark generated it, or calls of the data that
  do not match the update's steps (exact).

No number reads NaN: a gap of two values that agree exactly reads 0, also
over a floor of 0, and a gap where the program or the reference made a
value non-finite, or where two values differ over a floor of 0, reads
``inf``.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial, reduce
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from chipbench.reference.ops import F32, fedprox_sgd, row_nll

STEPS = 3
GRAD_FLOOR = 1e-3  # of the median leaf's reference gradient
FIRST = ("first_loss_gap", "loss_gap", "grad_gap", "change_gap")
ROUND = ("round_loss_gap", "round_change_gap", "sample_loss_gap",
         "aggregate_ulps", "eval_gap")
NUMBERS = FIRST + ROUND + ("foreign_rows",)


@jax.jit
def leaf_norms(a, b):
    """Norm of ``a - b`` for each leaf, in float32."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(F32) - y.astype(F32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


@jax.jit
def _fedavg(updates, weights):
    """The weighted sum of ``updates`` in float32; ``weights`` sum to one."""
    return jax.tree.map(
        lambda *xs: sum(w * x.astype(F32) for w, x in zip(weights, xs)),
        *updates)


@jax.jit
def _ulps(got, want, updates):
    """The largest gap, over all elements, between ``got`` and the float32
    ``want``, in units of ``got``'s dtype spacing at the larger of
    ``want`` and the largest update there: an aggregate stored in that
    dtype from the same float32 sum is within half a unit, one summed in
    another order within a few. Returns the gap and the index, among the
    leaves of ``got``, of the leaf that holds it.

    The unit is never under float32's smallest normal number: XLA flushes
    subnormals to 0, and an element that is 0 on every side would read
    0/0. So such an element reads 0, and the unit is as the dtype's
    spacing gives it wherever the scale is 2^-103 (float32) or 2^-119
    (bfloat16) or more. A NaN or inf in ``got``, ``want`` or an update
    reads ``inf``."""
    def leaf(g, w, *us):
        eps = jnp.finfo(g.dtype).eps
        scale = reduce(jnp.maximum, [jnp.abs(w)] + [
            jnp.abs(u.astype(F32)) for u in us])
        tiny = jnp.finfo(g.dtype).tiny
        unit = jnp.maximum(
            eps * jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(scale, tiny)))),
            jnp.finfo(F32).tiny)
        gap = jnp.abs(g.astype(F32) - w) / unit
        return jnp.max(jnp.where(jnp.isnan(gap), jnp.inf, gap))
    gaps = jnp.stack(jax.tree.leaves(jax.tree.map(leaf, got, want, *updates)))
    return jnp.max(gaps), jnp.argmax(gaps)


def name_leaf(got, want, updates, i: int) -> str:
    """The key path of ``got``'s leaf ``i``, and which of the program's
    aggregate, the reference's and the updates hold a non-finite value
    there."""
    path, _ = jax.tree_util.tree_flatten_with_path(got)
    sides = [("program", got), ("reference", want)] + [
        (f"update {k}", u) for k, u in enumerate(updates)]
    bad = [name for name, tree in sides
           if not bool(jnp.all(jnp.isfinite(jax.tree.leaves(tree)[i])))]
    return (jax.tree_util.keystr(path[i][0]) + ": "
            + (f"non-finite in {', '.join(bad)}" if bad else "all finite"))


def steps_of(n_batches: float, max_steps: int) -> int:
    """The configuration's rule: an update of ``n_batches`` planned batches
    runs that many steps, rounded, at least one and at most ``max_steps``."""
    return int(min(max(1, round(float(n_batches))), max_steps))


# ---------------------------------------------------------------------------
# what the program did


class Recorder:
    """Wraps the data object's ``sample_batch``: keeps, in call order, the
    registry row and the arrays of each batch handed out."""

    def __init__(self, data, names: List[str]):
        self.row_of = {n: i for i, n in enumerate(names)}
        self.calls: List[tuple] = []
        inner = data.sample_batch

        def sample_batch(client, batch_size, rng):
            out = inner(client, batch_size, rng)
            self.calls.append((self.row_of[client], out))
            return out

        data.sample_batch = sample_batch

    def take(self) -> List[tuple]:
        calls, self.calls = self.calls, []
        return calls


@dataclasses.dataclass
class Update:
    row: int
    steps: int          # by the configuration's rule
    weight: float       # FedAvg weight by that rule
    mean_loss: float
    sample_losses: np.ndarray
    batches: List[dict]
    probe: Optional[dict]
    calls_ok: bool      # one call per step, then the probe
    params: object = None


@dataclasses.dataclass
class LastRound:
    w_prev: object
    updates: List[dict]
    calls: List[tuple]
    agg: object = None
    rr: object = None


class Capture:
    """What the check needs of a run: set-up's first steps, and the last
    round with contributors, kept through the seams named above."""

    def __init__(self, trainer, recorder: Recorder, cfg: dict):
        self.trainer, self.recorder = trainer, recorder
        self.cfg = cfg
        self.first: Optional[dict] = None
        self.pending: Optional[LastRound] = None
        inner_update, inner_agg = trainer.local_update, trainer.aggregate

        def local_update(row, n_batches):
            # a new round's training drops the last round's capture before
            # it allocates, so no more than one round is ever held
            if self.pending is not None and self.pending.rr is not None:
                self.pending = None
            return inner_update(row, n_batches)

        def aggregate(updates):
            w_prev = trainer.params
            inner_agg(updates)
            self.pending = LastRound(w_prev, list(updates),
                                     recorder.take(), agg=trainer.params)

        trainer.local_update = local_update
        trainer.aggregate = aggregate

    def end_round(self, rr) -> None:
        """After each round: ties a fresh capture to its round result."""
        if self.pending is not None and self.pending.rr is None:
            self.pending.rr = rr
        self.recorder.take()

    def first_steps(self, row: int) -> None:
        """Set-up: one update of one step and one of three from the
        seed's global model, through the trainer's own call."""
        lr = self.cfg["train"]["lr"]
        tr = self.trainer
        w0 = tr.params
        self.recorder.take()
        u1 = tr.local_update(row, 1)
        calls1 = self.recorder.take()
        grad = np.asarray(leaf_norms(w0, u1["params"])) / lr
        u3 = tr.local_update(row, STEPS)
        calls3 = self.recorder.take()
        change = np.asarray(leaf_norms(u3["params"], w0))
        self.first = {
            "row": row, "loss1": float(u1["mean_loss"]),
            "loss3": float(u3["mean_loss"]), "grad": grad, "change": change,
            "calls": (calls1, calls3),
            "batches1": [b for _, b in calls1[:1]],
            "batches3": [b for _, b in calls3[:STEPS]]}
        self.pending = None


# ---------------------------------------------------------------------------
# the reference


class Reference:
    """The plain reference of one configuration, jitted once per check:
    ``mode`` names the products (``highest``, or the control's)."""

    def __init__(self, cell, mode: str = "highest"):
        self.cfg, self.ref, self.mode = cell.config, cell.reference, mode
        self.grad = self.ref.grad_fn(self.cfg, mode)
        self.block = self.ref.BLOCK_ROWS
        self._nll = jax.jit(lambda p, b: row_nll(
            self.ref.logits(p, b, self.cfg, mode), b["labels"]))
        self._pred = jax.jit(lambda p, b: jnp.argmax(
            self.ref.logits(p, b, self.cfg, mode), -1))
        self._upcast = jax.jit(lambda t: jax.tree.map(
            lambda x: x.astype(F32), t))

    def init(self, train_seed: int):
        return jax.jit(partial(self.ref.init_params, self.cfg))(
            jax.random.PRNGKey(train_seed))

    def train(self, w, batches):
        t = self.cfg["train"]
        return fedprox_sgd(self.grad, w, [_device(b) for b in batches],
                           t["lr"], t["prox_mu"])

    def _blocks(self, fn, params, batch):
        w = self._upcast(params)
        n = len(next(iter(batch.values())))
        return np.concatenate([
            np.asarray(fn(w, {k: jnp.asarray(v[i:i + self.block])
                              for k, v in batch.items()}))
            for i in range(0, n, self.block)])

    def sample_losses(self, params, batch) -> np.ndarray:
        return self._blocks(self._nll, params, batch)

    def accuracy(self, params, batch) -> float:
        pred = self._blocks(self._pred, params, batch)
        return float(np.mean(pred == np.asarray(batch["labels"])))


def _device(batch):
    return {k: jnp.asarray(v) for k, v in batch.items()}


def rel(a: float, b: float) -> float:
    """The gap of ``a`` from ``b``, relative where ``b`` is not 0; ``inf``
    where either is not finite."""
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / abs(b) if b else abs(a - b)


def gaps_over(got: np.ndarray, want: np.ndarray, floor) -> np.ndarray:
    """``|got - want| / floor`` elementwise: 0 where the two agree exactly,
    ``inf`` where they differ over a floor of 0 or either is not finite."""
    diff = np.abs(got - want)
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.where(diff == 0, 0.0, diff / floor)
    sound = np.isfinite(got) & np.isfinite(want) & ~np.isnan(gap)
    return np.where(sound, gap, np.inf)


def leaf_gaps(got: np.ndarray, want: np.ndarray, keep=None) -> np.ndarray:
    """Per leaf, the gap of the norms over the reference's norm of that leaf
    or of the median (kept) leaf, whichever is larger; 0 where not kept,
    unless a norm there is not finite."""
    if keep is None:
        keep = np.ones(len(want), bool)
    floor = np.maximum(want, np.median(want[keep]))
    finite = np.isfinite(got) & np.isfinite(want)
    return np.where(keep | ~finite, gaps_over(got, want, floor), 0.0)


def sample_loss_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The worst relative gap of a probe row's loss; ``inf`` where the
    shapes differ."""
    if got.shape != want.shape:
        return math.inf
    return float(np.max(gaps_over(got, want, np.abs(want))))


def kept(ref_grad: np.ndarray) -> np.ndarray:
    return ref_grad >= GRAD_FLOOR * np.median(ref_grad)


# ---------------------------------------------------------------------------
# the comparison


def first_numbers(first: dict, ref: Reference, w0,
                  control: Optional[Reference] = None) -> Dict[str, float]:
    """Set-up's first steps against the reference. With ``control``, the
    control's steps stand in the program's place."""
    lr = ref.cfg["train"]["lr"]
    l1, s1, _ = ref.train(w0, first["batches1"])
    l3, s3, e3 = ref.train(w0, first["batches3"])
    g_ref = np.asarray(leaf_norms(w0, s1)) / lr
    g3_ref = np.asarray(leaf_norms(w0, s3)) / lr
    c_ref = np.asarray(leaf_norms(e3, w0))
    if control is None:
        loss1, loss3 = first["loss1"], first["loss3"]
        grad, change = first["grad"], first["change"]
    else:
        k1, t1, _ = control.train(w0, first["batches1"])
        k3, _, f3 = control.train(w0, first["batches3"])
        loss1, loss3 = k1[0], float(np.mean(k3))
        grad = np.asarray(leaf_norms(w0, t1)) / lr
        change = np.asarray(leaf_norms(f3, w0))
    first_gap = rel(loss1, l1[0])
    return {
        "first_loss_gap": first_gap,
        "loss_gap": max(first_gap, rel(loss3, float(np.mean(l3)))),
        "grad_gap": float(np.max(leaf_gaps(grad, g_ref))),
        "change_gap": float(np.max(leaf_gaps(change, c_ref, kept(g3_ref)))),
    }


def round_updates(last: LastRound, cfg: dict) -> List[Update]:
    """The last round's updates, each with the rows handed to it and the
    steps and FedAvg weight the configuration's rule gives."""
    t = cfg["train"]
    rr = last.rr
    planned = {int(rr.participants[p]): float(rr.batches[p])
               for p in rr.contributor_idx}
    by_row: Dict[int, List[dict]] = {}
    for row, b in last.calls:
        by_row.setdefault(row, []).append(b)
    out = []
    for u in last.updates:
        row = int(u["row"])
        steps = steps_of(planned.get(row, 0.0), t["max_steps"])
        calls = by_row.get(row, [])
        out.append(Update(
            row=row, steps=steps, weight=float(steps * t["batch"]),
            mean_loss=float(u["mean_loss"]),
            sample_losses=np.asarray(u["sample_losses"], np.float64),
            batches=calls[:steps],
            probe=calls[steps] if len(calls) > steps else None,
            calls_ok=row in planned and len(calls) == steps + 1,
            params=u["params"]))
    return out


def round_numbers(last: LastRound, cfg: dict, ref: Reference,
                  replay: List[int], test: dict,
                  control: Optional[Reference] = None) -> Dict[str, float]:
    """The last round against the reference: ``replay`` indexes the updates
    the reference trains again. With ``control``, the control's replay and
    evaluation stand in the program's place. The program's arrays are freed
    once read, before the replay."""
    lr = cfg["train"]["lr"]
    updates = round_updates(last, cfg)
    w_prev = last.w_prev
    weights = np.array([u.weight for u in updates], np.float32)
    params = [u.params for u in updates]
    avg = _fedavg(params, list(weights / weights.sum()))
    ulps, at = _ulps(last.agg, avg, params)
    aggregate_ulps = float(ulps)
    from chipbench.bench import log  # bench imports this module
    log(f"aggregate_ulps {aggregate_ulps!r} at leaf "
        f"{name_leaf(last.agg, avg, params, int(at))}")
    avg = jax.tree.map(lambda a, w: a.astype(w.dtype), avg, w_prev)
    del params
    n_eval = min(cfg["train"]["eval_batch"], len(test["labels"]))
    test = {k: v[:n_eval] for k, v in test.items()}
    acc = ref.accuracy(avg, test)
    got_eval = (last.rr.eval_metric if control is None
                else control.accuracy(avg, test))
    changes = {i: np.asarray(leaf_norms(updates[i].params, w_prev))
               for i in replay}
    del avg
    for u in updates:
        u.params = None
    last.updates, last.agg = [], None
    numbers = {"aggregate_ulps": aggregate_ulps,
               "eval_gap": abs(float(got_eval) - acc)}
    gaps = {"round_loss_gap": [], "round_change_gap": [],
            "sample_loss_gap": []}
    for i in replay:
        u = updates[i]
        if not u.calls_ok:
            return {**numbers, **{k: math.inf for k in gaps}}
        losses, first, final = ref.train(w_prev, u.batches)
        keep = kept(np.asarray(leaf_norms(w_prev, first)) / lr)
        want_change = np.asarray(leaf_norms(final, w_prev))
        want_samples = ref.sample_losses(final, u.probe)
        del first, final
        if control is None:
            mean_loss, change = u.mean_loss, changes[i]
            samples = u.sample_losses
        else:
            c_losses, _, c_final = control.train(w_prev, u.batches)
            mean_loss = float(np.mean(c_losses))
            change = np.asarray(leaf_norms(c_final, w_prev))
            samples = control.sample_losses(c_final, u.probe)
            del c_final
        gaps["round_loss_gap"].append(rel(mean_loss,
                                          float(np.mean(losses))))
        gaps["round_change_gap"].append(
            float(np.max(leaf_gaps(change, want_change, keep))))
        gaps["sample_loss_gap"].append(
            sample_loss_gap(samples, want_samples))
    return {**numbers, **{k: max(v) for k, v in gaps.items()}}


def foreign_rows(calls: List[tuple], shards: Dict[int, dict]) -> int:
    """Rows handed out that are not rows of the named client's shard."""
    have: Dict[int, set] = {}
    bad = 0
    for row, batch in calls:
        if row not in have:
            shard = shards[row]
            keys = sorted(shard)
            have[row] = {b"".join(shard[k][i].tobytes() for k in keys)
                         for i in range(len(shard[keys[0]]))}
        keys = sorted(batch)
        bad += sum(b"".join(batch[k][i].tobytes() for k in keys)
                   not in have[row] for i in range(len(batch[keys[0]])))
    return bad


def replay_sample(n_updates: int, k: int, seed: int) -> List[int]:
    """``k`` of the round's updates, drawn from the seed, in order."""
    k = min(k, n_updates)
    rng = np.random.default_rng([seed, 1])
    return sorted(int(i) for i in rng.choice(n_updates, k, replace=False))


def compare(cell, capture: Capture, shards: Dict[int, dict], test: dict,
            train_seed: int, seed: int, control: bool = False
            ) -> Dict[str, float]:
    """Every number the check computes. ``control`` puts the reference at
    the configuration's control precision in the program's place."""
    cfg = cell.config
    last, first = capture.pending, capture.first
    if first is None:
        raise RuntimeError("set-up ran no first steps")
    ref = Reference(cell)
    ctl = Reference(cell, cell.reference.CONTROL) if control else None
    numbers: Dict[str, float] = {}
    if last is None or last.rr is None:
        numbers.update({k: math.inf for k in ROUND})
        calls = []
    else:
        replay = replay_sample(len(last.updates), cfg["replay_updates"],
                               seed)
        numbers.update(round_numbers(last, cfg, ref, replay, test, ctl))
        calls = last.calls
        ok = all(u.calls_ok for u in round_updates(last, cfg))
        if not ok:
            numbers["foreign_rows"] = math.inf
    capture.pending = None
    w0 = ref.init(train_seed)
    numbers.update(first_numbers(first, ref, w0, ctl))
    c1, c3 = first["calls"]
    firsts_ok = (len(c1) == 2 and len(c3) == STEPS + 1
                 and all(r == first["row"] for r, _ in c1 + c3))
    if "foreign_rows" not in numbers:
        numbers["foreign_rows"] = float(
            foreign_rows(c1 + c3 + calls, shards)) if firsts_ok else math.inf
    return numbers
