"""JAX backend: the scheduling hot path, jit-compiled.

Bit-exactness with the NumPy reference comes for free on the ops this
backend accelerates: uint64 mixing, float elementwise math, gathers and
``lax.top_k`` (run over the *reversed* score array so its
lowest-index-first tie rule becomes the contract's position-descending
rule) are all exactly specified, so jitting them cannot change a single
bit — **as long as XLA cannot re-round them**. Two hazards exist on
XLA:CPU and this module fences both (empirically pinned by
tests/test_backend_parity.py; see docs/backends.md, "fused ops &
dispatch budget"):

* **FMA contraction** — ``a*b + c`` inside one executable fuses into an
  FMA that skips the product's rounding. No in-jit barrier stops it
  (``optimization_barrier``, bitcast round-trips and dual-use tricks
  all fail), so float32 multiply→add seams are fenced with
  :func:`repro.kernels.counter_hash.round24` — the product is computed
  *exactly* in float64 (24-bit × 24-bit mantissas fit 53 bits) and
  rounded back to float32 by integer bit arithmetic XLA cannot fold —
  and float64 seams keep a kernel boundary (``_probe_parts_j`` /
  ``_probe_sum_j``).
* **reassociation** — back-to-back multiplies ``(x·c1)·c2`` fuse into
  one rounding; ``round24`` fences these identically.

Float *reductions* whose bits feed scheduling (``np.cumsum`` feeding
admission takes) are reproduced bit-exactly with a **sequential
per-column scan** (``lax.scan`` — adds in NumPy's left-to-right order,
unlike the tree-reduction ``jnp.cumsum``), which is what lets the
admission chunk pass run as one fused dispatch. ``np.exp`` and the
per-candidate ``np.bincount`` stay host-side per the parity contract in
:mod:`repro.backend.base`. The one reordered reduction, the per-domain
admission margin scan, is decision-safe and is vmapped over the domain
axis (declared as an abstract ``("domains",)`` mesh via
:func:`repro.sharding.specs.make_abstract_mesh`).

Two mechanical points keep jit practical on this workload:

* **x64** — the scheduler mixes uint64 hashes and float64 scores, so
  every device call runs under ``jax.enable_x64(True)`` (scoped:
  the training stack's float32 default is untouched);
* **shape bucketing** — candidate counts vary per round and per chunk,
  and XLA retraces per shape, so inputs are padded to power-of-two row
  buckets (pads score ``-inf`` / drain ``0`` and cannot be selected),
  bounding compilation to a handful of shapes per run. Downloads pull
  the **full padded buffer** (one contiguous copy) and slice host-side
  — ``np.asarray`` on a sliced device array is a strided copy that
  dominated the old per-op profile.

Dispatch budget: every op ticks ``ArrayBackend._tick`` once per device
executable launched, so ``dispatch_counts`` is the per-round dispatch
ledger the benchmarks surface and CI regresses. The fused coarse ops
hold the hot path to: 1 dispatch per synthesis window
(``synth_window``/``forecast_noise_z``), ≤ 2 per reach probe
(``probe_scores`` against the device-resident ``reach_state``; +1 if
the probe's ``top_m`` runs), and 1 per admission chunk pass
(``admit_domains``).

Small chunks stay on the inherited host reference (identical bits,
lower latency than a device dispatch); ``_DEVICE_MIN_ROWS`` is the
crossover.

Platforms: XLA:TPU refuses the ``round24`` fence (its float64 → uint64
``bitcast_convert_type`` cannot be rewritten without X64 types), so
``synth_window`` and ``forecast_noise_z`` do not compile for a TPU.
Constructing this backend on a TPU raises instead of routing those ops
to the host; the scheduler runs on the NumPy reference there until a
32-bit-limb lowering lands (ROADMAP S4).
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.counter_hash import round24

from .base import MARGIN
from .numpy_backend import NumpyBackend

_U64 = np.uint64
# below this many rows a device dispatch costs more than host math
_DEVICE_MIN_ROWS = 4096

# Ops measured to lose to the host reference at *every* size when the
# only "device" is the host CPU itself (benchmarks/e2e_simulation.py,
# 1M-client day): the admission walk and top-k are branch/bandwidth
# bound, so their device path is the same scalar work plus an upload
# and a download. On a CPU-only platform these route host; accelerator
# platforms keep the device kernels. The backend-parity and
# dispatch-budget tests monkeypatch this set empty to exercise the
# device kernels on CPU CI.
_CPU_HOST_OPS = frozenset({
    "take_matrix", "take_reach", "margin_prefix_ok", "admit_domains",
    "adopt_scores", "top_m",
})

_PLATFORM = None


def _platform() -> str:
    global _PLATFORM
    if _PLATFORM is None:
        _PLATFORM = jax.default_backend()
    return _PLATFORM


def _host_route(op: str) -> bool:
    """True when ``op`` should run the host reference on this platform."""
    return op in _CPU_HOST_OPS and _platform() == "cpu"


def _bucket(n: int) -> int:
    """Next power-of-two row count (min 16) — the jit shape bucket."""
    return max(16, 1 << (max(int(n), 1) - 1).bit_length())


def _pad_rows(a: np.ndarray, n_pad: int, fill=0):
    if n_pad == a.shape[0]:
        return a
    pad = np.full((n_pad - a.shape[0],) + a.shape[1:], fill, dtype=a.dtype)
    return np.concatenate([a, pad], axis=0)


# --------------------------------------------------------------------------
# bit-exact column scan (traced helper)


def _cumsum_cols(x):
    """[B, W] row-wise cumulative sum with NumPy's bit order.

    ``jnp.cumsum`` lowers to a tree reduction whose different add order
    breaks bit parity; a ``lax.scan`` over columns performs the adds
    sequentially left-to-right, exactly like ``np.cumsum(axis=1)``."""
    def step(c, col):
        c = c + col
        return c, c

    _, ys = jax.lax.scan(step, jnp.zeros(x.shape[0], x.dtype), x.T)
    return ys.T


# --------------------------------------------------------------------------
# jitted kernels (traced under x64; all integer/elementwise → bit-exact)


@jax.jit
def _sm64_j(x):
    x = x + _U64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)
    return x ^ (x >> _U64(31))


@jax.jit
def _chain_j(h, key):
    return _sm64_j(h ^ key)


@jax.jit
def _u01_j(h):
    return (h >> _U64(11)).astype(jnp.float64) * (2.0 ** -53)


def _mix_cheap(h):
    h = h * _U64(0xFF51AFD7ED558CCD)
    h = h ^ (h >> _U64(32))
    h = h * _U64(0xC4CEB9FE1A85EC53)
    h = h ^ (h >> _U64(29))
    return (h >> _U64(40)).astype(jnp.float32) * np.float32(2.0 ** -24)


@jax.jit
def _cheap_u01_j(fold, key):
    return _mix_cheap(key ^ fold)


def _cell_key(rows, t_grid):
    return (rows[:, None] << _U64(24)) ^ t_grid[None, :]


@jax.jit
def _cell_noise_j(fold, rows, t_grid):
    return _mix_cheap(_cell_key(rows, t_grid) ^ fold)


# fused synthesis window: level gather + cheap mixer + centered noise +
# clip in ONE dispatch. The f32 (u−½)·amp product feeding the add is
# round24-fenced against FMA contraction (the old two-kernel split at
# this seam is gone)
@jax.jit
def _synth_window_j(levels, slot, fold, rows, t0, amp):
    util = jnp.take_along_axis(levels, slot, axis=1)
    t_grid = (t0 + jnp.arange(slot.shape[1], dtype=jnp.int64)).astype(
        jnp.uint64)
    u = _mix_cheap(_cell_key(rows, t_grid) ^ fold)
    noise = round24((u - np.float32(0.5)).astype(jnp.float64)
                    * amp.astype(jnp.float64))
    return jnp.clip(util + noise, 0.0, 1.0)


# fused forecast exponent: splitmix row premix + cheap mixer + the two
# f32 scale multiplies in ONE dispatch, each multiply round24-fenced
# against reassociation (the old split before ``* std`` is gone)
@jax.jit
def _forecast_z_j(fold, rows, now, leads, std):
    row_h = _sm64_j(rows ^ fold)[:, None]
    key = row_h ^ ((now << _U64(20)) + leads[None, :])
    u = _mix_cheap(key ^ fold)
    t = round24((u - np.float32(0.5)).astype(jnp.float64)
                * np.float64(np.float32(np.sqrt(12.0))))
    return round24(t.astype(jnp.float64) * std[None, :].astype(jnp.float64))


@jax.jit
def _score_ub_j(spare_ub, delta, m_min, m_max, sigma, dom, excess_col, dd):
    ex = excess_col[dom]
    reach_ub = jnp.minimum(spare_ub * dd, ex / delta)
    ok = (reach_ub >= m_min) & (ex > 0)
    ub = jnp.where(ok, sigma * jnp.minimum(reach_ub, m_max), -jnp.inf)
    return ub, jnp.isfinite(ub).sum()


# top-k over the reversed array: lax.top_k breaks value ties by lowest
# index first, which on the reversed scores means *largest original
# position* first — the contract's tie rule. k = M+1 so the last value
# is the exact maximum upper bound over the unselected remainder.
@partial(jax.jit, static_argnums=1)
def _top_m_j(ub, M):
    n = ub.shape[0]
    vals, ridx = jax.lax.top_k(ub[::-1], M + 1)
    return (n - 1) - ridx[:M], vals[M]


# probe kernels against the device-resident reach state: step-bound
# clips recomputed on device (integer ops, free) so a probe uploads only
# its per-duration thresholds w and host ranks j. Split at the float64
# mul→add boundary (no wider type exists to widen-and-round through):
# the product kernel's convert + single multiply must round before the
# sum kernel's adds, exactly like the NumPy reference
@jax.jit
def _probe_parts_j(cnt, dom, a, b, j, w, dd):
    ai = jnp.minimum(a, dd)
    bi = jnp.minimum(b, dd)
    pa = w * (ai - cnt[dom, j, ai])
    pb = w * (bi - cnt[dom, j, bi])
    return pa, pb


@jax.jit
def _probe_sum_j(csum, dom, a, b, j, pa, pb, dd):
    ai = jnp.minimum(a, dd)
    bi = jnp.minimum(b, dd)
    return (csum[dom, j, bi] + pb) - (csum[dom, j, ai] + pa)


@jax.jit
def _take_matrix_j(spare, budget_rows, delta):
    return jnp.minimum(spare, budget_rows / delta[:, None])


@jax.jit
def _take_reach_j(spare, budget_rows, delta):
    return _cumsum_cols(jnp.minimum(spare, budget_rows / delta[:, None]))


@jax.jit
def _greedy_scores_j(sigma, reach, m_min, m_max):
    total = jnp.minimum(reach, m_max)
    return sigma * total, total >= m_min


def _margin_scan(drain, dom_sel, budgets, doms):
    def one(p):
        mask = dom_sel == p
        cd = jnp.cumsum(jnp.where(mask[:, None], drain, 0.0), axis=0)
        okp = (cd <= budgets[p][None, :] * MARGIN).all(axis=1)
        okp = okp & (budgets[p] >= 0.0).all()
        return jnp.where(mask, okp, True)

    return jax.vmap(one)(doms).all(axis=0)


@jax.jit
def _margin_j(drain, dom_sel, budgets, doms):
    return _margin_scan(drain, dom_sel, budgets, doms)


# fused admission chunk pass: takes, bit-exact sequential cumsum,
# feasibility, overshoot capping and the (decision-safe, vmapped) margin
# scan in ONE dispatch. The spare chunk is donated — it is a fresh
# upload each pass and its buffer is reusable for ``capped``. Infeasible
# rows contribute exactly-zero drain to the margin scan (+0.0 preserves
# every prefix bit), matching the reference's filtered-subset scan.
@partial(jax.jit, donate_argnums=0)
def _admit_j(spare, budgets, dom_sel, delta, m_min, m_max, doms):
    take = jnp.minimum(spare, budgets[dom_sel] / delta[:, None])
    cum = _cumsum_cols(take)
    total = jnp.minimum(cum[:, -1], m_max)
    feas = total >= m_min
    overshoot = cum - m_max[:, None]
    capped = jnp.where(overshoot > 0.0,
                       jnp.maximum(take - overshoot, 0.0), take)
    drain = jnp.where(feas[:, None], take * delta[:, None], 0.0)
    ok = _margin_scan(drain, dom_sel, budgets, doms)
    return feas, ok, capped


class JaxBackend(NumpyBackend):
    name = "jax"
    # what the v5e compiler refuses on this backend's path
    _TPU_REFUSED = ("XLA:TPU refuses the round24 fence in synth_window / "
                    "forecast_noise_z (bitcast_convert_type f64 -> u64 "
                    "needs X64 element types)")

    def __init__(self):
        if _platform() == "tpu":
            raise RuntimeError(
                f"backend {self.name!r} does not compile for a TPU: "
                f"{self._TPU_REFUSED}. Use backend='numpy' on a TPU; the "
                "32-bit-limb lowering is ROADMAP S4.")
        # the vmapped margin scan batches over this abstract axis; with
        # >1 device the axis maps onto real hardware, on one device it
        # lowers to a single batched scan
        from repro.sharding.specs import make_abstract_mesh
        self.domain_mesh = make_abstract_mesh((len(jax.devices()),),
                                              ("domains",))

    # -- counter-hash synthesis primitives -------------------------------
    def _flat(self, name, fn, x, dtype, *extra):
        """Pad-to-bucket → jit → slice/reshape for 1-d-able primitives."""
        x = np.asarray(x, dtype=np.uint64)
        flat = x.ravel()
        n = flat.size
        self._tick(name)
        with jax.enable_x64(True):
            out = fn(jnp.asarray(_pad_rows(flat, _bucket(n))), *extra)
            out = np.asarray(out)[:n].astype(dtype, copy=False)
        return out.reshape(x.shape)

    def sm64(self, x):
        return self._flat("sm64", _sm64_j, x, np.uint64)

    def u01(self, h):
        return self._flat("u01", _u01_j, h, np.float64)

    def cheap_u01(self, fold, key):
        key = np.asarray(key, dtype=np.uint64)
        flat = key.ravel()
        n = flat.size
        self._tick("cheap_u01")
        with jax.enable_x64(True):
            out = _cheap_u01_j(_U64(fold),
                               jnp.asarray(_pad_rows(flat, _bucket(n))))
            out = np.asarray(out)[:n]
        return out.reshape(key.shape)

    def hash64(self, seed, salt, *keys):
        from .base import sm64 as host_sm64
        h0 = host_sm64(np.asarray(
            _U64(seed) ^ host_sm64(np.asarray(_U64(salt)))))
        keys = [np.asarray(k, dtype=np.uint64) for k in keys]
        if not keys:
            return h0
        shape = np.broadcast_shapes(*(k.shape for k in keys))
        h = np.broadcast_to(np.asarray(h0), shape).copy()
        for k in keys:
            kb = np.ascontiguousarray(np.broadcast_to(k, shape))
            n = h.size
            self._tick("hash64")
            with jax.enable_x64(True):
                out = _chain_j(jnp.asarray(_pad_rows(h.ravel(), _bucket(n))),
                               jnp.asarray(_pad_rows(kb.ravel(), _bucket(n))))
                h = np.asarray(out)[:n].reshape(shape)
        return h

    # -- fused synthesis grids -------------------------------------------
    def cell_noise(self, fold, rows, t_grid):
        rows = np.asarray(rows, dtype=np.uint64)
        t_grid = np.asarray(t_grid, dtype=np.uint64)
        if rows.size * t_grid.size < _DEVICE_MIN_ROWS:
            return super().cell_noise(fold, rows, t_grid)
        rp = _bucket(rows.size)
        self._tick("cell_noise")
        with jax.enable_x64(True):
            out = _cell_noise_j(_U64(fold),
                                jnp.asarray(_pad_rows(rows, rp)),
                                jnp.asarray(t_grid))
            return np.asarray(out)[:rows.size]

    def synth_window(self, levels, slot, fold, rows, t0, amp):
        R, W = slot.shape
        if R * W < _DEVICE_MIN_ROWS:
            return super().synth_window(levels, slot, fold, rows, t0, amp)
        rp, wp = _bucket(R), _bucket(W)
        levels = _pad_rows(np.ascontiguousarray(levels), rp)
        slot_p = np.zeros((rp, wp), dtype=np.int64)
        slot_p[:R, :W] = slot
        rows_p = _pad_rows(np.asarray(rows, dtype=np.uint64), rp)
        self._tick("synth_window")
        with jax.enable_x64(True):
            out = _synth_window_j(jnp.asarray(levels), jnp.asarray(slot_p),
                                  _U64(fold), jnp.asarray(rows_p),
                                  np.int64(t0), np.float32(amp))
            return np.asarray(out)[:R, :W]

    def forecast_noise_z(self, fc_fold, rows, now, horizon, std):
        rows = np.asarray(rows, dtype=np.uint64)
        if rows.size * horizon < _DEVICE_MIN_ROWS:
            return super().forecast_noise_z(fc_fold, rows, now, horizon, std)
        rp, hp = _bucket(rows.size), _bucket(horizon)
        leads = np.arange(1, hp + 1, dtype=np.uint64)
        std_b = np.zeros(hp, dtype=np.float32)
        std_b[:horizon] = np.broadcast_to(
            np.asarray(std, dtype=np.float32), (horizon,))
        self._tick("forecast_noise_z")
        with jax.enable_x64(True):
            out = _forecast_z_j(_U64(fc_fold),
                                jnp.asarray(_pad_rows(rows, rp)),
                                _U64(now), jnp.asarray(leads),
                                jnp.asarray(std_b))
            # explicit copy: callers apply np.exp(z, out=z) in place, and
            # the sliced download may otherwise be a read-only device view
            return np.array(np.asarray(out)[:rows.size, :horizon])

    # -- greedy-solver elementwise math ----------------------------------
    def take_matrix(self, spare, budget_rows, delta):
        if spare.size < _DEVICE_MIN_ROWS or _host_route("take_matrix"):
            return super().take_matrix(spare, budget_rows, delta)
        B = spare.shape[0]
        bp = _bucket(B)
        self._tick("take_matrix")
        with jax.enable_x64(True):
            out = _take_matrix_j(
                jnp.asarray(_pad_rows(np.ascontiguousarray(spare), bp)),
                jnp.asarray(_pad_rows(np.ascontiguousarray(budget_rows), bp)),
                jnp.asarray(_pad_rows(np.asarray(delta), bp, fill=1.0)))
            return np.asarray(out)[:B]

    def take_reach(self, spare, budget_rows, delta):
        if spare.size < _DEVICE_MIN_ROWS or _host_route("take_reach"):
            return super().take_reach(spare, budget_rows, delta)
        B, W = spare.shape
        bp = _bucket(B)
        self._tick("take_reach")
        with jax.enable_x64(True):
            out = _take_reach_j(
                jnp.asarray(_pad_rows(np.ascontiguousarray(spare), bp)),
                jnp.asarray(_pad_rows(np.ascontiguousarray(budget_rows), bp)),
                jnp.asarray(_pad_rows(np.asarray(delta), bp, fill=1.0)))
            # full contiguous download, host-side slice (no strided copy)
            return np.asarray(out)[:B]

    def greedy_scores(self, sigma, reach, m_min, m_max):
        if sigma.size < _DEVICE_MIN_ROWS:
            return super().greedy_scores(sigma, reach, m_min, m_max)
        B = sigma.shape[0]
        bp = _bucket(B)
        self._tick("greedy_scores")
        with jax.enable_x64(True):
            score, feas = _greedy_scores_j(
                jnp.asarray(_pad_rows(sigma, bp)),
                jnp.asarray(_pad_rows(reach, bp)),
                jnp.asarray(_pad_rows(m_min, bp, fill=np.inf)),
                jnp.asarray(_pad_rows(m_max, bp)))
            return np.asarray(score)[:B], np.asarray(feas)[:B]

    # -- lazy-greedy candidate scoring / selection ------------------------
    def fleet_cols(self, **cols):
        """Move the per-round fleet columns device-resident, padded to
        the jit shape bucket (pads score -inf and are never selected)."""
        n = cols["delta"].shape[0]
        kp = _bucket(n)
        fills = {"delta": 1.0, "m_min": np.inf}
        self._tick("fleet_cols")
        with jax.enable_x64(True):
            out = {k: jnp.asarray(_pad_rows(
                np.ascontiguousarray(v), kp, fill=fills.get(k, 0)))
                for k, v in cols.items()}
        out["_rows"] = n
        return out

    def score_ub(self, cols, excess_col, dd):
        self._tick("score_ub")
        with jax.enable_x64(True):
            ub, n_viable = _score_ub_j(
                cols["spare_ub"], cols["delta"], cols["m_min"],
                cols["m_max"], cols["sigma"], cols["dom"],
                jnp.asarray(excess_col), np.float64(dd))
        return ub, int(n_viable)

    def top_m(self, ub, M):
        if _host_route("top_m"):
            # the padded handle's -inf pads sort identically under the
            # position-descending tie rule, so bits match either route
            return super().top_m(np.asarray(ub), int(M))
        self._tick("top_m")
        with jax.enable_x64(True):
            idx, bound = _top_m_j(ub, int(M))
        return np.asarray(idx, dtype=np.int64), float(bound)

    def adopt_scores(self, ub):
        ub = np.asarray(ub, dtype=np.float64)
        if ub.size < _DEVICE_MIN_ROWS or _host_route("adopt_scores"):
            return super().adopt_scores(ub)
        self._tick("adopt_scores")
        with jax.enable_x64(True):
            return jnp.asarray(_pad_rows(ub, _bucket(ub.size),
                                         fill=-np.inf))

    # -- segment-domain reach evaluator ----------------------------------
    def segment_reach(self, tables, dom, a, b, w, dom_sort=None):
        from .base import _reach_rank as base_reach_rank
        w = np.asarray(w, dtype=np.float64)
        if w.size < _DEVICE_MIN_ROWS:
            return super().segment_reach(tables, dom, a, b, w, dom_sort)
        dom = np.asarray(dom, dtype=np.int64)
        a = np.asarray(a, dtype=np.int64)
        b = np.asarray(b, dtype=np.int64)
        # the integer breakpoint rank stays host-side in every backend
        # (parity contract); pads (all-zero queries) contribute exactly 0
        j = base_reach_rank(tables["vals"], dom, w, dom_sort)
        n = w.size
        npad = _bucket(n)
        H = tables["cnt"].shape[1] - 1
        self._tick("segment_reach", 2)
        with jax.enable_x64(True):
            di, ji, ai, bi = (jnp.asarray(_pad_rows(x, npad))
                              for x in (dom, j, a, b))
            wj = jnp.asarray(_pad_rows(w, npad))
            pa, pb = _probe_parts_j(jnp.asarray(tables["cnt"]),
                                    di, ai, bi, ji, wj, np.int64(H))
            out = _probe_sum_j(jnp.asarray(tables["csum"]),
                               di, ai, bi, ji, pa, pb, np.int64(H))
            return np.asarray(out)[:n]

    # -- fused probe pipeline ---------------------------------------------
    def reach_state(self, r_excess, seg, kept, noise_mult_ub=None):
        state = super().reach_state(r_excess, seg, kept, noise_mult_ub)
        n = state["seg"]["a"].size
        if n >= _DEVICE_MIN_ROWS:
            npad = _bucket(n)
            with jax.enable_x64(True):
                state["_dev"] = {
                    "cnt": jnp.asarray(state["tables"]["cnt"]),
                    "csum": jnp.asarray(state["tables"]["csum"]),
                    "dom": jnp.asarray(_pad_rows(state["seg"]["dom"], npad)),
                    "a": jnp.asarray(_pad_rows(state["seg"]["a"], npad)),
                    "b": jnp.asarray(_pad_rows(state["seg"]["b"], npad)),
                    "npad": npad,
                }
        return state

    def reach_state_subset(self, state, keep):
        new = super().reach_state_subset(state, keep)
        n = new["seg"]["a"].size
        if n >= _DEVICE_MIN_ROWS:
            npad = _bucket(n)
            old = state.get("_dev")
            with jax.enable_x64(True):
                dev = {
                    "dom": jnp.asarray(_pad_rows(new["seg"]["dom"], npad)),
                    "a": jnp.asarray(_pad_rows(new["seg"]["a"], npad)),
                    "b": jnp.asarray(_pad_rows(new["seg"]["b"], npad)),
                    "npad": npad,
                }
                if old is not None:
                    # the prefix tables are subset-invariant: keep the
                    # resident device buffers, upload only the (smaller)
                    # compacted segment columns
                    dev["cnt"], dev["csum"] = old["cnt"], old["csum"]
                else:
                    dev["cnt"] = jnp.asarray(new["tables"]["cnt"])
                    dev["csum"] = jnp.asarray(new["tables"]["csum"])
            new["_dev"] = dev
        return new

    def probe_scores(self, state, dd, excess_col):
        dev = state.get("_dev")
        if dev is None:
            return super().probe_scores(state, dd, excess_col)
        # host: per-window ν thresholds + integer breakpoint ranks (the
        # reference bits); device: the fenced float middle, 2 dispatches
        # against the resident tables — only w and j cross per probe
        w, _a, _b, j = self.probe_segment_w(state, dd)
        n = w.size
        self._tick("probe_scores", 2)
        with jax.enable_x64(True):
            wj = jnp.asarray(_pad_rows(w, dev["npad"]))
            ji = jnp.asarray(_pad_rows(j, dev["npad"]))
            pa, pb = _probe_parts_j(dev["cnt"], dev["dom"], dev["a"],
                                    dev["b"], ji, wj, np.int64(dd))
            g = _probe_sum_j(dev["csum"], dev["dom"], dev["a"], dev["b"],
                             ji, pa, pb, np.int64(dd))
            g = np.asarray(g)[:n]
        return self._probe_tail(state, dd, excess_col, g)

    # -- chunked admission ------------------------------------------------
    def margin_prefix_ok(self, drain, dom_sel, budgets):
        B = drain.shape[0]
        if (B * drain.shape[1] < _DEVICE_MIN_ROWS
                or _host_route("margin_prefix_ok")):
            return super().margin_prefix_ok(drain, dom_sel, budgets)
        bp = _bucket(B)
        doms = np.arange(budgets.shape[0], dtype=np.int64)
        self._tick("margin_prefix_ok")
        with jax.enable_x64(True):
            ok = _margin_j(
                jnp.asarray(_pad_rows(np.ascontiguousarray(drain), bp)),
                jnp.asarray(_pad_rows(
                    np.asarray(dom_sel, dtype=np.int64), bp)),
                jnp.asarray(budgets), jnp.asarray(doms))
            return np.asarray(ok)[:B]

    def admit_domains(self, spare, budgets, dom_sel, delta, m_min, m_max):
        if spare.size < _DEVICE_MIN_ROWS or _host_route("admit_domains"):
            return super().admit_domains(spare, budgets, dom_sel, delta,
                                         m_min, m_max)
        B, W = spare.shape
        bp, wp = _bucket(B), _bucket(W)
        sp = np.zeros((bp, wp), dtype=np.float64)
        sp[:B, :W] = spare
        bu = np.zeros((budgets.shape[0], wp), dtype=budgets.dtype)
        bu[:, :W] = budgets
        doms = np.arange(budgets.shape[0], dtype=np.int64)
        self._tick("admit_domains")
        with jax.enable_x64(True):
            feas, ok, capped = _admit_j(
                jnp.asarray(sp), jnp.asarray(bu),
                jnp.asarray(_pad_rows(
                    np.asarray(dom_sel, dtype=np.int64), bp)),
                jnp.asarray(_pad_rows(np.asarray(delta), bp, fill=1.0)),
                jnp.asarray(_pad_rows(np.asarray(m_min), bp, fill=np.inf)),
                jnp.asarray(_pad_rows(np.asarray(m_max), bp)),
                jnp.asarray(doms))
            # full contiguous downloads, host-side slices
            return (np.asarray(feas)[:B], np.asarray(ok)[:B],
                    np.asarray(capped)[:B, :W])

    # -- misc -------------------------------------------------------------
    def asnumpy(self, x):
        return np.asarray(x)
