"""NumPy-vs-JAX array-backend parity (the contract in repro.backend.base).

Three layers, mirroring how the backend is consumed:

1. **Primitive parity** — the counter-hash mixers and the fused grid
   draws are integer/elementwise-float ops, so the JAX backend must
   return bit-identical arrays (both under and over its device-dispatch
   crossover, which pads to jit shape buckets).
2. **Synthesis parity** — :class:`_SparseUtil` windows and per-row
   forecast noise, gathered dense and as row subsets, must be
   bit-identical across backends (the scheduling stack consumes these
   bits directly).
3. **Decision parity** — greedy admission over both the materialized and
   the lazy/sharded path must pick the same rows at the same minimal
   feasible duration; since PR 7 that includes the reach-evaluator ops
   (``reach_tables`` / ``segment_reach`` / ``adopt_scores`` and the
   position-descending ``top_m``) that make the uncapped lazy walk
   exact. The slow markers pin the acceptance scenarios: a seeded
   10k-client dense store and an **uncapped** 1M-client sparse store,
   compared round for round.
"""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.backend import available_backends, get_backend
from repro.core import make_paper_registry
from repro.core.experiment import (ExperimentConfig, FleetSection,
                                   RunSection, ScenarioSection,
                                   StrategySection, run_experiment)
from repro.core.selection import (LazySelectionInputs, SelectionInputs,
                                  select_clients)
from repro.data.traces import _SparseUtil

NP = get_backend("numpy")
JX = get_backend("jax")
# exercise both sides of the JAX backend's host/device crossover
SIZES = [(7, 13), (300, 40), (5000, 64)]


@pytest.fixture(autouse=True)
def _force_device_kernels(monkeypatch):
    """On a CPU-only platform the jax backend routes the admission /
    top-k ops to the host reference (measured placement — see
    docs/backends.md), which would make their parity checks vacuous.
    Clear the routing set so this module always exercises the device
    kernels against the reference."""
    from repro.backend import jax_backend
    monkeypatch.setattr(jax_backend, "_CPU_HOST_OPS", frozenset())


def test_registry_lists_both_backends():
    names = available_backends()
    assert "numpy" in names and "jax" in names
    assert get_backend("jax") is JX          # singleton
    assert get_backend(JX) is JX             # instance passthrough
    assert get_backend(None) is NP
    with pytest.raises(KeyError):
        get_backend("no_such_backend")


@pytest.mark.parametrize("name,refused", [("jax", "round24"),
                                          ("pallas", "uint64")])
def test_device_backends_refuse_tpu(name, refused, monkeypatch):
    """On a TPU the jax and pallas backends raise, naming what the v5e
    compiler refuses, instead of routing to the host or interpreting."""
    import repro.backend as registry
    from repro.backend import jax_backend
    monkeypatch.setattr(jax_backend, "_platform", lambda: "tpu")
    monkeypatch.setattr(registry, "_SINGLETONS", {})
    with pytest.raises(RuntimeError, match=refused) as err:
        get_backend(name)
    assert "ROADMAP S4" in str(err.value)
    assert get_backend("numpy").name == "numpy"


# ---------------------------------------------------------------------------
# 1. primitives


@pytest.mark.parametrize("n", [1, 17, 4096, 70000])
def test_hash_primitives_bit_identical(n, rng):
    x = rng.integers(0, 2 ** 63, n, dtype=np.int64).astype(np.uint64)
    np.testing.assert_array_equal(NP.sm64(x), JX.sm64(x))
    np.testing.assert_array_equal(NP.u01(x), JX.u01(x))
    fold = np.uint64(0x9E3779B97F4A7C15)
    a, b = NP.cheap_u01(fold, x), JX.cheap_u01(fold, x)
    assert a.dtype == b.dtype == np.float32
    np.testing.assert_array_equal(a, b)


def test_hash64_chain_bit_identical(rng):
    rows = rng.integers(0, 10 ** 9, (257, 3)).astype(np.uint64)
    seg = rng.integers(0, 10 ** 6, (257, 3)).astype(np.uint64)
    np.testing.assert_array_equal(NP.hash64(42, 201, rows, seg),
                                  JX.hash64(42, 201, rows, seg))
    # scalar chain (no keys) stays host-exact too
    assert NP.hash64(7, 203) == JX.hash64(7, 203)


@pytest.mark.parametrize("R,W", SIZES)
def test_fused_grids_bit_identical(R, W, rng):
    fold = np.uint64(rng.integers(0, 2 ** 62))
    rows = np.sort(rng.choice(10 ** 6, R, replace=False)).astype(np.int64)
    t_grid = (10_000 + np.arange(W)).astype(np.int64)
    np.testing.assert_array_equal(NP.cell_noise(fold, rows, t_grid),
                                  JX.cell_noise(fold, rows, t_grid))

    n_slots = 5
    levels = rng.random((R, n_slots), dtype=np.float32)
    slot = rng.integers(0, n_slots, (R, W)).astype(np.int64)
    a = NP.piece_grid(levels.copy(), slot, fold, rows, 10_000, 0.1732)
    b = JX.piece_grid(levels.copy(), slot, fold, rows, 10_000, 0.1732)
    np.testing.assert_array_equal(a, b)

    std = (0.05 + 0.2 * np.minimum(np.arange(1, W + 1) / 1440.0, 1.0)
           ).astype(np.float32)
    a = NP.forecast_noise_z(fold, rows, 777, W, std)
    b = JX.forecast_noise_z(fold, rows, 777, W, std)
    np.testing.assert_array_equal(a, b)
    assert b.flags.writeable  # callers apply np.exp in place


# ---------------------------------------------------------------------------
# 2. sparse-util synthesis


@pytest.mark.parametrize("n_clients", [64, 20000])
def test_sparse_window_parity(n_clients, rng):
    a = _SparseUtil(11, n_clients, 2880, backend="numpy")
    b = _SparseUtil(11, n_clients, 2880, backend="jax")
    rows = np.sort(rng.choice(n_clients, min(n_clients, 4000),
                              replace=False))
    np.testing.assert_array_equal(a.window(rows, 100, 460),
                                  b.window(rows, 100, 460))
    # full-fleet gather and a chunk-boundary-crossing window
    np.testing.assert_array_equal(a.window(None, 1400, 1500),
                                  b.window(None, 1400, 1500))


def test_sparse_forecast_noise_parity(rng):
    a = _SparseUtil(5, 30000, 1440, backend="numpy")
    b = _SparseUtil(5, 30000, 1440, backend="jax")
    rows = np.sort(rng.choice(30000, 6000, replace=False))
    std = (0.05 + 0.2 * np.minimum(np.arange(1, 61) / 1440.0, 1.0)
           ).astype(np.float32)
    np.testing.assert_array_equal(a.forecast_noise(rows, 33, 60, std),
                                  b.forecast_noise(rows, 33, 60, std))


# ---------------------------------------------------------------------------
# 3. solver ops + admission decisions


def test_solver_elementwise_ops_bit_identical(rng):
    B, d, P = 6000, 48, 10
    spare = (rng.random((B, d)) * 5).astype(np.float64)
    budgets = rng.random((P, d)) * 300
    dom = rng.integers(0, P, B)
    delta = 0.5 + rng.random(B) * 3
    np.testing.assert_array_equal(
        NP.take_matrix(spare, budgets[dom], delta),
        JX.take_matrix(spare, budgets[dom], delta))

    sigma = rng.random(B)
    reach = rng.random(B) * 100
    m_min, m_max = rng.random(B) * 20, 20 + rng.random(B) * 80
    sa, fa = NP.greedy_scores(sigma, reach, m_min, m_max)
    sb, fb = JX.greedy_scores(sigma, reach, m_min, m_max)
    np.testing.assert_array_equal(sa, sb)
    np.testing.assert_array_equal(fa, fb)


def test_score_ub_top_m_parity(rng):
    K, P, M = 9000, 10, 256
    cols = dict(delta=0.5 + rng.random(K) * 3,
                m_min=rng.random(K) * 12,
                m_max=30 + rng.random(K) * 50,
                sigma=rng.random(K),
                spare_ub=rng.random(K) * 4,
                dom=rng.integers(0, P, K))
    excess = rng.random(P) * 400
    excess[0] = 0.0  # a dead domain: its candidates must score -inf
    for dd in (1.0, 17.0, 60.0):
        ha = NP.score_ub(NP.fleet_cols(**cols), excess, dd)
        hb = JX.score_ub(JX.fleet_cols(**cols), excess, dd)
        ub_a, nva = ha[0], ha[1]
        ub_b, nvb = np.asarray(hb[0])[:K], hb[1]
        np.testing.assert_array_equal(ub_a, ub_b)
        assert nva == nvb
        np.testing.assert_array_equal(NP.viable_positions(ub_a),
                                      NP.viable_positions(ub_b))
        ia, ba = NP.top_m(ub_a, M)
        ib, bb = JX.top_m(hb[0], M)
        # deterministic tie rule → identical SETS (the admission walk
        # re-sorts by score, so the return order is backend-local)
        assert len(ia) == len(ib) == M
        np.testing.assert_array_equal(np.sort(ia), np.sort(np.asarray(ib)))
        assert ba == bb


def test_margin_prefix_decisions_agree(rng):
    B, d, P = 5000, 32, 8
    drain = (rng.random((B, d)) * 2).astype(np.float64)
    dom_sel = np.sort(rng.integers(0, P, B))
    budgets = rng.random((P, d)) * drain.sum(0).mean() * 0.1
    np.testing.assert_array_equal(
        NP.margin_prefix_ok(drain, dom_sel, budgets),
        JX.margin_prefix_ok(drain, dom_sel, budgets))
    # a ±ulp-negative budget residue degrades that domain to all-False
    budgets[3, 5] = -1e-12
    np.testing.assert_array_equal(
        NP.margin_prefix_ok(drain, dom_sel, budgets),
        JX.margin_prefix_ok(drain, dom_sel, budgets))


def test_reach_tables_and_segment_reach_bit_identical(rng):
    """Reach-evaluator ops over device-crossover shapes (> 4096 queries),
    including zero rows, duplicated breakpoints and w at breakpoints —
    the 4-point contract in docs/backends.md demands bit equality, and
    the tie-exact lazy walk consumes these bits as admission bounds."""
    P, H, N = 8, 60, 9000
    excess = (rng.integers(0, 64, size=(P, H)) / 8.0)
    excess[2] = 0.0                        # dead domain
    excess[3, :10] = excess[3, 10]         # duplicated breakpoints
    ta, tb = NP.reach_tables(excess), JX.reach_tables(excess)
    dom = rng.integers(0, P, N)
    a = rng.integers(0, H + 1, N).astype(np.int64)
    b = np.minimum(a + rng.integers(0, H + 1, N), H).astype(np.int64)
    w = rng.integers(0, 80, N) / 8.0
    w[:P * 4] = excess[dom[:P * 4], rng.integers(0, H, P * 4)]  # on-breakpoint
    w[N - 16:] = 0.0
    ga = NP.segment_reach(ta, dom, a, b, w)
    gb = JX.segment_reach(tb, dom, a, b, w)
    np.testing.assert_array_equal(ga, gb)
    # below the crossover too (host fallback path)
    np.testing.assert_array_equal(
        NP.segment_reach(ta, dom[:100], a[:100], b[:100], w[:100]),
        JX.segment_reach(tb, dom[:100], a[:100], b[:100], w[:100]))


def test_top_m_parity_degenerate_all_ties(rng):
    """A wall-to-wall tie plateau (uniform sigma * m_max) is the landscape
    the retired candidate_cap existed for: both backends must select the
    same M positions (the LARGEST, per the position-descending tie rule)
    and report the identical remainder bound."""
    K, M = 20000, 512
    ub = np.full(K, 36.75)                  # dyadic: no rounding slack
    ub[rng.integers(0, K, 64)] = -np.inf    # a few non-viable holes
    ha, hb = NP.adopt_scores(ub), JX.adopt_scores(ub)
    ia, ba = NP.top_m(ha, M)
    ib, bb = JX.top_m(hb, M)
    assert ba == bb == 36.75                # bound == plateau value
    np.testing.assert_array_equal(np.sort(np.asarray(ia)),
                                  np.sort(np.asarray(ib)))
    finite = np.nonzero(np.isfinite(ub))[0]
    np.testing.assert_array_equal(          # largest finite positions win
        np.sort(np.asarray(ia)), finite[-M:])


def test_adopt_scores_roundtrip_parity(rng):
    """Host-assembled overlay scores adopted into each backend must gather
    back bit-identically and agree on viability and top-M selection."""
    K, M = 6000, 128
    ub = np.where(rng.random(K) < 0.1, -np.inf, rng.random(K) * 50)
    ha, hb = NP.adopt_scores(ub), JX.adopt_scores(ub)
    np.testing.assert_array_equal(np.asarray(NP.asnumpy(ha))[:K],
                                  np.asarray(JX.asnumpy(hb))[:K])
    np.testing.assert_array_equal(NP.viable_positions(ha),
                                  JX.viable_positions(hb))
    ia, ba = NP.top_m(ha, M)
    ib, bb = JX.top_m(hb, M)
    assert ba == bb
    np.testing.assert_array_equal(np.sort(np.asarray(ia)),
                                  np.sort(np.asarray(ib)))


def _random_selection_inputs(backend, seed, K=3000, P=10, H=60):
    rng = np.random.default_rng(seed)
    reg = make_paper_registry(n_clients=K, seed=seed)
    inp = SelectionInputs(
        registry=reg,
        m_spare=(rng.random((K, H)) * reg.capacity_arr[:, None]),
        r_excess=rng.random((P, H)) * 500,
        sigma=rng.random(K),
        rows=np.arange(K),
        dom=rng.integers(0, P, K),
        backend=backend)
    return inp


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_greedy_admission_parity_materialized(seed):
    sa = select_clients(_random_selection_inputs("numpy", seed),
                        n=20, d_max=60, solver="greedy")
    sb = select_clients(_random_selection_inputs("jax", seed),
                        n=20, d_max=60, solver="greedy")
    assert (sa is None) == (sb is None)
    if sa is not None:
        assert sa.expected_duration == sb.expected_duration
        np.testing.assert_array_equal(sa.rows, sb.rows)
        np.testing.assert_array_equal(sa.expected_batches,
                                      sb.expected_batches)


def _lazy_inputs(backend, seed, K=20000, P=10, H=60, cap=0):
    rng = np.random.default_rng(seed)
    reg = make_paper_registry(n_clients=K, seed=seed)
    spare_frac = rng.random((K, H))
    cap_col = reg.capacity_arr

    def spare_of(pos):
        return spare_frac[pos] * cap_col[pos][:, None]

    return LazySelectionInputs(
        registry=reg, spare_of=spare_of, m_spare_ub=cap_col,
        r_excess=rng.random((P, H)) * 800, sigma=rng.random(K),
        rows=np.arange(K), dom=rng.integers(0, P, K),
        candidate_cap=cap, backend=backend)


@pytest.mark.parametrize("seed,cap", [(0, 0), (1, 0), (2, 2048)])
def test_greedy_admission_parity_lazy(seed, cap):
    sa = select_clients(_lazy_inputs("numpy", seed, cap=cap),
                        n=24, d_max=60, solver="greedy")
    sb = select_clients(_lazy_inputs("jax", seed, cap=cap),
                        n=24, d_max=60, solver="greedy")
    assert (sa is None) == (sb is None)
    if sa is not None:
        assert sa.expected_duration == sb.expected_duration
        np.testing.assert_array_equal(sa.rows, sb.rows)
        np.testing.assert_array_equal(sa.expected_batches,
                                      sb.expected_batches)


# ---------------------------------------------------------------------------
# acceptance scenarios: whole simulations, round for round


def _run_rounds(backend, util_mode, n_clients, max_rounds, cap=0,
                exact_uncapped=None):
    options = {"solver": "greedy"}
    if cap:
        options["candidate_cap"] = cap
    cfg = ExperimentConfig(
        scenario=ScenarioSection(util_mode=util_mode, days=1, seed=0),
        fleet=FleetSection(n_clients=n_clients, seed=0),
        strategy=StrategySection(n=10, d_max=60, seed=0, options=options),
        run=RunSection(max_rounds=max_rounds, backend=backend,
                       exact_uncapped=exact_uncapped))
    sims = []
    run_experiment(cfg, sim_out=sims)
    sim = sims[0]
    assert sim.results, "no rounds ran"
    return [(r.round_idx, r.start_step, r.duration, r.participants.tolist(),
             r.contributors.tolist()) for r in sim.results]


def test_experiment_parity_sparse_exact_uncapped():
    """The selection-exactness CI step: a full (small) FedZero run with
    the reach-evaluator path *required*, compared round for round across
    backends. Fast enough for tier-1; the 1M variant is the slow pin."""
    a = _run_rounds("numpy", "sparse", 20_000, 2, exact_uncapped=True)
    b = _run_rounds("jax", "sparse", 20_000, 2, exact_uncapped=True)
    assert a == b


@pytest.mark.slow
def test_experiment_parity_10k_dense():
    a = _run_rounds("numpy", "dense", 10_000, 3)
    b = _run_rounds("jax", "dense", 10_000, 3)
    assert a == b


@pytest.mark.slow
def test_experiment_parity_1m_sparse():
    # uncapped since schema 6: the reach evaluator replaced candidate_cap
    a = _run_rounds("numpy", "sparse", 1_000_000, 2)
    b = _run_rounds("jax", "sparse", 1_000_000, 2)
    assert a == b
