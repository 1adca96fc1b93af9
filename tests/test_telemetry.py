"""The recorder of spans and counters (``repro.telemetry``) and where the FL
round loop calls it: off it records nothing and costs no annotation; on it
counts every readback, host-to-device byte, local step and compile where
it happens, and changes no number the loop computes."""
import glob
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core import (FLSimulation, JaxTrainer, ProxyTrainer,
                        make_paper_registry, make_strategy)
from repro.data.federated import synthetic_classification
from repro.data.traces import make_scenario
from repro.models import ConvNet


@pytest.fixture(autouse=True)
def recorder_off():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


class FakeClock:
    """``time.perf_counter`` that moves only when told to."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def proxy_sim(strategy="upper_bound", n_clients=16, seed=0):
    sc = make_scenario("global", n_clients=n_clients, days=1, seed=seed)
    reg = make_paper_registry(n_clients=n_clients, seed=seed,
                              domain_names=sc.domain_names)
    strat = make_strategy(strategy, reg, n=4, d_max=60, seed=seed)
    return FLSimulation(reg, sc, strat, ProxyTrainer(len(reg)),
                        eval_every=1, seed=seed)


def jax_sim(n_clients=6, seed=0):
    sc = make_scenario("global", n_clients=n_clients, days=1, seed=seed)
    reg = make_paper_registry(
        n_clients=n_clients, seed=seed, domain_names=sc.domain_names,
        samples_per_client=np.full(n_clients, 40))
    data = synthetic_classification(n_clients, reg.client_names,
                                    n_classes=4, n_samples=240, hw=4,
                                    seed=seed, n_test=32)
    trainer = JaxTrainer(ConvNet(n_classes=4, channels=(4,), hw=4), data,
                         lr=0.05, prox_mu=0.1, seed=seed,
                         max_steps_per_round=3, eval_batch=16)
    strat = make_strategy("upper_bound", reg, n=2, d_max=60, seed=seed)
    return FLSimulation(reg, sc, strat, trainer, eval_every=1, seed=seed)


def test_off_it_records_nothing_and_builds_no_annotation(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("an annotation was built while off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", refuse)
    assert telemetry.span("fl.a") is telemetry.span("fl.b")
    with telemetry.round(3), telemetry.span("fl.select"):
        telemetry.count("rounds")
    np.testing.assert_array_equal(telemetry.to_host(jnp.arange(3)),
                                  [0, 1, 2])
    moved = telemetry.to_device({"x": np.ones(4, np.float32)})
    assert isinstance(moved["x"], jax.Array)
    proxy_sim().run(max_rounds=2)
    assert telemetry.snapshot() == {"spans": {}, "counters": {},
                                    "compiles": {}}


def test_nested_spans_give_self_and_total_times_and_counters_add(
        monkeypatch):
    clock = FakeClock()
    monkeypatch.setattr(telemetry.time, "perf_counter", clock)
    telemetry.enable()
    for _ in range(2):
        with telemetry.round(0):                 # 10 s in all
            clock.now += 1
            with telemetry.span("fl.select"):    # 4 s, 3 of them its child
                clock.now += 1
                with telemetry.span("fl.select.solve"):
                    clock.now += 3
            with telemetry.span("fl.execute_round"):
                clock.now += 5
            telemetry.count("rounds")
            telemetry.count("rows_trained", 2.5)
    spans = telemetry.snapshot()["spans"]
    want = {"fl.round": (2, 20.0, 2.0), "fl.select": (2, 8.0, 2.0),
            "fl.select.solve": (2, 6.0, 6.0),
            "fl.execute_round": (2, 10.0, 10.0)}
    assert {k: (v["calls"], v["total_s"], v["self_s"])
            for k, v in spans.items()} == want
    assert telemetry.snapshot()["counters"] == {"rounds": 2,
                                                "rows_trained": 5.0}
    telemetry.reset()
    assert telemetry.snapshot()["spans"] == {}


def test_threads_keep_their_own_nesting_and_lose_no_count():
    telemetry.enable()

    def work():
        for _ in range(200):
            with telemetry.span("fl.outer"):
                with telemetry.span("fl.inner"):
                    telemetry.count("n")

    threads = [threading.Thread(target=work) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    snap = telemetry.snapshot()
    assert snap["counters"]["n"] == 1200
    assert snap["spans"]["fl.outer"]["calls"] == 1200
    assert snap["spans"]["fl.inner"]["calls"] == 1200
    outer = snap["spans"]["fl.outer"]
    assert 0 <= outer["self_s"] <= outer["total_s"]


@pytest.mark.parametrize("strategy", ["upper_bound", "fedzero"])
def test_the_round_loop_opens_one_round_select_and_execution_each(strategy):
    sim = proxy_sim(strategy)
    telemetry.enable()
    sim.run(until_step=10 * 60, max_rounds=6)
    snap = telemetry.snapshot()
    spans, counters = snap["spans"], snap["counters"]
    rounds = len(sim.results)
    assert rounds >= 1 and counters["rounds"] == rounds
    # every pass of the loop is a round span with one selection in it; a
    # pass that admits nobody fast-forwards and executes nothing
    assert spans["fl.round"]["calls"] == spans["fl.select"]["calls"]
    assert spans["fl.execute_round"]["calls"] == rounds
    assert spans["fl.record_round"]["calls"] == rounds
    assert spans["fl.evaluate"]["calls"] == rounds
    trained = [r for r in sim.results if r.contributors.size]
    assert spans["fl.aggregate"]["calls"] == len(trained)
    assert spans["fl.local_update"]["calls"] == sum(
        r.contributors.size for r in trained)
    assert counters["rows_trained"] == pytest.approx(sum(
        float(r.batches[r.contributor_idx].sum()) for r in trained))
    if strategy == "upper_bound":
        assert spans["fl.round"]["calls"] == rounds
        assert "fl.select.solve" not in spans
    else:
        solves = spans["fl.select.solve"]["calls"]
        assert spans["fl.select.inputs"]["calls"] == solves
        assert 1 <= solves <= spans["fl.select"]["calls"]
        assert counters["solver_probes"] >= solves
    assert spans["fl.round"]["self_s"] >= 0


@pytest.mark.parametrize("steps", [1, 3])
def test_a_local_update_counts_its_syncs_steps_and_bytes(steps):
    trainer = jax_sim().trainer
    sizes = []
    inner = trainer.data.sample_batch

    def sample_batch(client, batch_size, rng):
        batch = inner(client, batch_size, rng)
        sizes.append(sum(v.nbytes for v in batch.values()))
        return batch

    trainer.data.sample_batch = sample_batch
    trainer.local_update(0, 0.0)  # compiles outside the count
    sizes.clear()
    telemetry.enable()
    trainer.local_update(1, float(steps))
    snap = telemetry.snapshot()
    counters, spans = snap["counters"], snap["spans"]
    # one update, one device program: the step batches staged once,
    # padded to max_steps, with the probe and the step count (an int32)
    assert counters["host_syncs"] == 1
    assert counters["local_steps"] == steps
    assert counters["fused_updates"] == 1
    assert counters["pad_steps"] == trainer.max_steps - steps
    assert len(sizes) == steps + 1
    assert counters["h2d_bytes"] == (trainer.max_steps * sizes[0]
                                     + sizes[-1] + 4)
    assert spans["fl.sync"]["calls"] == 1
    assert spans["fl.local_update.batch"]["calls"] == 1
    assert spans["fl.local_update.step"]["calls"] == 1
    assert "fl.local_update.probe" not in spans


def test_a_compile_is_counted_under_the_span_open_then():
    x = jnp.arange(7.0)
    telemetry.enable()
    with telemetry.span("fl.select"):
        with telemetry.span("fl.compiling"):
            jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    jax.jit(lambda v: v - 2.0)(x).block_until_ready()
    compiles = telemetry.snapshot()["compiles"]
    assert set(compiles) == {"fl.compiling", telemetry.OUTSIDE}
    assert compiles["fl.compiling"]["programs"] == 1
    assert compiles[telemetry.OUTSIDE]["programs"] == 1
    assert all(0 <= c["loaded"] <= c["programs"] for c in compiles.values())


def _leaves(tree):
    return [np.asarray(leaf) for leaf in jax.tree.leaves(tree)]


def test_the_round_loop_computes_the_same_numbers_on_and_off():
    runs = []
    for on in (False, True):
        sim = jax_sim()
        updates = []
        inner = sim.trainer.local_update

        def local_update(row, n_batches):
            out = inner(row, n_batches)
            updates.append(out)
            return out

        sim.trainer.local_update = local_update
        if on:
            telemetry.enable()
        sim.run(max_rounds=3)
        telemetry.disable()
        runs.append((sim, updates))
    (off, off_updates), (on, on_updates) = runs
    assert telemetry.snapshot()["counters"]["rounds"] == 3
    assert len(off_updates) == len(on_updates) > 0
    for a, b in zip(off_updates, on_updates):
        assert a["row"] == b["row"] and a["weight"] == b["weight"]
        assert a["mean_loss"] == b["mean_loss"]
        np.testing.assert_array_equal(a["sample_losses"], b["sample_losses"])
        for x, y in zip(_leaves(a["params"]), _leaves(b["params"])):
            np.testing.assert_array_equal(x, y)
    for x, y in zip(_leaves(off.trainer.params), _leaves(on.trainer.params)):
        np.testing.assert_array_equal(x, y)
    assert [(r.train_loss, r.eval_metric) for r in off.results] == [
        (r.train_loss, r.eval_metric) for r in on.results]


def test_a_profiler_trace_holds_the_spans_and_the_round_markers(tmp_path):
    sim = jax_sim()
    sim.run(max_rounds=1)  # compiles outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    telemetry.enable()
    sim.run(max_rounds=3)
    telemetry.disable()
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    data = jax.profiler.ProfileData.from_file(files[0])
    names, steps = set(), []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                names.add(e.name)
                if e.name == "fl.round":
                    steps += [v for k, v in e.stats if k == "step_num"]
    assert {"fl.round", "fl.select", "fl.execute_round", "fl.local_update",
            "fl.local_update.batch", "fl.local_update.step", "fl.sync",
            "fl.aggregate", "fl.record_round", "fl.evaluate"} <= names
    assert sorted(steps) == [1, 2]
