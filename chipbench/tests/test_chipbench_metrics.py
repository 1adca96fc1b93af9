"""The window loop and the metric readers: a stall inside the window has
to move ``round_s`` and ``round_s.p90``, and every reader reads what its
name says."""
import time
import types

import numpy as np
import pytest

from chipbench import bench


class FakeTrainer:
    """Local updates of ``n_batches`` steps of batch 10, each sleeping
    ``step_s``, returning their FedAvg weight as JaxTrainer does."""

    def __init__(self, step_s):
        self.step_s = step_s

    def local_update(self, row, n_batches):
        for _ in range(int(n_batches)):
            time.sleep(self.step_s)
        return {"row": row, "weight": 10.0 * int(n_batches)}

    def aggregate(self, updates):
        pass

    def evaluate(self):
        return 0.5


class FakeSim:
    """The surface of FLSimulation the harness drives: rounds of one
    selection, one execution, two local updates of two steps, an aggregate
    and an evaluation; every ``stall_every``-th round sleeps ``stall_s``
    more in execution."""

    def __init__(self, step_s=0.001, stall_every=0, stall_s=0.0):
        self.round_idx = 0
        self.results = []
        self.trainer = FakeTrainer(step_s)
        self.strategy = types.SimpleNamespace(select=lambda env: [0, 1])
        self.stall_every, self.stall_s = stall_every, stall_s

    def _execute_round(self, sel):
        if self.stall_every and self.round_idx % self.stall_every == 0:
            time.sleep(self.stall_s)
        return types.SimpleNamespace(contributors=np.array(sel),
                                     round_idx=self.round_idx,
                                     train_loss=1.0, eval_metric=float("nan"))

    def run(self, max_rounds):
        sel = self.strategy.select(None)
        rr = self._execute_round(sel)
        updates = [self.trainer.local_update(row, 2) for row in sel]
        self.trainer.aggregate(updates)
        rr.eval_metric = self.trainer.evaluate()
        self.results.append(rr)
        self.round_idx += 1


def measure(sim, seconds=0.3):
    inst = bench.Instrument(sim, batch=10)
    compiles = bench.Compiles()
    durations, window_s, failed = bench.window(sim, inst, compiles, seconds)
    return bench.Run(
        window_s=window_s, round_s=durations, rows=inst.rows,
        spans=dict(inst.spans), updates=list(inst.updates), setup_s=1.5,
        flops_per_sample=1e9, peak_flops=1e12, memory_peak_bytes=2e9,
        trace={"busy_s": 0.25, "window_s": 1.0},
        attempted=len(durations), failed=failed)


def read(name, run):
    return bench.metric_reader(name).read(run)


def test_a_stall_moves_round_s_and_its_p90():
    steady = measure(FakeSim())
    stalled = measure(FakeSim(stall_every=4, stall_s=0.03))
    assert read("round_s", stalled) > 1.5 * read("round_s", steady)
    assert read("round_s.p90", stalled) > 0.03
    assert read("round_s.p90", steady) < 0.03
    # a rate over the window: the stall takes time from training
    assert read("samples_per_s", stalled) < read("samples_per_s", steady)
    # and shows in its layer
    assert read("execute_round_ms", stalled) > 5 * read("execute_round_ms",
                                                       steady)


def test_the_window_ends_on_a_finished_round_past_its_length():
    run = measure(FakeSim(step_s=0.002), seconds=0.1)
    assert run.window_s >= 0.1
    assert run.window_s == pytest.approx(sum(run.round_s), rel=0.05)
    assert run.attempted == len(run.round_s) and run.failed == 0


def test_readers_read_the_run():
    run = measure(FakeSim(step_s=0.002))
    rounds = len(run.round_s)
    assert read("round_s", run) == pytest.approx(run.window_s / rounds)
    assert run.rows == rounds * 2 * 2 * 10
    assert read("samples_per_s", run) == pytest.approx(run.rows
                                                       / run.window_s)
    assert read("local_step_ms", run) == pytest.approx(2.0, rel=0.5)
    assert read("select_ms", run) < 1.0
    assert read("aggregate_eval_ms", run) < 1.0
    assert read("setup_s", run) == 1.5
    assert read("train_mfu", run) == pytest.approx(
        100 * run.rows * 1e9 / (run.window_s * 1e12))
    assert read("device_idle", run) == pytest.approx(75.0)
    assert read("hbm_peak_gb", run) == pytest.approx(2.0)
    run.trace = None
    run.memory_peak_bytes = None
    run.spans = {}
    run.updates = []
    for name in ("device_idle", "hbm_peak_gb", "select_ms",
                 "execute_round_ms", "aggregate_eval_ms", "local_step_ms"):
        assert read(name, run) is None, name


def test_a_round_that_raises_ends_the_window_as_failed():
    sim = FakeSim()

    def broken(sel):
        raise RuntimeError("planted")

    sim._execute_round = broken
    run = measure(sim)
    assert run.attempted == 1 and run.failed == 1


def test_a_traced_run_stops_its_trace_after_its_rounds_and_not_on_the_clock():
    sim = FakeSim(step_s=0.002)
    inst = bench.Instrument(sim, batch=10)
    compiles = bench.Compiles()
    stops = []

    def stop_trace():
        stops.append(len(sim.results))
        time.sleep(0.2)

    durations, window_s, failed = bench.window(
        sim, inst, compiles, 0.1, stop_trace=stop_trace, trace_rounds=2)
    assert stops == [2]
    # the 0.2 s the trace took to stop lies outside the window
    assert window_s == pytest.approx(sum(durations), rel=0.05)
    assert failed == 0 and len(durations) >= 2


def test_execute_round_is_the_round_outside_the_layers_spans():
    sim = FakeSim(step_s=0.002, stall_every=1, stall_s=0.01)
    run = measure(sim, seconds=0.1)
    rest = run.spans["execute_round"]
    assert len(rest) == len(run.round_s)
    # each round's stall lies in its remainder; its training does not
    assert all(r >= 0.01 for r in rest), rest
    assert sum(rest) == pytest.approx(
        sum(run.round_s) - sum(run.spans["local_update"])
        - sum(run.spans["select"]) - sum(run.spans["aggregate_eval"]))
    assert sum(rest) < sum(run.round_s) - sum(run.spans["local_update"])
