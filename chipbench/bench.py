"""The harness: one cell of ``BENCHMARK.json``, run once.

A cell names a configuration and a traffic mix; everything specific to one
of them, or to one metric, sits in a file found by that name:

- ``configs/<config>.json``: sizes, training settings and the limits of the
  check; ``reference/<config>.py``: the plain reference beside them, with
  the weights and data this configuration is fed;
- ``traffic/<traffic>.json``: the fleet deployment (scenario, fleet,
  strategy), read by :func:`build` below;
- ``metrics/<metric>.py``: a ``read(run)`` that returns the metric, or
  ``None`` where the run holds nothing to read.

What runs is the program's own entry: ``FLSimulation.run``, built by
``core.experiment.build_experiment`` with a ``JaxTrainer``, one round at a
time. Host spans are taken at the program's public seams: the strategy's
``select`` and the trainer's ``local_update``, ``aggregate`` and
``evaluate``; round execution is the rest of each round.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import sys
import tempfile
import time
import traceback
from collections import defaultdict
from contextlib import contextmanager, nullcontext
from functools import partial
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

import jax

from chipbench import check, trace

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
SPANS = ("select", "local_update", "aggregate_eval")
TRACED_SPAN = "traced"
MAX_WARMUP_ROUNDS = 64

_modules: Dict[Path, object] = {}


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold ``-``)."""
    path = Path(path).resolve()
    if path not in _modules:
        name = "chipbench._by_name." + "_".join(
            part.replace("-", "_").replace(".", "_")
            for part in path.relative_to(HERE).with_suffix("").parts)
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return _modules[path]


# ---------------------------------------------------------------------------
# cells


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    reference: object
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, benchmark: Optional[dict] = None) -> Cell:
    bench = benchmark or load_json(REPO / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; the benchmark has "
                       f"{sorted(cells)}")
    w = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, chips=w["chips"],
        config=load_json(REPO / entry["file"]),
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        reference=load_module(HERE / "reference" / f"{entry['name']}.py"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def metric_reader(name: str):
    return load_module(HERE / "metrics" / f"{name}.py")


def peak_table() -> dict:
    return load_json(HERE / "peaks.json")["devices"]


# ---------------------------------------------------------------------------
# building the experiment


def program_model(cfg: dict):
    """The program's model for a configuration file's ``program`` entry:
    a model class with its arguments, or a ``repro.configs`` architecture
    with the fields it replaces (checked against the published numbers)."""
    spec = cfg["program"]
    if "class" in spec:
        module, _, cls = spec["class"].rpartition(".")
        return getattr(importlib.import_module(module), cls)(**spec["kwargs"])
    from repro.configs import get_config
    from repro.models import build_model
    mc = dataclasses.replace(get_config(spec["config"]),
                             **spec.get("replace", {}))
    for attr, key in spec.get("matches", {}).items():
        if getattr(mc, attr) != cfg["model"][key]:
            raise ValueError(f"{spec['config']}.{attr} = {getattr(mc, attr)}"
                             f" but the published {key} is "
                             f"{cfg['model'][key]}")
    return build_model(mc, remat=spec.get("remat", False))


def precision(cfg: dict):
    """The configuration's matmul precision, as a context."""
    p = cfg["matmul_precision"]
    return (jax.default_matmul_precision(p) if p != "default"
            else nullcontext())


def split_seed(seed: int):
    """The data's generator and the trainer's seed (weights and batch
    order), both drawn from ``--seed``; any whole number will do."""
    data, train = np.random.SeedSequence(seed).spawn(2)
    return (np.random.default_rng(data),
            int(train.generate_state(1)[0] & 0x7FFFFFFF))


@dataclasses.dataclass
class Built:
    sim: object
    shards: Dict[int, dict]
    test: dict
    recorder: object
    train_seed: int


def build(cell: Cell, seed: int) -> Built:
    from repro.core import (ExperimentConfig, FleetSection, JaxTrainer,
                            RunSection, ScenarioSection, StrategySection,
                            build_experiment, build_registry, build_scenario)
    from repro.data.federated import FederatedData

    cfg, tr, ref = cell.config, cell.traffic, cell.reference
    dep = tr["deployment_seed"]
    data_rng, train_seed = split_seed(seed)
    sizes = ref.shard_sizes(cfg, tr["fleet"]["n_clients"],
                            np.random.default_rng(dep))
    exp = ExperimentConfig(
        scenario=ScenarioSection(seed=dep, **tr["scenario"]),
        fleet=FleetSection(workload=cfg["fleet_workload"], seed=dep,
                           samples_per_client=sizes, **tr["fleet"]),
        strategy=StrategySection(seed=dep, **tr["strategy"]),
        run=RunSection(seed=dep, **tr["run"]))
    t0 = time.perf_counter()
    scenario = build_scenario(exp)
    registry = build_registry(exp, scenario)
    t1 = time.perf_counter()
    shards, test = ref.make_data(cfg, sizes, data_rng)
    data = FederatedData(
        client_data=dict(zip(registry.client_names, shards)),
        test_data=test, task="lm" if "tokens" in test else "classification")
    recorder = check.Recorder(data, list(registry.client_names))
    t2 = time.perf_counter()
    model = program_model(cfg)
    # the benchmark makes the weights, from the seed, on the device in one
    # jitted call; the trainer draws them through model.init
    model.init = jax.jit(partial(ref.init_params, cfg))
    t = cfg["train"]
    trainer = JaxTrainer(model, data, lr=t["lr"], batch_size=t["batch"],
                         prox_mu=t["prox_mu"], seed=train_seed,
                         max_steps_per_round=t["max_steps"],
                         eval_batch=t["eval_batch"])
    jax.block_until_ready(trainer.params)
    sim = build_experiment(exp, scenario=scenario, registry=registry,
                           trainer=trainer)
    log(f"set-up: fleet {t1 - t0:.3f} s, data {t2 - t1:.3f} s, "
        f"model and weights {time.perf_counter() - t2:.3f} s")
    return Built(sim=sim, shards=dict(enumerate(shards)), test=test,
                 recorder=recorder, train_seed=train_seed)


def first_row(seed: int, n_clients: int) -> int:
    """The client whose first steps set-up drives, drawn from the seed."""
    return int(np.random.default_rng([seed, 2]).integers(n_clients))


# ---------------------------------------------------------------------------
# instrumentation


class Compiles:
    """Counts the programs JAX compiles or loads from its persistent cache
    while ``on``: ``n`` of them, ``loaded`` of those from the cache."""

    def __init__(self):
        self.on = False
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def reset(self):
        self.n = self.loaded = 0
        self.names: List[str] = []

    def _duration(self, event, duration, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def _event(self, event, **kw):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1


class Instrument:
    """Host spans around the simulation's calls into each layer, through
    its public seams: the strategy's ``select`` and the trainer's
    ``local_update``, ``aggregate`` and ``evaluate``. Each span is also a
    ``TraceAnnotation`` when ``annotate``, so that it lies on the device
    trace's clock. Rows trained are the FedAvg weights (samples processed)
    of the updates ``aggregate`` receives; an update's steps are its weight
    over the batch size."""

    def __init__(self, sim, batch: int, annotate: bool = False):
        self.annotate = annotate
        self.reset()
        strategy, trainer = sim.strategy, sim.trainer
        strategy.select = self._timed("select", strategy.select)
        trainer.evaluate = self._timed("aggregate_eval", trainer.evaluate)
        update, aggregate = trainer.local_update, trainer.aggregate

        def local_update(row, n_batches):
            t0 = time.perf_counter()
            with self.span("local_update"):
                out = update(row, n_batches)
            self.updates.append((time.perf_counter() - t0,
                                 float(out["weight"]) / batch))
            return out

        def timed_aggregate(updates):
            self.rows += sum(float(u["weight"]) for u in updates)
            with self.span("aggregate_eval"):
                return aggregate(updates)

        trainer.local_update = local_update
        trainer.aggregate = timed_aggregate

    def reset(self):
        self.spans: Dict[str, List[float]] = defaultdict(list)
        self.updates: List[tuple] = []
        self.rows = 0.0

    def total(self) -> float:
        return sum(sum(v) for v in self.spans.values())

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with (jax.profiler.TraceAnnotation(name) if self.annotate
              else nullcontext()):
            yield
        self.spans[name].append(time.perf_counter() - t0)

    def _timed(self, name, fn):
        def timed(*args, **kw):
            with self.span(name):
                return fn(*args, **kw)
        return timed


# ---------------------------------------------------------------------------
# rounds


def one_round(sim):
    """Run the next FL round; returns it and whether its train loss and
    evaluation are finite."""
    before = sim.round_idx
    sim.run(max_rounds=before + 1)
    if sim.round_idx != before + 1:
        raise RuntimeError(f"the scenario ended at round {before}")
    rr = sim.results[-1]
    ok = math.isfinite(rr.eval_metric) and (
        rr.contributors.size == 0 or math.isfinite(rr.train_loss))
    return rr, ok


def warm_up(sim, rounds_with_contributors: int,
            on_round: Callable = lambda rr: None) -> int:
    """Whole rounds until that many had contributors: the local step, the
    sample-loss probe, aggregation and evaluation have all compiled."""
    trained = 0
    for n in range(1, MAX_WARMUP_ROUNDS + 1):
        rr, ok = one_round(sim)
        on_round(rr)
        if not ok:
            raise RuntimeError(f"warm-up round {rr.round_idx} gave a "
                               "non-finite loss or evaluation")
        trained += rr.contributors.size > 0
        if trained >= rounds_with_contributors:
            return n
    raise RuntimeError(f"{MAX_WARMUP_ROUNDS} warm-up rounds, only "
                       f"{trained} with contributors")


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read this."""
    window_s: float
    round_s: List[float]
    rows: float
    spans: Dict[str, List[float]]
    updates: List[tuple]
    setup_s: float
    flops_per_sample: float
    peak_flops: Optional[float]
    memory_peak_bytes: Optional[int]
    trace: Optional[dict]
    attempted: int
    failed: int


def window(sim, inst: Instrument, compiles: Compiles, seconds: float,
           on_round: Callable = lambda rr: None,
           stop_trace: Optional[Callable] = None, trace_rounds: int = 0):
    """Start rounds until ``seconds`` have passed; always ends on a
    finished round. With ``stop_trace``, the first ``trace_rounds`` rounds
    lie in the span :data:`TRACED_SPAN`, then the trace is stopped; the
    time that takes is left out of the window. Each round's time outside
    the layers' spans is kept as its ``execute_round`` span. Returns the
    per-round wall times, the window's length and the failed rounds."""
    inst.reset()
    compiles.reset()
    compiles.on = True
    durations, failed, paused = [], 0, 0.0
    traced = (jax.profiler.TraceAnnotation(TRACED_SPAN) if stop_trace
              else None)
    if traced:
        traced.__enter__()
    t0 = time.perf_counter()
    while True:
        r0, spans0 = time.perf_counter(), inst.total()
        try:
            rr, ok = one_round(sim)
        except Exception:  # a round that raises ends the window
            traceback.print_exc()
            durations.append(time.perf_counter() - r0)
            failed += 1
            break
        d = time.perf_counter() - r0
        durations.append(d)
        inst.spans["execute_round"].append(d - (inst.total() - spans0))
        failed += not ok
        on_round(rr)
        if traced and len(durations) == trace_rounds:
            p0 = time.perf_counter()
            traced.__exit__(None, None, None)
            traced = None
            stop_trace()
            paused += time.perf_counter() - p0
        if time.perf_counter() - t0 - paused >= seconds:
            break
    window_s = time.perf_counter() - t0 - paused
    compiles.on = False
    if traced:
        traced.__exit__(None, None, None)
        stop_trace()
    return durations, window_s, failed


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# one run of a cell


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             devices, t_start: float, peaks: Optional[dict] = None,
             fault: Optional[Callable] = None, control: bool = False
             ) -> dict:
    """Build, warm up, time a window, check; returns the result object.

    ``t_start`` is the process's start on ``time.perf_counter``'s clock.
    ``fault`` (tests and calibration only) plants a fault of
    ``faults.py`` in the built simulation, underneath the check's capture;
    ``control`` (calibration only) puts the reference at the control's
    precision in the program's place in the check."""
    cfg, tr = cell.config, cell.traffic
    device = devices[0]
    peaks = peak_table() if peaks is None else peaks
    if device.device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device.device_kind!r} in "
                       "chipbench/peaks.json")
    compiles = Compiles()
    with precision(cfg), jax.default_device(device):
        built = build(cell, seed)
        sim = built.sim
        if fault:
            fault(sim)
        capture = check.Capture(sim.trainer, built.recorder, cfg)
        inst = Instrument(sim, cfg["train"]["batch"], annotate=traced)
        t0 = time.perf_counter()
        capture.first_steps(first_row(seed, len(built.shards)))
        t1 = time.perf_counter()
        warm = warm_up(sim, tr["warmup_rounds_with_contributors"],
                       capture.end_round)
        setup_s = time.perf_counter() - t_start
        log(f"set-up: first steps {t1 - t0:.3f} s, {warm} warm-up rounds "
            f"{time.perf_counter() - t1:.3f} s; {setup_s:.3f} s in all")
        trace_dir = None
        if traced:
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(trace_dir, profiler_options=_options())

        def stop_trace():
            t = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"trace: stopped in {time.perf_counter() - t:.1f} s")

        durations, window_s, failed = window(
            sim, inst, compiles, seconds, capture.end_round,
            stop_trace if traced else None, tr["trace_rounds"])
        log(f"window: {len(durations)} rounds in {window_s:.3f} s; "
            f"{compiles.n} programs compiled or loaded inside it, "
            f"{compiles.loaded} of them from the persistent cache "
            f"{sorted(set(compiles.names))}")
        log("rounds (s): " + " ".join(f"{d:.3f}" for d in durations))
        log("select (s): " + " ".join(
            f"{d:.3f}" for d in inst.spans.get("select", [])))
        peak = memory_peak(devices[:cell.chips])
        reduced = None
        if traced:
            t0 = time.perf_counter()
            reduced = trace.reduce_dir(trace_dir, SPANS, TRACED_SPAN)
            log(f"trace: reduced in {time.perf_counter() - t0:.1f} s")
        run = Run(window_s=window_s, round_s=durations, rows=inst.rows,
                  spans=dict(inst.spans), updates=list(inst.updates),
                  setup_s=setup_s,
                  flops_per_sample=cell.reference.flops_per_sample(cfg),
                  peak_flops=peaks[device.device_kind]["bf16_flops"],
                  memory_peak_bytes=peak, trace=reduced,
                  attempted=len(durations), failed=failed)
        shards, test, train_seed = built.shards, built.test, built.train_seed
        # the program's state goes before the reference runs; the check
        # keeps the last round's arrays alone
        capture.trainer = None
        del built, sim, inst
        gc.collect()
        t0 = time.perf_counter()
        numbers = check.compare(cell, capture, shards, test, train_seed,
                                seed, control=control)
        log(f"check: {time.perf_counter() - t0:.1f} s")
    return result(cell, run, numbers, device, len(devices[:cell.chips]),
                  traced)


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1  # the harness's spans, not JAX's internals
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def result(cell: Cell, run: Run, numbers: Dict[str, float], device,
           count: int, traced: bool) -> dict:
    limits = cell.config["check"]
    for k in sorted(set(numbers) - set(limits)):
        log(f"not compared {k} {numbers[k]!r}")
    correct = run.failed == 0 and all(
        numbers[k] <= limits[k] for k in limits)  # nan is never within
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = metric_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": count, "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": bool(correct), "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": dev}
    if traced and run.trace is not None:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["check"] = {k: {"value": numbers[k], "limit": limits[k]}
                    for k in limits}
    return out

