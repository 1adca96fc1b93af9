"""Spans and counters of the FL round loop: one recorder, off by default.

The round loop (``FLSimulation.run``), the strategy, the solver and the
``JaxTrainer`` mark their layer boundaries with :func:`span` and
:func:`count`; every host/device crossing on that path goes through
:func:`to_host` or :func:`to_device`. While the recorder is off each of
these costs one flag check (``to_host`` / ``to_device`` still convert).
While it is on:

- each span is also a ``jax.profiler.TraceAnnotation`` (a round is a
  ``StepTraceAnnotation`` named ``fl.round`` with the round index as its
  step number), so a profiler trace taken meanwhile holds the spans on
  the device trace's clock; in memory the recorder keeps, per span name,
  its calls, total seconds and self seconds (total less the time its
  child spans cover), so memory stays bounded however long a job runs;
- counters add up by name;
- every program JAX compiles or loads from its persistent cache is
  counted under the innermost span open on the calling thread.

    from repro import telemetry
    telemetry.reset(); telemetry.enable()
    sim.run(max_rounds=10)
    snap = telemetry.snapshot(); telemetry.disable()

:func:`snapshot` returns ``{"spans": {name: {"calls", "total_s",
"self_s"}}, "counters": {name: value}, "compiles": {span: {"programs",
"loaded"}}}``; a compile outside every span is keyed ``"outside"``.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import defaultdict
from typing import Dict

import numpy as np

import jax

enabled = False

OUTSIDE = "outside"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_local = threading.local()
_listening = False
_calls: Dict[str, int] = defaultdict(int)
_total: Dict[str, float] = defaultdict(float)
_self: Dict[str, float] = defaultdict(float)
_counters: Dict[str, float] = defaultdict(int)
_programs: Dict[str, int] = defaultdict(int)
_loaded: Dict[str, int] = defaultdict(int)


class _Off:
    """The one context every span returns while the recorder is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "annotation", "t0", "children")

    def __init__(self, name: str, annotation):
        self.name = name
        self.annotation = annotation

    def __enter__(self):
        self.annotation.__enter__()
        self.children = 0.0
        _stack().append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        d = time.perf_counter() - self.t0
        stack = _stack()
        stack.pop()
        if stack:
            stack[-1].children += d
        with _lock:
            _calls[self.name] += 1
            _total[self.name] += d
            _self[self.name] += d - self.children
        self.annotation.__exit__(*exc)
        return False


def span(name: str):
    """A named span of host time; a no-op while the recorder is off."""
    if not enabled:
        return _OFF
    return _Span(name, jax.profiler.TraceAnnotation(name))


def round(idx: int):  # noqa: A001 - the round loop's own word
    """Round ``idx``: the ``fl.round`` span, and a profiler step marker
    whose step number every span of the round lies under."""
    if not enabled:
        return _OFF
    return _Span("fl.round",
                 jax.profiler.StepTraceAnnotation("fl.round", step_num=idx))


def spanned(name: str):
    """Decorate a function so that each call is the span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kw):
            with span(name):
                return fn(*args, **kw)
        return inner
    return wrap


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name`` while the recorder is on."""
    if enabled:
        with _lock:
            _counters[name] += n


def to_host(x):
    """``jax.device_get(x)``: the NumPy arrays of ``x`` (an array or a
    pytree of them), fetched together as one readback, in the span
    ``fl.sync`` and counted once as ``host_syncs`` while the recorder is
    on."""
    if not enabled:
        return jax.device_get(x)
    with span("fl.sync"):
        out = jax.device_get(x)
    count("host_syncs")
    return out


def to_device(batch):
    """A pytree of host arrays put on the default device in one
    ``jax.device_put``; their ``nbytes`` are counted as ``h2d_bytes``
    while the recorder is on (arrays already on the device count
    nothing)."""
    if enabled:
        count("h2d_bytes", sum(int(np.asarray(v).nbytes)
                               for v in jax.tree.leaves(batch)
                               if not isinstance(v, jax.Array)))
    return jax.device_put(batch)


def _innermost() -> str:
    stack = getattr(_local, "stack", None)
    return stack[-1].name if stack else OUTSIDE


def _on_duration(event, duration, **kw):
    if enabled and event == _COMPILE_EVENT:
        with _lock:
            _programs[_innermost()] += 1


def _on_event(event, **kw):
    if enabled and event == _CACHE_HIT_EVENT:
        with _lock:
            _loaded[_innermost()] += 1


def enable() -> None:
    """Turn the recorder on; the first call registers the compile
    listener (public ``jax.monitoring``)."""
    global enabled, _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    enabled = True


def disable() -> None:
    global enabled
    enabled = False


def reset() -> None:
    """Forget every span, counter and compile recorded so far."""
    with _lock:
        for table in (_calls, _total, _self, _counters, _programs, _loaded):
            table.clear()


def snapshot() -> dict:
    """What has been recorded since the last :func:`reset`, as plain
    numbers (see the module's docstring for the schema)."""
    with _lock:
        return {
            "spans": {name: {"calls": _calls[name], "total_s": _total[name],
                             "self_s": _self[name]} for name in _calls},
            "counters": dict(_counters),
            "compiles": {name: {"programs": _programs.get(name, 0),
                                "loaded": _loaded.get(name, 0)}
                         for name in set(_programs) | set(_loaded)},
        }
