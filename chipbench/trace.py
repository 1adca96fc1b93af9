"""From a profiler trace to the device's busy time, its idle gaps and its
busiest operations.

:func:`events` reads an ``.xplane.pb``: the operations on each TPU (the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane) and the host spans the
harness annotated. :func:`reduce` is plain arithmetic on those lists:

- busy: the union of a chip's operation intervals inside the window,
  averaged over the chips;
- idle gaps: the rest of the window, each gap named after the harness span
  open at its middle (``other`` where none is), summed by name;
- device operations: the operations' time summed by name, each op named
  ``<program>/<op>`` (:func:`in_programs`).
"""
from __future__ import annotations

import glob
import os
import shutil
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float, str]   # start ns, end ns, name
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
TOP = 10


def in_programs(ops: List[Interval], programs: List[Interval]
                ) -> List[Interval]:
    """Each operation named ``<program>/<op>`` after the program (an ``XLA
    Modules`` event) that was running when it started; the op's own name is
    cut to the instruction's name (``%fusion.3``), the program's to its
    function (``jit_local_step``)."""
    programs = sorted(programs)
    starts = np.array([p[0] for p in programs], float)
    where = np.searchsorted(starts, np.array([o[0] for o in ops], float),
                            side="right") - 1
    names = [p[2].split("(", 1)[0] + "/" for p in programs]
    out = []
    for (a, b, name), j in zip(ops, where.tolist()):
        op = name.split(" = ", 1)[0]
        if j >= 0 and a < programs[j][1]:
            op = names[j] + op
        out.append((a, b, op))
    return out


def events(path: str, span_names: Sequence[str]):
    """``(ops per chip, host spans)`` of one ``.xplane.pb`` file."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    wanted = set(span_names)
    chips: List[List[Interval]] = []
    spans: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {line.name: [(e.start_ns, e.end_ns, e.name)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, PROGRAMS_LINE)}
            chips.append(in_programs(lines.get(OPS_LINE, []),
                                     lines.get(PROGRAMS_LINE, [])))
        else:
            spans.extend((e.start_ns, e.end_ns, e.name)
                         for line in plane.lines for e in line.events
                         if e.name in wanted)
    return chips, spans


def union(intervals: np.ndarray) -> np.ndarray:
    """Disjoint, sorted ``[start, end]`` rows covering the same time."""
    if not len(intervals):
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    ends = reach[np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]]
    return np.stack([starts, ends], 1)


def reduce(chips: List[List[Interval]], spans: List[Interval],
           window_name: str) -> Dict:
    """Busy and window seconds, the idle gaps by host span and the top
    device operations. The window is the host span ``window_name``."""
    win = [s for s in spans if s[2] == window_name]
    if not win or not chips:
        raise ValueError("the trace holds no window span or no device")
    w0, w1 = win[0][0], win[0][1]
    host = sorted((s for s in spans if s[2] != window_name
                   and s[1] > w0 and s[0] < w1))
    busy, gaps_by_name = [], defaultdict(float)
    op_time = defaultdict(float)
    for i, ops in enumerate(chips):
        iv = np.array([(max(a, w0), min(b, w1)) for a, b, _ in ops
                       if b > w0 and a < w1], float).reshape(-1, 2)
        covered = union(iv)
        busy.append(float(np.sum(covered[:, 1] - covered[:, 0])) * 1e-9)
        for a, b, name in ops:
            if b > w0 and a < w1:
                op_time[name] += (min(b, w1) - max(a, w0)) * 1e-9
        if i == 0:
            for name, sec in _label_gaps(covered, w0, w1, host).items():
                gaps_by_name[name] += sec
    window_s = (w1 - w0) * 1e-9
    return {
        "busy_s": float(np.mean(busy)),
        "window_s": window_s,
        "idle_gaps": _top(gaps_by_name),
        "device_ops": _top(op_time),
    }


def _label_gaps(covered: np.ndarray, w0: float, w1: float,
                host: List[Interval]) -> Dict[str, float]:
    edges = np.concatenate([[w0], covered.ravel(), [w1]]).reshape(-1, 2)
    lengths = edges[:, 1] - edges[:, 0]
    edges, lengths = edges[lengths > 0], lengths[lengths > 0]
    mids = (edges[:, 0] + edges[:, 1]) / 2
    # the harness's spans do not nest: the latest opened before the middle
    # holds it, or none does
    starts = np.array([s[0] for s in host] + [np.inf])
    ends = np.array([s[1] for s in host] + [-np.inf])
    names = np.array([s[2] for s in host] + ["other"])
    j = np.searchsorted(starts, mids, side="right") - 1
    j[(j < 0) | (ends[j] < mids)] = len(host)
    out: Dict[str, float] = defaultdict(float)
    for name in np.unique(names[j]):
        out[str(name)] += float(np.sum(lengths[names[j] == name])) * 1e-9
    return out


def _top(seconds_by_name: Dict[str, float]) -> List[list]:
    return [[name, sec] for name, sec in
            sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce_dir(trace_dir: str, span_names: Sequence[str],
               window_name: str) -> Dict:
    """Reduce the one trace a run wrote under ``trace_dir``, then delete
    it."""
    try:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise ValueError(f"expected one trace under {trace_dir}, found "
                             f"{len(files)}")
        chips, spans = events(files[0], list(span_names) + [window_name])
        return reduce(chips, spans, window_name)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
