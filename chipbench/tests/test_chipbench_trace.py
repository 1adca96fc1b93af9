"""The reduction from a profiler trace to busy time, idle gaps by host span
and the busiest device operations."""
import glob
import os
import time

import numpy as np
import pytest

from chipbench import trace

MS = 1e6  # ns


def test_union_merges_overlaps_and_keeps_gaps():
    iv = np.array([[5, 20], [0, 10], [30, 40], [35, 38], [40, 45]], float)
    np.testing.assert_array_equal(trace.union(iv), [[0, 20], [30, 45]])
    assert trace.union(np.zeros((0, 2))).shape == (0, 2)


def test_reduce_busy_idle_and_labels():
    ops = [(0, 10 * MS, "fusion"), (5 * MS, 20 * MS, "dot"),
           (30 * MS, 40 * MS, "fusion"),
           (-10 * MS, 2 * MS, "outside"), (200 * MS, 210 * MS, "outside")]
    spans = [(0, 100 * MS, "window"), (20 * MS, 30 * MS, "select"),
             (45 * MS, 100 * MS, "local_update")]
    out = trace.reduce([ops], spans, "window")
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.030)
    gaps = dict(out["idle_gaps"])
    # a gap is named after the span open at its middle: 20-30 ms under
    # select; 40-100 ms (middle 70 ms) under local_update
    assert gaps == pytest.approx({"select": 0.010, "local_update": 0.060})
    assert sum(gaps.values()) == pytest.approx(0.1 - out["busy_s"])
    assert dict(out["device_ops"]) == pytest.approx(
        {"fusion": 0.020, "dot": 0.015, "outside": 0.002})
    assert [n for n, _ in out["device_ops"]] == ["fusion", "dot", "outside"]


def test_ops_are_named_after_their_program():
    programs = [(100, 200, "jit_local_step(123)"), (0, 50, "jit_evaluate(7)")]
    ops = [(10, 20, "%fusion.3 = f32[10,99,64]{1,2,0} fusion(...)"),
           (120, 150, "%while.5 = (s32[], f32[12,64]) while(...)"),
           (60, 70, "%copy.1 = f32[4] copy(...)")]
    assert trace.in_programs(ops, programs) == [
        (10, 20, "jit_evaluate/%fusion.3"),
        (120, 150, "jit_local_step/%while.5"),
        (60, 70, "%copy.1")]
    assert trace.in_programs([], []) == []


def test_reduce_averages_busy_over_chips():
    spans = [(0, 100 * MS, "window")]
    out = trace.reduce([[(0, 50 * MS, "a")], [(0, 10 * MS, "a")]], spans,
                       "window")
    assert out["busy_s"] == pytest.approx(0.030)


def test_reduce_needs_a_window_and_a_device():
    with pytest.raises(ValueError):
        trace.reduce([[(0, 1, "a")]], [], "window")
    with pytest.raises(ValueError):
        trace.reduce([], [(0, 1, "window")], "window")


def test_host_spans_of_a_trace_recorded_here(tmp_path):
    """A trace recorded on the CPU holds the harness's annotations on the
    trace's clock; with device operations laid into its window, the
    reduction names the idle time after the span open then."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("select"):
                time.sleep(0.02)
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    chips, spans = trace.events(files[0], ["window", "select"])
    assert chips == []  # the CPU is no device plane
    selects = [s for s in spans if s[2] == "select"]
    window = [s for s in spans if s[2] == "window"]
    assert len(selects) == 2 and len(window) == 1
    assert all(0.02 <= (b - a) * 1e-9 < 1.0 for a, b, _ in selects)
    w0, w1 = window[0][:2]
    assert all(w0 <= a and b <= w1 for a, b, _ in selects)
    # device work everywhere but in the first select
    s0, s1 = selects[0][:2]
    out = trace.reduce([[(w0, s0, "op"), (s1, w1, "op")]], spans, "window")
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"select": (s1 - s0) * 1e-9})


def test_reduce_dir_deletes_the_trace(tmp_path):
    d = tmp_path / "t"
    d.mkdir()
    with pytest.raises(ValueError):
        trace.reduce_dir(str(d), ["select"], "window")
    assert not d.exists()
