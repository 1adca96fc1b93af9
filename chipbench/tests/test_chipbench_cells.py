"""``BENCHMARK.json`` against the benchmark's rules, and every name in it
found as a file of its own."""
import json
import re

import numpy as np
import pytest

from chipbench import bench, check

BENCH = json.loads((bench.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
REFERENCE_API = ("CONTROL", "BLOCK_ROWS", "init_params", "logits",
                 "grad_fn", "flops_per_sample", "shard_sizes", "make_data")


def line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def test_top_level_command_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((bench.REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (bench.REPO / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(line(w) for w in cmd)
    for word in cmd:
        assert not word.startswith("/") and ".." not in word
        if (bench.REPO / word).exists():
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_keys_names_and_lines(section):
    entries = BENCH[section]
    limit = {"configs": 24, "workloads": 24, "end_to_end": 16,
             "per_layer": 128}[section]
    assert 1 <= len(entries) <= limit
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        extra = set(e) - KEYS[section]
        assert set(e) >= KEYS[section] and extra <= (
            {"workloads"} if section in ("end_to_end", "per_layer")
            else set()), e
        assert NAME.match(e["name"])
        if "why" in e:
            assert line(e["why"])
        if section in ("end_to_end", "per_layer"):
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
        if section == "per_layer":
            assert line(e["layer"])


def test_configurations():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert line(c["source"]) and c["source"].startswith("https://")
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        cfg = bench.load_json(bench.REPO / c["file"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert "foreign_rows" in cfg["check"]
        assert set(cfg["check"]) <= set(check.NUMBERS)
        assert cfg["replay_updates"] >= 1
        ref = bench.load_module(bench.HERE / "reference"
                                / f"{c['name']}.py")
        for attr in REFERENCE_API:
            assert hasattr(ref, attr), (c["name"], attr)


def test_workloads_and_their_metrics():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(pairs) // 2)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and line(w["why"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        cell = bench.find_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names, (w["name"], m["name"])


def test_every_metric_has_a_reader_of_its_own():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(bench.metric_reader(m["name"]).read), m["name"]
        for cell in m.get("workloads", []):
            assert cell in {w["name"] for w in BENCH["workloads"]}


def test_unknown_cell_is_an_error_naming_the_cells():
    with pytest.raises(KeyError, match="kwt1.paper100"):
        bench.find_cell("no.such")


def test_peaks_name_their_source_and_a_v5e():
    table = bench.load_json(bench.HERE / "peaks.json")
    assert line(table["source"])
    assert table["devices"]["TPU v5 lite"]["bf16_flops"] == 197e12


def test_leaf_gaps_floor_and_the_kept_leaves():
    want = np.array([1.0, 2.0, 4.0, 1e-6])
    got = np.array([1.1, 2.0, 4.4, 0.5])
    gaps = check.leaf_gaps(got, want, check.kept(want))
    # the tiny leaf is left out; each gap is over the larger of the leaf's
    # norm and the median kept leaf's (2.0)
    np.testing.assert_allclose(gaps, [0.05, 0.0, 0.1, 0.0])
    assert check.steps_of(0.4, 50) == 1 and check.steps_of(73.6, 50) == 50
    assert check.steps_of(12.5, 50) == 12
