"""The harness end to end on the CPU at a small size, and the command's
refusal to measure anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import bench
from chipbench.tests.small import run_small

CELLS = ["kwt1.paper100", "smollm-360m.paper100"]


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_of_a_cell(name):
    """Set-up, two warm-up rounds with contributors, a window of at least
    one round and the check, through the harness's own round loop."""
    out = run_small(name, seed=2**31 + 7)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    cell = bench.find_cell(name)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "check"
    assert set(out["check"]) == set(cell.config["check"])


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "kwt1.paper100",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_the_command_fails_off_a_tpu():
    proc = _run(bench.REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_the_command_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(bench.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
