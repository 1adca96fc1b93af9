"""The readings that the check's limits are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1-12 \\
        [--control-seeds 1-3] [--fault-seeds 1-3] [--seconds 0] \\
        [--set matmul_precision="high"]

For each seed it runs the cell as a benchmark run does, with a window of
``--seconds`` (0: one round), and prints the check's numbers:

- ``program``: the program as the cell runs it (the lower readings);
- ``control``: the reference at the configuration's control precision in
  the program's place (the upper readings);
- ``fault:<name>``: the program with a fault of ``faults.py`` planted.

``--set`` overrides a value of the configuration for every run: with
``matmul_precision="high"`` the program's own path one precision down
stands as the control of a float32 configuration. One JSON line per
reading on standard output, then one summary line: the largest program
reading and the smallest control and fault readings of each number.
Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _seeds(text: str):
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out.extend(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="",
                    help="comma-separated names; all of faults.py if empty")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--set", action="append", default=[],
                    help="override a configuration value: train.lr=0.5")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(REPO), str(REPO / "src")]
    import jax
    from chipbench import bench
    from chipbench.faults import FAULTS
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell = bench.find_cell(args.workload)
    for item in args.set:
        key, value = item.split("=", 1)
        *path, last = key.split(".")
        node = cell.config
        for p in path:
            node = node[p]
        node[last] = json.loads(value)
    faults = ({n: FAULTS[n] for n in args.faults.split(",")} if args.faults
              else FAULTS)
    devices = jax.devices()
    worst, least = {}, {}

    def reading(seed, kind, **kw):
        t0 = time.perf_counter()
        out = bench.run_cell(cell, seed, args.seconds, False, devices,
                             time.perf_counter(), **kw)
        numbers = {k: c["value"] for k, c in out["check"].items()}
        print(json.dumps({"seed": seed, "reading": kind, **numbers,
                          "correct": out["correct"],
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)
        table, pick = (worst, max) if kind == "program" else (least, min)
        row = table.setdefault(kind, {})
        for k, v in numbers.items():
            row[k] = pick(row.get(k, v), v)

    for seed in _seeds(args.seeds):
        reading(seed, "program")
    for seed in _seeds(args.control_seeds):
        reading(seed, "control", control=True)
    for seed in _seeds(args.fault_seeds):
        for name, fault in faults.items():
            reading(seed, f"fault:{name}", fault=fault)
    print(json.dumps({"summary": {"program_max": worst.get("program", {}),
                                  **{f"{k}_min": v for k, v in least.items()}
                                  }}), flush=True)


if __name__ == "__main__":
    main()
