"""Run one cell of the benchmark once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``check``: each number compared beside its limit,
also the last lines of standard error. The run fails, printing no result,
where JAX's first device is not a TPU or there are fewer chips than the
cell asks for. JAX's persistent compilation cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache`` at the root of
the checkout (``repro.compile_cache``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]


def process_age() -> float:
    """Seconds from the process's start to now, on Linux; else 0."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def main(argv=None):
    t_start = T_START - process_age()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(REPO), str(REPO / "src")]
    import jax
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from chipbench import bench

    cell = bench.find_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; JAX's first device is "
                         f"{devices[0].platform!r}")
    if len(devices) < cell.chips:
        raise SystemExit(f"{args.workload} needs {cell.chips} chips; JAX "
                         f"sees {len(devices)}")
    out = bench.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         devices, t_start)
    for name, c in out["check"].items():
        bench.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
