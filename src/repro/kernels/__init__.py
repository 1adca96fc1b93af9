"""Pallas kernels for the repo's compute hot-spots.

FedZero itself is a scheduling contribution (no kernel in the paper), but
the client training workloads it schedules have three hot loops that we
implement TPU-native: flash attention (+sliding window), the MoE grouped
GEMM, and the RWKV6 chunked scan. Each has a pure-jnp oracle in ref.py and
is validated in interpret mode over shape/dtype sweeps, and compiled for
a described v5e by tests/test_v5e_compile.py. The scheduler side
contributes the counter-hash synthesis kernels (:mod:`.counter_hash`:
piece-grid window + forecast exponent), validated in interpret mode
against the NumPy counter-hash reference bit-for-bit and selected via
``backend="pallas"`` in the backend registry.

No kernel module imports :mod:`repro.backend`; the dependency runs the
other way.
"""
from . import ops, ref
from .ops import (flash_attention, forecast_z, moe_gemm, piece_window,
                  rwkv_scan)

__all__ = ["ops", "ref", "flash_attention", "moe_gemm", "rwkv_scan",
           "piece_window", "forecast_z"]
