import os
import sys

# src-layout import without installation
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture(scope="session", autouse=True)
def jax_kernel_compilation_cache():
    """Persist XLA compilations of the jitted interpret-mode kernels.

    The Pallas kernel tests dominate suite wall-time, and most of that is
    XLA re-compiling the same interpreter graphs for every (shape, block,
    dtype) parametrization on every run. JAX's persistent compilation
    cache (:func:`repro.compile_cache.enable_compile_cache`) makes every
    parametrization compile once: repeat runs (and other test modules
    reusing a kernel shape) load the executable from disk.
    """
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    # interpret-mode kernels compile on CPU in well under the default
    # 1s/64KB thresholds — cache everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    yield


@pytest.fixture
def rng():
    return np.random.default_rng(0)
