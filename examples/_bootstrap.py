"""Shared example bootstrapping: make ``repro`` importable when an
example is run straight from a checkout (``python examples/<name>.py``)
without installing the package or exporting ``PYTHONPATH=src``, and turn
on JAX's persistent compilation cache
(:func:`repro.compile_cache.enable_compile_cache`).

Every example starts with::

    import _bootstrap  # noqa: F401  (repo-checkout sys.path setup)

The path setup is a no-op when ``repro`` is already importable (installed
package, or ``PYTHONPATH=src`` set as the doc headers show).
"""
import os
import sys

try:
    import repro  # noqa: F401
except ImportError:
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "..", "src"))

from repro.compile_cache import enable_compile_cache

enable_compile_cache()
