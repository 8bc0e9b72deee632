"""The benchmark's harness: everything that is not one cell's data.

``run_cell`` is the Python entry ``benchmarks/run.py`` calls; the tests call
it too, on a tiny test-only root. Nothing here imports jax at import time.
"""

from benchmarks.harness.manifest import ManifestError, RefusedError
from benchmarks.harness.window import run_cell

__all__ = ["ManifestError", "RefusedError", "run_cell"]
