"""Run with the repository's sources and the benchmark's own modules on
the path, on the CPU:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests
"""
import os
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parent, HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
# a compilation cache of the test process's own, never the checkout's
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-jax-cache-"))
