"""Run one benchmark cell once, from the root of a checkout:

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last the ``checks``: each number compared with the
plain reference beside its limit.  The same checks are the last lines of
standard error.  The run refuses anything but a TPU with the compiled
Pallas kernels.  JAX's persistent compilation cache lives in
``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# before JAX is imported: the cache directory is fixed inside the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
# libtpu's logs stay inside the checkout too, unless the machine says where
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench_out" / "tpu_logs"))

if __name__ == "__main__":
    import harness
    sys.exit(harness.main(sys.argv[1:], T_START))
