"""Readings that the limits of a cell whose driver checks round by round,
holding one reference round at a time (``Cell.compare_stepwise``: the
hybrid LM, whose planes are GBs on the host), are set from, all in one
process on the chip:

    python bench/probe_stepwise.py --workload <name> --seeds 1 2 3 ... \
        [--control-seeds 1 2] [--altered-seeds 1] [--out file]

For each seed the cell's set-up runs the program through its checked
rounds and the numbers ``correct`` compares are read for it
(``program``).  For each control seed the same numbers are read for the
control (the reference in bfloat16 in the program's place) and for the
fault ``half_batch`` planted in the reference (half of each DPU's batch,
or of its one sequence, left out); for each altered seed, for the fault
``altered`` (each round's update scaled by 1.1, which reads 0.1 on the
update gaps by construction).  Each is a trajectory judged round by round
from its own boundaries, as ``bench/probe.py`` reads a stepped cell.  A
round that returns its state unchanged reads 1 on the update gaps by
construction.  One JSON line per seed and kind goes to standard output
and to ``--out``.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench_out" / "tpu_logs"))


def main(argv):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--altered-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax.numpy as jnp

    import harness
    from repro.utils.compile_cache import enable_compile_cache
    _, w, cfg, traffic = harness.find_cell(args.workload)
    enable_compile_cache()
    harness.require_tpu(w["chips"])
    driver = harness.load_module(harness.BENCH / "drivers" /
                                 f"{cfg['kind']}.py", "bench_driver")
    out = open(args.out, "a") if args.out else None

    def emit(seed, kind, t0, nums):
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "kind": kind, "s": time.perf_counter() - t0,
                           **nums})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in args.seeds:
        cell = driver.Cell(cfg, traffic, seed, harness.Recorder())
        t0 = time.perf_counter()
        cell.setup()
        emit(seed, "setup", t0, {"loss": cell.prog["loss"]})
        t0 = time.perf_counter()
        emit(seed, "program", t0, cell.check())
        kinds = ((("control", jnp.bfloat16, ""),
                  ("half_batch", jnp.float32, "half_batch"))
                 if seed in args.control_seeds else ()) + \
            ((("altered", jnp.float32, "altered"),)
             if seed in args.altered_seeds else ())
        for kind, dtype, fault in kinds:
            t0 = time.perf_counter()
            emit(seed, kind, t0, cell.compare_trajectory(dtype, fault))
        del cell
    harness.log(f"probe: {time.perf_counter() - T_START!r} s in all")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
