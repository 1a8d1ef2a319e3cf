"""Readings that the limits of ``bench/limits/<workload>.json`` are set
from, all in one process on the chip:

    python bench/probe.py --workload <name> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--witness-seeds 1] [--out file]

For each seed the cell's set-up runs the program through its checked
rounds, then the numbers ``correct`` compares are read for the program.
For each control seed they are read also for the control (the reference
in bfloat16 in the program's place) and for the faults planted in the
reference (``half_batch``: half of each batch left out; ``altered``:
each round's update scaled by 1.1).  A round that returns its state
unchanged reads 1 on the update gaps by construction.  For a cell whose
check runs the reference from each of the program's round boundaries
(an LM), every seed also compares the program against the reference
running on by itself, leaf by leaf, and each witness seed does the same
for the program with every matmul at ``highest`` precision.  One JSON
line per seed and kind goes to ``--out``.
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


def rows_classifier(cell, numbers, control: bool):
    import jax.numpy as jnp

    import check
    ref = cell.trajectory()
    rows = {"program": dict(numbers,
                            grad_leaves=check.leaf_gaps(cell.prog, ref, 1),
                            change_leaves=check.leaf_gaps(cell.prog, ref,
                                                          -1))}
    if control:
        rows["control"] = check.compare(cell.trajectory(jnp.bfloat16), ref)
        rows["control"]["acc_gap"] = cell.acc_gap(jnp.bfloat16)
        for fault in ("half_batch", "altered"):
            rows[fault] = check.compare(cell.trajectory(fault=fault), ref)
    return rows


def step_leaves(traj, steps) -> dict:
    """Per round, the per-leaf gaps of the program's update against the
    reference's from the same weights."""
    import check
    out = []
    for r, st in enumerate(steps):
        x_r = traj["x"][r]
        p = check.leaf_norms(traj["x"][r + 1], x_r)
        q = check.leaf_norms(st["x"], x_r)
        med = float(sorted(q.values())[len(q) // 2])
        out.append({k: abs(p[k] - q[k]) / max(q[k], med) for k in q})
    return out


def rows_stepped(cell, numbers, control: bool, witness: bool, driver,
                 cfg, traffic, seed):
    import jax
    import jax.numpy as jnp

    import check
    import harness
    rows = {"program": dict(numbers, step_leaves=step_leaves(
        cell.prog, cell.ref_steps))}
    free = cell.trajectory()
    rows["program_free"] = dict(
        check.compare(cell.prog, free),
        change_leaves=check.leaf_gaps(cell.prog, free, -1))
    if witness:
        try:
            with jax.default_matmul_precision("highest"):
                hi = driver.Cell(cfg, traffic, seed, harness.Recorder())
                hi.setup()
            hi.release()
            rows["highest_free"] = dict(
                check.compare(hi.prog, free),
                change_leaves=check.leaf_gaps(hi.prog, free, -1))
            rows["highest_vs_program"] = check.compare(hi.prog, cell.prog)
            steps = cell.steps(hi.prog)
            rows["highest"] = dict(check.compare_steps(hi.prog, steps),
                                   step_leaves=step_leaves(hi.prog, steps))
            del hi
        except Exception as e:          # a witness that fails says so
            rows["highest_error"] = {"error": repr(e)[:1000]}
    if control:
        for kind, traj in (("control", cell.trajectory(jnp.bfloat16)),
                           ("half_batch", cell.trajectory(
                               fault="half_batch")),
                           ("altered", cell.trajectory(fault="altered"))):
            rows[kind] = check.compare_steps(traj, cell.steps(traj))
    return rows


def main(argv):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=None,
                    help="seeds that also read the control and the faults "
                    "(default: every seed)")
    ap.add_argument("--witness-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    from repro.utils.compile_cache import enable_compile_cache
    _, w, cfg, traffic = harness.find_cell(args.workload)
    enable_compile_cache()
    harness.require_tpu(w["chips"])
    driver = harness.load_module(harness.BENCH / "drivers" /
                                 f"{cfg['kind']}.py", "bench_driver")
    control_seeds = args.seeds if args.control_seeds is None \
        else args.control_seeds
    out = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        rec = harness.Recorder()
        cell = driver.Cell(cfg, traffic, seed, rec)
        t0 = time.perf_counter()
        cell.setup()
        t_setup = time.perf_counter() - t0
        t0 = time.perf_counter()
        numbers = cell.check()
        t_ref = time.perf_counter() - t0
        control = seed in control_seeds
        if hasattr(cell, "steps"):
            rows = rows_stepped(cell, numbers, control,
                                seed in args.witness_seeds, driver, cfg,
                                traffic, seed)
        else:
            rows = rows_classifier(cell, numbers, control)
        for kind, nums in rows.items():
            line = json.dumps({"workload": args.workload, "seed": seed,
                               "kind": kind, "setup_s": t_setup,
                               "check_s": t_ref, **nums})
            print(line if kind == "program" else line[:2000], flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
