"""``correct`` on the tiny cells (CPU, the harness's look for a chip
skipped): true for the program as it is, false for the control (the
bfloat16 reference put in the program's place) and for each fault a
one-chip training cell can have, planted in the timed path:

* ``unchanged``: a round returns its state unchanged;
* ``half_batch``: half of each DPU's batch left out, the mean taken over
  the rest;
* ``altered``: the round's answer altered where it is produced (the
  update it applies scaled by 1.1);
* ``unchanged_later``: the first round is sound, every later one returns
  its state unchanged (a fault the first round's numbers cannot see).

A one-chip cell has no exchange between chips, so that fault is not
planted.  The limits are the real cells' (``bench/limits``).
"""
import time

import jax
import jax.numpy as jnp
import pytest

import harness
import tiny

FAULTS = ("unchanged", "half_batch", "altered", "unchanged_later")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.write_root(tmp_path_factory.mktemp("tiny"))


def plane_fault(fault, before, after_fn, calls):
    """Apply ``fault`` around a round that maps the plane ``before``;
    ``calls``: the rounds run before this one."""
    if fault == "unchanged_later":
        fault = "unchanged" if calls else "none"
    if fault == "none":
        return after_fn(before)
    if fault == "unchanged":
        after_fn(before.with_data(jnp.copy(before.data)))
        return before
    if fault == "altered":
        keep = jnp.copy(before.data)
        after = after_fn(before)
        return after.with_data(keep + 1.1 * (after.data - keep))
    raise ValueError(fault)


def break_classifier(cell, fault):
    ex = cell.engine.executor
    run_round = ex.run_round
    calls = []

    def broken(params, plan, datasets, **kw):
        calls.append(1)
        if fault == "half_batch":
            datasets = [d if d is None or len(d["y"]) < 2 else
                        {k: v[:len(d["y"]) // 2] for k, v in d.items()}
                        for d in datasets]
            return run_round(params, plan, datasets, **kw)
        out = []

        def after(p):
            out[:] = run_round(p, plan, datasets, **kw)
            return out[0]

        new = plane_fault(fault, params, after, len(calls) - 1)
        return (new,) + tuple(out[1:])

    ex.run_round = broken


def break_lm(cell, fault):
    step = cell.step
    calls = []

    def broken(plane, batch, meta):
        calls.append(1)
        if fault == "half_batch":
            half = {k: v[:, :, :v.shape[2] // 2] for k, v in batch.items()}
            return step(plane, half, meta)
        out = []

        def after(p):
            out[:] = step(p, batch, meta)
            return out[0]

        return plane_fault(fault, plane, after, len(calls) - 1), out[1]

    cell.step = broken


def run(root, workload, hook=None, seed=2 ** 31 + 5):
    res, checks = harness.run(workload, seed, 0.2, False,
                              t_start=time.perf_counter(),
                              require_chip=False, root=root, cell_hook=hook)
    return res, {n: (v, lim) for n, v, lim, _ in checks}


@pytest.mark.parametrize("workload", [tiny.MLP, tiny.LM])
def test_program_is_correct(root, workload):
    """Also: the window replays shapes set-up warmed, so nothing compiles
    in it."""
    cells = []
    res, checks = run(root, workload, cells.append)
    assert res["correct"], checks
    assert list(res)[-1] == "checks"
    assert cells[0].rec.compiles["window"] == 0
    assert res["attempted"] % cells[0].traffic["window_multiple"] == 0


@pytest.mark.parametrize("workload", [tiny.MLP, tiny.LM])
def test_control_is_not_correct(root, workload):
    def hook(cell):
        setup = cell.setup

        def control_setup():
            setup()
            acc = cell.prog.get("acc")
            cell.prog = cell.trajectory(jnp.bfloat16)
            if acc is not None:
                cell.prog["acc"] = acc
        cell.setup = control_setup

    res, checks = run(root, workload, hook)
    assert not res["correct"], checks


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", [tiny.MLP, tiny.LM])
def test_fault_is_not_correct(root, workload, fault):
    breaker = break_classifier if workload == tiny.MLP else break_lm

    def hook(cell):
        cell.on_built = lambda: breaker(cell, fault)

    res, checks = run(root, workload, hook)
    assert not res["correct"], (fault, checks)
    jax.clear_caches()
