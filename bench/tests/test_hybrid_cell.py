"""``correct`` on a tiny hybrid LM cell (CPU, the harness's look for a
chip skipped): the ``granite4_h_micro`` file at narrow widths (hidden 64,
4 / 1 heads, vocab 512, the ``MMMMMAMMMM`` period kept) under a short
mix, written into a directory the harness reads as a checkout.  True
for the program as it is; false for the control (the bfloat16 reference
in the program's place) and for faults planted in the timed path from
the second round on, which the first round's numbers cannot see.  The
limits are the real cell's (``bench/limits``)."""
import copy
import json
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import harness

BENCH = Path(__file__).resolve().parents[1]
CELL = "tiny_granite.tiny_hybrid"
REAL = "granite4_h_micro.hybrid_4k"
F32 = 1e-6          # float32 sums against float64 (see the last test)


def _load(rel: str) -> dict:
    with open(BENCH / rel) as f:
        return json.load(f)


def tiny_config() -> dict:
    cfg = copy.deepcopy(_load("configs/granite4_h_micro.json"))
    cfg.update(name="tiny_granite", hidden_size=64, num_attention_heads=4,
               num_key_value_heads=1, intermediate_size=128,
               shared_intermediate_size=128, vocab_size=512,
               mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
               mamba_chunk_size=8)
    return cfg


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_hybrid")
    for sub in ("configs", "traffic", "limits"):
        (root / "bench" / sub).mkdir(parents=True)
    cfg = tiny_config()
    traffic = dict(_load("traffic/hybrid_4k.json"), name="tiny_hybrid",
                   seq=32)
    bench = _load("../BENCHMARK.json")
    bench["workloads"] = [{"name": CELL, "config": cfg["name"],
                           "traffic": traffic["name"], "chips": 1,
                           "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench/configs/tiny_granite.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny_hybrid.json").write_text(json.dumps(traffic))
    (root / "bench/limits" / f"{CELL}.json").write_text(
        json.dumps(_load(f"limits/{REAL}.json")))
    return root


def run(root, hook=None, seed=2 ** 31 + 16):
    res, checks = harness.run(CELL, seed, 0.2, False,
                              t_start=time.perf_counter(),
                              require_chip=False, root=root, cell_hook=hook)
    return res, {n: (v, lim) for n, v, lim, _ in checks}


def from_round_two(cell, fault):
    """Plant ``fault`` in the timed step from its second call on."""
    def on_built():
        step, calls = cell.step, []

        def broken(plane, batch, meta):
            calls.append(1)
            if len(calls) < 2:
                return step(plane, batch, meta)
            if fault == "half_batch":        # half of the one sequence
                half = {k: v[..., :v.shape[-1] // 2]
                        for k, v in batch.items()}
                return step(plane, half, meta)
            keep = jnp.copy(plane.data)
            new, metrics = step(plane, batch, meta)
            if fault == "unchanged":
                return new.with_data(keep), metrics
            return new.with_data(keep + 1.1 * (new.data - keep)), metrics
        cell.step = broken
    cell.on_built = on_built


def test_program_is_correct(root):
    """Also: the window compiles nothing, and the counted parameters are
    the program's."""
    cells = []
    res, checks = run(root, cells.append)
    assert res["correct"], checks
    cell = cells[0]
    assert cell.rec.compiles["window"] == 0
    n = sum(int(np.prod(np.shape(v))) for v in cell.x0_host.values())
    assert n == cell.params


def test_control_is_not_correct(root):
    def hook(cell):
        setup = cell.setup

        def control_setup():
            setup()
            cell.prog = cell.trajectory(jnp.bfloat16)
        cell.setup = control_setup

    res, checks = run(root, hook)
    assert not res["correct"], checks


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_fault_from_round_two_is_not_correct(root, fault):
    res, checks = run(root, lambda cell: from_round_two(cell, fault))
    assert not res["correct"], (fault, checks)


def test_attn_and_mlp_readers():
    """``attn_ms`` and ``mlp_ms`` read device self time under their
    scopes per round, forward and backward ops alike; a trace without
    the scopes reads None."""
    import scope_times

    def op(s, e, name):
        return (s, e, "%op", {"tf_op": name})

    planes = [("/device:TPU:0", {
        "XLA Modules": [(0, 1000, "jit_round", {})],
        # a while op of the attention scope holds one of the MLP's
        "XLA Ops": [op(100, 400, "jit(round)/while/cefl.attn/while"),
                    op(120, 170, "jit(round)/transpose(jvp(cefl.mlp))/dot"),
                    op(500, 560, "jit(round)/cefl.mlp/dot"),
                    op(600, 700, "jit(round)/cefl.ssd/add"),
                    op(700, 720, "jit(round)/add")]})]
    run = harness.RunData(rounds=2, window_s=1e-6, spans={}, compiles={},
                          setup_rounds=0, model_flops=0.0, trace={},
                          peaks={})
    run._scope_times = scope_times.from_planes(planes)
    read = {m: harness.load_module(harness.BENCH / "metrics" / f"{m}.py",
                                   "test_metric_" + m).read
            for m in ("attn_ms", "mlp_ms")}
    assert read["attn_ms"](run) == pytest.approx(1e3 * 250e-9 / 2)
    assert read["mlp_ms"](run) == pytest.approx(1e3 * 110e-9 / 2)
    run._scope_times = scope_times.from_planes(
        [("/device:TPU:0", {"XLA Modules": [(0, 10, "m", {})],
                            "XLA Ops": [op(0, 10, "jit(round)/add")]})])
    assert read["attn_ms"](run) is None and read["mlp_ms"](run) is None


def test_stepwise_numbers_are_check_compare_steps(root):
    """The driver's round-by-round comparison, one reference round held at
    a time and leaves compared on the device, reads as
    ``check.compare_steps`` over whole trajectories.  Its sums of squares
    are float32 (``check``'s float64), good to about 1e-7 of a norm, so a
    gap ``|a - b| / b`` of two norms may move by about 1e-7 of ``1 +
    gap``: ``F32`` of the number and ``F32`` besides, a hundredth of the
    smallest limit."""
    import check
    cells = []
    run(root, cells.append)
    cell = cells[0]
    for traj in (cell.prog, cell.trajectory(jnp.bfloat16)):
        want = check.compare_steps(traj, cell.steps(traj))
        got = cell.compare_stepwise(traj)
        assert got == pytest.approx(want, rel=F32, abs=F32), (got, want)
    want = check.compare_steps(*(lambda t: (t, cell.steps(t)))(
        cell.trajectory(fault="half_batch")))
    assert cell.compare_trajectory(fault="half_batch") == \
        pytest.approx(want, rel=F32, abs=F32)
