"""The yardstick on the CPU: trace reduction on small hand-made and
recorded traces, kernel operation and byte counts, and the parameter
counts of the configuration files tied to the program's."""
import json
from pathlib import Path

import pytest

import costs
import harness
import trace_reduce as tr

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
SPANS = ("decide", "stage", "execute", "finish", "batch")


def reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py",
                               "test_metric_" + name)


def load(rel):
    with open(BENCH / rel) as f:
        return json.load(f)


ACCUM = ("%fedprox_accum_2d.8 = (f32[3,176,1024]{2,1,0:T(8,128)}, "
         "f32[3,176,1024]{2,1,0:T(8,128)}) custom-call(f32[3,176,1024]"
         "{2,1,0:T(8,128)} %a, f32[3,176,1024]{2,1,0} %b, f32[176,1024]"
         "{1,0} %c, f32[3,176,1024]{2,1,0} %d, f32[1,3]{1,0} %e, "
         "f32[1,3]{1,0} %f, f32[1,1]{1,0} %g, f32[1,1]{1,0} %h), "
         "custom_call_target=\"tpu_custom_call\", operand_layout_"
         "constraints={f32[3,176,1024]{2,1,0}}")


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (38, 45)]
    assert tr.union_ns(iv, 0, 100) == 35
    assert tr.union_ns(iv, 8, 39) == 12 + 9
    assert tr.gaps(iv, 0, 50) == [(20, 30), (45, 50)]


def test_self_times_of_nested_ops():
    ev = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"), (50, 60, "c")]
    st = tr.self_times(ev)
    assert st == {"while": 30, "a": 20, "b": 40, "c": 10}


def test_kernel_shapes_and_cost():
    assert tr.kernel_of(ACCUM) == "fedprox_accum_2d"
    assert tr.kernel_of("%fusion.3 = f32[8] fusion(f32[8] %a)") is None
    res, opnd = tr.call_arrays(ACCUM)
    assert res == [("f32", (3, 176, 1024), True)] * 2
    assert [d for _, d, _ in opnd][:4] == [(3, 176, 1024)] * 2 + \
        [(176, 1024), (3, 176, 1024)]
    accum = reader("fedprox_accum_roofline")
    assert "fedprox_accum_2d" in accum.KERNELS
    nbytes = costs.hbm_bytes(res, opnd)
    plane = 176 * 1024 * 4
    assert nbytes == 5 * 3 * plane + plane + 4 * 3 * 2 + 4 * 2
    assert accum.flops(res, opnd) == 8 * 3 * 176 * 1024
    # the same call with its gradient operand in on-chip memory
    on_chip = ACCUM.replace("{2,1,0} %b", "{2,1,0:T(8,128)S(1)} %b")
    res, opnd = tr.call_arrays(on_chip)
    assert costs.hbm_bytes(res, opnd) == nbytes - 3 * plane


def test_reduce_hand_made_trace():
    planes = [
        ("/device:TPU:0", {
            "XLA Modules": [(100, 400, "jit_a"), (600, 700, "jit_b")],
            "XLA Ops": [(100, 400, "%while.1 = (f32[2]) while(...)"),
                        (150, 250, ACCUM)],
        }),
        ("/host:CPU", {"python": [(0, 500, "execute"), (450, 900, "stage"),
                                  (480, 520, "backend_compile_and_load")]}),
    ]
    r = tr.reduce_profile(planes, 1000.0, SPANS)
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["breakdown"]["idle_gaps"] == [
        ["stage", pytest.approx(300e-9)],
        ["stage+compile", pytest.approx(200e-9)],
        ["execute", pytest.approx(100e-9)]]
    assert r["breakdown"]["device_ops"][0][0] == "while.1"
    (k,) = r["kernels"]
    assert k["kernel"] == "fedprox_accum_2d" and k["ns"] == 100


def test_recorded_trace():
    """A slice of a traced ``cefl_mlp_paper.static_cefl`` run on one TPU
    v5e (``bench/tests/data``): busy time within the window, every plane
    kernel call found with its bytes, and both roofline shares within
    100 %."""
    rec = load("tests/data/trace_cefl_slice.json")
    r = tr.reduce_profile([(p, {k: [tuple(e) for e in v]
                                for k, v in lines.items()})
                           for p, lines in rec["planes"]], rec["window_ns"],
                          SPANS)
    assert 0 < r["busy_s"] <= r["window_s"]
    readers = [reader(n) for n in ("fedprox_accum_roofline",
                                   "nova_aggregate_roofline")]
    names = set().union(*(m.KERNELS for m in readers))
    assert {k["kernel"] for k in r["kernels"]} <= names
    assert all(costs.hbm_bytes(k["results"], k["operands"]) > 0
               and k["ns"] > 0 for k in r["kernels"])
    assert len(r["breakdown"]["idle_gaps"]) == 10

    class Run:
        trace = r
        peaks = load("peaks.json")["TPU v5 lite"]

    for m in readers:
        share = m.read(Run)
        assert share is not None and 0 < share <= 100, (m.KERNELS, share)


def test_param_counts_match_the_program():
    import jax

    from repro.configs import get_config
    from repro.configs.cefl_paper import ClassifierConfig
    from repro.models.classifier import init_classifier_params
    mlp = load("configs/cefl_mlp_paper.json")
    m = mlp["spec"]["model"]
    p = jax.eval_shape(lambda: init_classifier_params(
        jax.random.PRNGKey(0), ClassifierConfig(
            input_shape=tuple(m["input_shape"]), hidden=tuple(m["hidden"]),
            num_classes=m["num_classes"])))
    n = sum(x.size for x in jax.tree.leaves(p))
    assert costs.mlp_params(mlp) == n == mlp["params"] == 178110
    from repro.models import lm as L
    lm = load("configs/mamba2_130m.json")
    # the program's preset pads 50,277 to 50,280 rows, the source to 50,288
    preset = get_config(lm["program_config"])
    p = jax.eval_shape(lambda: L.init_lm_params(jax.random.PRNGKey(0),
                                                preset))
    n = sum(x.size for x in jax.tree.leaves(p))
    assert costs.mamba2_params(dict(lm, vocab_size=preset.vocab_size,
                                    pad_vocab_size_multiple=1)) == n == \
        128983488
    _, cell = lm_cell(lm)
    p = jax.eval_shape(lambda: L.init_lm_params(
        jax.random.PRNGKey(0), cell.model_config()))
    n = sum(x.size for x in jax.tree.leaves(p))
    assert costs.mamba2_params(lm) == n == lm["params"] == 128989632


def lm_cell(cfg):
    drv = harness.load_module(harness.BENCH / "drivers" / "lm.py",
                              "bench_driver_lm")
    return drv, drv.Cell(cfg, load("traffic/local_heavy.json"), 0, None)


def test_lm_weights_have_the_program_layout():
    """The harness's weights and the program's init give the same tree of
    shapes, so the program trains exactly what the reference reads; the
    model differs from the program's preset only where the preset departs
    from the source."""
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.models import lm as L
    cfg = load("configs/mamba2_130m.json")
    drv, cell = lm_cell(cfg)
    ours = jax.eval_shape(lambda: drv.init_mamba2(jax.random.PRNGKey(0),
                                                  cell.dims()))
    theirs = jax.eval_shape(lambda: L.init_lm_params(
        jax.random.PRNGKey(0), cell.model_config(), jax.numpy.float32))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), ours) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), theirs)
    preset = get_config(cfg["program_config"])
    assert cell.model_config() == dataclasses.replace(
        preset, vocab_size=50288,
        ssm=dataclasses.replace(preset.ssm, chunk_size=256))
