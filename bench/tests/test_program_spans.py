"""The program-span reader on the CPU: self times, counters, device scopes
and idle time on hand-made planes, one real trace written by the
program's tracing module, and the eight metric readers built on it."""
import jax
import jax.numpy as jnp
import pytest

import harness
import program_spans as ps
from repro.utils import tracing

METRICS = ("scenario_ms", "offload_ms", "batch_stage_ms", "h2d_mb_per_round",
           "syncs_per_round", "sca_iters_per_solve", "ssd_ms",
           "plane_update_ms")


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               "test_metric_" + name)


def op(s, e, scope=""):
    tf_op = f"jit(round)/{scope}/add:" if scope else "jit(round)/add:"
    return (s, e, "%op", {ps.OP_NAME_STAT: tf_op})


PLANES = [
    ("/device:TPU:0", {
        "XLA Modules": [(100, 400, "jit_a", {}), (600, 700, "jit_b", {})],
        # a while op of the SSD scope holds an eq.-10 op and one of its own
        "XLA Ops": [op(100, 400, "cefl.ssd"), op(120, 170, "cefl.eq10"),
                    op(200, 260, "cefl.ssd"), op(600, 640, "cefl.eq11"),
                    op(650, 700)],
    }),
    ("/host:CPU", {
        "python": [
            (0, 500, "execute", {}),                 # a driver's span
            (0, 500, "cefl/begin_round", {"round": 0}),
            (10, 200, "cefl/scenario", {"round": 0, "h2d_bytes": 1000}),
            (300, 480, "cefl/offload", {"round": 0, "h2d_bytes": 500}),
            (320, 330, "cefl/sync", {"round": 0, "site": "offload_data"}),
            (340, 360, "cefl/sync", {"round": 0, "site": "offload_data"}),
            (500, 900, "cefl/solve", {"round": 0, "outer_iters": 3}),
            (510, 620, "cefl/sca_outer", {"round": 0, "iter": 0}),
            (620, 880, "cefl/sca_outer", {"round": 0, "iter": 1}),
        ],
        "worker": [(0, 1000, "cefl/stage_batches", {"dispatches": 7})],
    }),
]


@pytest.fixture
def spans():
    return ps.from_planes(PLANES, 1000.0)


def test_self_time_less_direct_children(spans):
    assert spans.self_s("begin_round") == pytest.approx((500 - 190 - 180) / 1e9)
    assert spans.self_s("offload") == pytest.approx((180 - 10 - 20) / 1e9)
    assert spans.self_s("solve") == pytest.approx((400 - 110 - 260) / 1e9)
    assert spans.total_s("solve") == pytest.approx(400 / 1e9)
    # a span on another thread line is nobody's child
    assert spans.self_s("stage_batches") == pytest.approx(1000 / 1e9)
    assert spans.self_s("group") is None
    assert spans.count("sync") == 2 and spans.count("sca_outer") == 2


def test_stat_sums(spans):
    assert spans.stat("h2d_bytes") == 1500.0
    assert spans.stat("h2d_bytes", "offload") == 500.0
    assert spans.stat("outer_iters", "solve") == 3.0
    assert spans.stat("outer_iters", "offload") is None
    assert spans.stat("rows") is None


def test_device_scope_self_times(spans):
    # the while op's own time is 300 less its two children's 110
    assert spans.scope_s("cefl.ssd") == pytest.approx((300 - 50) / 1e9)
    assert spans.scope_s("cefl.eq10", "cefl.eq11") == pytest.approx(90 / 1e9)
    assert spans.scope_s("cefl.other") is None


def test_idle_under_a_span(spans):
    # idle gaps of the window: [0, 100), [400, 600), [700, 1000)
    assert spans.idle_under("offload") == pytest.approx(80 / 1e9)
    assert spans.idle_under("scenario") == pytest.approx(90 / 1e9)
    assert spans.idle_under("solve") == pytest.approx((100 + 200) / 1e9)
    assert spans.idle_under("begin_round") == pytest.approx(200 / 1e9)
    assert spans.idle_under("eval") is None


def test_a_real_trace_of_the_program_tracing_module(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x).sum())
    f(jnp.ones(8))
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("solve", round=4) as sp:
            with tracing.span("sca_outer", iter=0):
                tracing.sync(f(jnp.ones(8)), "sca_objective")
            sp.set(outer_iters=1)
    planes, window_ns = ps.read_pb(next(tmp_path.rglob("*.xplane.pb")))
    spans = ps.from_planes(planes, window_ns)
    assert window_ns > 0
    assert [spans.count(n) for n in ("solve", "sca_outer", "sync")] == \
        [1, 1, 1]
    assert spans.stat("outer_iters", "solve") == 1.0
    assert {ev[4]["round"] for ev in spans.host} == {4}
    sync = next(ev for ev in spans.host if ev[3] == "sync")
    assert sync[4]["site"] == "sca_objective"
    assert 0 < spans.self_s("solve") < spans.total_s("solve")


def _run_data(rounds=2):
    return harness.RunData(rounds=rounds, window_s=1.0, spans={},
                           compiles={}, setup_rounds=0, model_flops=0.0,
                           trace={}, peaks={})


def test_one_parse_serves_every_reader(monkeypatch, tmp_path):
    (tmp_path / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    parses = []

    def read_pb(path):
        parses.append(path)
        return PLANES, 1000.0

    monkeypatch.setattr(ps, "read_pb", read_pb)
    run = _run_data()
    values = {m: reader(m).read(run) for m in METRICS}
    assert len(parses) == 1
    assert values["scenario_ms"] == pytest.approx(1e3 * 190e-9 / 2)
    assert values["h2d_mb_per_round"] == pytest.approx(1500 / 1e6 / 2)
    assert values["syncs_per_round"] == 1.0
    assert values["sca_iters_per_solve"] == 3.0
    assert values["ssd_ms"] == pytest.approx(1e3 * 250e-9 / 2)
    assert values["plane_update_ms"] == pytest.approx(1e3 * 90e-9 / 2)
    assert values["batch_stage_ms"] == pytest.approx(1e3 * 1000e-9 / 2)
    assert values["offload_ms"] == pytest.approx(1e3 * 150e-9 / 2)


@pytest.mark.parametrize("planes", [
    [],                                       # no trace file at all
    [("/device:TPU:0", {"XLA Modules": [(0, 10, "jit_a", {})],
                        "XLA Ops": [op(0, 10)]}),
     ("/host:CPU", {"python": [(0, 10, "execute", {})]})],
])
def test_every_reader_is_none_without_its_spans(monkeypatch, tmp_path,
                                                 planes):
    if planes:
        (tmp_path / "host.xplane.pb").write_bytes(b"")
        monkeypatch.setattr(ps, "read_pb", lambda path: (planes, 10.0))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    run = _run_data()
    assert {m: reader(m).read(run) for m in METRICS} == \
        dict.fromkeys(METRICS)
