"""The ``unflatten_ms`` reader on hand-made planes: device self time under
the ``cefl.unflatten`` scope per round, forward and backward ops alike,
and None where no op carries the scope."""
import pytest

import harness
import program_spans as ps

reader = harness.load_module(harness.BENCH / "metrics" / "unflatten_ms.py",
                             "test_metric_unflatten_ms")


def op(s, e, name):
    return (s, e, "%op", {ps.OP_NAME_STAT: name})


def _run(monkeypatch, tmp_path, planes):
    if planes is not None:
        (tmp_path / "host.xplane.pb").write_bytes(b"")
        monkeypatch.setattr(ps, "read_pb", lambda path: (planes, 1000.0))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    return harness.RunData(rounds=2, window_s=1e-6, spans={}, compiles={},
                           setup_rounds=0, model_flops=0.0, trace={},
                           peaks={})


def test_reads_the_scopes_self_time_per_round(monkeypatch, tmp_path):
    # a while op outside the scope holds the view's forward and backward;
    # an op of the scope holds one that is not of it
    device = {"XLA Modules": [(0, 1000, "jit_round", {})],
              "XLA Ops": [
                  op(0, 900, "jit(round_step)/while"),
                  op(100, 150, "jit(round_step)/while/jvp(cefl.unflatten)"
                               "/slice"),
                  op(200, 300, "jit(round_step)/while/transpose(jvp("
                               "cefl.unflatten))/concatenate"),
                  op(250, 270, "jit(round_step)/while/cefl.eq10/add"),
                  op(400, 500, "jit(round_step)/while/cefl.ssd/dot")]}
    planes = [("/device:TPU:0", device), ("/device:TPU:1", device),
              ("/host:CPU", {"python": [(0, 10, "execute", {})]})]
    run = _run(monkeypatch, tmp_path, planes)
    assert reader.read(run) == pytest.approx(1e3 * 130e-9 / 2)


@pytest.mark.parametrize("planes", [
    None,                                     # no trace file at all
    [("/device:TPU:0", {"XLA Modules": [(0, 10, "jit_a", {})],
                        "XLA Ops": [op(0, 10, "jit(round)/cefl.ssd/add")]})],
    # the scope on a plane that ran no program
    [("/device:TPU:0", {"XLA Ops": [op(0, 10, "jit(a)/cefl.unflatten/x")]})],
])
def test_none_without_the_scope(monkeypatch, tmp_path, planes):
    assert reader.read(_run(monkeypatch, tmp_path, planes)) is None
