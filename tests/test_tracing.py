"""The program's host spans, counters and read-backs (``utils/tracing.py``)
as the JAX profiler records them: two rounds of a small classifier
``Engine`` under a heterogeneous plan (two DPU groups), read back from the
trace file."""
import collections
import pathlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.cefl_paper import ClassifierConfig
from repro.core import Engine, EngineOptions, MLConstants
from repro.core.api import PLAN_KEYS
from repro.core.strategies import CEFLStrategy
from repro.data import make_image_dataset, make_online_ues
from repro.models.classifier import (classifier_accuracy, classifier_loss,
                                     init_classifier_params)
from repro.network import NetworkConfig, make_network
from repro.solver import ObjectiveWeights, sca
from repro.utils import tracing

N_UE, N_DC, ARRIVALS = 4, 2, 100
NET = make_network(NetworkConfig(num_ue=N_UE, num_bs=2, num_dc=N_DC))
(TRX, TRY), (TEX, TEY) = make_image_dataset(1000, (8, 8, 1))
P0 = init_classifier_params(jax.random.PRNGKey(0),
                            ClassifierConfig(input_shape=(8, 8, 1),
                                             hidden=(16,)))
CONSTS = MLConstants(L=5.0, theta_i=np.ones(N_UE + N_DC) * 2,
                     sigma_i=np.ones(N_UE + N_DC) * 3, zeta1=2.0, zeta2=1.0)
HARNESS_NAMES = {"decide", "stage", "execute", "finish", "restart", "batch"}
# the innermost enclosing span of each span (None: a round's top level)
PARENT = {"begin_round": None, "execute_round": None, "finish_round": None,
          "scenario": "begin_round", "solve": "begin_round",
          "offload": "begin_round", "sca_outer": "solve",
          "select_aggregator": "solve", "group": "execute_round",
          "stage_batches": "group", "group_program": "group",
          "aggregate": "execute_round", "costs": "finish_round",
          "eval": "finish_round"}
SYNC_PARENTS = {"solve", "sca_outer", "select_aggregator", "offload",
                "execute_round", "group", "costs", "eval", "finish_round"}


class TwoGroupCEFL(CEFLStrategy):
    """The SCA plan with offloading off and gamma 1 / 2 on alternate UEs:
    every UE trains its own ``ARRIVALS`` rows, in two (gamma, m, bucket)
    groups of two DPUs each."""

    def decide(self, net, D_bar, ctx):
        plan = super().decide(net, D_bar, ctx)
        n_dpu = N_UE + N_DC
        return plan.replace(
            rho_nb=np.zeros(np.shape(plan.rho_nb), np.float32),
            gamma=np.array([1.0, 2.0] * (n_dpu // 2), np.float32),
            m=np.ones(n_dpu, np.float32))


def _eval(p):
    return classifier_accuracy(p, jnp.asarray(TEX[:100]),
                               jnp.asarray(TEY[:100]))


def _engine():
    opts = EngineOptions(rounds=2, eta=0.1, solver_outer=2)
    return Engine(NET, TwoGroupCEFL(), consts=CONSTS, ow=ObjectiveWeights(),
                  opts=opts)


def _ues():
    return make_online_ues(TRX, TRY, num_ue=N_UE, mean_arrivals=ARRIVALS,
                           std_arrivals=0.0, seed=0)


def _run(engine, ues, rounds):
    state = engine.init_loop(ues, init_params=P0, loss_fn=classifier_loss,
                             eval_fn=_eval)
    staged_all = []
    for _ in range(rounds):
        staged = engine.begin_round(state, ues)
        staged_all.append(staged)
        loss, acc = engine.execute_round(state, staged)
        engine.finish_round(state, staged, loss, acc)
    jax.block_until_ready(state.params)
    return staged_all


def _cefl_events(trace_dir):
    """[(line, start, end, name without the prefix, stats)] of every
    ``cefl/*`` host event of the newest trace under ``trace_dir``."""
    pb = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    with warnings.catch_warnings():
        # the profiler's event-stats type warns on first use
        warnings.simplefilter("ignore", DeprecationWarning)
        pd = jax.profiler.ProfileData.from_file(str(pb))
        for plane in pd.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(tracing.PREFIX):
                        out.append((line.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    e.name[len(tracing.PREFIX):],
                                    dict(e.stats)))
    return out


def _parents(events):
    """Each event with the innermost cefl event that encloses it on its
    line (None at the top)."""
    out = []
    for ev in events:
        line, s, e = ev[:3]
        around = [o for o in events if o is not ev and o[0] == line
                  and o[1] <= s and e <= o[2]]
        out.append((ev, min(around, key=lambda o: o[2] - o[1])
                    if around else None))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two traced rounds: (cefl events, staged rounds, solver results)."""
    engine, ues = _engine(), _ues()
    _run(engine, _ues(), 1)            # compile outside the trace
    results, solve = [], sca.solve

    def recording_solve(*args, **kw):
        results.append(solve(*args, **kw))
        return results[-1]

    trace_dir = tmp_path_factory.mktemp("trace")
    mp = pytest.MonkeyPatch()
    mp.setattr(sca, "solve", recording_solve)
    try:
        with jax.profiler.trace(str(trace_dir)):
            staged = _run(engine, ues, 2)
    finally:
        mp.undo()
    return _cefl_events(trace_dir), staged, results


def test_every_span_appears_nested_with_its_round(traced):
    events, _, _ = traced
    names = collections.Counter(ev[3] for ev in events)
    assert set(PARENT) | {"sync"} == set(names)
    for name in ("begin_round", "execute_round", "finish_round", "solve",
                 "scenario", "offload", "costs", "eval"):
        assert names[name] == 2, name
    assert names["group"] == names["stage_batches"] == \
        names["group_program"] == 4          # two groups a round
    for ev, parent in _parents(events):
        name, stats = ev[3], ev[4]
        if name == "sync":
            assert parent is not None and parent[3] in SYNC_PARENTS
        else:
            assert (parent and parent[3]) == PARENT[name], (name, parent)
        assert stats["round"] in (0, 1)
        if parent is not None:
            assert stats["round"] == parent[4]["round"]
    rounds = sorted(ev[4]["round"] for ev in events
                    if ev[3] == "begin_round")
    assert rounds == [0, 1]


def test_span_names_keep_the_naming_rules(traced):
    events, _, _ = traced
    for name in {ev[3] for ev in events}:
        assert "compile" not in (tracing.PREFIX + name).lower()
        assert name not in HARNESS_NAMES


def test_solve_carries_the_solvers_outer_iterations(traced):
    events, _, results = traced
    solves = sorted((ev for ev in events if ev[3] == "solve"),
                    key=lambda ev: ev[1])
    assert len(results) == len(solves) == 2
    assert [ev[4]["outer_iters"] for ev in solves] == \
        [r.iterations for r in results]
    outer = [ev for ev in events if ev[3] == "sca_outer"]
    assert len(outer) == sum(r.iterations for r in results)


def test_group_counters(traced):
    events, _, _ = traced
    groups = [ev[4] for ev in events if ev[3] == "group"]
    assert sorted((g["G"], g["gamma"], g["bucket"]) for g in groups) == \
        [(2, 1, 128), (2, 1, 128), (2, 2, 128), (2, 2, 128)]
    for ev in events:
        if ev[3] == "execute_round":
            assert (ev[4]["groups"], ev[4]["live_dpus"]) == (2, N_UE)
        if ev[3] == "stage_batches":
            # the (gamma, G, bucket) f32 weights; two leaves (x, y)
            gamma = next(g["gamma"] for (_, s, e, n, g) in events
                         if n == "group" and s <= ev[1] and ev[2] <= e)
            assert ev[4]["h2d_bytes"] == gamma * 2 * 128 * 4
            assert ev[4]["dispatches"] == 2 * (2 + 1) + 2 * 2 + 1


def test_offload_counts_the_bytes_it_transfers(traced):
    events, staged, _ = traced
    offload = sorted((ev for ev in events if ev[3] == "offload"),
                     key=lambda ev: ev[1])
    for ev, st in zip(offload, staged):
        live = [d for d in st.datasets if d is not None]
        assert ev[4]["h2d_bytes"] == sum(
            int(d["x"].nbytes) + int(d["y"].nbytes) for d in live)
        assert ev[4]["rows"] == N_UE * ARRIVALS
    # the scenario hands its rows over as device arrays: the same bytes,
    # since no row is offloaded
    scen = sorted((ev for ev in events if ev[3] == "scenario"),
                  key=lambda ev: ev[1])
    assert [ev[4]["h2d_bytes"] for ev in scen] == \
        [ev[4]["h2d_bytes"] for ev in offload]


def test_syncs_count_every_read_back_site(traced):
    events, _, results = traced
    sites = collections.Counter(ev[4]["site"] for ev in events
                                if ev[3] == "sync")
    iters = sum(r.iterations for r in results)
    n_plan = len(PLAN_KEYS)            # warm start of the second solve
    assert sites == {
        "sca_warm_start": n_plan,
        "sca_objective": iters + 2, "sca_violation": iters,
        "select_aggregator": 2,        # one read-back a solve
        "offload_plan": 2 * 2, "offload_data": 2 * 2 * N_UE,
        "plan_settings": 2 * 4, "a_norm": 2 * 2, "group_losses": 2 * 2,
        "costs": 2 * 2, "eval": 2}
    assert sum(sites.values()) == sum(ev[3] == "sync" for ev in events)


def test_counters_cost_nothing_with_no_trace(monkeypatch):
    calls = []
    nbytes = tracing.nbytes

    def counting(*trees):
        calls.append(1)
        return nbytes(*trees)

    monkeypatch.setattr(tracing, "nbytes", counting)
    assert not tracing.enabled()
    _run(_engine(), _ues(), 1)
    assert calls == []
    with tracing.span("probe") as sp:
        sp.set(n=1)                    # no trace: a no-op
        tracing.add("probe", m=2)
    assert tracing.sync(np.ones(2), "host").tolist() == [1.0, 1.0]
