"""Differential harness: the jitted batched solver backend vs the numpy
Python-loop oracle (``solver/ref.py``) across a seeded grid of random
``NetworkConfig``s, including degenerate topologies (single BS, disconnected
server mesh, zero-data UE).

Parity contract (ISSUE 3): objective within 1e-4 relative, identical
rounded plans, and matching feasibility residuals on every grid point.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineOptions
from repro.core.api import DecisionContext
from repro.core.convergence import MLConstants
from repro.core.strategies import CEFLStrategy
from repro.data import make_image_dataset, make_online_ues
from repro.network import NetworkConfig, make_network
from repro.scenario import get_scenario
from repro.solver import (ObjectiveWeights, PDHyper, apply_required_deltas,
                          constraint_vector, objective, objective_breakdown,
                          sca)
from repro.solver.variables import NetView, WSpec, init_w, project

OW = ObjectiveWeights()
PD = PDHyper(max_iters=3, consensus_rounds=15)


def _consts(net):
    nd = net.cfg.num_ue + net.cfg.num_dc
    rng = np.random.RandomState(net.cfg.seed + 7)
    return MLConstants(L=4.0, theta_i=rng.uniform(1.0, 3.0, nd),
                       sigma_i=rng.uniform(0.5, 1.5, nd),
                       zeta1=2.0, zeta2=1.0)


def _d_bar(net, zero_ue=False):
    rng = np.random.RandomState(net.cfg.seed + 13)
    D = rng.normal(1000.0, 100.0, net.cfg.num_ue).clip(100)
    if zero_ue:
        D[0] = 0.0
    return D


def _cut_server_mesh(net):
    """Disconnect the DC-DC part of the consensus graph (degenerate mesh)."""
    N, B, S = net.dims
    A = np.array(net.adjacency)
    A[N + B:, N + B:] = 0
    return dataclasses.replace(net, adjacency=A)


GRID = [
    # (cfg, degenerate transform, zero-data UE)
    (NetworkConfig(num_ue=6, num_bs=3, num_dc=2, seed=0), None, False),
    (NetworkConfig(num_ue=5, num_bs=1, num_dc=2, seed=1), None, False),
    (NetworkConfig(num_ue=8, num_bs=4, num_dc=3, seed=2), None, True),
    (NetworkConfig(num_ue=6, num_bs=3, num_dc=3, seed=3),
     _cut_server_mesh, False),
]


def _solve_both(net, D_bar, distributed):
    consts = _consts(net)
    kw = dict(distributed=distributed, max_outer=2, pd=PD)
    return (sca.solve(net, D_bar, consts, OW, backend="ref", **kw),
            sca.solve(net, D_bar, consts, OW, backend="jit", **kw))


def _assert_parity(net, D_bar, res_ref, res_jit):
    # objective trajectory: 1e-4 relative agreement at every outer iterate
    ref_h = np.asarray(res_ref.objective_history)
    jit_h = np.asarray(res_jit.objective_history)
    assert ref_h.shape == jit_h.shape
    np.testing.assert_allclose(jit_h, ref_h, rtol=1e-4)
    # identical rounded plans (the executable decision)
    for k in ("I_s", "I_nb", "I_bn"):
        np.testing.assert_array_equal(
            np.asarray(res_ref.w_rounded[k]), np.asarray(res_jit.w_rounded[k]),
            err_msg=f"rounded {k} differs")
    # continuous decisions agree tightly in physical units
    for k in ("rho_nb", "rho_bs", "f_n", "z_s", "gamma", "m", "R_bs"):
        a, b = np.asarray(res_ref.w[k]), np.asarray(res_jit.w[k])
        scale = max(1.0, float(np.abs(a).max()))
        np.testing.assert_allclose(b, a, atol=5e-3 * scale,
                                   err_msg=f"relaxed {k} differs")
    # feasibility residuals of the rounded plan match
    v_ref = np.asarray(constraint_vector(res_ref.w_rounded, net, D_bar))
    v_jit = np.asarray(constraint_vector(res_jit.w_rounded, net, D_bar))
    scale = max(1.0, float(np.abs(v_ref).max()))
    np.testing.assert_allclose(v_jit, v_ref, atol=1e-3 * scale)
    np.testing.assert_allclose(res_jit.violation_history,
                               res_ref.violation_history, atol=1e-2)


@pytest.mark.parametrize("cfg,transform,zero_ue", GRID,
                         ids=["base", "single_bs", "zero_data_ue",
                              "cut_server_mesh"])
@pytest.mark.parametrize("distributed", [False, True],
                         ids=["centralized", "distributed"])
def test_jit_matches_ref(cfg, transform, zero_ue, distributed):
    net = make_network(cfg)
    if transform is not None:
        net = transform(net)
    D_bar = _d_bar(net, zero_ue)
    res_ref, res_jit = _solve_both(net, D_bar, distributed)
    _assert_parity(net, D_bar, res_ref, res_jit)


def test_warm_resolve_hits_compile_cache(assert_no_retrace):
    """Re-solving at the same dims with fresh rates / arrivals must NOT
    build a new compiled step (rates are traced args, dims key the
    cache).  Pinned with the process-wide retrace guard (zero XLA
    compiles anywhere, not just a stable sca cache size)."""
    cfg = NetworkConfig(num_ue=6, num_bs=3, num_dc=2, seed=5)
    net = make_network(cfg)
    consts = _consts(net)
    w0 = sca.solve(net, _d_bar(net), consts, OW, distributed=False,
                   max_outer=2, pd=PD, backend="jit").w
    n0 = sca.jit_cache_size()
    rng = np.random.RandomState(1)
    net2 = net.resample_rates(rng, 0.2)
    with assert_no_retrace():
        res = sca.solve(net2, _d_bar(net) * 1.3, consts, OW,
                        distributed=False, max_outer=2, pd=PD,
                        backend="jit", w0=w0)
    assert sca.jit_cache_size() == n0
    assert len(res.objective_history) >= 2


def test_netview_roundtrip_and_flat_spec():
    net = make_network(NetworkConfig(num_ue=5, num_bs=2, num_dc=2, seed=4))
    nv = NetView.from_network(net)
    assert nv.dims == net.dims
    np.testing.assert_allclose(np.asarray(nv.R_nb),
                               np.asarray(net.R_nb, np.float32))
    spec = WSpec(net.dims)
    w = project(init_w(net, _d_bar(net)), net)
    back = spec.unflatten(spec.flatten(w))
    for k in w:
        np.testing.assert_allclose(np.asarray(back[k]),
                                   np.asarray(w[k], np.float32), rtol=1e-6)


# ------------------------------------- the compiled aggregator choice --

def _eager_candidates(w, net, D_bar, consts):
    """The S aggregator candidates of a rounded plan, one eager objective
    each, as the solver chose them before it compiled the enumeration."""
    S = net.dims[2]
    objs, plans = [], []
    for s in range(S):
        ws = apply_required_deltas(dict(w, I_s=jax.nn.one_hot(s, S)), net,
                                   D_bar)
        objs.append(float(objective(ws, net, D_bar, consts, OW)))
        plans.append(ws)
    return np.asarray(objs), plans


def _assert_choice_matches_eager(res, net, D_bar, consts):
    objs, plans = _eager_candidates(res.w_rounded, net, D_bar, consts)
    np.testing.assert_allclose(res.aggregator_objectives, objs, rtol=1e-5)
    assert res.aggregator == int(np.argmin(objs)) == \
        int(np.argmin(res.aggregator_objectives))
    want = plans[res.aggregator]
    for k in want:
        np.testing.assert_allclose(np.asarray(res.plan[k]),
                                   np.asarray(want[k]), rtol=1e-5,
                                   atol=1e-6, err_msg=k)
    eager = objective_breakdown(res.w_rounded, net, D_bar, consts, OW)
    for k in ("ml", "delay", "energy", "total"):
        np.testing.assert_allclose(res.breakdown[k], eager[k], rtol=1e-5)
    np.testing.assert_allclose(res.breakdown["delay_required"],
                               eager["delay_required"], rtol=1e-5)


@pytest.mark.parametrize("cfg,transform,zero_ue", GRID,
                         ids=["base", "single_bs", "zero_data_ue",
                              "cut_server_mesh"])
@pytest.mark.parametrize("distributed", [False, True],
                         ids=["centralized", "distributed"])
def test_compiled_aggregator_choice_matches_eager(cfg, transform, zero_ue,
                                                  distributed):
    """The finish's vmapped enumeration (jit backend) and the standalone
    one over the oracle's rounded plan (ref backend) give the eager
    per-candidate objectives, their argmin, its plan and the breakdown."""
    net = make_network(cfg)
    if transform is not None:
        net = transform(net)
    D_bar = _d_bar(net, zero_ue)
    for res in _solve_both(net, D_bar, distributed):
        _assert_choice_matches_eager(res, net, D_bar, _consts(net))
        assert sca.select_aggregator(res.w_rounded, net, D_bar,
                                     _consts(net), OW) == res.aggregator


def _campus_walk_rounds(rounds):
    """(net_t, D_bar_t) of the first ``rounds`` rounds of ``campus_walk``
    over the base grid network."""
    net = make_network(GRID[0][0])
    scen = get_scenario("campus_walk")
    scen.bind(net, EngineOptions())
    (x, y), _ = make_image_dataset(600, (4, 4, 1))
    ues = make_online_ues(x, y, num_ue=net.cfg.num_ue, mean_arrivals=80,
                          std_arrivals=20, seed=0)
    rng = np.random.RandomState(0)
    out = []
    for t in range(rounds):
        net_t, data, _ = scen.step(t, ues, rng)
        out.append((net_t, np.asarray(
            [0.0 if d is None else len(d["y"]) for d in data])))
    return out


@pytest.mark.parametrize("t", [0, 1, 2])
def test_compiled_aggregator_choice_on_evolved_networks(t):
    net_t, D_bar = _campus_walk_rounds(t + 1)[t]
    res = sca.solve(net_t, D_bar, _consts(net_t), OW, distributed=False,
                    max_outer=2, pd=PD)
    _assert_choice_matches_eager(res, net_t, D_bar, _consts(net_t))


def test_second_decide_compiles_nothing(assert_no_retrace):
    """Once one cold and one warm decision have compiled the solver's
    programs, a decision with new rates and arrivals, cold or warm,
    compiles nothing and adds no program to the solver's cache."""
    net = make_network(NetworkConfig(num_ue=7, num_bs=2, num_dc=3, seed=9))
    consts = _consts(net)
    opts = EngineOptions(solver_outer=2)
    rng = np.random.RandomState(3)

    def decide(prev):
        ctx = DecisionContext(round=0, consts=consts, ow=OW, opts=opts,
                              prev_plan=prev)
        D = jnp.asarray(rng.uniform(500, 1500, 7), jnp.float32)
        plan = CEFLStrategy().decide(net.resample_rates(rng, 0.3), D, ctx)
        return jax.block_until_ready(plan)

    n0 = sca.jit_cache_size()
    decide(decide(None))
    # the jitted init_w, the feasible start, the outer step and the
    # finish; the cold and the warm start share one compiled start
    assert sca.jit_cache_size() == n0 + 4
    assert [f._cache_size() for key, f in sca._PROGRAM_CACHE.items()
            if key[1] == net.dims] == [1, 1, 1, 1]
    with assert_no_retrace():
        decide(decide(None))
    assert sca.jit_cache_size() == n0 + 4
