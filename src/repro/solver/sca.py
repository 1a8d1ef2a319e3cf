"""Algorithm 1: successive convex solver wrapper for network-aware CE-FL.

Each outer iteration convexifies P at w^l (proximal surrogate), solves the
surrogate with the distributed primal-dual method (Algorithm 2 + consensus
Algorithm 3), and moves w^{l+1} = w^l + zeta (w_hat - w^l) (eq. 81).

Two backends share this entry point (``solve(..., backend=...)``):

* ``"jit"`` (default) — the batched JAX path: the whole outer iteration
  (Algorithm 2 inner solve + eq.-81 step + projection + objective) is ONE
  jitted function over flat (P,) decision vectors.  Shapes are static,
  keyed only on the network dims, and every network quantity (rates,
  arrivals, consensus weights, ML constants arrays) is a *traced* argument,
  so warm-started re-solves across rounds hit the compile cache.
* ``"ref"`` — the original host-side numpy / Python-loop oracle
  (``solver/ref.py``), kept for differential testing and benchmarking.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.network.costs import network_costs
from repro.solver import constraints as K
from repro.solver import ref as _ref
from repro.solver import variables as V
from repro.solver.consensus import consensus_weights
from repro.solver.objective import (ObjectiveWeights, apply_required_deltas,
                                    objective, objective_breakdown)
from repro.solver.primal_dual import PDHyper, make_surrogate
from repro.solver.ref import SCAResult  # noqa: F401  (public re-export)

if TYPE_CHECKING:   # annotation-only: keeps repro.solver import-cycle free
    from repro.core.convergence import MLConstants

_OUTER_STEP_CACHE: Dict[tuple, callable] = {}


def jit_cache_size() -> int:
    """Number of distinct compiled outer steps (diagnostics/tests)."""
    return len(_OUTER_STEP_CACHE)


def _consts_scalars(consts: MLConstants):
    return (float(consts.L), float(consts.zeta1), float(consts.zeta2),
            float(consts.F0_gap))


def _outer_step(dims, hyper: PDHyper, ow: ObjectiveWeights, cs,
                distributed: bool, zeta: float, gamma_cap: float = 20.0):
    """The jitted SCA outer iteration for static (dims, hyper, ow, zeta)."""
    from repro.core.convergence import MLConstants  # local: avoids cycle
    key = (tuple(dims), hyper, ow, cs, distributed, float(zeta), gamma_cap)
    if key in _OUTER_STEP_CACHE:
        return _OUTER_STEP_CACHE[key]
    spec = V.WSpec(dims)
    surrogate = make_surrogate(spec, hyper, ow, cs, distributed=distributed,
                               gamma_cap=gamma_cap)
    L_s, zeta1_s, zeta2_s, f0_s = cs

    def step(w, Lambda, net, D_bar, theta_i, sigma_i, scale_flat, W_cons):
        consts = MLConstants(L=L_s, theta_i=theta_i, sigma_i=sigma_i,
                             zeta1=zeta1_s, zeta2=zeta2_s, F0_gap=f0_s)
        w_hat, Lambda, _, max_viol = surrogate(
            w, Lambda, net, D_bar, theta_i, sigma_i, scale_flat, W_cons)
        w_new = w + zeta * (w_hat - w)                          # eq. (81)
        w_phys = V.project(spec.unflatten(w_new * scale_flat), net,
                           gamma_cap=gamma_cap)
        w_phys = apply_required_deltas(w_phys, net, D_bar)
        obj = objective(w_phys, net, D_bar, consts, ow)
        return spec.flatten(w_phys) / scale_flat, Lambda, obj, max_viol

    _OUTER_STEP_CACHE[key] = jax.jit(step)
    return _OUTER_STEP_CACHE[key]


def _solve_jit(net, D_bar, consts: MLConstants, ow: ObjectiveWeights,
               *, zeta: float, max_outer: int, tol: float,
               pd: PDHyper, distributed: bool,
               w0: Optional[Dict]) -> SCAResult:
    spec = V.WSpec(net.dims)
    nv = V.NetView.from_network(net)
    scaler = V.Scaler(net)
    scale_flat = scaler.flat(spec)
    D_j = jnp.asarray(D_bar, jnp.float32)
    theta_i = jnp.asarray(consts.theta_i, jnp.float32)
    sigma_i = jnp.asarray(consts.sigma_i, jnp.float32)
    n_nodes = net.node_count() if distributed else 1
    W_cons = jnp.asarray(consensus_weights(net.adjacency), jnp.float32) \
        if distributed else jnp.zeros((1, 1), jnp.float32)
    Lambda = jnp.zeros((n_nodes, K.num_constraints(spec.dims)), jnp.float32)

    # feasible start — same construction as the oracle (host-side, once)
    w_phys = V.project(w0 if w0 is not None else V.init_w(net, D_bar), net)
    w_phys = apply_required_deltas(w_phys, net, D_bar, slack=1.05)
    w = spec.flatten(w_phys) / scale_flat

    step = _outer_step(spec.dims, pd, ow, _consts_scalars(consts),
                       distributed, zeta)
    hist = [float(objective(w_phys, net, D_bar, consts, ow))]
    viol = []
    ell = 0
    for ell in range(max_outer):
        w, Lambda, obj, max_viol = step(w, Lambda, nv, D_j, theta_i,
                                        sigma_i, scale_flat, W_cons)
        obj = float(obj)
        viol.append(float(max_viol))
        improved = hist[-1] - obj
        hist.append(obj)
        if 0 <= improved < tol * max(1.0, abs(hist[0])):
            break
    w_phys = spec.unflatten(w * scale_flat)
    w_rounded = V.round_indicators(w_phys)
    c = network_costs(w_rounded, net, D_bar)
    w_rounded["delta_A"] = c["delta_A_req"]
    w_rounded["delta_R"] = c["delta_R_req"]
    return SCAResult(
        w=w_phys, w_rounded=w_rounded, objective_history=hist,
        violation_history=viol,
        breakdown=objective_breakdown(w_rounded, net, D_bar, consts, ow),
        iterations=ell + 1)


def select_aggregator(w: Dict, net, D_bar, consts, ow) -> int:
    """Exact discrete rounding of the floating-aggregator indicator I_s.

    With few SCA outer iterations the relaxed I_s stays near the simplex
    interior, so argmax rounding picks a vertex by noise rather than by
    cost.  S is small (DC tier), so enumerate the S one-hot candidates —
    each with its own required delay budgets — and return the index that
    minimizes the true objective.  This is what makes the aggregation
    point actually *float* round-to-round under dynamic scenarios.
    """
    S = int(np.asarray(w["I_s"]).shape[0])
    objs = []
    for s in range(S):
        ws = dict(w)
        ws["I_s"] = jax.nn.one_hot(jnp.asarray(s), S)
        ws = apply_required_deltas(ws, net, D_bar)
        objs.append(float(objective(ws, net, D_bar, consts, ow)))
    return int(np.argmin(objs))


def solve(net, D_bar, consts: MLConstants, ow: ObjectiveWeights,
          *, zeta: float = 0.5, max_outer: int = 20, tol: float = 1e-4,
          pd: Optional[PDHyper] = None, distributed: bool = True,
          w0: Optional[Dict] = None, seed: int = 0,
          backend: str = "jit") -> SCAResult:
    """Solve problem P at the current network state.

    ``backend="jit"`` runs the batched jitted solver (static shapes keyed
    on ``net.dims``; re-solves with fresh rates / arrivals reuse the
    compiled step).  ``backend="ref"`` runs the Python-loop numpy oracle.
    """
    pd = pd or PDHyper()
    if backend == "ref":
        return _ref.solve(net, D_bar, consts, ow, zeta=zeta,
                          max_outer=max_outer, tol=tol, pd=pd,
                          distributed=distributed, w0=w0, seed=seed)
    if backend != "jit":
        raise ValueError(f"unknown solver backend {backend!r} "
                         "(expected 'jit' or 'ref')")
    if w0 is not None:
        w0 = {k: jnp.asarray(np.asarray(v), jnp.float32)
              for k, v in w0.items()}
    return _solve_jit(net, D_bar, consts, ow, zeta=zeta,
                      max_outer=max_outer, tol=tol, pd=pd,
                      distributed=distributed, w0=w0)


# ----------------------------------------------------- trace contract --

from repro.analysis.jaxpr.contracts import Program, contract  # noqa: E402


@contract(
    "solver_sca_step",
    collectives={},
    forbid_f64=False,   # outer step mixes np host constants by design
    # jnp.sort/cumsum (simplex projections) are internally jitted
    # single-eqn helpers — library noise, not our nesting
    fusion_allow=("sort", "cumsum"),
)
def _sca_step_contract():
    """One centralized SCA outer iteration on a 6-UE/3-BS/2-DC net."""
    from repro.core.convergence import MLConstants
    from repro.network import NetworkConfig, make_network

    net = make_network(NetworkConfig(num_ue=6, num_bs=3, num_dc=2, seed=0))
    D_bar = np.full(6, 1000.0)
    consts = MLConstants(L=4.0, theta_i=np.ones(8) * 2,
                         sigma_i=np.ones(8), zeta1=2.0, zeta2=1.0)
    ow = ObjectiveWeights()
    pd = PDHyper(max_iters=2, consensus_rounds=2)

    # mirror the _solve_jit staging (host-side, once)
    spec = V.WSpec(net.dims)
    nv = V.NetView.from_network(net)
    scale_flat = V.Scaler(net).flat(spec)
    D_j = jnp.asarray(D_bar, jnp.float32)
    theta_i = jnp.asarray(consts.theta_i, jnp.float32)
    sigma_i = jnp.asarray(consts.sigma_i, jnp.float32)
    W_cons = jnp.zeros((1, 1), jnp.float32)
    Lambda = jnp.zeros((1, K.num_constraints(spec.dims)), jnp.float32)
    w_phys = V.project(V.init_w(net, D_bar), net)
    w_phys = apply_required_deltas(w_phys, net, D_bar, slack=1.05)
    w = spec.flatten(w_phys) / scale_flat
    step = _outer_step(spec.dims, pd, ow, _consts_scalars(consts),
                       False, 0.5)
    return Program(fn=step, args=(w, Lambda, nv, D_j, theta_i, sigma_i,
                                  scale_flat, W_cons))
