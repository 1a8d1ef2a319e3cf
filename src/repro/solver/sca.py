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

import dataclasses
from typing import TYPE_CHECKING, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.solver import constraints as K
from repro.solver import ref as _ref
from repro.solver import variables as V
from repro.solver.consensus import consensus_weights
from repro.solver.objective import (BREAKDOWN_TERMS, ObjectiveWeights,
                                    apply_required_deltas, breakdown_dict,
                                    breakdown_terms, objective)
from repro.solver.primal_dual import PDHyper, make_surrogate
from repro.utils import tracing

if TYPE_CHECKING:   # annotation-only: keeps repro.solver import-cycle free
    from repro.core.convergence import MLConstants


@dataclasses.dataclass
class SCAResult(_ref.SCAResult):
    """The oracle's result plus the executable decision: ``plan`` is
    ``w_rounded`` with ``I_s`` one-hot at ``aggregator`` (the DC whose
    candidate has the least of ``aggregator_objectives``,
    :func:`select_aggregator`) and that candidate's required delay
    budgets."""
    plan: Optional[Dict] = None
    aggregator: int = -1
    aggregator_objectives: Optional[np.ndarray] = None


_PROGRAM_CACHE: Dict[tuple, callable] = {}


def jit_cache_size() -> int:
    """Number of distinct compiled solver programs: outer steps, feasible
    starts, finishes and aggregator choices (diagnostics/tests)."""
    return len(_PROGRAM_CACHE)


def _program(key: tuple, build) -> callable:
    """The jitted program of ``key``, built by ``build()`` on first use."""
    if key not in _PROGRAM_CACHE:
        _PROGRAM_CACHE[key] = jax.jit(build())
    return _PROGRAM_CACHE[key]


def _consts_scalars(consts: MLConstants):
    return (float(consts.L), float(consts.zeta1), float(consts.zeta2),
            float(consts.F0_gap))


def _consts_at(cs, theta_i, sigma_i) -> MLConstants:
    """MLConstants from the static scalars ``cs`` and traced arrays."""
    from repro.core.convergence import MLConstants  # local: avoids cycle
    L_s, zeta1_s, zeta2_s, f0_s = cs
    return MLConstants(L=L_s, theta_i=theta_i, sigma_i=sigma_i,
                       zeta1=zeta1_s, zeta2=zeta2_s, F0_gap=f0_s)


def _outer_step(dims, hyper: PDHyper, ow: ObjectiveWeights, cs,
                distributed: bool, zeta: float, gamma_cap: float = 20.0):
    """The jitted SCA outer iteration for static (dims, hyper, ow, zeta)."""
    def build():
        spec = V.WSpec(dims)
        surrogate = make_surrogate(spec, hyper, ow, cs,
                                   distributed=distributed,
                                   gamma_cap=gamma_cap)

        def step(w, Lambda, net, D_bar, theta_i, sigma_i, scale_flat,
                 W_cons):
            consts = _consts_at(cs, theta_i, sigma_i)
            w_hat, Lambda, _, max_viol = surrogate(
                w, Lambda, net, D_bar, theta_i, sigma_i, scale_flat, W_cons)
            w_new = w + zeta * (w_hat - w)                      # eq. (81)
            w_phys = V.project(spec.unflatten(w_new * scale_flat), net,
                               gamma_cap=gamma_cap)
            w_phys = apply_required_deltas(w_phys, net, D_bar)
            obj = objective(w_phys, net, D_bar, consts, ow)
            return spec.flatten(w_phys) / scale_flat, Lambda, obj, max_viol
        return step

    return _program(("outer", tuple(dims), hyper, ow, cs, distributed,
                     float(zeta), gamma_cap), build)


def _cold_start(dims):
    """The jitted ``V.init_w``, as strong f32 leaves, so that a cold start
    hands the feasible start the same avals as a warm one."""
    def build():
        def init(net):
            return {k: jnp.asarray(v, jnp.float32)
                    for k, v in V.init_w(net, None).items()}
        return init

    return _program(("init", tuple(dims)), build)


def _start(dims, ow: ObjectiveWeights, cs, distributed: bool):
    """The jitted feasible start: ``w0`` projected, its delay budgets set to
    the required ones with 5 % slack, normalized and flattened; the scale
    vector, the zero duals and the start's objective with it."""
    def build():
        spec = V.WSpec(dims)
        n_rows = sum(spec.dims) if distributed else 1
        n_cons = K.num_constraints(spec.dims)

        def start(w0, net, D_bar, theta_i, sigma_i):
            scale_flat = V.Scaler(net).flat(spec)
            w_phys = apply_required_deltas(V.project(w0, net), net, D_bar,
                                           slack=1.05)
            obj = objective(w_phys, net, D_bar,
                            _consts_at(cs, theta_i, sigma_i), ow)
            Lambda = jnp.zeros((n_rows, n_cons), jnp.float32)
            return spec.flatten(w_phys) / scale_flat, scale_flat, Lambda, obj
        return start

    return _program(("start", tuple(dims), ow, cs, distributed), build)


def _candidates(w: Dict, net, D_bar, consts, ow):
    """The S one-hot aggregator candidates of ``w`` as one ``vmap``, each
    with its own required delay budgets.  Returns the read-back summary
    (the least candidate's index as f32, then the S objectives) and the
    least candidate's dict."""
    S = w["I_s"].shape[0]

    def candidate(s):
        ws = apply_required_deltas(dict(w, I_s=jax.nn.one_hot(s, S)),
                                   net, D_bar)
        return objective(ws, net, D_bar, consts, ow), ws

    objs, plans = jax.vmap(candidate)(jnp.arange(S))
    s = jnp.argmin(objs)
    summary = jnp.concatenate([s.astype(jnp.float32)[None], objs])
    return summary, jax.tree.map(lambda x: x[s], plans)


def _finish(dims, ow: ObjectiveWeights, cs):
    """The jitted finish of a solve: the last iterate unflattened, its
    indicators rounded with their required delay budgets, the floating
    aggregator enumerated over it, and the read-back summary with the
    breakdown terms in ``BREAKDOWN_TERMS`` order after the objectives."""
    def build():
        spec = V.WSpec(dims)

        def finish(w, scale_flat, net, D_bar, theta_i, sigma_i):
            consts = _consts_at(cs, theta_i, sigma_i)
            w_phys = spec.unflatten(w * scale_flat)
            w_rounded = apply_required_deltas(V.round_indicators(w_phys),
                                              net, D_bar)
            summary, plan = _candidates(w_rounded, net, D_bar, consts, ow)
            terms = breakdown_terms(w_rounded, net, D_bar, consts, ow)
            summary = jnp.concatenate(
                [summary, jnp.stack([terms[k] for k in BREAKDOWN_TERMS])])
            return w_phys, w_rounded, plan, summary
        return finish

    return _program(("finish", tuple(dims), ow, cs), build)


def _select(dims, ow: ObjectiveWeights, cs):
    """The jitted aggregator enumeration over a rounded plan."""
    def build():
        def select(w, net, D_bar, theta_i, sigma_i):
            return _candidates(w, net, D_bar,
                               _consts_at(cs, theta_i, sigma_i), ow)
        return select

    return _program(("select", tuple(dims), ow, cs), build)


def _traced_consts(consts: MLConstants):
    return (jnp.asarray(consts.theta_i, jnp.float32),
            jnp.asarray(consts.sigma_i, jnp.float32))


def _solve_jit(net, D_bar, consts: MLConstants, ow: ObjectiveWeights,
               *, zeta: float, max_outer: int, tol: float,
               pd: PDHyper, distributed: bool,
               w0: Optional[Dict]) -> SCAResult:
    dims = tuple(net.dims)
    cs = _consts_scalars(consts)
    nv = V.NetView.from_network(net)
    D_j = jnp.asarray(D_bar, jnp.float32)
    theta_i, sigma_i = _traced_consts(consts)
    W_cons = jnp.asarray(consensus_weights(net.adjacency), jnp.float32) \
        if distributed else jnp.zeros((1, 1), jnp.float32)

    # feasible start — the oracle's construction, as one program
    if w0 is None:
        w0 = _cold_start(dims)(nv)
    w, scale_flat, Lambda, obj0 = _start(dims, ow, cs, distributed)(
        w0, nv, D_j, theta_i, sigma_i)

    step = _outer_step(dims, pd, ow, cs, distributed, zeta)
    hist = [float(tracing.sync(obj0, "sca_objective"))]
    viol = []
    ell = 0
    for ell in range(max_outer):
        with tracing.span("sca_outer", iter=ell):
            w, Lambda, obj, max_viol = step(w, Lambda, nv, D_j, theta_i,
                                            sigma_i, scale_flat, W_cons)
            obj = float(tracing.sync(obj, "sca_objective"))
            viol.append(float(tracing.sync(max_viol, "sca_violation")))
        improved = hist[-1] - obj
        hist.append(obj)
        if 0 <= improved < tol * max(1.0, abs(hist[0])):
            break
    with tracing.span("select_aggregator"):
        w_phys, w_rounded, plan, summary = _finish(dims, ow, cs)(
            w, scale_flat, nv, D_j, theta_i, sigma_i)
        summary = tracing.sync(summary, "select_aggregator")
    S = dims[2]
    terms = dict(zip(BREAKDOWN_TERMS, summary[1 + S:].tolist()))
    return SCAResult(
        w=w_phys, w_rounded=w_rounded, objective_history=hist,
        violation_history=viol, breakdown=breakdown_dict(terms),
        iterations=ell + 1, plan=plan, aggregator=int(summary[0]),
        aggregator_objectives=summary[1:1 + S])


def _choose(w: Dict, net, D_bar, consts, ow):
    """(aggregator index, the S candidates' objectives, executable plan)
    of a rounded plan ``w``."""
    dims = tuple(net.dims)
    theta_i, sigma_i = _traced_consts(consts)
    with tracing.span("select_aggregator"):
        summary, plan = _select(dims, ow, _consts_scalars(consts))(
            {k: jnp.asarray(w[k], jnp.float32) for k in V.W_KEYS},
            V.NetView.from_network(net), jnp.asarray(D_bar, jnp.float32),
            theta_i, sigma_i)
        summary = tracing.sync(summary, "select_aggregator")
    return int(summary[0]), summary[1:], plan


def select_aggregator(w: Dict, net, D_bar, consts, ow) -> int:
    """Exact discrete rounding of the floating-aggregator indicator I_s.

    With few SCA outer iterations the relaxed I_s stays near the simplex
    interior, so argmax rounding picks a vertex by noise rather than by
    cost.  S is small (DC tier), so enumerate the S one-hot candidates —
    each with its own required delay budgets — and return the index that
    minimizes the true objective.  This is what makes the aggregation
    point actually *float* round-to-round under dynamic scenarios.  The
    enumeration is one compiled program and one read-back; ``w`` may hold
    numpy or device arrays.
    """
    return _choose(w, net, D_bar, consts, ow)[0]


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
        res = _ref.solve(net, D_bar, consts, ow, zeta=zeta,
                         max_outer=max_outer, tol=tol, pd=pd,
                         distributed=distributed, w0=w0, seed=seed)
        s, objs, plan = _choose(res.w_rounded, net, D_bar, consts, ow)
        res = SCAResult(**vars(res), plan=plan, aggregator=s,
                        aggregator_objectives=objs)
    elif backend == "jit":
        if w0 is not None:
            w0 = {k: jnp.asarray(tracing.sync(v, "sca_warm_start"),
                                 jnp.float32) for k, v in w0.items()}
        res = _solve_jit(net, D_bar, consts, ow, zeta=zeta,
                         max_outer=max_outer, tol=tol, pd=pd,
                         distributed=distributed, w0=w0)
    else:
        raise ValueError(f"unknown solver backend {backend!r} "
                         "(expected 'jit' or 'ref')")
    # the outer iterations of the engine's enclosing cefl/solve span
    tracing.add("solve", outer_iters=res.iterations)
    return res


# ----------------------------------------------------- trace contract --

from repro.analysis.jaxpr.contracts import Program, contract  # noqa: E402


def _contract_args():
    """A 6-UE/3-BS/2-DC net, its arrivals, constants and weights, and the
    traced arguments every solver program takes after its iterate."""
    from repro.core.convergence import MLConstants
    from repro.network import NetworkConfig, make_network

    net = make_network(NetworkConfig(num_ue=6, num_bs=3, num_dc=2, seed=0))
    consts = MLConstants(L=4.0, theta_i=np.ones(8) * 2,
                         sigma_i=np.ones(8), zeta1=2.0, zeta2=1.0)
    traced = (V.NetView.from_network(net), jnp.full(6, 1000.0, jnp.float32),
              *_traced_consts(consts))
    return net, _consts_scalars(consts), ObjectiveWeights(), traced


# every solver program: no collectives; jnp.sort/cumsum (simplex
# projections) are internally jitted single-eqn helpers — library noise,
# not our nesting; the programs mix np host constants by design
_SOLVER_CONTRACT = dict(collectives={}, forbid_f64=False,
                        fusion_allow=("sort", "cumsum"))


@contract("solver_sca_start", **_SOLVER_CONTRACT)
def _sca_start_contract():
    """The feasible start of a solve on a 6-UE/3-BS/2-DC net."""
    net, cs, ow, traced = _contract_args()
    w0 = _cold_start(net.dims)(traced[0])
    return Program(fn=_start(net.dims, ow, cs, False), args=(w0, *traced))


@contract("solver_sca_step", **_SOLVER_CONTRACT)
def _sca_step_contract():
    """One centralized SCA outer iteration on a 6-UE/3-BS/2-DC net."""
    net, cs, ow, traced = _contract_args()
    w0 = _cold_start(net.dims)(traced[0])
    w, scale_flat, Lambda, _ = _start(net.dims, ow, cs, False)(w0, *traced)
    step = _outer_step(net.dims, PDHyper(max_iters=2, consensus_rounds=2),
                       ow, cs, False, 0.5)
    nv, D_j, theta_i, sigma_i = traced
    return Program(fn=step, args=(w, Lambda, nv, D_j, theta_i, sigma_i,
                                  scale_flat, jnp.zeros((1, 1), jnp.float32)))


@contract("solver_sca_finish", **_SOLVER_CONTRACT)
def _sca_finish_contract():
    """The finish of a solve (rounding, DC choice) on a 6-UE/3-BS/2-DC net."""
    net, cs, ow, traced = _contract_args()
    w0 = _cold_start(net.dims)(traced[0])
    w, scale_flat, _, _ = _start(net.dims, ow, cs, False)(w0, *traced)
    return Program(fn=_finish(net.dims, ow, cs),
                   args=(w, scale_flat, *traced))
