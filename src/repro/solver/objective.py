"""Objective of problem P (paper eq. 44): ML-performance bound (term a,
replaced by the Corollary-1 / eq.-33-style bound with tau ~ delta^A+delta^R)
+ delay (term b) + weighted energies (terms c-e).  Fully differentiable jnp.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Dict

import jax.numpy as jnp

from repro.network import costs as C

if TYPE_CHECKING:   # annotation-only: keeps repro.solver import-cycle free
    from repro.core.convergence import MLConstants


@dataclasses.dataclass(frozen=True)
class ObjectiveWeights:
    xi1: float = 1.0          # ML performance weight
    xi2: float = 1e-2         # delay weight
    xi3: float = 1e-3         # energy weight
    xi3_sub: tuple = (1.0, 1.0, 1.0, 1.0, 1.0, 1.0)   # xi_{3,1..6}
    eta: float = 1e-2
    mu: float = 0.01
    theta: float = 1.0
    T: int = 50
    drift: float = 0.3        # Delta_i (Table III default)


def a_stats_jnp(gamma, eta, mu):
    r = 1.0 - eta * mu
    g = jnp.maximum(gamma, 0.5)
    if abs(r - 1.0) < 1e-12:
        return g, g, jnp.ones_like(g)
    a1 = (1.0 - r ** g) / (1.0 - r)
    a2 = (1.0 - r ** (2 * g)) / (1.0 - r ** 2)
    return a1, a2, jnp.ones_like(g)


def ml_bound(w: Dict, net, D_bar, consts: MLConstants,
             ow: ObjectiveWeights):
    """Differentiable eq.-25/33 bound as a function of the decision vars."""
    N = net.cfg.num_ue
    D_n, D_b, D_s = C.data_configuration(w, jnp.asarray(D_bar, jnp.float32))
    D_i = jnp.concatenate([D_n, D_s])
    D_i = jnp.maximum(D_i, 1.0)
    D_tot = jnp.sum(D_i)
    p_i = D_i / D_tot
    m_i = jnp.clip(w["m"], 1e-3, 1.0)
    gamma_i = jnp.maximum(w["gamma"], 0.5)
    eta, mu, theta, T = ow.eta, ow.mu, ow.theta, ow.T
    L = consts.L
    th = jnp.asarray(consts.theta_i, jnp.float32)
    sg = jnp.asarray(consts.sigma_i, jnp.float32)
    a1, a2, alast = a_stats_jnp(gamma_i, eta, mu)

    term_a = 4.0 * consts.F0_gap / (theta * eta * T)
    tau = w["delta_A"] + w["delta_R"]
    term_b = 4.0 * tau * ow.drift * (N + net.cfg.num_dc) / (theta * eta)
    noise = (p_i ** 2) * (1 - m_i) * (D_i - 1) * th ** 2 * sg ** 2 \
        / (m_i * D_i ** 2) * (a2 / a1 ** 2)
    term_c = 16.0 * eta * L * theta * jnp.sum(noise)
    inner = (1 - m_i) * (D_i - 1) * th ** 2 * sg ** 2 * p_i * gamma_i \
        / (m_i * a1 * D_i ** 2) * (a2 - alast ** 2)
    term_e = 12.0 * eta ** 2 * L ** 2 * jnp.sum(inner)
    het = jnp.max(gamma_i ** 2 * (a1 - alast) / a1)
    term_d = 12.0 * eta ** 2 * L ** 2 * consts.zeta2 * het
    return term_a + term_b + term_c + term_d + term_e


def objective(w: Dict, net, D_bar, consts: MLConstants,
              ow: ObjectiveWeights):
    """J(w): eq. (44) for one representative round."""
    costs = C.network_costs(w, net, D_bar)
    ml = ml_bound(w, net, D_bar, consts, ow)
    delay = w["delta_A"] + w["delta_R"]
    energy = C.round_energy(costs, ow.xi3_sub)
    return ow.xi1 * ml + ow.xi2 * delay + ow.xi3 * energy


def apply_required_deltas(w: Dict, net, D_bar, slack: float = 1.0) -> Dict:
    """Overwrite the delay budgets delta^A / delta^R with the realized path
    requirements (eqs. 34/40) times ``slack`` — the feasible-point
    construction shared by both solver backends and the baseline
    strategies.  Differentiable; works under jit with a traced net view."""
    c = C.network_costs(w, net, D_bar)
    w = dict(w)
    w["delta_A"] = jnp.asarray(c["delta_A_req"] * slack)
    w["delta_R"] = jnp.asarray(c["delta_R_req"] * slack)
    return w


BREAKDOWN_TERMS = ("ml", "delay", "delta_A_req", "delta_R_req", "energy",
                   "total")


def breakdown_terms(w, net, D_bar, consts, ow) -> Dict:
    """The terms of J(w) as arrays, keyed by ``BREAKDOWN_TERMS``.
    Traceable: the jitted solver computes them inside its finish."""
    costs = C.network_costs(w, net, D_bar)
    return {
        "ml": ml_bound(w, net, D_bar, consts, ow),
        "delay": w["delta_A"] + w["delta_R"],
        "delta_A_req": costs["delta_A_req"],
        "delta_R_req": costs["delta_R_req"],
        "energy": C.round_energy(costs, ow.xi3_sub),
        "total": objective(w, net, D_bar, consts, ow),
    }


def breakdown_dict(terms: Dict) -> Dict:
    """The host-side breakdown (floats) of ``breakdown_terms``' values."""
    f = {k: float(terms[k]) for k in BREAKDOWN_TERMS}
    return {"ml": f["ml"], "delay": f["delay"],
            "delay_required": (f["delta_A_req"], f["delta_R_req"]),
            "energy": f["energy"], "total": f["total"]}


def objective_breakdown(w, net, D_bar, consts, ow):
    return breakdown_dict(breakdown_terms(w, net, D_bar, consts, ow))
