"""JXP005 — fusion-boundary detector (the PR-7 regression, codified).

PR 7 found the mesh plane round running at HALF speed because a jitted
kernel fallback called from inside the round's ``fori_loop`` lowered to
a nested XLA call boundary that blocked fusion with the surrounding
loop body.  The fix (``ops._tracing``) inlines the expression when
already under a trace — this pass pins that property: no ``jit`` /
``closed_call`` / ``custom-call`` equation may appear inside a
``scan``/``while`` body.

Escape hatches, because jax.numpy itself jits tiny helpers
(``take_along_axis`` can trace as a nested jit):

* ``DEFAULT_FUSION_ALLOW`` + the contract's ``fusion_allow`` — inner
  jits allowed *by name*;
* ``fusion_max_inner_eqns`` — bodies at or below this equation count
  are considered trivially inlinable (default 0: strict).
"""
from __future__ import annotations

from typing import List

from repro.analysis.jaxpr.passes import (AuditFinding, audit_pass,
                                         subjaxprs)

#: Loop primitives whose bodies must stay call-free.
LOOP_PRIMS = ("scan", "while")

#: Call-boundary primitives (a nested jit traces as `jit`).
CALL_PRIMS = ("jit", "closed_call", "core_call", "custom_call")

#: jax.numpy-internal helper jits that XLA inlines anyway.
DEFAULT_FUSION_ALLOW = ("take_along_axis", "_where", "_one_hot",
                        "_take", "clip")


def _inner_eqn_count(eqn) -> int:
    return sum(len(sub.eqns) for sub in subjaxprs(eqn))


def _collect(jaxpr, in_loop: bool, hits: list) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if in_loop and name in CALL_PRIMS:
            hits.append(eqn)
        nested_loop = in_loop or name in LOOP_PRIMS
        for sub in subjaxprs(eqn):
            _collect(sub, nested_loop, hits)


@audit_pass("JXP005")
def check_fusion_boundaries(trace, spec) -> List[AuditFinding]:
    jaxpr = trace.jaxpr()
    closed = getattr(jaxpr, "jaxpr", jaxpr)
    hits: list = []
    _collect(closed, False, hits)
    allow = set(DEFAULT_FUSION_ALLOW) | set(spec.fusion_allow)
    findings: List[AuditFinding] = []
    for eqn in hits:
        label = str(eqn.params.get("name", eqn.primitive.name))
        if label in allow:
            continue
        n_eqns = _inner_eqn_count(eqn)
        if n_eqns <= spec.fusion_max_inner_eqns:
            continue
        findings.append(AuditFinding(
            spec.name, "JXP005",
            f"nested `{eqn.primitive.name}` boundary `{label}` "
            f"({n_eqns} inner eqns) inside a loop body",
            hint="a jit-inside-jit lowers to an XLA call that blocks "
                 "fusion with the surrounding scan/fori_loop (the PR-7 "
                 "mesh-round 2x regression) — inline the expression "
                 "when traced (see ops._tracing) or hoist the call out "
                 "of the loop; if it is a known-trivial jnp helper, "
                 "add it to the contract's fusion_allow"))
    return findings
