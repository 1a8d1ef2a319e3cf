"""JXP002 — dtype discipline.

The master plane is f32 and the kernel layer round-trips bf16 leaves;
nothing on the hot path may silently widen.  Two checks:

* **f64 promotion probe** — re-trace the program under
  ``jax.enable_x64(True)``.  Weak-typed Python scalars stay
  narrow under x64; *strong* f64 values (``jnp.array([0.5])`` with no
  dtype, ``np.asarray`` constants, ``np.float64`` scalars) widen the
  whole downstream graph.  Any equation producing an f64/c128 output
  under the probe is exactly the site that breaks the moment a user —
  or a dependency — flips ``jax_enable_x64``.  (The AST twin is rule
  RPA010; this pass sees through helpers the linter cannot.)
* **declared output dtypes** — the normal (x64-off) trace's outputs
  must match ``out_dtypes`` when the contract declares them: the bf16
  leaf round-trip pin (an upstream promotion to f32 fails here).
"""
from __future__ import annotations

from collections import Counter
from typing import List

from repro.analysis.jaxpr.passes import AuditFinding, audit_pass, iter_eqns

_WIDE = ("float64", "complex128")


@audit_pass("JXP002")
def check_dtypes(trace, spec) -> List[AuditFinding]:
    findings: List[AuditFinding] = []
    if spec.forbid_f64:
        wide = Counter()
        for eqn in iter_eqns(trace.jaxpr_x64()):
            for var in eqn.outvars:
                dtype = getattr(var.aval, "dtype", None)
                if (dtype is not None and dtype.name in _WIDE
                        # weak-typed scalars (jnp.log of a Python float)
                        # cannot widen strong f32 arrays — only strong
                        # f64 values poison the downstream graph
                        and not getattr(var.aval, "weak_type", False)):
                    wide[(eqn.primitive.name, dtype.name)] += 1
        for (prim, dtype), n in sorted(wide.items()):
            findings.append(AuditFinding(
                spec.name, "JXP002",
                f"{n} `{prim}` equation(s) produce {dtype} under "
                f"jax_enable_x64 — a strong-typed wide literal is "
                f"promoting the graph",
                hint="pin the dtype at the source: "
                     "`jnp.array([...], dtype=jnp.float32)` / "
                     "`jnp.asarray(x, jnp.float32)`; Python scalars "
                     "are weak-typed and safe, list literals and "
                     "np arrays are not (AST twin: RPA010)"))
    if spec.out_dtypes is not None:
        actual = tuple(getattr(a, "dtype", None) and a.dtype.name
                       for a in trace.jaxpr().out_avals)
        if actual != tuple(spec.out_dtypes):
            findings.append(AuditFinding(
                spec.name, "JXP002",
                f"output dtypes {actual} != declared "
                f"{tuple(spec.out_dtypes)}",
                hint="an op in the program widened/narrowed the "
                     "carried dtype — for the bf16 round-trip keep "
                     "scalars weak (Python floats) and avoid strong "
                     "f32 constants on the leaf path"))
    return findings
