"""Pass framework for the jaxpr/HLO contract auditor.

A *pass* is a function ``(trace: ProgramTrace, spec: ContractSpec) ->
List[AuditFinding]`` registered in :data:`PASSES`.  :class:`ProgramTrace`
owns the (lazily computed, cached) artifacts every pass reads:

* ``jaxpr()`` — ``jax.make_jaxpr`` of the program on its example args;
* ``jaxpr_x64()`` — the same trace under ``jax.enable_x64(True)``
  (the f64-promotion probe: weak Python scalars stay narrow, strong
  literals widen — exactly what runs if a user flips the flag);
* ``lowered_text()`` — StableHLO of the jitted lowering (with the
  contract's ``donate_argnums`` applied — the donation audit reads the
  ``tf.aliasing_output`` attributes);
* ``compiled_text()`` — post-GSPMD compiled HLO (collective audit of
  the partitioned executable).

The eqn walker descends into every sub-jaxpr (pjit bodies, scan/while
bodies, shard_map, cond branches) so counts cover the whole program.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Dict, Iterator, List, Tuple

import jax

from jax.extend import core as _core


@dataclasses.dataclass(frozen=True)
class AuditFinding:
    """One contract violation (or waived observation)."""
    contract: str
    pass_id: str
    message: str
    hint: str = ""
    waived: bool = False

    def render(self) -> str:
        tag = " [waived]" if self.waived else ""
        out = f"{self.contract}: {self.pass_id}{tag}: {self.message}"
        if self.hint:
            out += f"\n    hint: {self.hint}"
        return out


#: pass id -> (name, one-line summary) — the catalogue the CLI prints.
PASS_DOCS: Dict[str, Tuple[str, str]] = {
    "JXP001": ("collective-audit",
               "jaxpr collective-primitive counts match the contract; "
               "compiled HLO contains no unexpected collectives"),
    "JXP002": ("dtype-discipline",
               "no silent f32->f64 promotion under jax_enable_x64; "
               "declared output dtypes (bf16 round-trip) hold"),
    "JXP003": ("memory-estimator",
               "estimated peak live bytes stay under the contract "
               "budget; TilePlans fit their VMEM/SMEM budgets"),
    "JXP004": ("donation-audit",
               "buffers passed with donate_argnums are actually "
               "aliased in the compiled executable"),
    "JXP005": ("fusion-boundary",
               "no nested call boundary (pjit/closed_call/custom-call) "
               "inside a scan/while loop body"),
}

PASSES: Dict[str, Callable] = {}


def audit_pass(pass_id: str):
    """Register a pass implementation under its JXP id."""

    def deco(fn):
        PASSES[pass_id] = fn
        return fn

    return deco


# ------------------------------------------------------- jaxpr walker --

def subjaxprs(eqn) -> Iterator:
    """Every jaxpr nested in one equation's params (pjit/scan/while
    bodies, cond/switch branch lists, shard_map, custom_*_call)."""
    for val in eqn.params.values():
        yield from _as_jaxprs(val)


def _as_jaxprs(val) -> Iterator:
    if isinstance(val, _core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, _core.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for item in val:
            yield from _as_jaxprs(item)


def iter_eqns(jaxpr) -> Iterator:
    """All equations of ``jaxpr`` (open or closed), depth-first."""
    if isinstance(jaxpr, _core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in subjaxprs(eqn):
            yield from iter_eqns(sub)


def count_primitives(jaxpr, names) -> Dict[str, int]:
    """Occurrence count of each primitive name across all nesting."""
    counts = {n: 0 for n in names}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in counts:
            counts[name] += 1
    return counts


def aval_bytes(aval) -> int:
    """Concrete byte size of a shaped aval (0 for tokens/abstract)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    size = 1
    for dim in shape:
        if not isinstance(dim, int):
            return 0        # polymorphic dim — don't guess
        size *= dim
    return size * dtype.itemsize


# ---------------------------------------------------- trace artifacts --

class ProgramTrace:
    """Lazily computed, cached trace artifacts of one contract Program."""

    def __init__(self, spec, program):
        self.spec = spec
        self.program = program
        self._cache: dict = {}

    def _memo(self, key, thunk):
        if key not in self._cache:
            self._cache[key] = thunk()
        return self._cache[key]

    def jaxpr(self):
        return self._memo("jaxpr", lambda: jax.make_jaxpr(
            self.program.fn)(*self.program.args))

    def jaxpr_x64(self):
        def trace():
            with jax.enable_x64(True):
                return jax.make_jaxpr(self.program.fn)(*self.program.args)
        return self._memo("jaxpr_x64", trace)

    def _lowered(self):
        def lower():
            donate = self.program.donate_argnums
            with warnings.catch_warnings():
                # an UNUSED donation warns at lowering time; the
                # donation pass reports it as a structured finding
                warnings.simplefilter("ignore")
                return jax.jit(self.program.fn,
                               donate_argnums=donate).lower(
                                   *self.program.args)
        return self._memo("lowered", lower)

    def lowered_text(self) -> str:
        return self._memo("lowered_text",
                          lambda: self._lowered().as_text())

    def compiled_text(self) -> str:
        return self._memo("compiled_text",
                          lambda: self._lowered().compile().as_text())


def run_passes(trace: ProgramTrace, pass_ids=None) -> List[AuditFinding]:
    """Run the contract's applicable passes (or ``pass_ids``) over one
    ProgramTrace; waived findings are tagged, not dropped."""
    spec = trace.spec
    ids = pass_ids if pass_ids is not None else spec.applicable_passes()
    findings: List[AuditFinding] = []
    for pid in ids:
        for f in PASSES[pid](trace, spec):
            if pid in spec.waivers:
                f = dataclasses.replace(
                    f, waived=True,
                    message=f"{f.message} (waived: {spec.waivers[pid]})")
            findings.append(f)
    return findings
