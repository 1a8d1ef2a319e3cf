"""Contract registry for the trace-level program auditor.

A *contract* pins the traced/compiled shape of one hot-path entry point:
which collectives its jaxpr may contain, that it never promotes the
master plane to f64, how many bytes its intermediates may keep live,
that donated buffers are actually donated, and that no nested call
boundary hides inside its round loop (the PR-7 fusion regression).

Entry points register at their definition sites with the lightweight
:func:`contract` decorator::

    @contract("fused_round", collectives={}, memory_budget_bytes=1 << 22)
    def _fused_round_contract():
        \"\"\"One-line description shown in the audit report.\"\"\"
        spec, args = _tiny_round_args()
        return Program(fn=_plane_round_fn(_audit_loss, spec, "cpu", None),
                       args=args)

Registration is a dict insert — the decorated *builder* only runs when
``python -m repro.analysis audit`` traces it with tiny static shapes, so
hot modules pay nothing at import time.  This module must stay free of
module-level ``repro.core``/``repro.solver``/``repro.sharding`` imports
(the RPA007 cycle rule): those packages import *us* to register.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

#: Modules whose import registers the repo's hot-path contracts.  Kept
#: here (not imported at module level!) so ``discover()`` is the single
#: lazy entry point — the audit CLI and tests both go through it.
DISCOVER_MODULES: Tuple[str, ...] = (
    "repro.core.fedprox",
    "repro.core.aggregation",
    "repro.core.engine",
    "repro.solver.sca",
    "repro.experiments.sweep",
    "repro.sharding.plane",
)


@dataclasses.dataclass(frozen=True)
class Program:
    """A concrete traceable program: a callable plus tiny example args.

    ``fn`` may be jitted or plain — the passes trace through either.
    ``donate_argnums`` names the positional args whose buffers the
    compiled executable must alias (the donation audit, JXP004).
    """
    fn: Callable
    args: Tuple[Any, ...]
    donate_argnums: Tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class ContractSpec:
    """One registered contract: a lazy Program builder + expectations.

    Expectation fields (all optional — a pass only runs when its inputs
    are present; see ``applicable_passes``):

    * ``collectives`` — jaxpr collective-primitive counts, e.g.
      ``{"all_gather": 2, "psum": 0}``.  Values are exact ints or
      ``"N+"`` (at least N).  Collective primitives NOT mentioned are
      expected absent (zero-surprise default).  ``None`` skips JXP001.
    * ``hlo_collectives`` — allowed collective op names in the COMPILED
      HLO (GSPMD may insert its own); anything else found is a finding.
      Triggers a ``.compile()`` of the lowering.
    * ``forbid_f64`` / ``enable_x64`` — JXP002 traces the program under
      ``jax.enable_x64(True)`` and flags any f64/c128 equation
      output: a literal or helper that silently widens under x64.
    * ``out_dtypes`` — expected dtype names of the program outputs on
      the normal (x64-off) trace, e.g. ``("bfloat16",)`` for the bf16
      leaf round-trip contract.
    * ``memory_budget_bytes`` — JXP003 bound on estimated peak live
      bytes of the traced program (tiny shapes; catches accidentally
      materialized cross products).
    * ``tile_plans`` — ``(R, L, n_operands, dtype, backend)`` tuples;
      JXP003 re-derives each TilePlan and checks its double-buffered
      block bytes against the backend VMEM/SMEM budget that sized it.
    * ``fusion_allow`` / ``fusion_max_inner_eqns`` — JXP005 escape
      hatches: named inner jits to permit (jnp internals like
      ``take_along_axis`` are allowed by default) and a size below
      which an inner call is considered trivially inlinable.
    * ``min_devices`` — contracts that build a device mesh skip (with a
      note) when fewer devices are available.
    * ``waivers`` — ``{pass_id: reason}``: findings from that pass are
      reported but do not fail the audit (the suppression mechanism;
      the reason string is mandatory documentation).
    """
    name: str
    build: Callable[[], Program]
    module: str
    doc: str = ""
    collectives: Optional[Mapping[str, object]] = None
    hlo_collectives: Optional[frozenset] = None
    enable_x64: bool = True
    forbid_f64: bool = True
    out_dtypes: Optional[Tuple[str, ...]] = None
    memory_budget_bytes: Optional[int] = None
    tile_plans: Tuple[tuple, ...] = ()
    fusion_allow: Tuple[str, ...] = ()
    fusion_max_inner_eqns: int = 0
    min_devices: int = 1
    waivers: Mapping[str, str] = dataclasses.field(default_factory=dict)

    def applicable_passes(self) -> Tuple[str, ...]:
        out = []
        if self.collectives is not None or \
                self.hlo_collectives is not None:
            out.append("JXP001")
        if self.forbid_f64 or self.out_dtypes is not None:
            out.append("JXP002")
        if self.memory_budget_bytes is not None or self.tile_plans:
            out.append("JXP003")
        out.append("JXP004")    # no-ops without donate_argnums
        out.append("JXP005")
        return tuple(out)


REGISTRY: Dict[str, ContractSpec] = {}


def contract(name: str, **expectations):
    """Register a Program builder under ``name`` (see module docstring).

    The decorated function is returned unchanged; its docstring becomes
    the contract description in the audit report.
    """

    def deco(build: Callable[[], Program]):
        if name in REGISTRY and REGISTRY[name].build is not build:
            raise ValueError(f"duplicate contract name {name!r} "
                             f"(already registered by "
                             f"{REGISTRY[name].module})")
        REGISTRY[name] = ContractSpec(
            name=name, build=build, module=build.__module__,
            doc=(build.__doc__ or "").strip().split("\n")[0],
            **expectations)
        return build

    return deco


def discover() -> Dict[str, ContractSpec]:
    """Import every contract-defining module and return the registry."""
    import importlib
    for mod in DISCOVER_MODULES:
        importlib.import_module(mod)
    return dict(REGISTRY)
