"""Backend dispatch + jitted public wrappers around the Pallas kernels.

Two API levels:

* **Plane level** (the hot path): ``fedprox_plane``, ``fedprox_accum_plane``,
  ``nova_aggregate_plane`` operate directly on ``(R, LANE)`` /
  ``(G, R, LANE)`` parameter planes (see ``plane.py``) — no flattening,
  no padding, no host round-trips.  This is what ``core.fedprox``,
  ``core.round_step`` and the engine executors call every round.
* **Tree level** (convenience / API boundaries): ``fedprox_update``,
  ``nova_aggregate`` accept pytrees.

Backend dispatch — THE single place that decides how a kernel op runs:

* ``"tpu"`` / ``"gpu"`` — compiled ``pallas_call`` with a tiled,
  double-buffered grid sized for the backend memory space by
  :func:`repro.kernels.tiling.plan_tiles` (VMEM / SMEM byte budgets from
  dtype and plane dims).
* ``"interpret"`` — the Pallas interpreter with the grid=1 whole-array
  block fallback (the kernel body traces into plain XLA ops under jit);
  numerically identical to the compiled decomposition, and the substrate
  the tiled grids are parity-tested on.
* ``"cpu"`` — jitted pure-jnp ops (``kernels/ref.py``).  The kernel
  bodies are expression-identical to the refs, so this is bitwise equal
  to ``"interpret"`` — but skips Pallas interpreter overhead entirely,
  and at the TREE level fuses per leaf without the FlatSpec
  flatten/unflatten round-trip.  This is why the default CPU path now
  beats the unfused XLA baseline instead of losing to it.

The active backend is auto-detected from ``jax.default_backend()``
(accelerators pass through, anything else becomes ``"cpu"``), can be
seeded via the ``REPRO_KERNEL_BACKEND`` env var, overridden process-wide
with :func:`set_backend` / scoped with :func:`use_backend`, or forced
per-call with the ``backend=`` kwarg (``EngineOptions.kernel_backend``
and ``EngineSpec.kernel_backend`` thread through to it).  The legacy
``interpret=`` kwarg is still honored: ``True`` selects ``"interpret"``,
``False`` selects the detected hardware backend.

Weight contract (see docs/kernels.md): tree-level ``nova_aggregate`` takes
ABSOLUTE dataset sizes and normalizes exactly once; the plane/kernel level
takes already-normalized weights and never re-normalizes.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels import fedprox_update as _fp
from repro.kernels import nova_aggregate as _na
from repro.kernels import ref as _ref
from repro.kernels import robust_aggregate as _ra
from repro.kernels.plane import FlatSpec, ParamPlane, spec_of  # noqa: F401
from repro.kernels.tiling import TilePlan, plan_tiles  # noqa: F401
from repro.utils import tracing

# NOTE: no serving-kernel imports here.  ops.py is on the import path of
# every training module, and swa_decode_attention is a pure re-export
# used only by serving callers — reach it via ``repro.kernels`` (lazy)
# or the defining module.  Keeping ops import-light matters because
# importing it initializes the jax backend (the device probe below),
# which pins the device count before XLA_FLAGS overrides can land.

BACKENDS = ("cpu", "interpret", "gpu", "tpu")

# module-level backend probe: jax.default_backend() initializes the
# platform client (the probe below and the _BACKEND default share it);
# deliberately NOT jax.devices() — the platform name is enough and the
# device list is not needed at import time
_ON_TPU = jax.default_backend() == "tpu"
# Back-compat alias (pre-dispatch callers flag-check this): interpret-or-
# equivalent is the right default everywhere except on real TPUs.
INTERPRET = not _ON_TPU


def detect_backend() -> str:
    """Hardware-detected default: accelerator platforms pass through,
    everything else runs the jitted-ref ``"cpu"`` path."""
    plat = jax.default_backend()
    return plat if plat in ("tpu", "gpu") else "cpu"


def _validate(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r}; known: {BACKENDS}")
    return backend


_BACKEND = _validate(os.environ.get("REPRO_KERNEL_BACKEND") or
                     detect_backend())


def current_backend() -> str:
    """The process-wide default backend ops dispatch to."""
    return _BACKEND


def set_backend(backend: str) -> str:
    """Set the process-wide default backend (returns it)."""
    global _BACKEND
    _BACKEND = _validate(backend)
    return _BACKEND


@contextlib.contextmanager
def use_backend(backend: str):
    """Scoped :func:`set_backend` (restores the previous default)."""
    global _BACKEND
    prev = _BACKEND
    _BACKEND = _validate(backend)
    try:
        yield _BACKEND
    finally:
        _BACKEND = prev


def resolve_backend(backend: Optional[str] = None,
                    interpret: Optional[bool] = None) -> str:
    """Resolution order: explicit ``backend`` (``"auto"`` defers) >
    legacy ``interpret`` flag > process default."""
    if backend is not None and backend != "auto":
        return _validate(backend)
    if interpret is not None:
        return "interpret" if interpret else detect_backend()
    return _BACKEND


def normalize_weights(weights: Sequence) -> jnp.ndarray:
    """Absolute D_i -> simplex weights (f32).  THE single normalization
    point of the tree-level weight contract (docs/kernels.md); the
    kernel level below takes already-normalized weights.  Re-exported as
    ``core.aggregation.normalize_weights``."""
    w = jnp.asarray(weights, jnp.float32)
    return w / jnp.sum(w)


def _plan_for(backend: str, R: int, L: int, *, n_operands: int, dtype):
    """Tiled plan for accelerator backends; None (legacy whole-array /
    row_tile decomposition) elsewhere."""
    if backend in ("tpu", "gpu"):
        return plan_tiles(R, L, n_operands=n_operands, dtype=jnp.dtype(dtype),
                          backend=backend)
    return None


# jitted pure-jnp fallbacks for the "cpu" backend (bitwise equal to the
# interpret-mode kernels — the kernel bodies are expression-identical)
_fedprox_plane_cpu = jax.jit(_ref.fedprox_update_ref)
_fedprox_accum_cpu = jax.jit(_ref.fedprox_accum_ref)
_nova_plane_cpu = jax.jit(_ref.nova_aggregate_ref)
_robust_plane_cpu = jax.jit(_ref.robust_aggregate_ref,
                            static_argnames=("k", "median"))

ROBUST_MODES = ("trimmed_mean", "median")


def trim_count(n_dpu: int, trim_frac: float) -> int:
    """Per-side trim count for an n_dpu stack: floor(n * frac), clamped so
    at least one value survives (2k < n)."""
    if not 0.0 <= trim_frac < 0.5:
        raise ValueError(f"trim_frac must be in [0, 0.5), got {trim_frac}")
    return min(int(n_dpu * trim_frac), (n_dpu - 1) // 2)


def _tracing(*xs) -> bool:
    """True when any leaf is a tracer — i.e. we're already inside an outer
    jit/scan.  The "cpu" branches then inline the ref expression instead of
    calling the nested-jitted fallback: a jit-inside-jit lowers to an XLA
    call boundary that blocks fusion with the surrounding loop (measurably
    slower inside the round-step fori_loop); inlining keeps the op fusable.
    Eager calls keep the jitted fast path."""
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(xs))


@jax.jit
def _fedprox_tree_cpu(params, grads, anchor, eta, mu):
    return jax.tree_util.tree_map(
        lambda x, g, a: _ref.fedprox_update_ref(x, g, a, eta, mu),
        params, grads, anchor)


@jax.jit
def _nova_tree_cpu(x, d_list, w, theta_eta):
    return jax.tree_util.tree_map(
        lambda xl, *dl: _ref.nova_aggregate_ref(
            xl, jnp.stack(dl), w, theta_eta),
        x, *d_list)


# ------------------------------------------------------ plane level -----

def fedprox_plane(x, g, anchor, eta, mu, *,
                  interpret: Optional[bool] = None,
                  backend: Optional[str] = None):
    """Fused x <- x - eta*(g + mu*(x - anchor)) on (R, LANE) planes."""
    b = resolve_backend(backend, interpret)
    if b == "cpu":
        if _tracing(x, g, anchor):
            return _ref.fedprox_update_ref(x, g, anchor, eta, mu)
        return _fedprox_plane_cpu(x, g, anchor, eta, mu)
    plan = _plan_for(b, *x.shape, n_operands=4, dtype=x.dtype)
    return _fp.fedprox_update_2d(x, g, anchor, eta, mu,
                                 interpret=(b == "interpret"), plan=plan)


def fedprox_accum_plane(x, g, anchor, acc, coef, active, eta, mu, *,
                        interpret: Optional[bool] = None,
                        backend: Optional[str] = None):
    """Batched proximal step + eq.-10 accumulation on (G, R, LANE) planes
    (one launch per local iteration for a whole DPU group), under the
    ``cefl.eq10`` named scope."""
    b = resolve_backend(backend, interpret)
    with jax.named_scope(tracing.EQ10):
        if b == "cpu":
            coef = jnp.asarray(coef, jnp.float32)
            active = jnp.asarray(active, jnp.float32)
            if _tracing(x, g, anchor, acc, coef, active):
                return _ref.fedprox_accum_ref(x, g, anchor, acc, coef,
                                              active, eta, mu)
            return _fedprox_accum_cpu(x, g, anchor, acc, coef, active, eta,
                                      mu)
        # resident blocks per grid step: x, g, anchor, acc, x_new, acc_new
        plan = _plan_for(b, x.shape[1], x.shape[2], n_operands=6,
                         dtype=x.dtype)
        return _fp.fedprox_accum_2d(x, g, anchor, acc, coef, active, eta, mu,
                                    interpret=(b == "interpret"), plan=plan)


def nova_aggregate_plane(x, d_stack, weights, theta_eta, *,
                         interpret: Optional[bool] = None,
                         backend: Optional[str] = None):
    """eq. 11 on planes.  ``weights`` must already be normalized.  ``x``
    may be (R, LANE) or (n_dpu, R, LANE) (stacked per-DPU replicas).
    Runs under the ``cefl.eq11`` named scope."""
    b = resolve_backend(backend, interpret)
    with jax.named_scope(tracing.EQ11):
        if b == "cpu":
            w32 = jnp.asarray(weights, jnp.float32)
            if _tracing(x, d_stack, w32):
                return _ref.nova_aggregate_ref(x, d_stack, w32, theta_eta)
            return _nova_plane_cpu(x, d_stack, w32, theta_eta)
        n = d_stack.shape[0]
        itp = b == "interpret"
        if x.ndim == 3:
            # resident: x/out keep the n-stack, d streams one tile, + scratch
            plan = _plan_for(b, x.shape[1], x.shape[2],
                             n_operands=2 * n + 2, dtype=x.dtype)
            return _na.nova_aggregate_stacked_2d(
                x, d_stack, weights, theta_eta, interpret=itp, plan=plan)
        plan = _plan_for(b, *x.shape, n_operands=4, dtype=x.dtype)
        return _na.nova_aggregate_2d(x, d_stack, weights, theta_eta,
                                     interpret=itp, plan=plan)


def robust_aggregate_plane(x, d_stack, theta_eta, *,
                           mode: str = "trimmed_mean",
                           trim_frac: float = 0.1,
                           interpret: Optional[bool] = None,
                           backend: Optional[str] = None):
    """Byzantine-robust eq. 11 on planes: x - theta_eta * reduce(d_stack)
    with a coordinate-wise trimmed mean (``mode="trimmed_mean"``) or
    median (``mode="median"``) over the DPU axis.  UNWEIGHTED by design —
    dataset-size weights are the lever a byzantine client inflates."""
    if mode not in ROBUST_MODES:
        raise ValueError(
            f"unknown robust mode {mode!r}; known: {ROBUST_MODES}")
    median = mode == "median"
    k = 0 if median else trim_count(d_stack.shape[0], trim_frac)
    b = resolve_backend(backend, interpret)
    if b == "cpu":
        if _tracing(x, d_stack):
            return _ref.robust_aggregate_ref(x, d_stack, theta_eta,
                                             k=k, median=median)
        return _robust_plane_cpu(x, d_stack, theta_eta, k=k, median=median)
    # the sort needs the full DPU stack resident per (rows, lanes) tile
    plan = _plan_for(b, *x.shape, n_operands=d_stack.shape[0] + 3,
                     dtype=x.dtype)
    return _ra.robust_aggregate_2d(x, d_stack, theta_eta, k=k,
                                   median=median, interpret=(b == "interpret"),
                                   plan=plan)


# ------------------------------------------------------- tree level -----

def fedprox_update(params, grads, anchor, eta, mu, *,
                   interpret: Optional[bool] = None,
                   backend: Optional[str] = None):
    """Fused x <- x - eta*(g + mu*(x - anchor)) over a whole pytree."""
    b = resolve_backend(backend, interpret)
    if b == "cpu":
        # per-leaf fused jnp — no FlatSpec flatten/unflatten round-trip
        if _tracing(params, grads, anchor):
            return jax.tree_util.tree_map(
                lambda x, g, a: _ref.fedprox_update_ref(x, g, a, eta, mu),
                params, grads, anchor)
        return _fedprox_tree_cpu(params, grads, anchor, eta, mu)
    spec = spec_of(params)
    out = fedprox_plane(spec.flatten(params), spec.flatten(grads),
                        spec.flatten(anchor), eta, mu, backend=b)
    return spec.unflatten(out)


def nova_aggregate(x, d_list: Sequence, weights, theta_eta, *,
                   interpret: Optional[bool] = None,
                   backend: Optional[str] = None):
    """x <- x - theta*eta*sum_i w_i d_i over pytrees (eq. 11).

    ``weights``: absolute dataset sizes D_i — normalized here (the single
    normalization point for this path, see docs/kernels.md).
    """
    b = resolve_backend(backend, interpret)
    w = normalize_weights(weights)
    if b == "cpu":
        if _tracing(x, list(d_list), w):
            return jax.tree_util.tree_map(
                lambda xl, *dl: _ref.nova_aggregate_ref(
                    xl, jnp.stack(dl), w, theta_eta), x, *d_list)
        return _nova_tree_cpu(x, list(d_list), w, theta_eta)
    spec = spec_of(x)
    d_stack = jnp.stack([spec.flatten(d) for d in d_list], axis=0)
    out = nova_aggregate_plane(spec.flatten(x), d_stack, w, theta_eta,
                               backend=b)
    return spec.unflatten(out)
