"""Pallas TPU kernels: fused FedProx local update (paper eqs. 5-6, 8-10).

    x_new   = x - active * eta * (g + mu * (x - anchor))
    acc_new = acc + active * a_k * g            # eq. 10 numerator

Unfused, XLA emits sub/mul/add chains with 5 HBM reads + 3 writes over
params-sized buffers; the fused kernel does 3 reads + 1 write per element in
one VMEM pass.  This op runs every local SGD iteration of every DPU, on
every parameter — the highest-frequency elementwise hot spot in CE-FL.

Layout: parameters live on the flat parameter plane (see ``plane.py``):
(R, LANE) f32 with R a multiple of 8.  Compiled launches run a 2-D
(row-tile x lane-tile) grid whose block extents come from a
:class:`~repro.kernels.tiling.TilePlan` — sized against the target
memory space's byte budget (TPU VMEM / GPU SMEM) from the operand count
and dtype, with ``pl.cdiv`` grids padding edge blocks when the plane
extents don't divide the tile.  Mosaic double-buffers the streamed
blocks (two live copies per operand), so the next tile's DMA overlaps
the current tile's compute.

In interpret mode (the CPU fallback, ``plan=None``) the grid collapses
to a SINGLE whole-array block: the interpreter's per-grid-step cost is a
full-buffer copy, so one fused step is the fast path and the same
pallas_call lowers to plain XLA elementwise ops under jit.  (Passing an
explicit tiled ``plan`` with ``interpret=True`` runs the tiled grid in
the interpreter — that is the parity-test path for the compiled
decomposition.)

Backend selection — which of these paths a caller gets — lives in ONE
place: the dispatch layer in ``ops.py``.  Callers should not pick
``interpret``/``plan`` by hand outside tests.

Two entry points:

* :func:`fedprox_update_2d` — single plane, plain eq. 5-6 update.
* :func:`fedprox_accum_2d` — the batched ``(G, R, LANE)`` variant used by
  the group/mesh hot paths: one launch updates every DPU of the group AND
  folds the per-step FedNova coefficient ``a_k`` and the activity mask
  into the eq.-10 accumulator, so a local iteration is one kernel launch
  instead of a per-leaf tree_map chain plus a separate accumulation pass.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import TilePlan

LANE = 1024          # last-dim tile (multiple of 128)
ROWS = 256           # max rows per tile (multiple of 8)


def row_tile(r: int, cap: int = ROWS) -> int:
    """Largest power-of-two multiple of 8 dividing ``r`` (<= cap)."""
    assert r % 8 == 0, r    # repro: noqa(RPA004) r is a static row count (plane shape), never a tracer
    t = 8
    while t * 2 <= cap and r % (t * 2) == 0:    # repro: noqa(RPA004) static tile-size arithmetic on concrete ints
        t *= 2
    return t


def _default_plan(R: int, interpret: bool) -> TilePlan:
    """The no-plan fallbacks: whole-array block in interpret mode (see
    module doc), legacy ``row_tile`` decomposition otherwise."""
    if interpret:    # repro: noqa(RPA004) interpret is a jit-static flag, never a tracer
        return TilePlan(rows=R, lanes=LANE, backend="interpret")
    return TilePlan(rows=row_tile(R), lanes=LANE, backend="tpu")


def _compiler_params(plan: TilePlan, interpret: bool, semantics):
    """Mosaic dimension semantics for compiled TPU launches (the grid
    dims of these kernels are embarrassingly parallel unless marked)."""
    if interpret or plan.backend != "tpu":    # repro: noqa(RPA004) static flag + static plan metadata
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _kernel(x_ref, g_ref, a_ref, eta_ref, mu_ref, o_ref):
    eta = eta_ref[0, 0]
    mu = mu_ref[0, 0]
    x = x_ref[...]
    g = g_ref[...]
    a = a_ref[...]
    xf = x.astype(jnp.float32)
    upd = xf - eta * (g.astype(jnp.float32) + mu * (xf - a.astype(jnp.float32)))
    o_ref[...] = upd.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "plan"))
def fedprox_update_2d(x, g, anchor, eta, mu, *, interpret: bool = False,
                      plan: Optional[TilePlan] = None):
    """x, g, anchor: (R, LANE) with R % 8 == 0."""
    R, L = x.shape
    assert L == LANE and R % 8 == 0, (R, L)
    plan = plan or _default_plan(R, interpret)
    rows, lanes = plan.rows, plan.lanes
    grid = (pl.cdiv(R, rows), pl.cdiv(L, lanes))
    spec = pl.BlockSpec((rows, lanes), lambda i, j: (i, j))
    sspec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    eta = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    mu = jnp.asarray(mu, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec, spec, spec, sspec, sspec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(plan, interpret,
                                         ("parallel", "parallel")),
    )(x, g, anchor, eta, mu)


def _accum_kernel(x_ref, g_ref, anc_ref, acc_ref, coef_ref, act_ref,
                  eta_ref, mu_ref, ox_ref, oacc_ref):
    eta = eta_ref[0, 0]
    mu = mu_ref[0, 0]
    if x_ref.shape[0] == 1:
        # one DPU per grid step: its scalars from the whole (1, G) SMEM
        # arrays, indexed by the DPU grid coordinate
        i = pl.program_id(0)
        a_k = coef_ref[0, i]
        act = act_ref[0, i]
    else:
        a_k = coef_ref[0, :][:, None, None]     # (gblk, 1, 1)
        act = act_ref[0, :][:, None, None]
    x = x_ref[...].astype(jnp.float32)          # (gblk, rows, lanes)
    g = g_ref[...].astype(jnp.float32)
    anc = anc_ref[...].astype(jnp.float32)      # (rows, lanes) or (gblk, ...)
    upd = x - act * eta * (g + mu * (x - anc))
    ox_ref[...] = upd.astype(ox_ref.dtype)
    oacc_ref[...] = (acc_ref[...].astype(jnp.float32)
                     + (act * a_k) * g).astype(oacc_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "plan"))
def fedprox_accum_2d(x, g, anchor, acc, coef, active, eta, mu, *,
                     interpret: bool = False,
                     plan: Optional[TilePlan] = None):
    """Batched fused proximal step + eq.-10 accumulation.

    x, g, acc: (G, R, LANE); anchor: (R, LANE) shared or (G, R, LANE)
    per-DPU; coef, active: (G,) per-DPU a_{i,k} and activity mask.
    Returns (x_new, acc_new), both (G, R, LANE):

        x_new   = x - active*eta*(g + mu*(x - anchor))
        acc_new = acc + active*coef*g
    """
    G, R, L = x.shape
    assert L == LANE and R % 8 == 0, (G, R, L)
    assert g.shape == x.shape and acc.shape == x.shape
    plan = plan or _default_plan(R, interpret)
    if interpret and plan.backend == "interpret":
        gblk = G                     # one whole-array block (see module doc)
    else:
        gblk = 1                     # memory-budget tiles, one DPU per step
    rows, lanes = min(plan.rows, R), plan.lanes
    grid = (G // gblk, pl.cdiv(R, rows), pl.cdiv(L, lanes))
    bspec = pl.BlockSpec((gblk, rows, lanes), lambda i, j, k: (i, j, k))
    if anchor.ndim == 2:
        aspec = pl.BlockSpec((rows, lanes), lambda i, j, k: (j, k))
    else:
        assert anchor.shape == x.shape
        aspec = bspec
    # per-DPU scalars: the whole (1, G) array in SMEM (a (1, gblk) VMEM
    # block would break Mosaic's (8, 128) block rule)
    pspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    sspec = pl.BlockSpec((1, 1), lambda i, j, k: (0, 0))
    coef = jnp.asarray(coef, jnp.float32).reshape(1, G)
    active = jnp.asarray(active, jnp.float32).reshape(1, G)
    eta = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    mu = jnp.asarray(mu, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _accum_kernel,
        grid=grid,
        in_specs=[bspec, bspec, aspec, bspec, pspec, pspec, sspec, sspec],
        out_specs=[bspec, bspec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(acc.shape, acc.dtype)],
        interpret=interpret,
        compiler_params=_compiler_params(
            plan, interpret, ("parallel", "parallel", "parallel")),
    )(x, g, anchor, acc, coef, active, eta, mu)
