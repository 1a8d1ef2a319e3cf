"""Pallas TPU kernel: floating-point aggregation update (paper eq. 11).

    x_new = x - theta * eta * sum_i w_i * d_i

The aggregation DC applies this to the stacked scaled accumulated gradients
it received (post-collective, the D_i/D weights folded into w).  Fusing the
weighted reduction with the model update avoids materializing sum_i w_i d_i
in HBM: one pass reads the gradient tiles plus the x tile and writes x_new.

Weight contract: ``weights`` here are ALREADY NORMALIZED (sum to 1) — the
kernels never re-normalize.  Tree/plane-level wrappers (``ops.py``,
``core.aggregation``) take absolute D_i sizes and normalize exactly once
via ``core.aggregation.normalize_weights`` (see docs/kernels.md).

Two kernel families:

* **Whole-stack einsum** (``plan=None``): each grid step loads the full
  (n_dpu, rows, LANE) d block and reduces with one einsum.  Fine in
  interpret mode (one whole-array block — see ``fedprox_update.py``) and
  for small n_dpu, but the resident block grows linearly with n_dpu.
* **Grid accumulation** (``plan`` given): the DPU axis becomes the
  innermost grid dimension.  Each step streams ONE (rows, lanes) d tile,
  a float32 scratch accumulator (``pltpu.VMEM`` scratch shape) is
  zero-initialized under ``@pl.when(k == 0)`` and flushed into the
  output under ``@pl.when(k == n-1)``, so resident bytes are independent
  of n_dpu and Mosaic overlaps the next tile's DMA with the current
  accumulate.  Row/lane extents come from the :class:`TilePlan` (sized
  for the backend memory budget); edge blocks are padded via ``pl.cdiv``
  grids.

Backend/plan selection is centralized in ``ops.py`` — callers should not
pick ``interpret``/``plan`` by hand outside tests.

Two entry points:

* :func:`nova_aggregate_2d` — single global plane x: (R, LANE).
* :func:`nova_aggregate_stacked_2d` — x: (n_dpu, R, LANE), each row
  updated with the SAME weighted reduction (the mesh round keeps one
  replica of the global model per DPU row).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fedprox_update import _compiler_params, row_tile
from repro.kernels.tiling import TilePlan

LANE = 1024
ROWS = 128


def _kernel(x_ref, d_ref, w_ref, se_ref, o_ref):
    scale = se_ref[0, 0]                     # theta * eta
    x = x_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)       # (n_dpu, rows, LANE)
    w = w_ref[0, :]                           # (n_dpu,)
    agg = jnp.einsum("n,nrl->rl", w, d)
    o_ref[...] = (x - scale * agg).astype(o_ref.dtype)


def _kernel_acc(x_ref, d_ref, w_ref, se_ref, o_ref, acc_ref):
    """Grid-accumulation body: DPU axis = innermost grid dim k."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += w_ref[0, k] * d_ref[0].astype(jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        scale = se_ref[0, 0]
        o_ref[...] = (x_ref[...].astype(jnp.float32)
                      - scale * acc_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "plan"))
def nova_aggregate_2d(x, d_stack, weights, theta_eta, *,
                      interpret: bool = False,
                      plan: Optional[TilePlan] = None):
    """x: (R, LANE); d_stack: (n_dpu, R, LANE); weights: (n_dpu,),
    normalized (sum to 1)."""
    R, L = x.shape
    n = d_stack.shape[0]
    assert L == LANE and R % 8 == 0 and d_stack.shape == (n, R, L)
    w = weights.reshape(1, n).astype(jnp.float32)
    se = jnp.asarray(theta_eta, jnp.float32).reshape(1, 1)
    if plan is None:
        # legacy whole-stack einsum decomposition
        rows = R if interpret else row_tile(R, ROWS)
        grid = (R // rows,)
        xspec = pl.BlockSpec((rows, LANE), lambda i: (i, 0))
        dspec = pl.BlockSpec((n, rows, LANE), lambda i: (0, i, 0))
        wspec = pl.BlockSpec((1, n), lambda i: (0, 0))
        sspec = pl.BlockSpec((1, 1), lambda i: (0, 0))
        return pl.pallas_call(
            _kernel,
            grid=grid,
            in_specs=[xspec, dspec, wspec, sspec],
            out_specs=xspec,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret,
        )(x, d_stack, w, se)
    rows, lanes = min(plan.rows, R), plan.lanes
    grid = (pl.cdiv(R, rows), pl.cdiv(L, lanes), n)
    xspec = pl.BlockSpec((rows, lanes), lambda i, j, k: (i, j))
    dspec = pl.BlockSpec((1, rows, lanes), lambda i, j, k: (k, i, j))
    wspec = pl.BlockSpec(memory_space=pltpu.SMEM)    # whole (1, n), by k
    sspec = pl.BlockSpec((1, 1), lambda i, j, k: (0, 0))
    return pl.pallas_call(
        _kernel_acc,
        grid=grid,
        in_specs=[xspec, dspec, wspec, sspec],
        out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((rows, lanes), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(
            plan, interpret, ("parallel", "parallel", "arbitrary")),
    )(x, d_stack, w, se)


def _kernel_stacked(x_ref, d_ref, w_ref, se_ref, o_ref):
    scale = se_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)        # (n_dpu, rows, LANE)
    d = d_ref[...].astype(jnp.float32)        # (n_dpu, rows, LANE)
    w = w_ref[0, :]
    agg = jnp.einsum("n,nrl->rl", w, d)
    o_ref[...] = (x - scale * agg[None]).astype(o_ref.dtype)


def _kernel_stacked_acc(x_ref, d_ref, w_ref, se_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += w_ref[0, k] * d_ref[0].astype(jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        scale = se_ref[0, 0]
        o_ref[...] = (x_ref[...].astype(jnp.float32)
                      - scale * acc_ref[...][None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "plan"))
def nova_aggregate_stacked_2d(x, d_stack, weights, theta_eta, *,
                              interpret: bool = False,
                              plan: Optional[TilePlan] = None):
    """x, d_stack: (n_dpu, R, LANE); weights: (n_dpu,), normalized.  Every
    row of x receives the same eq.-11 update (per-DPU global replicas)."""
    n, R, L = x.shape
    assert L == LANE and R % 8 == 0 and d_stack.shape == (n, R, L)
    w = weights.reshape(1, n).astype(jnp.float32)
    se = jnp.asarray(theta_eta, jnp.float32).reshape(1, 1)
    if plan is None:
        rows = R if interpret else row_tile(R, ROWS)
        grid = (R // rows,)
        xspec = pl.BlockSpec((n, rows, LANE), lambda i: (0, i, 0))
        wspec = pl.BlockSpec((1, n), lambda i: (0, 0))
        sspec = pl.BlockSpec((1, 1), lambda i: (0, 0))
        return pl.pallas_call(
            _kernel_stacked,
            grid=grid,
            in_specs=[xspec, xspec, wspec, sspec],
            out_specs=xspec,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret,
        )(x, d_stack, w, se)
    rows, lanes = min(plan.rows, R), plan.lanes
    grid = (pl.cdiv(R, rows), pl.cdiv(L, lanes), n)
    # x/out keep the full stack per block (the replicas all receive the
    # same update); only d is streamed one DPU tile at a time.
    xspec = pl.BlockSpec((n, rows, lanes), lambda i, j, k: (0, i, j))
    dspec = pl.BlockSpec((1, rows, lanes), lambda i, j, k: (k, i, j))
    wspec = pl.BlockSpec(memory_space=pltpu.SMEM)    # whole (1, n), by k
    sspec = pl.BlockSpec((1, 1), lambda i, j, k: (0, 0))
    return pl.pallas_call(
        _kernel_stacked_acc,
        grid=grid,
        in_specs=[xspec, dspec, wspec, sspec],
        out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((rows, lanes), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(
            plan, interpret, ("parallel", "parallel", "arbitrary")),
    )(x, d_stack, w, se)
