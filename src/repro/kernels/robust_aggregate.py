"""Pallas kernel: coordinate-wise robust aggregation (byzantine counter).

    x_new = x - theta * eta * reduce(d_stack)

where ``reduce`` is the coordinate-wise k-trimmed mean or median over the
DPU axis — the classical byzantine-robust replacements for the weighted
eq.-11 sum (Yin et al. 2018).  Unlike ``nova_aggregate``, the reduction
is UNWEIGHTED: dataset-size weights are exactly what a malicious client
can inflate to dominate the average, so the robust path ignores them.

Kernel shape: the reduce needs every DPU's value of a coordinate at once
(a sort along the stack axis), so the DPU axis can never be a grid
dimension the way the nova grid-accumulation streams it.  Instead the
grid tiles the (rows, lanes) plane and each step loads the full
``(n_dpu, rows, lanes)`` d block and sorts it with the static odd-even
transposition network of ``ref.sort_network`` (``sort`` has no Pallas
TPU lowering; the network is O(n_dpu^2) compare-exchanges) — fine for
the n_dpu counts a robust quorum makes sense at (tens), and the
:class:`~repro.kernels.tiling.TilePlan` budget accounts the n-fold
resident block (``ops.robust_aggregate_plane`` passes
``n_operands = n + 3``).  Like the PR-7 tiled grids, the compiled form
is parity-tested through the Pallas interpreter; real-hardware runs go
through the same ``ops.py`` dispatch.

``k``/``median`` are static (they shape the sort-trim expression); the
trim fraction is resolved to ``k`` once in ``ops.robust_aggregate_plane``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fedprox_update import _compiler_params, row_tile
from repro.kernels.ref import robust_reduce_ref
from repro.kernels.tiling import TilePlan

LANE = 1024
ROWS = 128


def _kernel(x_ref, d_ref, se_ref, o_ref, *, k: int, median: bool):
    d = d_ref[...].astype(jnp.float32)        # (n_dpu, rows, lanes)
    red = robust_reduce_ref(d, k=k, median=median)
    o_ref[...] = (x_ref[...].astype(jnp.float32)
                  - se_ref[0, 0] * red).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("k", "median", "interpret", "plan"))
def robust_aggregate_2d(x, d_stack, theta_eta, *, k: int = 0,
                        median: bool = False, interpret: bool = False,
                        plan: Optional[TilePlan] = None):
    """x: (R, LANE); d_stack: (n_dpu, R, LANE).  Returns
    x - theta_eta * trimmed_mean/median(d_stack, axis=0)."""
    R, L = x.shape
    n = d_stack.shape[0]
    assert L == LANE and R % 8 == 0 and d_stack.shape == (n, R, L)
    se = jnp.asarray(theta_eta, jnp.float32).reshape(1, 1)
    body = functools.partial(_kernel, k=k, median=median)
    if plan is None:
        rows = R if interpret else row_tile(R, ROWS)
        grid = (R // rows,)
        xspec = pl.BlockSpec((rows, LANE), lambda i: (i, 0))
        dspec = pl.BlockSpec((n, rows, LANE), lambda i: (0, i, 0))
        sspec = pl.BlockSpec((1, 1), lambda i: (0, 0))
        return pl.pallas_call(
            body,
            grid=grid,
            in_specs=[xspec, dspec, sspec],
            out_specs=xspec,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret,
        )(x, d_stack, se)
    rows, lanes = min(plan.rows, R), plan.lanes
    grid = (pl.cdiv(R, rows), pl.cdiv(L, lanes))
    xspec = pl.BlockSpec((rows, lanes), lambda i, j: (i, j))
    dspec = pl.BlockSpec((n, rows, lanes), lambda i, j: (0, i, j))
    sspec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[xspec, dspec, sspec],
        out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(
            plan, interpret, ("parallel", "parallel")),
    )(x, d_stack, se)
