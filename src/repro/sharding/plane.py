"""Sharded parameter-plane execution over a ``('dpu', 'rows')`` device
mesh — the multi-device form of the fused CE-FL round.

Mesh axes:

* ``'dpu'`` — data parallelism over the per-DPU leading axis of stacked
  ``(G, R, LANE)`` planes and their minibatch index/weight arrays: each
  device trains its own slice of the DPU group (eqs. 5-10) and the eq.-11
  aggregation combines the per-device ``d_i`` blocks.
* ``'rows'`` — FSDP-style sharding of the ``(R, LANE)`` master/anchor
  plane rows (the LM-track layout, built on ``sharding/specs.py``):
  parameters are stored row-sharded, all-gathered just-in-time for the
  loss/grad evaluation, and each device keeps only its own row block of
  the gradient and optimizer state.

Divisibility follows the ``sanitize_spec`` rule: an axis whose size does
not divide the corresponding plane dim degrades to replication for that
dim.  Rows are counted in ``SUBLANE = 8`` tiles, because the TPU kernels
need every shard to hold whole tiles: the rows axis degrades unless it
divides ``R / 8`` (the 176-row paper classifier splits 2 ways, not 4),
and the DPU axis degrades whenever the live group size ``G`` is ragged.

Bit-exactness contract (the ``shard-parity`` CI lane): with the default
``reduce="exact"`` mode, every sharded op and the sharded fused round are
**bitwise identical** to the single-device path.  The eq.-10/11 weighted
reduction all-gathers the per-DPU ``d_i`` stack over ``'dpu'`` and runs
the SAME local reduction (same contracted size, same order) on every
device — redundant compute, zero reduction reordering.  ``reduce="psum"``
is the scale mode the paper-sized meshes want: each device accumulates
its local partial weighted sum and one ``psum`` combines them — one
G/n_dpu-sized reduction per device instead of G, but float addition
reorders, so it is allclose- (not bitwise-) equal and stays opt-in.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import fedprox
from repro.kernels import ops
from repro.kernels.plane import LANE, SUBLANE, as_plane
from repro.sharding.specs import sanitize_spec

DPU_AXIS = "dpu"
ROW_AXIS = "rows"

_MESH_CACHE: dict = {}


def plane_mesh(shape: Optional[Tuple[int, int]] = None) -> Mesh:
    """The ``('dpu', 'rows')`` mesh for a device-count split ``shape``
    (cached per shape so jit caches keyed on the mesh stay warm).  With
    ``shape=None`` all devices go to the DPU axis."""
    devices = jax.devices()
    if shape is None:
        shape = (len(devices), 1)
    d, r = int(shape[0]), int(shape[1])
    if d < 1 or r < 1 or d * r > len(devices):
        raise ValueError(
            f"mesh_shape {shape} needs {d * r} devices, "
            f"have {len(devices)} (hint: XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N for a virtual mesh)")
    key = (d, r, tuple(id(dev) for dev in devices[:d * r]))
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        mesh = Mesh(np.asarray(devices[:d * r]).reshape(d, r),
                    (DPU_AXIS, ROW_AXIS))
        _MESH_CACHE[key] = mesh
    return mesh


def plane_axes(mesh: Mesh, n_lead: Optional[int], n_rows: int):
    """(dpu_axis_or_None, rows_axis_or_None) after the sanitize_spec
    divisibility degradation for an (n_lead, n_rows, LANE) stack.  Rows
    split only into whole SUBLANE tiles: the TPU kernels need every
    shard's row count to be a multiple of 8."""
    spec = sanitize_spec(P(DPU_AXIS, ROW_AXIS, None),
                         (n_lead if n_lead is not None else 0,
                          n_rows // SUBLANE, LANE), mesh)
    g_ax = spec[0] if n_lead is not None else None
    return g_ax, spec[1]


# ------------------------------------------------- sharded plane ops -----
#
# The three round kernels, data-parallel over 'dpu' / row-sharded over
# 'rows'.  Each is a thin shard_map around the single-device ops.* entry
# point, so backend dispatch (cpu/interpret/gpu/tpu) stays in ONE place.

@functools.lru_cache(maxsize=64)
def _fedprox_accum_fn(mesh: Mesh, backend: str):
    def fn(x, g, anchor, acc, coef, active, eta, mu):
        g_ax, r_ax = plane_axes(mesh, x.shape[0], x.shape[1])
        stacked = P(g_ax, r_ax, None)
        anchor_spec = stacked if anchor.ndim == 3 else P(r_ax, None)

        def body(x_l, g_l, an_l, acc_l, coef_l, act_l, eta_s, mu_s):
            return ops.fedprox_accum_plane(x_l, g_l, an_l, acc_l, coef_l,
                                           act_l, eta_s, mu_s,
                                           backend=backend)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(stacked, stacked, anchor_spec, stacked,
                      P(g_ax), P(g_ax), P(), P()),
            out_specs=(stacked, stacked), check_vma=False)(
                x, g, anchor, acc, coef, active, eta, mu)

    return jax.jit(fn)


def fedprox_accum_plane_sharded(x, g, anchor, acc, coef, active, eta, mu, *,
                                mesh: Mesh, backend: Optional[str] = None):
    """Sharded batched proximal step + eq.-10 accumulation: purely
    elementwise over (G, R, LANE), so any sharding is bitwise exact."""
    b = ops.resolve_backend(backend)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    return _fedprox_accum_fn(mesh, b)(x, g, anchor, acc, f32(coef),
                                      f32(active), f32(eta), f32(mu))


@functools.lru_cache(maxsize=64)
def _nova_fn(mesh: Mesh, backend: str, reduce: str):
    def fn(x, d_stack, weights, theta_eta):
        g_ax, r_ax = plane_axes(mesh, d_stack.shape[0], x.shape[0])

        def body(x_l, d_l, w_l, te):
            if reduce == "psum" and g_ax is not None:
                # local partial weighted sum + one psum over 'dpu'
                # (eq. 10/11 at scale; reduction reorders -> allclose)
                part = jnp.einsum("g,grl->rl", w_l, d_l)
                return x_l - te * jax.lax.psum(part, DPU_AXIS)
            if g_ax is not None:
                d_l = jax.lax.all_gather(d_l, DPU_AXIS, axis=0, tiled=True)
                w_l = jax.lax.all_gather(w_l, DPU_AXIS, tiled=True)
            return ops.nova_aggregate_plane(x_l, d_l, w_l, te,
                                            backend=backend)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(r_ax, None), P(g_ax, r_ax, None), P(g_ax), P()),
            out_specs=P(r_ax, None), check_vma=False)(
                x, d_stack, weights, theta_eta)

    return jax.jit(fn)


def nova_aggregate_plane_sharded(x, d_stack, weights, theta_eta, *,
                                 mesh: Mesh, reduce: str = "exact",
                                 backend: Optional[str] = None):
    """Sharded eq.-11 aggregation.  ``weights`` already normalized (the
    plane-level contract).  ``reduce="exact"`` (default) all-gathers the
    d-stack over 'dpu' and reduces locally — bitwise equal to the
    single-device op; ``reduce="psum"`` combines local partials with one
    psum (allclose)."""
    if reduce not in ("exact", "psum"):
        raise ValueError(f"unknown reduce mode {reduce!r}")
    b = ops.resolve_backend(backend)
    return _nova_fn(mesh, b, reduce)(
        x, d_stack, jnp.asarray(weights, jnp.float32),
        jnp.asarray(theta_eta, jnp.float32))


@functools.lru_cache(maxsize=64)
def _robust_fn(mesh: Mesh, backend: str, mode: str, trim_frac: float):
    def fn(x, d_stack, theta_eta):
        g_ax, r_ax = plane_axes(mesh, d_stack.shape[0], x.shape[0])

        def body(x_l, d_l, te):
            # the coordinate-wise sort needs the full DPU stack: gather
            # over 'dpu', reduce each device's own row block locally
            if g_ax is not None:
                d_l = jax.lax.all_gather(d_l, DPU_AXIS, axis=0, tiled=True)
            return ops.robust_aggregate_plane(x_l, d_l, te, mode=mode,
                                              trim_frac=trim_frac,
                                              backend=backend)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(r_ax, None), P(g_ax, r_ax, None), P()),
            out_specs=P(r_ax, None), check_vma=False)(x, d_stack, theta_eta)

    return jax.jit(fn)


def robust_aggregate_plane_sharded(x, d_stack, theta_eta, *, mesh: Mesh,
                                   mode: str = "trimmed_mean",
                                   trim_frac: float = 0.1,
                                   backend: Optional[str] = None):
    """Sharded byzantine-robust eq.-11: all-gather the d-stack over
    'dpu', per-coordinate trimmed-mean/median on own rows — bitwise equal
    to the single-device op."""
    b = ops.resolve_backend(backend)
    return _robust_fn(mesh, b, mode, float(trim_frac))(
        x, d_stack, jnp.asarray(theta_eta, jnp.float32))


# ------------------------------------------------ sharded fused round -----

_SHARDED_ROUND_CACHE: dict = {}


def _sharded_round_fn(loss_fn, spec, mesh: Mesh, kernel_backend: str,
                      eval_fn=None, reduce: str = "exact"):
    """The shard_map'd twin of ``fedprox._plane_round_fn``: one jitted
    program for a homogeneous-group round — gamma-step training scan,
    eq.-10 normalization, eq.-11 aggregation, optional fused eval — with
    the (G, R, LANE) stack split over 'dpu' and plane rows over 'rows'.

    Row sharding is FSDP-shaped: params/acc/gradient state live row-
    sharded; the full plane is all-gathered per local step only for the
    loss/grad evaluation, and each device slices back its own row block
    of the gradient.  Losses are computed redundantly per 'rows' member
    (identical values).  The aggregate is returned row-sharded and
    replicated over 'dpu' — bitwise identical to the single-device
    ``round_run`` under ``reduce="exact"``.
    """
    backend = ops.resolve_backend(kernel_backend)
    key = (loss_fn, spec, mesh, backend, eval_fn, reduce)
    if key in _SHARDED_ROUND_CACHE:
        return _SHARDED_ROUND_CACHE[key]

    def plane_loss(pp, batch, w):
        return loss_fn(spec.unflatten(pp), batch, w)

    vgrad = jax.vmap(jax.value_and_grad(plane_loss))
    take = jax.vmap(lambda xd, ik: xd[ik])

    def round_run(p0, anchor, data_stack, idx, weights, a, eta, mu,
                  w_abs, theta_eta):
        G = p0.shape[0]
        g_ax, r_ax = plane_axes(mesh, G, spec.rows)
        stacked = P(g_ax, r_ax, None)
        master = P(r_ax, None)
        per_dpu = P(g_ax)
        step_arr = P(None, g_ax, None)        # (gamma, G, bucket)
        data_specs = jax.tree_util.tree_map(lambda _: per_dpu, data_stack)

        def shard_body(p0_l, anchor_l, data_l, idx_l, w_l, a_l, eta_s,
                       mu_s, wabs_l, te_s):
            R_loc = p0_l.shape[1]

            def gather_rows(x, axis):
                if r_ax is None:
                    return x
                return jax.lax.all_gather(x, ROW_AXIS, axis=axis,
                                          tiled=True)

            def my_rows(x, axis):
                if r_ax is None:
                    return x
                start = jax.lax.axis_index(ROW_AXIS) * R_loc
                return jax.lax.dynamic_slice_in_dim(x, start, R_loc, axis)

            ones = jnp.ones((p0_l.shape[0],), jnp.float32)
            acc0 = jnp.zeros_like(p0_l)

            def body(carry, inp):
                p, acc = carry
                idx_k, wts_k, a_k = inp
                batch_k = jax.tree_util.tree_map(
                    lambda xd: take(xd, idx_k), data_l)
                losses, g_full = vgrad(gather_rows(p, 1), batch_k, wts_k)
                p, acc = ops.fedprox_accum_plane(
                    p, my_rows(g_full, 1), anchor_l, acc, a_k * ones,
                    ones, eta_s, mu_s, backend=backend)
                return (p, acc), losses

            (_p, acc), losses = jax.lax.scan(
                body, (p0_l, acc0), (idx_l, w_l, a_l))
            d = acc / jnp.sum(a_l)
            if reduce == "psum" and g_ax is not None:
                s = jnp.sum(jax.lax.all_gather(wabs_l, DPU_AXIS,
                                               tiled=True))
                part = jnp.einsum("g,grl->rl", wabs_l / s, d)
                new = anchor_l - te_s * jax.lax.psum(part, DPU_AXIS)
            else:
                if g_ax is not None:
                    d = jax.lax.all_gather(d, DPU_AXIS, axis=0, tiled=True)
                    wabs_l = jax.lax.all_gather(wabs_l, DPU_AXIS,
                                                tiled=True)
                w = wabs_l / jnp.sum(wabs_l)   # the single normalization
                new = ops.nova_aggregate_plane(anchor_l, d, w, te_s,
                                               backend=backend)
            if eval_fn is None:
                return new, losses, ()
            # eval on the gathered full plane, redundantly per shard —
            # same compute graph as single-device, so bitwise identical
            return new, losses, eval_fn(spec.unflatten(gather_rows(new, 0)))

        return jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(stacked, master, data_specs, step_arr, step_arr,
                      P(None), P(), P(), per_dpu, P()),
            out_specs=(master, P(None, g_ax),
                       () if eval_fn is None else P()),
            check_vma=False)(
                p0, anchor, data_stack, idx, weights, a, eta, mu,
                w_abs, theta_eta)

    _SHARDED_ROUND_CACHE[key] = jax.jit(round_run)
    return _SHARDED_ROUND_CACHE[key]


def local_round_plane_sharded(params, loss_fn, datasets, *, gamma: int,
                              m_frac: float, eta: float, mu: float, keys,
                              theta: float, mesh: Mesh,
                              kernel_backend: str = "auto", eval_fn=None,
                              reduce: str = "exact"):
    """Drop-in sharded twin of :func:`fedprox.local_round_plane` — same
    host staging (identical PRNG draws), same return contract, with the
    device program shard_map'd over ``mesh``.  ``reduce="exact"`` is
    bitwise equal to the single-device round."""
    if reduce not in ("exact", "psum"):
        raise ValueError(f"unknown reduce mode {reduce!r}")
    plane = as_plane(params)
    spec = plane.spec
    G = len(datasets)
    p0 = plane.broadcast(G).data
    Ds = [jax.tree_util.tree_leaves(d)[0].shape[0] for d in datasets]
    bszs = [fedprox.batch_size(D, m_frac) for D in Ds]
    bucket = fedprox._bucket(max(bszs))
    assert all(fedprox._bucket(b) == bucket for b in bszs), \
        "grouping must put same-bucket DPUs together"
    a = fedprox.a_coefficients(gamma, eta, mu)
    step_keys = jax.vmap(lambda k: jax.random.split(k, gamma))(
        jnp.stack(keys))
    data_stack, idx, weights = fedprox._stage_group_batches(
        datasets, step_keys, Ds, bucket, gamma, m_frac)
    run = _sharded_round_fn(loss_fn, spec, mesh, kernel_backend, eval_fn,
                            reduce)
    new_data, losses, acc = run(
        p0, plane.data, data_stack, idx, weights, a,
        jnp.asarray(eta, jnp.float32), jnp.asarray(mu, jnp.float32),
        jnp.asarray(Ds, jnp.float32),
        jnp.asarray(theta * eta, jnp.float32))
    mean_loss = np.asarray(losses).mean(axis=0)
    return (plane.with_data(new_data), mean_loss,
            None if eval_fn is None else float(acc))


# ---------------------------------------------------- trace contracts --

from repro.analysis.jaxpr.contracts import Program, contract  # noqa: E402


def _audit_nova_args(mesh: Mesh):
    # 16 rows = 2 SUBLANE tiles, so the (4, 2) mesh row-shards the plane
    x = jnp.zeros((16, 1024), jnp.float32)
    d_stack = jnp.ones((4, 16, 1024), jnp.float32)
    assert plane_axes(mesh, 4, 16) == (DPU_AXIS, ROW_AXIS)
    weights = jnp.full((4,), 0.25, jnp.float32)
    return (x, d_stack, weights, jnp.asarray(0.05, jnp.float32))


@contract(
    "nova_sharded_exact",
    min_devices=8,
    collectives={"all_gather": 2, "psum": 0},
)
def _nova_exact_contract():
    """reduce="exact" eq.-11: gathers the d-stack + weights over 'dpu'
    and reduces locally — bitwise path, so psum MUST NOT appear."""
    mesh = plane_mesh((4, 2))
    return Program(fn=_nova_fn(mesh, "cpu", "exact"),
                   args=_audit_nova_args(mesh))


@contract(
    "nova_sharded_psum",
    min_devices=8,
    collectives={"psum": 1, "all_gather": 0},
)
def _nova_psum_contract():
    """reduce="psum" eq.-11: local partial weighted sums combined by
    EXACTLY ONE psum over 'dpu' (allclose path)."""
    mesh = plane_mesh((4, 2))
    return Program(fn=_nova_fn(mesh, "cpu", "psum"),
                   args=_audit_nova_args(mesh))


def _audit_sharded_round_program(reduce: str) -> Program:
    from repro.core import fedprox as _fp
    mesh = plane_mesh((4, 2))
    # 8,208 params -> a 16-row plane (2 tiles): rows really split 2 ways,
    # so the FSDP gather_rows/my_rows path is traced
    spec, args = _fp._audit_round_args(n_group=4, n_features=512,
                                       n_classes=16)
    assert plane_axes(mesh, 4, spec.rows) == (DPU_AXIS, ROW_AXIS)
    fn = _sharded_round_fn(_fp._audit_loss, spec, mesh, "cpu",
                           reduce=reduce)
    return Program(fn=fn, args=args)


@contract(
    "sharded_round_exact",
    min_devices=8,
    collectives={"psum": 0, "all_gather": "1+"},
)
def _sharded_round_exact_contract():
    """FSDP-shaped sharded round, reduce="exact": row/dpu all-gathers
    only — the bitwise twin of the fused single-device round."""
    return _audit_sharded_round_program("exact")


@contract(
    "sharded_round_psum",
    min_devices=8,
    collectives={"psum": 1, "all_gather": "1+"},
)
def _sharded_round_psum_contract():
    """Sharded round, reduce="psum": exactly one eq.-11 psum over 'dpu'
    on top of the FSDP row gathers."""
    return _audit_sharded_round_program("psum")
