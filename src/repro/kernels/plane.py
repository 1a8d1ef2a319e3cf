"""Flat parameter-plane representation: the canonical layout the Pallas
kernels operate on.

A pytree of parameters/gradients is stored ONCE as a padded ``(R, LANE)``
float32 plane plus a static :class:`FlatSpec` (treedef, leaf shapes/dtypes,
offsets) computed at model init and cached per structure.  Every
parameter-sized elementwise op of the CE-FL round — the proximal update
(eqs. 5-6), the FedNova-weighted accumulation (eqs. 8-10), and the
floating aggregation (eq. 11) — runs directly on planes through the
kernels in ``fedprox_update.py`` / ``nova_aggregate.py``; tree views are
materialized only at API boundaries (loss/grad evaluation, ``RoundReport``,
checkpoints, eval).

A tree view never builds a flat view of the whole plane: each leaf is
sliced from its own static row window ``plane[r0:r1]``, and the view's
backward (a ``jax.custom_vjp``) is :meth:`FlatSpec.flatten` of the
cotangent tree, one concatenation into a fresh plane.  Both halves run
under the ``cefl.unflatten`` named scope.

Layout rules:

* ``LANE = 1024`` (multiple of the 128-lane register width) is the fixed
  last dimension.
* ``R`` is the element count rounded up to a whole number of lanes and
  then to a multiple of ``SUBLANE = 8`` rows (the f32 min tile), so any
  plane is directly tileable by the kernels.
* Planes are always float32 — the master copy.  ``unflatten`` casts back
  to the recorded leaf dtypes (bf16 values round-trip exactly because
  f32 ⊃ bf16).
* A leading batch axis is allowed: a ``(G, R, LANE)`` plane holds one row
  per DPU of a homogeneous group (or per DPU of the mesh round).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import tracing

LANE = 1024      # last-dim tile width (multiple of 128)
SUBLANE = 8      # f32 min sublane multiple; every plane has R % 8 == 0


def _row_count(n: int) -> int:
    """Rows needed for n elements, padded to a SUBLANE multiple (>= 8);
    large planes pad to a multiple of 128 rows so the TPU path gets big
    power-of-two row tiles (<= 0.5MB f32 of waste)."""
    r = max(1, -(-n // LANE))
    if r > 256:
        return -(-r // 128) * 128
    return -(-r // SUBLANE) * SUBLANE


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static description of a pytree's flat layout.  Hashable, so it can
    ride through ``jax.jit`` as a static argument or pytree aux data."""
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    offsets: Tuple[int, ...]     # start element of each leaf in the plane
    n: int                       # total real elements
    rows: int                    # padded row count (R)

    @classmethod
    def from_tree(cls, tree) -> "FlatSpec":
        return spec_of(tree)

    # -- conversions ----------------------------------------------------

    def flatten(self, tree) -> jnp.ndarray:
        """Pytree -> (R, LANE) f32 plane (zero padding past ``n``)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        assert treedef == self.treedef, (treedef, self.treedef)
        parts = [jnp.ravel(x).astype(jnp.float32) for x in leaves]
        # the zero padding as the last part: one concatenation writes the
        # plane, where a pad after it would copy the whole plane again
        parts.append(jnp.zeros((self.rows * LANE - self.n,), jnp.float32))
        return jnp.concatenate(parts).reshape(self.rows, LANE)

    def unflatten(self, plane: jnp.ndarray):
        """(R, LANE) plane -> pytree with the original shapes/dtypes; its
        backward is :meth:`flatten` of the cotangent tree."""
        view = _VIEW_CACHE.get(self)
        if view is None:
            view = _VIEW_CACHE[self] = _tree_view(self)
        return view(plane)

    def unflatten_batched(self, planes: jnp.ndarray):
        """(G, R, LANE) -> pytree whose leaves carry the leading G axis."""
        return jax.vmap(self.unflatten)(planes)

    # -- hashing (treedef and dtype objects are hashable) ---------------

    def _key(self):
        return (self.treedef, self.shapes,
                tuple(jnp.dtype(d).name for d in self.dtypes))

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, FlatSpec) and self._key() == other._key()


def _tree_view(spec: FlatSpec):
    """``spec``'s plane -> tree view.  Each leaf comes from the static row
    window that holds it (rows ``off // LANE`` up to ``ceil((off + k) /
    LANE)``), never from a flat view of the whole plane, which a v5e
    relays out whole and XLA may rematerialize at each use.  The backward
    is ``spec.flatten`` of the cotangents, one concatenation into a fresh
    plane, where autodiff's own would pad each leaf's cotangent into
    plane-sized zeros."""

    def tree(plane):
        with jax.named_scope(tracing.UNFLATTEN):
            out = []
            for shape, dtype, off in zip(spec.shapes, spec.dtypes,
                                         spec.offsets):
                k = int(np.prod(shape)) if shape else 1
                r0, r1 = off // LANE, -(-(off + k) // LANE)
                s = off - r0 * LANE
                out.append(plane[r0:r1].reshape(-1)[s:s + k]
                           .reshape(shape).astype(dtype))
            return jax.tree_util.tree_unflatten(spec.treedef, out)

    def forward(plane):
        return tree(plane), None

    def backward(_, cotangent):
        with jax.named_scope(tracing.UNFLATTEN):
            return (spec.flatten(cotangent),)

    view = jax.custom_vjp(tree)
    view.defvjp(forward, backward)
    return view


_SPEC_CACHE: dict = {}
_VIEW_CACHE: dict = {}      # FlatSpec -> its tree view (custom_vjp)


def spec_of(tree) -> FlatSpec:
    """The cached FlatSpec of a pytree — computed once per (treedef,
    shapes, dtypes) structure, at model init in practice."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(tuple(x.shape) for x in leaves)
    dtypes = tuple(jnp.dtype(x.dtype) for x in leaves)
    key = (treedef, shapes, tuple(d.name for d in dtypes))
    spec = _SPEC_CACHE.get(key)
    if spec is None:
        sizes = [int(np.prod(s)) if s else 1 for s in shapes]
        offsets = tuple(int(o) for o in np.cumsum([0] + sizes[:-1]))
        n = int(sum(sizes))
        spec = FlatSpec(treedef=treedef, shapes=shapes, dtypes=dtypes,
                        offsets=offsets, n=n, rows=_row_count(n))
        _SPEC_CACHE[key] = spec
    return spec


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ParamPlane:
    """A pytree's parameters as a flat plane: ``data`` is ``(R, LANE)``
    f32 (or ``(G, R, LANE)`` with a leading batch axis), ``spec`` the
    static layout.  Registered as a pytree (spec is aux data), so planes
    pass through jit/vmap/scan like any array."""
    data: jnp.ndarray
    spec: FlatSpec

    # -- pytree protocol ------------------------------------------------

    def tree_flatten(self):
        return (self.data,), self.spec

    @classmethod
    def tree_unflatten(cls, spec, children):
        return cls(data=children[0], spec=spec)

    # -- constructors / views -------------------------------------------

    @classmethod
    def from_tree(cls, tree) -> "ParamPlane":
        if isinstance(tree, ParamPlane):
            return tree
        spec = spec_of(tree)
        return cls(data=spec.flatten(tree), spec=spec)

    def to_tree(self):
        if self.data.ndim == 2:
            return self.spec.unflatten(self.data)
        return self.spec.unflatten_batched(self.data)

    # -- conveniences ---------------------------------------------------

    @property
    def batched(self) -> bool:
        return self.data.ndim == 3

    def __getitem__(self, i) -> "ParamPlane":
        return ParamPlane(data=self.data[i], spec=self.spec)

    def with_data(self, data) -> "ParamPlane":
        return ParamPlane(data=data, spec=self.spec)

    def broadcast(self, g: int) -> "ParamPlane":
        """(R, LANE) -> (g, R, LANE) view (no copy until mutated)."""
        assert self.data.ndim == 2
        return ParamPlane(
            data=jnp.broadcast_to(self.data[None], (g,) + self.data.shape),
            spec=self.spec)


def as_plane(params) -> ParamPlane:
    """Coerce a pytree or ParamPlane to a ParamPlane."""
    return ParamPlane.from_tree(params)


def as_tree(params):
    """Coerce a ParamPlane or pytree to a pytree (API-boundary helper)."""
    return params.to_tree() if isinstance(params, ParamPlane) else params
