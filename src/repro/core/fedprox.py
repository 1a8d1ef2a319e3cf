"""FedProx-style heterogeneous local training at a DPU (paper Sec. II-D).

Implements eqs. (5)-(10): gamma_i local SGD steps on the proximal loss
g_i(x, x^t) = F_i(x) + (mu/2)||x - x^t||^2, with mini-batch ratio m_i, and
the FedNova-normalized accumulated gradient

    d_i = (1/||a_i||_1) sum_l a_{i,l} grad F_i(x^{t,l}),
    a_{i,l} = (1 - eta*mu)^(gamma_i - 1 - l).

``local_train`` is the simulation-level entry point (one DPU, its own
dataset); the mesh-native vectorized round lives in repro.core.round_step.

Backends (``backend=`` on both entry points):

* ``"plane"`` (default, the hot path): parameters/gradients live on the
  flat ``(G, R, LANE)`` parameter plane (``kernels.plane``).  All gamma
  local iterations of a whole homogeneous DPU group run as ONE jitted
  ``lax.scan`` whose per-step body is a vmapped loss/grad evaluation plus
  a single fused Pallas launch (``fedprox_accum_2d``) doing the proximal
  update AND the eq.-10 accumulation — no per-leaf tree_map chains, no
  per-step host sync.
* ``"tree"`` — the pre-plane per-leaf reference path, kept for
  equivalence tests and the tree-vs-plane benchmark.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.plane import ParamPlane, as_plane
from repro.utils import tracing


def a_coefficients(gamma: int, eta: float, mu: float) -> jnp.ndarray:
    """a_{i,l} for l = 0..gamma-1 (eq. 8)."""
    ell = jnp.arange(gamma, dtype=jnp.float32)
    return (1.0 - eta * mu) ** (gamma - 1.0 - ell)


def a_norms(gamma, eta, mu):
    a = a_coefficients(gamma, eta, mu)
    return jnp.sum(a), jnp.sum(a * a)


@dataclasses.dataclass
class LocalResult:
    params: object        # x_i^{(t, gamma_i)} (pytree or ParamPlane)
    d_i: object           # normalized accumulated gradient (same kind)
    num_examples: int     # D_i^{(t)}
    gamma: int
    sgd_flops: float      # processed examples * gamma (for cost models)
    loss: float = float("nan")   # mean mini-batch loss over the gamma steps


def batch_size(num_examples: int, m_frac: float) -> int:
    """clamp(round(m_frac * D), 1, D) — the one mini-batch size rule
    (0 for a degenerate D == 0 dataset)."""
    if num_examples <= 0:
        return 0
    return max(1, min(num_examples, int(round(m_frac * num_examples))))


def sample_minibatch(key, num_examples: int, m_frac: float):
    """Uniform without-replacement mini-batch indices of size
    ``batch_size(D, m_frac)``; empty for a degenerate D == 0 dataset
    (offloading splits can leave a DPU with nothing)."""
    bsz = batch_size(num_examples, m_frac)
    if bsz == 0:
        return jnp.zeros((0,), jnp.int32)
    return jax.random.choice(key, num_examples, (bsz,), replace=False)


def _bucket(n: int) -> int:
    """Round batch sizes up to a power of two so jitted steps are reused
    across rounds with varying dataset sizes."""
    b = 1
    while b < n:
        b *= 2
    return b


# ------------------------------------------------- plane hot path -----

_PLANE_TRAIN_CACHE = {}
_PLANE_ROUND_CACHE = {}


def _plane_train_core(loss_fn, spec, batched_anchor: bool, backend: str):
    """The (untraced) full gamma-step local-training loop of a DPU group
    on parameter planes.  The tree view needed by ``loss_fn`` is a
    compile-time slice/reshape of the plane inside the traced graph (its
    transpose re-flattens the gradient) — there is no host-level
    flatten/unflatten anywhere in the loop, and the per-step mini-batch
    GATHER happens inside the scan too: the group's datasets arrive as
    one stacked (G, Db, ...) device tree plus (gamma, G, bucket) index
    arrays, so rounds cost zero per-DPU host gathers.

    ``batched_anchor``: the anchor is (G, R, LANE) — one per element —
    instead of one (R, LANE) plane shared by the group.  This is the
    multi-run form (``local_train_multi``): elements from different
    seeded runs, each proximal to its own global model, in one scan.
    """
    del batched_anchor  # the fused kernel broadcasts either anchor form

    def plane_loss(pp, batch, w):
        return loss_fn(spec.unflatten(pp), batch, w)

    vgrad = jax.vmap(jax.value_and_grad(plane_loss))
    take = jax.vmap(lambda xd, ik: xd[ik])     # per-DPU in-jit gather

    def run(p_stack, anchor, data_stack, idx, weights, a, eta, mu):
        """p_stack: (G, R, LANE); anchor: (R, LANE) shared or
        (G, R, LANE) per-element; ``data_stack`` leaves (G, Db, ...);
        idx: (gamma, G, bucket) i32; weights (gamma, G, bucket);
        a: (gamma,) FedNova coefficients."""
        G = p_stack.shape[0]
        ones = jnp.ones((G,), jnp.float32)
        acc0 = jnp.zeros_like(p_stack)

        def body(carry, inp):
            p, acc = carry
            idx_k, w_k, a_k = inp
            batch_k = jax.tree_util.tree_map(
                lambda xd: take(xd, idx_k), data_stack)
            losses, g = vgrad(p, batch_k, w_k)
            p, acc = ops.fedprox_accum_plane(
                p, g, anchor, acc, a_k * ones, ones, eta, mu,
                backend=backend)
            return (p, acc), losses

        (p, acc), losses = jax.lax.scan(
            body, (p_stack, acc0), (idx, weights, a))
        return p, acc, losses      # losses: (gamma, G)

    return run


def _plane_train_fn(loss_fn, spec, batched_anchor: bool = False,
                    kernel_backend: str = "auto"):
    """Jitted :func:`_plane_train_core` (cached per loss/spec/backend —
    ``"auto"`` resolves against the process default at build time)."""
    backend = ops.resolve_backend(kernel_backend)
    key = (loss_fn, spec, batched_anchor, backend)
    if key not in _PLANE_TRAIN_CACHE:
        _PLANE_TRAIN_CACHE[key] = jax.jit(
            _plane_train_core(loss_fn, spec, batched_anchor, backend))
    return _PLANE_TRAIN_CACHE[key]


def _plane_round_fn(loss_fn, spec, kernel_backend: str = "auto",
                    eval_fn=None):
    """ONE jitted program for a whole homogeneous-group round: the full
    gamma-step training scan, the eq.-10 normalization d = acc/||a||_1,
    the eq.-11 aggregation, and (when ``eval_fn`` is given) the eval
    forward pass on the aggregated model — train+eval in a single jit
    per group, so an eval round costs zero extra dispatches beyond the
    round itself.  Returns (new_plane_data, losses, acc_or_())."""
    backend = ops.resolve_backend(kernel_backend)
    key = (loss_fn, spec, backend, eval_fn)
    if key not in _PLANE_ROUND_CACHE:
        run = _plane_train_core(loss_fn, spec, False, backend)

        def round_run(p_stack, anchor, data_stack, idx, weights, a,
                      eta, mu, w_abs, theta_eta):
            _p, acc, losses = run(p_stack, anchor, data_stack, idx,
                                  weights, a, eta, mu)
            d = acc / jnp.sum(a)               # == host acc/float(sum(a))
            w = w_abs / jnp.sum(w_abs)         # the single normalization
            new = ops.nova_aggregate_plane(anchor, d, w, theta_eta,
                                           backend=backend)
            if eval_fn is None:
                return new, losses, ()
            return new, losses, eval_fn(spec.unflatten(new))

        _PLANE_ROUND_CACHE[key] = jax.jit(round_run)
    return _PLANE_ROUND_CACHE[key]


def local_round_plane(params, loss_fn: Callable, datasets, *, gamma: int,
                      m_frac: float, eta: float, mu: float, keys,
                      theta: float, kernel_backend: str = "auto",
                      eval_fn=None):
    """One FUSED CE-FL round for a homogeneous-(gamma, m) DPU group.

    The gamma-step training scan, the eq.-10 normalization, the eq.-11
    aggregation at ``theta``, and (optionally) the eval forward pass on
    the aggregated model run as ONE jitted program — semantically equal
    to ``local_train_batched`` + ``aggregation.aggregate`` + ``eval_fn``
    but with zero intermediate host round-trips.  The engine's
    :class:`~repro.core.engine.SimExecutor` routes single-group plane
    rounds here.

    Returns ``(new_plane, per_dpu_mean_losses, acc)`` where the losses
    are a host ``(G,)`` array (mean over the gamma steps, the
    ``LocalResult.loss`` convention) and ``acc`` is None unless
    ``eval_fn`` was given.
    """
    plane = as_plane(params)
    spec = plane.spec
    G = len(datasets)
    Ds = [jax.tree_util.tree_leaves(d)[0].shape[0] for d in datasets]
    bszs = [batch_size(D, m_frac) for D in Ds]
    bucket = _bucket(max(bszs))
    assert all(_bucket(b) == bucket for b in bszs), \
        "grouping must put same-bucket DPUs together"
    with tracing.span("group", G=G, gamma=gamma, bucket=bucket):
        p0 = plane.broadcast(G).data
        a = a_coefficients(gamma, eta, mu)
        step_keys = jax.vmap(lambda k: jax.random.split(k, gamma))(
            jnp.stack(keys))
        data_stack, idx, weights = _stage_group_batches(
            datasets, step_keys, Ds, bucket, gamma, m_frac)
        run = _plane_round_fn(loss_fn, spec, kernel_backend, eval_fn)
        with tracing.span("group_program"):
            new_data, losses, acc = run(
                p0, plane.data, data_stack, idx, weights, a,
                jnp.asarray(eta, jnp.float32), jnp.asarray(mu, jnp.float32),
                jnp.asarray(Ds, jnp.float32),
                jnp.asarray(theta * eta, jnp.float32))
        # (G,) — one sync
        mean_loss = tracing.sync(losses, "group_losses").mean(axis=0)
        return (plane.with_data(new_data), mean_loss,
                None if eval_fn is None else float(tracing.sync(acc, "eval")))


@functools.lru_cache(maxsize=512)
def _choice_all_steps(num_examples: int, bsz: int):
    """Jitted vmapped without-replacement choice: (gamma, 2) step keys ->
    (gamma, bsz) indices.  Identical draws to per-step sample_minibatch
    calls (jax.random is elementwise in the key), but ONE dispatch per DPU
    per round instead of gamma."""
    return jax.jit(jax.vmap(
        lambda k: jax.random.choice(k, num_examples, (bsz,),
                                    replace=False)))


def _stage_group_batches(datasets, step_keys, Ds, bucket, gamma, m_frac):
    """Stage a group's round data DEVICE-SIDE: datasets right-padded to a
    shared power-of-two example bucket and stacked to (G, Db, ...), plus
    (gamma, G, bucket) mini-batch index/weight arrays (same PRNG streams
    as the sequential path).  The per-step gather then happens inside the
    training scan — unlike the old host-side pre-gather, nothing here
    synchronizes on a device value, so staging costs O(G) async dispatches
    instead of O(G) blocking round-trips (the dominant term of the old
    ``sim_round_plane_us`` profile).

    Runs in a ``cefl/stage_batches`` span that counts the bytes it
    transfers (``h2d_bytes``) and the eager pads, stacks and
    ``_choice_all_steps`` calls it issues (``dispatches``)."""
    G = len(datasets)
    Db = _bucket(max(Ds))
    with tracing.span("stage_batches") as sp:
        data_stack = jax.tree_util.tree_map(
            lambda *xs: jnp.stack([
                jnp.pad(x, [(0, Db - x.shape[0])] + [(0, 0)] * (x.ndim - 1))
                for x in xs]), *datasets)
        idx_cols = []
        wts = np.zeros((gamma, G, bucket), np.float32)
        for j in range(G):
            bsz = batch_size(Ds[j], m_frac)
            idx = _choice_all_steps(Ds[j], bsz)(step_keys[j])  # (gamma, bsz)
            idx_cols.append(jnp.pad(idx, ((0, 0), (0, bucket - bsz))))
            wts[:, j, :bsz] = 1.0
        idx_all = jnp.stack(idx_cols, axis=1).astype(jnp.int32)
        weights = jnp.asarray(wts)
        if tracing.enabled():
            leaves = len(jax.tree_util.tree_leaves(datasets[0]))
            sp.set(h2d_bytes=tracing.nbytes(weights),
                   dispatches=leaves * (G + 1) + 2 * G + 1)
    return data_stack, idx_all, weights


def _local_train_batched_plane(params, loss_fn, datasets, *, gamma, m_frac,
                               eta, mu, keys, keep_planes=False,
                               anchors=None, kernel_backend="auto"):
    G = len(datasets)
    with tracing.span("group", G=G, gamma=gamma) as sp:
        if anchors is None:
            plane = as_plane(params)
            spec = plane.spec
            p0 = plane.broadcast(G).data
            anchor = plane.data
        else:
            planes = [as_plane(a) for a in anchors]
            spec = planes[0].spec
            assert all(p.spec == spec for p in planes), \
                "multi-run groups must share one FlatSpec (same model)"
            p0 = jnp.stack([p.data for p in planes], axis=0)
            anchor = p0
        Ds = [jax.tree_util.tree_leaves(d)[0].shape[0] for d in datasets]
        bszs = [batch_size(D, m_frac) for D in Ds]
        bucket = _bucket(max(bszs))
        assert all(_bucket(b) == bucket for b in bszs), \
            "grouping must put same-bucket DPUs together"
        sp.set(bucket=bucket)
        a = a_coefficients(gamma, eta, mu)
        a1 = float(tracing.sync(jnp.sum(a), "a_norm"))
        # one vmapped split for the whole group (same per-DPU streams as
        # sequential `jax.random.split(k, gamma)` calls)
        step_keys = jax.vmap(lambda k: jax.random.split(k, gamma))(
            jnp.stack(keys))
        data_stack, idx, weights = _stage_group_batches(
            datasets, step_keys, Ds, bucket, gamma, m_frac)
        run = _plane_train_fn(loss_fn, spec,
                              batched_anchor=anchors is not None,
                              kernel_backend=kernel_backend)
        with tracing.span("group_program"):
            p_stack, acc, losses = run(p0, anchor,
                                       data_stack, idx, weights, a,
                                       jnp.asarray(eta, jnp.float32),
                                       jnp.asarray(mu, jnp.float32))
        d_stack = acc / a1
        mean_loss = tracing.sync(losses, "group_losses").mean(axis=0)  # (G,)

        def view(stack, j):
            p = ParamPlane(data=stack[j], spec=spec)
            return p if keep_planes else p.to_tree()

        return [LocalResult(
            params=view(p_stack, j), d_i=view(d_stack, j),
            num_examples=Ds[j], gamma=gamma,
            sgd_flops=float(gamma) * m_frac * Ds[j],
            loss=float(mean_loss[j])) for j in range(G)]


# ------------------------------------------------ tree reference path -----

_STEP_CACHE = {}


def _prox_step(loss_fn, params, anchor, batch, weights, eta, mu):
    """One proximal SGD step on g_i(x, x^t) (eq. 6) — the single source of
    truth for both the sequential and the vmapped batched tree paths."""
    loss, gF = jax.value_and_grad(loss_fn)(params, batch, weights)
    new = jax.tree_util.tree_map(
        lambda p, g, x0: p - eta * (g + mu * (p - x0)),
        params, gF, anchor)
    return new, gF, loss


def _prox_step_fn(loss_fn):
    if loss_fn not in _STEP_CACHE:
        _STEP_CACHE[loss_fn] = jax.jit(functools.partial(_prox_step, loss_fn))
    return _STEP_CACHE[loss_fn]


def _local_train_tree(params, loss_fn, data, *, gamma, m_frac, eta, mu,
                      key) -> LocalResult:
    anchor = params
    D = jax.tree_util.tree_leaves(data)[0].shape[0]
    a = a_coefficients(gamma, eta, mu)
    a1 = float(tracing.sync(jnp.sum(a), "a_norm"))
    step = _prox_step_fn(loss_fn)
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    keys = jax.random.split(key, gamma)
    eta_j = jnp.asarray(eta, jnp.float32)
    mu_j = jnp.asarray(mu, jnp.float32)
    loss_sum = 0.0
    for k in range(gamma):
        idx = tracing.sync(sample_minibatch(keys[k], D, m_frac),
                           "minibatch_idx")
        bsz = _bucket(len(idx))
        pad = np.concatenate([idx, np.zeros(bsz - len(idx), idx.dtype)])
        weights = jnp.asarray(
            np.concatenate([np.ones(len(idx)), np.zeros(bsz - len(idx))]),
            jnp.float32)
        batch = jax.tree_util.tree_map(lambda x: x[pad], data)
        params, gF, loss = step(params, anchor, batch, weights, eta_j, mu_j)
        loss_sum += float(tracing.sync(loss, "step_loss"))
        acc = jax.tree_util.tree_map(
            lambda acU, g: acU + a[k] * g, acc, gF)       # eq. (10) numerator
    d_i = jax.tree_util.tree_map(lambda x: x / a1, acc)
    return LocalResult(params=params, d_i=d_i, num_examples=D, gamma=gamma,
                       sgd_flops=float(gamma) * m_frac * D,
                       loss=loss_sum / gamma)


_BATCH_STEP_CACHE = {}


def _prox_step_batched_fn(loss_fn):
    """`_prox_step` for a stack of DPUs (leading group axis on
    params/batch/weights; the anchor x^t is shared)."""
    if loss_fn not in _BATCH_STEP_CACHE:
        step = jax.vmap(functools.partial(_prox_step, loss_fn),
                        in_axes=(0, None, 0, 0, None, None))
        _BATCH_STEP_CACHE[loss_fn] = jax.jit(step)
    return _BATCH_STEP_CACHE[loss_fn]


def _local_train_batched_tree(params, loss_fn, datasets, *, gamma, m_frac,
                              eta, mu, keys):
    G = len(datasets)
    anchor = params
    Ds = [jax.tree_util.tree_leaves(d)[0].shape[0] for d in datasets]
    bszs = [batch_size(D, m_frac) for D in Ds]
    bucket = _bucket(max(bszs))
    assert all(_bucket(b) == bucket for b in bszs), \
        "grouping must put same-bucket DPUs together"
    a = a_coefficients(gamma, eta, mu)
    a1 = float(tracing.sync(jnp.sum(a), "a_norm"))
    step = _prox_step_batched_fn(loss_fn)
    p_stack = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (G,) + x.shape), params)
    acc = jax.tree_util.tree_map(
        lambda x: jnp.zeros((G,) + x.shape, x.dtype), params)
    step_keys = [jax.random.split(k, gamma) for k in keys]
    eta_j = jnp.asarray(eta, jnp.float32)
    mu_j = jnp.asarray(mu, jnp.float32)
    loss_sum = np.zeros(G)
    for k in range(gamma):
        micro, wts = [], []
        for j, d in enumerate(datasets):
            idx = tracing.sync(
                sample_minibatch(step_keys[j][k], Ds[j], m_frac),
                "minibatch_idx")
            pad = np.concatenate([idx, np.zeros(bucket - len(idx), idx.dtype)])
            wts.append(np.concatenate([np.ones(len(idx)),
                                       np.zeros(bucket - len(idx))]))
            micro.append(jax.tree_util.tree_map(lambda x: x[pad], d))
        batch = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *micro)
        weights = jnp.asarray(np.stack(wts), jnp.float32)
        p_stack, gF, losses = step(p_stack, anchor, batch, weights,
                                   eta_j, mu_j)
        loss_sum += tracing.sync(losses, "step_loss")
        acc = jax.tree_util.tree_map(
            lambda acU, g: acU + a[k] * g, acc, gF)
    d_stack = jax.tree_util.tree_map(lambda x: x / a1, acc)
    return [LocalResult(
        params=jax.tree_util.tree_map(lambda x: x[j], p_stack),
        d_i=jax.tree_util.tree_map(lambda x: x[j], d_stack),
        num_examples=Ds[j], gamma=gamma,
        sgd_flops=float(gamma) * m_frac * Ds[j],
        loss=float(loss_sum[j] / gamma)) for j in range(G)]


# --------------------------------------------------- public entry points -----

def _empty_result(params, gamma: int, keep_planes: bool) -> LocalResult:
    """A D == 0 DPU trains nothing: params unchanged, d_i = 0, nan loss."""
    if keep_planes:
        plane = as_plane(params)
        return LocalResult(params=plane,
                           d_i=plane.with_data(jnp.zeros_like(plane.data)),
                           num_examples=0, gamma=gamma, sgd_flops=0.0)
    tree = params.to_tree() if isinstance(params, ParamPlane) else params
    return LocalResult(params=tree,
                       d_i=jax.tree_util.tree_map(jnp.zeros_like, tree),
                       num_examples=0, gamma=gamma, sgd_flops=0.0)


def local_train(params, loss_fn: Callable, data: dict, *, gamma: int,
                m_frac: float, eta: float, mu: float, key,
                backend: str = "plane", kernel_backend: str = "auto",
                keep_planes: bool = False) -> LocalResult:
    """Run gamma proximal SGD steps at one DPU.

    loss_fn(params, batch, example_weights) -> weighted mean loss.
    data: dict of arrays with leading dim D_i (the DPU's current dataset).
    Mini-batches are padded to power-of-two buckets (zero example weights)
    so the jitted step is shared across DPUs and rounds.

    ``backend="plane"`` (default) runs the whole loop on the flat
    parameter plane through the fused Pallas kernels (the per-DPU PRNG
    stream and numerics match the tree path to float tolerance).
    """
    if jax.tree_util.tree_leaves(data)[0].shape[0] == 0:
        return _empty_result(params, gamma,
                             keep_planes and backend != "tree")
    if backend == "tree":
        return _local_train_tree(params, loss_fn, data, gamma=gamma,
                                 m_frac=m_frac, eta=eta, mu=mu, key=key)
    return _local_train_batched_plane(
        params, loss_fn, [data], gamma=gamma, m_frac=m_frac, eta=eta,
        mu=mu, keys=[key], keep_planes=keep_planes,
        kernel_backend=kernel_backend)[0]


def local_train_batched(params, loss_fn: Callable, datasets, *, gamma: int,
                        m_frac: float, eta: float, mu: float, keys,
                        backend: str = "plane", kernel_backend: str = "auto",
                        keep_planes: bool = False):
    """``local_train`` for a homogeneous-(gamma, m) group of DPUs, all
    starting from the same global ``params``.

    ``datasets``: list of per-DPU data dicts (sizes may differ — every
    DPU's mini-batch must land in the same power-of-two bucket, which the
    caller guarantees by grouping).  ``keys``: one PRNG key per DPU; each
    is split into gamma step keys exactly like the sequential path, so the
    per-DPU mini-batch draws match ``local_train`` bit-for-bit.

    ``backend="plane"`` (default): ONE jitted scan for all gamma steps —
    a vmapped loss/grad plus a single fused kernel launch per local
    iteration.  ``backend="tree"``: one vmapped jitted step per iteration
    with per-leaf tree_map update/accumulation (the reference path).
    ``keep_planes`` returns ParamPlane-backed results (the executors'
    end-to-end plane path); ignored by the tree backend.
    """
    live = [j for j, d in enumerate(datasets)
            if jax.tree_util.tree_leaves(d)[0].shape[0] > 0]
    if len(live) < len(datasets):
        out = [_empty_result(params, gamma,
                             keep_planes and backend != "tree")
               for _ in datasets]
        if live:
            sub = local_train_batched(
                params, loss_fn, [datasets[j] for j in live], gamma=gamma,
                m_frac=m_frac, eta=eta, mu=mu,
                keys=[keys[j] for j in live], backend=backend,
                kernel_backend=kernel_backend, keep_planes=keep_planes)
            for j, r in zip(live, sub):
                out[j] = r
        return out
    if backend == "tree":
        return _local_train_batched_tree(params, loss_fn, datasets,
                                         gamma=gamma, m_frac=m_frac,
                                         eta=eta, mu=mu, keys=keys)
    return _local_train_batched_plane(params, loss_fn, datasets,
                                      gamma=gamma, m_frac=m_frac, eta=eta,
                                      mu=mu, keys=keys,
                                      keep_planes=keep_planes,
                                      kernel_backend=kernel_backend)


def local_train_multi(anchors, loss_fn: Callable, datasets, *, gamma: int,
                      m_frac: float, eta: float, mu: float, keys,
                      kernel_backend: str = "auto",
                      keep_planes: bool = True):
    """Grouped local training where every element carries ITS OWN global
    params/anchor — the cross-run hot path of the multi-seed sweep
    executor (``repro.experiments``): elements (run k, DPU i) drawn from
    K different seeded runs batch into ONE jitted scan, each proximal to
    its own run's global model.

    ``anchors``: one ParamPlane (or pytree) per element, all sharing one
    FlatSpec; ``datasets``/``keys``: as ``local_train_batched`` (all
    datasets non-empty; empty DPUs are the caller's ``_empty_result``).
    Per-element numerics are identical to ``local_train`` with that
    element's anchor: the kernel applies the same elementwise update
    whether the anchor is shared or per-element, and the per-element PRNG
    streams don't depend on the group composition.
    """
    assert len(anchors) == len(datasets) == len(keys)
    assert all(jax.tree_util.tree_leaves(d)[0].shape[0] > 0
               for d in datasets), "local_train_multi needs live datasets"
    return _local_train_batched_plane(
        None, loss_fn, datasets, gamma=gamma, m_frac=m_frac, eta=eta,
        mu=mu, keys=keys, keep_planes=keep_planes, anchors=anchors,
        kernel_backend=kernel_backend)


# ------------------------------------------------ trace-level contracts -----
#
# Registered for `python -m repro.analysis audit` (docs/static_analysis.md).
# The builders below only run when the auditor traces them — registration
# itself is a dict insert.

def _audit_loss(params, batch, w):
    """Pure-jnp weighted CE loss for contract tracing.  Deliberately no
    jax.nn helpers: one_hot/log_softmax are internally jitted and
    default to f64 under jax_enable_x64, which would pollute the
    dtype (JXP002) and fusion-boundary (JXP005) audits with library
    noise instead of auditing OUR round program."""
    logits = batch["x"] @ params["w"] + params["b"]
    s = logits - jax.lax.stop_gradient(
        jnp.max(logits, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(s), axis=-1))
    n_classes = logits.shape[-1]
    one = (batch["y"][:, None] == jnp.arange(n_classes)[None, :])
    ll = jnp.sum(s * one.astype(jnp.float32), axis=-1) - lse
    return -jnp.sum(ll * w) / jnp.maximum(jnp.sum(w), 1.0)


def _audit_round_args(n_group: int = 2, n_examples: int = 8,
                      n_features: int = 4, n_classes: int = 3,
                      gamma: int = 2, m_frac: float = 1.0):
    """Tiny staged fused-round arguments — the exact 10-tuple
    ``local_round_plane`` feeds ``_plane_round_fn`` (shared with the
    sharded-round and sweep contract builders)."""
    rng = np.random.RandomState(0)
    params = {"w": jnp.zeros((n_features, n_classes), jnp.float32),
              "b": jnp.zeros((n_classes,), jnp.float32)}
    plane = as_plane(params)
    datasets = [
        {"x": jnp.asarray(rng.normal(size=(n_examples, n_features)),
                          jnp.float32),
         "y": jnp.asarray(rng.randint(0, n_classes, size=(n_examples,)),
                          jnp.int32)}
        for _ in range(n_group)]
    Ds = [n_examples] * n_group
    bucket = _bucket(batch_size(n_examples, m_frac))
    a = a_coefficients(gamma, 0.1, 0.01)
    step_keys = jax.vmap(lambda k: jax.random.split(k, gamma))(
        jnp.stack([jax.random.PRNGKey(i) for i in range(n_group)]))
    data_stack, idx, weights = _stage_group_batches(
        datasets, step_keys, Ds, bucket, gamma, m_frac)
    args = (plane.broadcast(n_group).data, plane.data, data_stack, idx,
            weights, a, jnp.asarray(0.1, jnp.float32),
            jnp.asarray(0.01, jnp.float32), jnp.asarray(Ds, jnp.float32),
            jnp.asarray(0.1, jnp.float32))
    return plane.spec, args


from repro.analysis.jaxpr.contracts import Program, contract  # noqa: E402


@contract(
    "fused_round",
    collectives={},                 # single-device: zero collectives
    memory_budget_bytes=4 << 20,    # tiny shapes; ~0.6 MiB today
)
def _fused_round_contract():
    """Single-group fused round: gamma-step train scan + eq.-10/11."""
    spec, args = _audit_round_args()
    return Program(fn=_plane_round_fn(_audit_loss, spec, "cpu", None),
                   args=args)


def verify_accumulation_identity(params0, result: LocalResult, *, eta, mu):
    """Check eq. (9): sum_l a_l grad F = (x^t - x^{t,gamma})/eta  holds only
    for mu=0 (with prox, the update uses grad g, not grad F).  Returns the
    max abs deviation of the mu=0 identity — used by tests."""
    from repro.kernels.plane import as_tree
    res_params = as_tree(result.params)
    res_d = as_tree(result.d_i)
    diff = jax.tree_util.tree_map(
        lambda x0, xg: (x0 - xg) / eta, as_tree(params0), res_params)
    a1 = float(jnp.sum(a_coefficients(result.gamma, eta, mu)))
    dev = jax.tree_util.tree_map(
        lambda d, acc: jnp.max(jnp.abs(d - acc * a1)), diff, res_d)
    return max(float(x) for x in jax.tree_util.tree_leaves(dev))
