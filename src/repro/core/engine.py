"""The CE-FL orchestration engine: one loop, two execution backends.

Each global round t (paper Secs. II+IV-VI):
  1. the pluggable :class:`~repro.scenario.Scenario` evolves the world:
     UE mobility re-derives rates/associations, the server mesh churns,
     and UEs observe new (possibly drifted) online data,
  2. the pluggable :class:`~repro.core.api.DecisionStrategy` picks the
     orchestration plan w^t (offloading rho, compute settings f/z/gamma/m,
     floating aggregator I_s) — warm-started from the previous plan,
  3. data offloading is realized (UE -> BS -> DC partitions),
  4. every DPU runs FedProx local training (eqs. 5-10) via the configured
     executor,
  5. scaled accumulated gradients are aggregated at the floating
     aggregation DC (eq. 11) — or FedNova / FedAvg for the baselines,
  6. delay / energy are charged per Sec. II-E and reported through
     :class:`~repro.core.api.RoundReport` callbacks.

Executors:
  * :class:`SimExecutor` — the simulation path: per-DPU FedProx with
    homogeneous-(gamma, m) DPUs batched through one vmapped proximal step
    (``fedprox.local_train_batched``).
  * :class:`MeshExecutor` — wraps the jitted SPMD round
    (``core.round_step.build_cefl_round_step``), the same code path the
    production launcher (``launch/train.py``) runs on real meshes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, fedprox
from repro.core import strategies as _strategies  # noqa: F401  (registers)
from repro.core.api import (DecisionContext, EngineOptions, RoundCallback,
                            RoundPlan, RoundReport, RunResult, get_strategy,
                            weighted_mean)
from repro.core.round_step import CEFLHyper, build_cefl_round_step
from repro.kernels.plane import ParamPlane, as_plane, as_tree
from repro.network.costs import network_costs, round_delay, round_energy
from repro.scenario import get_scenario
from repro.utils import tracing


# ------------------------------------------------------- offloading -----

def realize_offloading(rng, data_per_ue: List[dict], w, net):
    """Split each UE's round data per rho_nb / rho_bs into DPU datasets.

    Returns (ue_datasets, dc_datasets) as lists of {'x','y'} dicts.  The
    split conserves datapoints exactly: every input point lands at exactly
    one DPU, even in the all-offload edge case (each UE always keeps at
    least one point by clawing it back from its BS allocation) and the
    degenerate case where every rho_bs share floors to zero (the whole BS
    pool then goes to the DC with the largest rho share).
    """
    if isinstance(w, RoundPlan):
        w = w.to_w()
    N, B, S = net.dims
    rho_nb = tracing.sync(w["rho_nb"], "offload_plan")
    rho_bs = tracing.sync(w["rho_bs"], "offload_plan")
    bs_pool_x, bs_pool_y = [[] for _ in range(B)], [[] for _ in range(B)]
    ue_data = []
    for n, d in enumerate(data_per_ue):
        x = tracing.sync(d["x"], "offload_data")
        y = tracing.sync(d["y"], "offload_data")
        D = len(y)
        if D == 0:
            ue_data.append({"x": jnp.asarray(x), "y": jnp.asarray(y)})
            continue
        perm = rng.permutation(D)
        counts = np.floor(rho_nb[n] * D).astype(int)
        # all-offload guard: every UE keeps >= 1 point, taken back from
        # its largest BS allocation (rather than duplicating a point)
        excess = counts.sum() - (D - 1)
        while excess > 0:
            j = int(np.argmax(counts))
            take = min(excess, counts[j])
            counts[j] -= take
            excess -= take
        start = 0
        for b in range(B):
            take = perm[start:start + counts[b]]
            start += counts[b]
            if len(take):
                bs_pool_x[b].append(x[take])
                bs_pool_y[b].append(y[take])
        keep = perm[start:]
        ue_data.append({"x": jnp.asarray(x[keep]), "y": jnp.asarray(y[keep])})
    dc_x, dc_y = [[] for _ in range(S)], [[] for _ in range(S)]
    for b in range(B):
        if not bs_pool_x[b]:
            continue
        x = np.concatenate(bs_pool_x[b])
        y = np.concatenate(bs_pool_y[b])
        perm = rng.permutation(len(y))
        counts = np.floor(rho_bs[b] * len(y)).astype(int)
        # BSs keep no data: the rounding remainder goes to the DC with the
        # largest rho share (covers the all-floored-to-zero pool case);
        # shave from the largest counts if a row ever over-allocates.
        rem = len(y) - counts.sum()
        while rem < 0:
            j = int(np.argmax(counts))
            give = min(-rem, counts[j])
            counts[j] -= give
            rem += give
        counts[int(np.argmax(rho_bs[b]))] += rem
        start = 0
        for s in range(S):
            take = perm[start:start + counts[s]]
            start += counts[s]
            if len(take):
                dc_x[s].append(x[take])
                dc_y[s].append(y[take])
    dc_data = []
    for s in range(S):
        if dc_x[s]:
            dc_data.append({"x": jnp.asarray(np.concatenate(dc_x[s])),
                            "y": jnp.asarray(np.concatenate(dc_y[s]))})
        else:
            dc_data.append(None)
    return ue_data, dc_data


# -------------------------------------------------------- executors -----

def _plan_settings(plan: RoundPlan):
    gammas = np.maximum(np.rint(tracing.sync(plan.gamma, "plan_settings")),
                        1).astype(int)
    ms = np.clip(tracing.sync(plan.m, "plan_settings"), 0.05, 1.0)
    return gammas, ms


def _aggregate(params, results, agg: str, *, eta: float,
               theta: Optional[float], robust: str = "none",
               trim_frac: float = 0.1):
    weights = [r.num_examples for r in results]
    if robust != "none":
        # byzantine counter: coordinate-wise trimmed-mean/median instead
        # of the weighted sum.  Deliberately weight-free — and theta
        # (when not pinned) is the UNWEIGHTED gamma mean, because the
        # D_i a compromised client reports are not trusted either.
        if agg == "fedavg":
            return aggregation.robust_fedavg_aggregate(
                [r.params for r in results], mode=robust,
                trim_frac=trim_frac)
        theta_val = float(theta) if (agg != "fednova"
                                     and theta is not None) \
            else float(np.mean([r.gamma for r in results]))
        return aggregation.robust_aggregate(
            params, [r.d_i for r in results], theta=theta_val, eta=eta,
            mode=robust, trim_frac=trim_frac)
    if agg == "fedavg":
        return aggregation.fedavg_aggregate(
            [r.params for r in results], weights)
    if agg == "fednova":
        return aggregation.fednova_aggregate(
            params, [r.d_i for r in results], weights,
            [r.gamma for r in results], eta=eta)
    wn = np.asarray(weights, float)
    wn = wn / wn.sum()
    theta_val = theta if theta is not None else float(
        np.sum(wn * np.array([r.gamma for r in results])))   # tau_eff
    return aggregation.aggregate(params, [r.d_i for r in results], weights,
                                 theta=theta_val, eta=eta)


def _corrupt_value(x, fn):
    """Apply a plane-space transform to a ParamPlane or pytree value."""
    plane = as_plane(x)
    out = plane.with_data(fn(plane.data))
    return out if isinstance(x, ParamPlane) else out.to_tree()


def corrupt_local_results(results, live, corrupt, anchor, noise_key):
    """Apply the round's update corruptions (``ScenarioEvents.corrupted``
    triples ``(ue, mode, scale)``) to the matching ``LocalResult``s, in
    place, between local training and aggregation.

    sign_flip: d_i -> -scale * d_i and params -> anchor - scale *
    (params - anchor) (the anchor-relative flip, so fedavg model
    averaging sees the same attack direction eq.-11 does).  gauss: adds
    scale-std Gaussian noise to both, with per-target subkeys split off
    ``noise_key`` in deterministic (sorted) order.
    """
    by_dpu = {i: j for j, (i, _) in enumerate(live)}
    todo = [c for c in sorted(corrupt) if c[0] in by_dpu]
    n_gauss = sum(1 for _, mode, _ in todo if mode == "gauss")
    nkeys = iter(jax.random.split(noise_key, 2 * n_gauss)) if n_gauss \
        else iter(())
    anchor_data = as_plane(anchor).data
    for ue, mode, scale in todo:
        r = results[by_dpu[ue]]
        if mode == "sign_flip":
            r.d_i = _corrupt_value(r.d_i, lambda d: -scale * d)
            r.params = _corrupt_value(
                r.params, lambda p: anchor_data - scale * (p - anchor_data))
        elif mode == "gauss":
            kd, kp = next(nkeys), next(nkeys)
            r.d_i = _corrupt_value(
                r.d_i, lambda d: d + scale * jax.random.normal(
                    kd, d.shape, d.dtype))
            r.params = _corrupt_value(
                r.params, lambda p: p + scale * jax.random.normal(
                    kp, p.shape, p.dtype))
        else:
            raise ValueError(f"unknown corruption mode {mode!r}")


@dataclasses.dataclass
class SimExecutor:
    """Simulation backend: per-DPU FedProx on each DPU's own dataset.

    With ``batch_homogeneous`` (default), DPUs sharing (gamma, m,
    mini-batch bucket) train through one vmapped proximal step per local
    iteration — numerically identical to the sequential path (per-DPU PRNG
    streams are preserved), but with G-DPU groups costing one dispatch
    instead of G.

    With ``use_plane`` (default), parameters stay on the flat parameter
    plane end-to-end: local training runs the fused kernels
    (``fedprox.local_train*`` plane backend, dispatched per
    ``kernel_backend`` — see ``kernels/ops.py``) and eq.-11 aggregation is
    one fused kernel launch over the stacked d_i planes.
    ``use_plane=False`` is the pre-plane per-leaf tree path, kept for
    equivalence tests and the tree-vs-plane benchmark.

    With ``fuse_round`` (default), a round whose live DPUs form ONE
    homogeneous (gamma, m, bucket) group — the common case outside
    heterogeneous-plan strategies — runs as a single jitted program
    (``fedprox.local_round_plane``): training scan + eq.-10 + eq.-11
    aggregation, and on eval-cadence rounds the engine passes ``eval_fn``
    so the eval forward pass fuses into the SAME program (no separate
    vmapped eval dispatch, no tree materialization).

    With ``mesh_shape`` set, the fused round runs shard_map'd over the
    ``('dpu', 'rows')`` device mesh (``repro.sharding.plane``): the DPU
    stack data-parallel over 'dpu', plane rows FSDP-sharded over 'rows'
    — bitwise identical to the single-device fused round.  Rounds that
    cannot fuse (heterogeneous groups, fedavg, corruption, robust agg)
    fall back to the single-device paths.
    """
    batch_homogeneous: bool = True
    use_plane: bool = True
    fuse_round: bool = True
    kernel_backend: str = "auto"    # ops.resolve_backend name
    mesh_shape: Optional[tuple] = None   # (dpu, rows) device split

    @property
    def fused_eval(self) -> bool:
        """The engine hands eval_fn to ``run_round`` when this is set
        (the executor then returns the round's accuracy, or None when a
        round couldn't fuse and eval must run separately)."""
        return self.use_plane and self.batch_homogeneous and \
            self.fuse_round

    def run_round(self, params, plan: RoundPlan, datasets, *, loss_fn,
                  eta: float, mu: float, theta: Optional[float], agg: str,
                  key, eval_fn=None, corrupt=(), robust_agg: str = "none",
                  trim_frac: float = 0.1):
        backend = "plane" if self.use_plane else "tree"
        if self.use_plane:
            params = as_plane(params)
        gammas, ms = _plan_settings(plan)
        live = [(i, d) for i, d in enumerate(datasets)
                if d is not None and len(d["y"])]
        tracing.add("execute_round", live_dpus=len(live))
        if not live:
            out = (params, float("nan"))
            return out + (None,) if eval_fn is not None else out
        # gaussian update corruption needs one extra key; clean rounds
        # keep the historical split count so existing seeded traces are
        # unchanged bit for bit
        needs_noise = any(mode == "gauss" for _, mode, _ in corrupt)
        keys = jax.random.split(key, len(live) + (1 if needs_noise else 0))
        noise_key = keys[len(live)] if needs_noise else None
        results = [None] * len(live)
        if self.batch_homogeneous:
            groups: Dict[tuple, list] = {}
            for j, (i, d) in enumerate(live):
                bucket = fedprox._bucket(
                    fedprox.batch_size(len(d["y"]), ms[i]))
                groups.setdefault(
                    (int(gammas[i]), float(ms[i]), bucket), []).append(j)
            tracing.add("execute_round", groups=len(groups))
            if (self.fuse_round and self.use_plane and len(groups) == 1
                    and agg in ("cefl", "fednova")
                    and not corrupt and robust_agg == "none"):
                # single homogeneous group: the whole round (train +
                # aggregate [+ eval]) is ONE jitted program
                (gamma, m, _bucket), idxs = next(iter(groups.items()))
                # tau_eff = sum_i p_i gamma_i degenerates to gamma here,
                # which is also FedNova's theta
                theta_val = float(theta) if (agg == "cefl"
                                             and theta is not None) \
                    else float(gamma)
                Ds = [len(live[j][1]["y"]) for j in idxs]
                if self.mesh_shape is not None:
                    # deferred import: sharding is opt-in, the engine's
                    # import surface stays mesh-free
                    from repro.sharding import plane as shard_plane
                    new_params, losses, acc = \
                        shard_plane.local_round_plane_sharded(
                            params, loss_fn, [live[j][1] for j in idxs],
                            gamma=gamma, m_frac=m, eta=eta, mu=mu,
                            keys=[keys[j] for j in idxs], theta=theta_val,
                            mesh=shard_plane.plane_mesh(self.mesh_shape),
                            kernel_backend=self.kernel_backend,
                            eval_fn=eval_fn)
                else:
                    new_params, losses, acc = fedprox.local_round_plane(
                        params, loss_fn, [live[j][1] for j in idxs],
                        gamma=gamma, m_frac=m, eta=eta, mu=mu,
                        keys=[keys[j] for j in idxs], theta=theta_val,
                        kernel_backend=self.kernel_backend, eval_fn=eval_fn)
                mean_loss = weighted_mean(list(losses), Ds)
                if eval_fn is not None:
                    return new_params, mean_loss, acc
                return new_params, mean_loss
            for (gamma, m, _bucket), idxs in groups.items():
                out = fedprox.local_train_batched(
                    params, loss_fn, [live[j][1] for j in idxs],
                    gamma=gamma, m_frac=m, eta=eta, mu=mu,
                    keys=[keys[j] for j in idxs],
                    backend=backend, keep_planes=self.use_plane,
                    kernel_backend=self.kernel_backend)
                for j, r in zip(idxs, out):
                    results[j] = r
        else:
            tracing.add("execute_round", groups=len(live))
            for j, (i, d) in enumerate(live):
                results[j] = fedprox.local_train(
                    params, loss_fn, d, gamma=int(gammas[i]),
                    m_frac=float(ms[i]), eta=eta, mu=mu, key=keys[j],
                    backend=backend, keep_planes=self.use_plane,
                    kernel_backend=self.kernel_backend)
        if corrupt:
            corrupt_local_results(results, live, corrupt, params, noise_key)
        with tracing.span("aggregate"):
            new_params = _aggregate(params, results, agg, eta=eta,
                                    theta=theta, robust=robust_agg,
                                    trim_frac=trim_frac)
        mean_loss = weighted_mean([r.loss for r in results],
                                  [r.num_examples for r in results])
        if eval_fn is not None:
            # couldn't fuse (heterogeneous groups / fedavg): the caller
            # evaluates separately
            return new_params, mean_loss, None
        return new_params, mean_loss


@dataclasses.dataclass
class MeshExecutor:
    """Mesh backend: the paper loop through the jitted SPMD round step.

    Active DPUs are packed on a leading DPU axis (datasets right-padded to
    a shared power-of-two batch, the CE-FL mini-batch ratio applied as a
    leading-example mask), so one ``round_step`` call trains and
    aggregates every DPU — the same code the production launcher runs on
    TPU meshes.  Differences vs :class:`SimExecutor`: mini-batches are the
    deterministic leading slice rather than random draws (identical when
    m=1), the reported loss is the unweighted DPU mean of the final local
    iteration (not the weighted all-step mean), and FedAvg
    model-averaging has no SPMD equivalent here.

    The jitted step is cached per (loss_fn, gamma_max, DPU count, batch
    bucket, mu); theta is applied outside the jit so per-round tau_eff
    changes never recompile.

    With ``use_plane`` (default) the round runs on the flat parameter
    plane: the jitted step receives a ``(n_dpu, R, LANE)`` ParamPlane and
    ``round_step`` dispatches to the fused Pallas kernels (interpret mode
    on CPU) — zero pytree flatten/unflatten in the inner loop.
    """
    agg_schedule: str = "all_reduce"
    use_plane: bool = True
    kernel_backend: str = "auto"    # ops.resolve_backend name
    mesh_shape: Optional[tuple] = None   # (dpu, rows): device_put the
                                         # plane stack with a NamedSharding
                                         # over the plane mesh; GSPMD then
                                         # partitions the jitted step
    _cache: dict = dataclasses.field(default_factory=dict, repr=False)

    def build_step(self, micro_loss_fn, hyper: CEFLHyper, *, jit=True):
        """The jitted SPMD round step for a mesh-layout ``micro_loss_fn``
        (params, microbatch, mask) -> (loss, aux).  Used directly by the
        LM launcher; ``run_round`` goes through the same cache."""
        step = build_cefl_round_step(micro_loss_fn, hyper)
        return jax.jit(step, donate_argnums=(0,)) if jit else step

    def _get_step(self, loss_fn, n_dpu, bucket, gamma_max, mu, eta):
        cache_key = (id(loss_fn), n_dpu, bucket, gamma_max, mu, eta)
        if cache_key not in self._cache:
            def micro_loss(p, micro, mask):
                return loss_fn(p, micro, mask), {}
            hyper = CEFLHyper(eta=eta, mu=mu, theta=1.0,
                              gamma_max=gamma_max, n_micro=1,
                              agg_schedule=self.agg_schedule,
                              kernel_backend=self.kernel_backend)
            # no donation here: run_round still needs the undonated params
            self._cache[cache_key] = jax.jit(
                build_cefl_round_step(micro_loss, hyper))
        return self._cache[cache_key]

    def run_round(self, params, plan: RoundPlan, datasets, *, loss_fn,
                  eta: float, mu: float, theta: Optional[float], agg: str,
                  key, corrupt=(), robust_agg: str = "none",
                  trim_frac: float = 0.1):
        del key, trim_frac  # deterministic leading-slice mini-batches
        if agg == "fedavg":
            raise NotImplementedError(
                "MeshExecutor aggregates accumulated gradients (eq. 11); "
                "FedAvg model averaging needs SimExecutor")
        if corrupt or robust_agg != "none":
            raise NotImplementedError(
                "update corruption / robust aggregation run between local "
                "training and aggregation, which the fused SPMD round "
                "step does not expose; use SimExecutor")
        gammas, ms = _plan_settings(plan)
        live = [(i, d) for i, d in enumerate(datasets)
                if d is not None and len(d["y"])]
        if not live:
            return params, float("nan")
        Ds = [len(d["y"]) for _, d in live]
        bucket = fedprox._bucket(max(Ds))
        n = len(live)
        padded = []
        for (i, d), D in zip(live, Ds):
            padded.append(jax.tree_util.tree_map(
                lambda x: jnp.pad(
                    x, [(0, bucket - D)] + [(0, 0)] * (x.ndim - 1)), d))
        # (n_dpu, n_micro=1, mb, ...) mesh batch layout
        batch = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs)[:, None], *padded)
        live_gammas = np.array([gammas[i] for i, _ in live])
        gamma_max = int(live_gammas.max())
        # real examples sit first, so folding the pad into the mini-batch
        # ratio makes the leading-example mask select ceil(m_i * D_i) of
        # them and none of the padding
        m_eff = np.array([ms[i] * D / bucket for (i, _), D in zip(live, Ds)])
        w = np.asarray(Ds, float)
        w = w / w.sum()
        if agg == "fednova" or theta is None:
            theta_val = float(np.sum(w * live_gammas))      # tau_eff
        else:
            theta_val = float(theta)
        meta = {"gamma": jnp.asarray(live_gammas, jnp.int32),
                "m_frac": jnp.asarray(m_eff, jnp.float32),
                "weight": jnp.asarray(w, jnp.float32)}
        step = self._get_step(loss_fn, n, bucket, gamma_max, mu, eta)
        if self.use_plane:
            plane = as_plane(params)
            stack = plane.broadcast(n)
            if self.mesh_shape is not None:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                from repro.sharding import plane as shard_plane
                from repro.sharding.specs import sanitize_spec
                mesh = shard_plane.plane_mesh(self.mesh_shape)
                spec = sanitize_spec(
                    P(shard_plane.DPU_AXIS, shard_plane.ROW_AXIS, None),
                    stack.data.shape, mesh)
                stack = stack.with_data(jax.device_put(
                    stack.data, NamedSharding(mesh, spec)))
            new_stack, metrics = step(stack, batch, meta)
            # theta=1 inside the step; rescale outside the jit so per-round
            # tau_eff never triggers recompilation (plane arithmetic only)
            new_params = plane.with_data(
                plane.data + theta_val * (new_stack.data[0] - plane.data))
            return new_params, float(tracing.sync(metrics["loss"],
                                                  "mesh_loss"))
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), params)
        new_stack, metrics = step(stacked, batch, meta)
        # the step ran with theta=1; rescale the global update outside the
        # jit so per-round tau_eff never triggers recompilation
        new_params = jax.tree_util.tree_map(
            lambda p, p1: p + theta_val * (p1[0] - p), params, new_stack)
        return new_params, float(tracing.sync(metrics["loss"],
                                              "mesh_loss"))


# ---------------------------------------------------- cohort sampling -----

def _gather_plan(plan: RoundPlan, cohort: np.ndarray, n_ue: int) -> RoundPlan:
    """Restrict a full-population plan to the cohort rows (the warm-start
    view handed to the solver, and the costing view of off-cadence
    rounds)."""
    g = tracing.sync(plan.gamma, "cohort_plan")
    m = tracing.sync(plan.m, "cohort_plan")
    return RoundPlan(
        rho_nb=jnp.asarray(tracing.sync(plan.rho_nb, "cohort_plan")[cohort]),
        rho_bs=plan.rho_bs,
        f_n=jnp.asarray(tracing.sync(plan.f_n, "cohort_plan")[cohort]),
        z_s=plan.z_s,
        gamma=jnp.asarray(np.concatenate([g[:n_ue][cohort], g[n_ue:]])),
        m=jnp.asarray(np.concatenate([m[:n_ue][cohort], m[n_ue:]])),
        I_s=plan.I_s,
        I_nb=jnp.asarray(tracing.sync(plan.I_nb, "cohort_plan")[cohort]),
        I_bn=jnp.asarray(tracing.sync(plan.I_bn, "cohort_plan")[:, cohort]),
        R_bs=plan.R_bs, delta_A=plan.delta_A, delta_R=plan.delta_R)


def _scatter_plan(sub: RoundPlan, cohort: np.ndarray, net,
                  opts: EngineOptions) -> RoundPlan:
    """Embed a cohort plan back into a full-population RoundPlan.

    Non-cohort UEs sit the round out: zero offloading (they hold no round
    data anyway), idle CPU frequency ``f_min``, the default (gamma, m)
    settings, and rate-argmax one-hot associations — every field still
    satisfies :meth:`RoundPlan.validate` at the full dims.
    """
    N, B, S = net.dims
    K = int(cohort.shape[0])
    rho_nb = np.zeros((N, B), np.float32)
    rho_nb[cohort] = tracing.sync(sub.rho_nb, "cohort_plan")
    f_n = np.full(N, net.cfg.f_min, np.float32)
    f_n[cohort] = tracing.sync(sub.f_n, "cohort_plan")
    gamma = np.full(N + S, float(opts.gamma_default), np.float32)
    sg = tracing.sync(sub.gamma, "cohort_plan")
    gamma[:N][cohort] = sg[:K]
    gamma[N:] = sg[K:]
    m = np.full(N + S, float(opts.m_default), np.float32)
    sm = tracing.sync(sub.m, "cohort_plan")
    m[:N][cohort] = sm[:K]
    m[N:] = sm[K:]
    I_nb = np.eye(B, dtype=np.float32)[
        np.argmax(tracing.sync(net.R_nb, "cohort_plan"), axis=1)]
    I_nb[cohort] = tracing.sync(sub.I_nb, "cohort_plan")
    I_bn = np.zeros((B, N), np.float32)
    I_bn[np.argmax(tracing.sync(net.R_bn, "cohort_plan"), axis=0),
         np.arange(N)] = 1.0
    I_bn[:, cohort] = tracing.sync(sub.I_bn, "cohort_plan")
    return RoundPlan(
        rho_nb=jnp.asarray(rho_nb), rho_bs=sub.rho_bs,
        f_n=jnp.asarray(f_n), z_s=sub.z_s,
        gamma=jnp.asarray(gamma), m=jnp.asarray(m),
        I_s=sub.I_s, I_nb=jnp.asarray(I_nb), I_bn=jnp.asarray(I_bn),
        R_bs=sub.R_bs, delta_A=sub.delta_A, delta_R=sub.delta_R)


# ----------------------------------------------------------- engine -----

def _rng_state_dict(rng: np.random.RandomState) -> dict:
    """A numpy ``RandomState`` state as array/scalar leaves (MT19937)."""
    kind, keys, pos, has_gauss, cached = rng.get_state()
    assert kind == "MT19937", kind
    return {"keys": np.asarray(keys), "pos": int(pos),
            "has_gauss": int(has_gauss), "cached": float(cached)}


def _rng_from_state_dict(d: dict) -> np.random.RandomState:
    rng = np.random.RandomState()
    rng.set_state(("MT19937", np.asarray(d["keys"], np.uint32),
                   int(d["pos"]), int(d["has_gauss"]), float(d["cached"])))
    return rng


@dataclasses.dataclass
class LoopState:
    """The full mutable state of one orchestration run between rounds.

    Everything the loop reads or writes lives here (the engine itself
    stays stateless across rounds), so a run can be advanced one round at
    a time (:meth:`Engine.begin_round` / :meth:`Engine.finish_round`),
    checkpointed mid-run (:meth:`state_dict`), and resumed bit-exactly.
    ``loss_fn`` / ``eval_fn`` are behavior, not state — they are rebound
    by the caller on resume and excluded from :meth:`state_dict`.
    """
    rng: np.random.RandomState
    key: jax.Array
    params: object
    loss_fn: object = None
    eval_fn: object = None
    reports: List[RoundReport] = dataclasses.field(default_factory=list)
    cum_E: float = 0.0
    cum_D: float = 0.0
    plan: Optional[RoundPlan] = None
    prev_agg: Optional[int] = None
    t: int = 0
    stopped: bool = False
    last_acc: float = float("nan")

    def state_dict(self) -> dict:
        """Array/scalar leaves of the loop state (reports excluded — the
        metric trace serializes as JSON-able records at the experiments
        layer, see ``repro.experiments.runstate``)."""
        plane = as_plane(self.params)
        plan = {} if self.plan is None else \
            {k: np.asarray(v) for k, v in self.plan.to_w().items()}
        return {
            "t": int(self.t),
            "cum_E": float(self.cum_E), "cum_D": float(self.cum_D),
            "prev_agg": -1 if self.prev_agg is None else int(self.prev_agg),
            "last_acc": float(self.last_acc),
            "stopped": int(self.stopped),
            "rng": _rng_state_dict(self.rng),
            "key": np.asarray(self.key),
            "params_plane": np.asarray(plane.data),
            "plan": plan,
        }

    def load_state_dict(self, d: dict, *, use_plane: bool) -> None:
        self.t = int(d["t"])
        self.cum_E = float(d["cum_E"])
        self.cum_D = float(d["cum_D"])
        self.prev_agg = None if int(d["prev_agg"]) < 0 else \
            int(d["prev_agg"])
        self.last_acc = float(d["last_acc"])
        self.stopped = bool(int(d["stopped"]))
        self.rng = _rng_from_state_dict(d["rng"])
        self.key = jnp.asarray(np.asarray(d["key"], np.uint32))
        spec = as_plane(self.params).spec
        plane = ParamPlane(data=jnp.asarray(d["params_plane"]), spec=spec)
        self.params = plane if use_plane else plane.to_tree()
        self.plan = RoundPlan.from_w(d["plan"]) if d["plan"] else None


@dataclasses.dataclass
class StagedRound:
    """Host-side output of :meth:`Engine.begin_round`: everything the
    executor needs to run the device work of round ``t``."""
    t: int
    net_t: object
    D_bar: np.ndarray
    plan: RoundPlan
    datasets: list                 # ue_data + dc_data, one entry per DPU
    n_dc: int
    key: jax.Array
    events: object
    t0: float                      # perf_counter at begin_round's start
    # --- per-round client sampling (EngineOptions.cohort_size) ---
    cohort: Optional[np.ndarray] = None   # sorted drawn UE indices, or None
    sub_net: object = None                # topology.subnetwork view
    sub_plan: Optional[RoundPlan] = None  # the cohort-dims plan (costing)


class Engine:
    """Drives the CE-FL loop with a pluggable strategy and executor.

    >>> engine = Engine(net, "cefl", consts=consts, ow=ow,
    ...                 opts=EngineOptions(rounds=8))
    >>> result = engine.run(online_ues, init_params=p0,
    ...                     loss_fn=loss_fn, eval_fn=eval_fn)
    >>> result.final.acc, result.to_history()["loss"]
    """

    def __init__(self, net, strategy=None, *, consts, ow,
                 opts: Optional[EngineOptions] = None,
                 executor=None, scenario=None,
                 callbacks: Sequence[RoundCallback] = (),
                 validate_plans: bool = True):
        self.net = net
        self.opts = opts or EngineOptions()
        self.strategy = get_strategy(
            strategy if strategy is not None else self.opts.strategy)
        # environment dynamics: a name from the scenario registry
        # ("static", "campus_walk", ...) or a Scenario instance
        self.scenario = get_scenario(
            scenario if scenario is not None else self.opts.scenario)
        self.executor = executor if executor is not None else \
            SimExecutor(kernel_backend=self.opts.kernel_backend,
                        mesh_shape=self.opts.mesh_shape)
        self.callbacks: List[RoundCallback] = list(callbacks)
        self.validate_plans = validate_plans
        self.consts = consts
        self.ow = ow

    def on_round_end(self, callback: RoundCallback) -> RoundCallback:
        """Register a callback (usable as a decorator).  Returning True
        from a callback stops the run after the current round."""
        self.callbacks.append(callback)
        return callback

    def decide(self, net_t, D_bar, t: int,
               prev_plan: Optional[RoundPlan], *,
               consts=None) -> RoundPlan:
        """``consts`` overrides the engine's MLConstants for this call —
        the cohort path hands in constants gathered to the cohort's
        per-DPU rows."""
        ctx = DecisionContext(round=t,
                              consts=self.consts if consts is None
                              else consts,
                              ow=self.ow, opts=self.opts,
                              prev_plan=prev_plan)
        # strategies receive D_bar as a device array: the jit solver backend
        # consumes it directly (no numpy bounce on the decision hot path)
        plan = self.strategy.decide(net_t, jnp.asarray(D_bar, jnp.float32),
                                    ctx)
        if self.validate_plans:
            plan.validate(net_t)
        return plan

    # --- the round loop, exposed one round at a time -------------------
    #
    # init_loop / begin_round / finish_round are the resumable form of
    # the loop: Engine.run is literally init + while + (begin, execute,
    # finish), and the multi-seed sweep executors in repro.experiments
    # drive K LoopStates through the same three calls in lockstep so the
    # per-seed host work (scenario tick, solver decision, offloading,
    # PRNG chains) stays bit-identical to a solo Engine.run.

    @property
    def aggregation(self) -> str:
        return getattr(self.strategy, "aggregation", "cefl")

    @property
    def mu_effective(self) -> float:
        return self.opts.mu if getattr(self.strategy, "proximal", True) \
            else 0.0

    def init_loop(self, online_datasets, *, init_params, loss_fn=None,
                  eval_fn=None) -> LoopState:
        """Bind the scenario and build the round-0 loop state."""
        del online_datasets  # streams carry their own state; staged later
        opts = self.opts
        params = init_params
        if getattr(self.executor, "use_plane", False):
            # plane-backed executors keep params flat across rounds;
            # tree views are materialized only at API boundaries (eval,
            # RoundReport, the final RunResult)
            params = as_plane(init_params)
        self.scenario.bind(self.net, opts)
        return LoopState(rng=np.random.RandomState(opts.seed),
                         key=jax.random.PRNGKey(opts.seed),
                         params=params, loss_fn=loss_fn, eval_fn=eval_fn)

    def _cohort_consts(self, n_ue: int, cohort: np.ndarray):
        """MLConstants with the per-DPU arrays gathered to the cohort's
        (K + S) rows (scalar / mis-sized fields pass through)."""
        c = self.consts

        def gather(a):
            a = np.asarray(a)
            if a.ndim == 0 or a.shape[0] < n_ue:
                return a
            return np.concatenate([a[:n_ue][cohort], a[n_ue:]])

        return dataclasses.replace(c, theta_i=gather(c.theta_i),
                                   sigma_i=gather(c.sigma_i))

    def begin_round(self, state: LoopState, online_datasets) -> StagedRound:
        """Host side of round ``state.t``: scenario tick, cohort draw,
        plan decision, offloading realization, PRNG advance.  Mutates
        ``state`` (rng, key, plan) exactly as the solo loop does."""
        with tracing.span("begin_round", round=state.t):
            return self._begin_round(state, online_datasets)

    def _begin_round(self, state: LoopState, online_datasets) -> StagedRound:
        opts = self.opts
        t = state.t
        t0 = time.perf_counter()
        # one scenario tick: evolved network (same cfg/dims -> the
        # solver's NetView pytree keeps hitting its compile cache),
        # drifted per-UE data, and the round's environment events
        with tracing.span("scenario") as sp:
            net_t, data_per_ue, events = self.scenario.step(
                t, online_datasets, state.rng)
            if tracing.enabled():
                sp.set(h2d_bytes=tracing.nbytes(data_per_ue))
        N = len(data_per_ue)
        cohort = sub_net = sub_plan = None
        if opts.cohort_size is not None and opts.cohort_size < N:
            # per-round client sampling: K UEs drawn uniformly without
            # replacement; the rest observe no round data, so the
            # executors' live-DPU filter drops them before any device
            # work and the solver sees only the (K, B, S) subproblem.
            # The rng draw happens ONLY on this branch, so cohort-off
            # runs keep their seeded traces bit-identical.
            if opts.distributed_solver:
                raise ValueError(
                    "cohort_size is incompatible with distributed_solver: "
                    "the cohort subnetwork has no consensus graph")
            cohort = np.sort(state.rng.choice(N, opts.cohort_size,
                                              replace=False))
            mask = np.zeros(N, bool)
            mask[cohort] = True
            data_per_ue = [
                d if mask[n] else
                jax.tree_util.tree_map(lambda x: x[:0], d)
                for n, d in enumerate(data_per_ue)]
            from repro.network.topology import subnetwork
            sub_net = subnetwork(net_t, cohort)
        D_bar = np.array([len(d["y"]) for d in data_per_ue], float)
        if state.plan is None or t % opts.reoptimize_every == 0:
            with tracing.span("solve"):
                if cohort is None:
                    state.plan = self.decide(net_t, D_bar, t,
                                             prev_plan=state.plan)
                else:
                    # gather -> solve the K-UE subproblem -> scatter.  A
                    # fixed K keeps hitting the solver's (K, B, S)
                    # compile cache no matter how large the population is.
                    sub_prev = None if state.plan is None else \
                        _gather_plan(state.plan, cohort, N)
                    sub_plan = self.decide(
                        sub_net, D_bar[cohort], t, prev_plan=sub_prev,
                        consts=self._cohort_consts(N, cohort))
                    state.plan = _scatter_plan(sub_plan, cohort, net_t,
                                               opts)
                    if self.validate_plans:
                        state.plan.validate(net_t)
        elif cohort is not None:
            sub_plan = _gather_plan(state.plan, cohort, N)
        with tracing.span("offload") as sp:
            ue_data, dc_data = realize_offloading(state.rng, data_per_ue,
                                                  state.plan, net_t)
            if tracing.enabled():
                sp.set(h2d_bytes=tracing.nbytes(ue_data, dc_data),
                       rows=sum(len(d["y"]) for d in ue_data + dc_data
                                if d is not None))
        state.key, sub = jax.random.split(state.key)
        return StagedRound(t=t, net_t=net_t, D_bar=D_bar, plan=state.plan,
                           datasets=ue_data + dc_data, n_dc=len(dc_data),
                           key=sub, events=events, t0=t0,
                           cohort=cohort, sub_net=sub_net,
                           sub_plan=sub_plan)

    def should_eval(self, t: int) -> bool:
        every = max(1, getattr(self.opts, "eval_every", 1))
        return t % every == 0 or t == self.opts.rounds - 1

    def execute_round(self, state: LoopState, staged: StagedRound, *,
                      fuse_eval: bool = True):
        """Device phase of round ``staged.t``: executor dispatch with the
        round's adversary corruptions and the configured robust
        aggregation threaded through.  Updates ``state.params`` and
        returns ``(mean_loss, acc)`` — ``acc`` is None unless the round
        fused its eval.  The single source of truth for the executor
        call: ``_run_loop``, the sweep executors, and the scenario fuzzer
        all route through here."""
        with tracing.span("execute_round", round=staged.t):
            return self._execute_round(state, staged, fuse_eval)

    def _execute_round(self, state: LoopState, staged: StagedRound,
                       fuse_eval: bool):
        opts = self.opts
        kw = {}
        corrupt = tuple(getattr(staged.events, "corrupted", ()) or ())
        if corrupt or opts.robust_agg != "none":
            # passed only when active so custom executors with the
            # pre-adversary run_round signature keep working on clean runs
            kw["corrupt"] = corrupt
            kw["robust_agg"] = opts.robust_agg
            kw["trim_frac"] = opts.trim_frac
        if (fuse_eval and state.eval_fn is not None
                and self.should_eval(staged.t)
                and getattr(self.executor, "fused_eval", False)):
            # fuse the eval forward pass into the round program; the
            # executor returns acc=None if the round couldn't fuse
            # (finish_round then evaluates separately)
            kw["eval_fn"] = state.eval_fn
        out = self.executor.run_round(
            state.params, staged.plan, staged.datasets,
            loss_fn=state.loss_fn, eta=opts.eta, mu=self.mu_effective,
            theta=opts.theta, agg=self.aggregation, key=staged.key, **kw)
        if "eval_fn" in kw:
            state.params, mean_loss, acc = out
        else:
            state.params, mean_loss = out
            acc = None
        return mean_loss, acc

    def finish_round(self, state: LoopState, staged: StagedRound,
                     mean_loss: float, acc: Optional[float] = None) -> \
            RoundReport:
        """Account the finished round: costs, eval (per the cadence, or
        the precomputed ``acc`` a sweep executor hands in), report,
        callbacks.  Advances ``state.t``."""
        with tracing.span("finish_round", round=staged.t):
            return self._finish_round(state, staged, mean_loss, acc)

    def _finish_round(self, state: LoopState, staged: StagedRound,
                      mean_loss: float, acc: Optional[float]) -> RoundReport:
        plan = staged.plan
        scale = tuple(getattr(staged.events, "compute_scale", ()) or ())
        if staged.cohort is not None and staged.sub_plan is not None:
            # cohort round: charge the K-UE subproblem, not all N UEs'
            # model-upload paths — non-cohort UEs transmit nothing
            w = staged.sub_plan.to_w()
            cost_net = staged.sub_net
            cost_D = staged.D_bar[staged.cohort]
            if scale:
                scale = tuple(np.asarray(scale)[staged.cohort])
        else:
            w = plan.to_w()
            cost_net = staged.net_t
            cost_D = staged.D_bar
        if scale:
            # stragglers: the plan's idealized f_n vs the realized rate —
            # the slowdown is charged through the Sec. II-E cost model
            # (compute delay ~ 1/f_n, compute energy ~ f_n^2)
            w = dict(w)
            w["f_n"] = jnp.asarray(w["f_n"]) * jnp.asarray(
                scale, jnp.float32)
        with tracing.span("costs"):
            costs = network_costs(w, cost_net, cost_D)
            E = float(tracing.sync(round_energy(costs, self.ow.xi3_sub),
                                   "costs"))
            Dl = float(tracing.sync(round_delay(costs), "costs"))
        state.cum_E += E
        state.cum_D += Dl
        if acc is None:
            if self.should_eval(staged.t):
                with tracing.span("eval"):
                    acc = float(tracing.sync(
                        state.eval_fn(as_tree(state.params)), "eval"))
            else:
                acc = state.last_acc
        state.last_acc = float(acc)
        gammas, ms = _plan_settings(plan)
        dc_data = staged.datasets[len(staged.datasets) - staged.n_dc:]
        report = RoundReport(
            round=staged.t, acc=float(acc), loss=mean_loss,
            energy=E, delay=Dl, cum_energy=state.cum_E,
            cum_delay=state.cum_D,
            aggregator=plan.aggregator,
            dc_points=tuple(0 if d is None else len(d["y"])
                            for d in dc_data),
            gamma_mean=float(gammas.mean()), m_mean=float(ms.mean()),
            plan=plan, wall_time=time.perf_counter() - staged.t0,
            handovers=tuple(staged.events.handovers),
            aggregator_moved=(state.prev_agg is not None
                              and plan.aggregator != state.prev_agg),
            active_ues=int(staged.events.active_ues))
        if self.opts.sanitize:
            # deferred import: the analysis package is a debug dependency,
            # not part of the engine's import-time surface
            from repro.analysis.sanitize import check_finite
            check_finite(state.params,
                         f"params after round {staged.t}")
        state.prev_agg = plan.aggregator
        state.reports.append(report)
        for cb in self.callbacks:
            if cb(report) is True:
                state.stopped = True
        state.t += 1
        return report

    def run(self, online_datasets, *, init_params, loss_fn,
            eval_fn) -> RunResult:
        """Run the full orchestration loop.

        ``online_datasets``: one ``core.drift.OnlineDataset`` per UE.
        ``loss_fn(params, batch, example_weights) -> scalar``;
        ``eval_fn(params) -> accuracy``.
        """
        state = self.init_loop(online_datasets, init_params=init_params,
                               loss_fn=loss_fn, eval_fn=eval_fn)
        return self.run_loop(state, online_datasets)

    def run_loop(self, state: LoopState, online_datasets) -> RunResult:
        """Drive an (initialized or resumed) LoopState to completion.

        With ``opts.sanitize`` the whole loop runs under the
        :class:`repro.analysis.sanitize.KeyReuseDetector`: any host-level
        ``jax.random`` call that consumes an already-consumed key raises,
        and :meth:`finish_round` additionally checks the aggregated
        params for NaN/Inf every round.
        """
        if self.opts.sanitize:
            from repro.analysis.sanitize import KeyReuseDetector
            with KeyReuseDetector(mode="raise"):
                return self._run_loop(state, online_datasets)
        return self._run_loop(state, online_datasets)

    def _run_loop(self, state: LoopState, online_datasets) -> RunResult:
        while state.t < self.opts.rounds and not state.stopped:
            staged = self.begin_round(state, online_datasets)
            mean_loss, acc = self.execute_round(state, staged)
            self.finish_round(state, staged, mean_loss, acc)
        return RunResult(reports=state.reports,
                         params=as_tree(state.params))


# ---------------------------------------------------- trace contracts --

from repro.analysis.jaxpr.contracts import Program, contract  # noqa: E402


def _audit_micro_loss(p, micro, mask):
    return fedprox._audit_loss(p, micro, mask), {}


def _audit_mesh_round_args(n_dpu: int = 4, mb: int = 8,
                           n_features: int = 4, n_classes: int = 3):
    """Tiny (stack, batch, meta) triple in the exact mesh layout
    ``MeshExecutor.run_round`` stages (batch leaves (n_dpu, n_micro=1,
    mb, ...), absolute-size weights)."""
    rng = np.random.RandomState(0)
    params = {"w": jnp.zeros((n_features, n_classes), jnp.float32),
              "b": jnp.zeros((n_classes,), jnp.float32)}
    stack = as_plane(params).broadcast(n_dpu)
    batch = {"x": jnp.asarray(rng.normal(size=(n_dpu, 1, mb, n_features)),
                              jnp.float32),
             "y": jnp.asarray(rng.randint(0, n_classes,
                                          size=(n_dpu, 1, mb)), jnp.int32)}
    meta = {"gamma": jnp.full((n_dpu,), 2, jnp.int32),
            "m_frac": jnp.ones((n_dpu,), jnp.float32),
            "weight": jnp.full((n_dpu,), float(mb), jnp.float32)}
    return stack, batch, meta


_AUDIT_HYPER = CEFLHyper(eta=0.1, mu=0.01, theta=1.0, gamma_max=2,
                         n_micro=1, kernel_backend="cpu")


@contract(
    "mesh_round_donation",
    collectives={},
)
def _mesh_round_donation_contract():
    """build_step donation: the (n_dpu, R, LANE) plane stack passed with
    donate_argnums=(0,) must alias an output in the compiled step."""
    stack, batch, meta = _audit_mesh_round_args()
    step = build_cefl_round_step(_audit_micro_loss, _AUDIT_HYPER)
    return Program(fn=step, args=(stack, batch, meta),
                   donate_argnums=(0,))


@contract(
    "mesh_round_gspmd",
    min_devices=8,
    hlo_collectives=frozenset(
        {"all-gather", "all-reduce", "collective-permute"}),
)
def _mesh_round_gspmd_contract():
    """run_round mesh_shape path: GSPMD partitioning of the fused round
    over the ('dpu', 'rows') plane mesh must introduce no collectives
    beyond the gather/reduce/permute schedule of eq. 11."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.sharding import plane as shard_plane
    from repro.sharding.specs import sanitize_spec

    stack, batch, meta = _audit_mesh_round_args(n_dpu=4)
    mesh = shard_plane.plane_mesh((4, 2))
    spec = sanitize_spec(
        P(shard_plane.DPU_AXIS, shard_plane.ROW_AXIS, None),
        stack.data.shape, mesh)
    stack = stack.with_data(jax.device_put(
        stack.data, NamedSharding(mesh, spec)))
    step = build_cefl_round_step(_audit_micro_loss, _AUDIT_HYPER)
    return Program(fn=step, args=(stack, batch, meta))
