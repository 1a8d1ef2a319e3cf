"""Mesh-native CE-FL LM training from a spec (``ModelSpec.kind="lm"``).

This is the old ``launch/train.py`` main loop expressed over
:class:`~repro.experiments.spec.ExperimentSpec`: the jitted SPMD round
step built through the engine's :class:`~repro.core.engine.MeshExecutor`
on the flat parameter plane, driven for ``engine.rounds`` rounds of
synthetic token batches.  ``launch/train.py`` remains as a thin argparse
shim over this function.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.core.api import RunResult, RoundReport
from repro.core.engine import MeshExecutor
from repro.core.round_step import CEFLHyper, make_dpu_meta
from repro.data import make_token_batches
from repro.experiments.spec import ExperimentSpec, get_experiment
from repro.kernels.plane import ParamPlane
from repro.models import lm as L
from repro.training.checkpoint import save_checkpoint


def run_lm(spec: ExperimentSpec, *, seed=None, checkpoint=None,
           use_plane: bool = True, verbose: bool = True) -> RunResult:
    """Train the spec's LM arch with the mesh-native CE-FL round.

    Returns a :class:`RunResult` whose reports carry the per-round loss
    (network-cost fields are zero — there is no radio plane under the
    mesh launcher); ``result.params`` is the trained tree of DPU 0.
    """
    spec = get_experiment(spec)
    m = spec.model
    assert m.kind == "lm", m.kind
    seed = spec.run_seeds[0] if seed is None else int(seed)
    cfg = get_config(m.arch)
    if m.reduced:
        cfg = reduced(cfg)
    if m.layers:
        cfg = dataclasses.replace(cfg, num_layers=m.layers)
    if m.vocab:
        cfg = dataclasses.replace(cfg, vocab_size=m.vocab)
    if verbose:
        print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
              f"{m.n_dpu} DPUs x gamma={m.gamma}")
    key = jax.random.PRNGKey(seed)
    params0 = L.init_lm_params(key, cfg, jnp.float32)
    if use_plane:
        # flat-plane hot path: params stay (n_dpu, R, LANE) for the whole
        # run; the tree view is materialized only at the checkpoint
        params = ParamPlane.from_tree(params0).broadcast(m.n_dpu)
        del params0          # the planes hold the weights from here on
    else:
        params = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (m.n_dpu,) + x.shape),
            params0)

    def loss_fn(p, micro, mask):
        return L.lm_loss(p, cfg, micro, example_mask=mask, remat=True,
                         q_block=min(512, m.seq),
                         kv_block=min(512, m.seq))

    hyper = CEFLHyper(eta=spec.engine.eta, mu=spec.engine.mu,
                      theta=float(m.gamma),   # tau_eff compensation
                      gamma_max=m.gamma, n_micro=m.n_micro)
    step = MeshExecutor().build_step(loss_fn, hyper)   # jitted, donating
    meta = make_dpu_meta(m.n_dpu, gammas=[m.gamma] * m.n_dpu)

    mb = m.batch // (m.n_dpu * m.n_micro)
    reports = []
    for t in range(spec.engine.rounds):
        b = make_token_batches(
            cfg.vocab_size, m.n_dpu, m.n_micro, mb, m.seq,
            seed=seed * 10000 + t,
            enc_seq=cfg.encoder_seq if cfg.is_encdec else 0,
            d_model=cfg.d_model)
        b = {k: jnp.asarray(v) for k, v in b.items()}
        t0 = time.time()
        params, metrics = step(params, b, meta)
        loss = float(metrics["loss"])
        wall = time.time() - t0
        if verbose:
            print(f"  round {t:4d}  loss {loss:8.4f}  ({wall:.2f}s)")
        reports.append(RoundReport(
            round=t, acc=float("nan"), loss=loss, energy=0.0, delay=0.0,
            cum_energy=0.0, cum_delay=0.0, aggregator=0, dc_points=(),
            gamma_mean=float(m.gamma), m_mean=1.0, wall_time=wall))
    final = (params[0].to_tree() if isinstance(params, ParamPlane)
             else jax.tree_util.tree_map(lambda x: x[0], params))
    if checkpoint:
        save_checkpoint(checkpoint, final, step=spec.engine.rounds,
                        metadata={"arch": m.arch, "seed": seed})
        if verbose:
            print(f"[train] checkpoint -> {checkpoint}")
    losses = [r.loss for r in reports]
    assert losses[-1] < losses[0], "loss did not decrease"
    if verbose:
        print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return RunResult(reports=reports, params=final)
