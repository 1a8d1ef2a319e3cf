"""granite-4.0-h-micro's hybrid period through the program, against the
plain reference of the benchmark (``bench/reference_hybrid.py``) on the
CPU at a small size: the ``MMMMMAMMMM`` period kept, hidden 64, 4 / 1
attention heads, vocab 512, seeded random weights.

Also: the new ``ModelConfig`` fields at their defaults add nothing to the
programs of the models that do not set them, per-layer recomputation
leaves a one-layer period as it was, the hybrid step labels its device
ops with the attention, MLP and SSD scopes, and the preset builds the
benchmark cell's model.
"""
import copy
import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.engine import MeshExecutor
from repro.core.round_step import CEFLHyper, make_dpu_meta
from repro.kernels.plane import ParamPlane
from repro.models import attention as A
from repro.models import blocks as B
from repro.models import lm as L
from repro.utils import tracing

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import reference_hybrid as R  # noqa: E402

# Tolerances, each with its reason.  The program and the reference sum in
# different orders (chunked SSD scan against its masked-attention form,
# flash attention against a plain softmax, chunked against whole-row
# logits), all in float32 on the CPU: the loss agrees to rounding
# (measured 0 to 2e-7 relative) and each leaf's gradient to a few parts
# in 1e6.  A bfloat16 reference misses every gradient tolerance by more
# than 100x (asserted below); its loss, ln(512) plus small terms, does not
# tell the two apart, so the loss is checked only for agreement.
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4       # worst leaf: |g - g_ref| / |g_ref|
# The round's update x^{t+1} - x^t is read as a difference of float32
# weights: each rounds to 6e-8 of its size, up to ~5e-4 of the update a
# leaf moves by in one round at eta 0.03 (measured 4.9e-4); a bfloat16
# round misses it by far more (asserted below).
STEP_RTOL = 2e-3
SEQ, BATCH = 32, 2


def _driver():
    spec = importlib.util.spec_from_file_location(
        "bench_driver_hybrid_lm_test", BENCH / "drivers" / "hybrid_lm.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


DRV = _driver()


def _real_file() -> dict:
    with open(BENCH / "configs" / "granite4_h_micro.json") as f:
        return json.load(f)


def small_file() -> dict:
    cfg = copy.deepcopy(_real_file())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=1,
               intermediate_size=128, shared_intermediate_size=128,
               vocab_size=512, mamba_n_heads=8, mamba_d_head=16,
               mamba_d_state=16, mamba_chunk_size=8)
    return cfg


def model_config(file: dict):
    return DRV.Cell.model_config(SimpleNamespace(cfg=file))


def weights(file: dict, seed: int = 0) -> dict:
    """The driver's seeded weights, with the norm scales drawn too."""
    cell = SimpleNamespace(cfg=file)
    x = DRV.init_hybrid(jax.random.PRNGKey(seed), DRV.Cell.dims(cell))
    leaves, tree = jax.tree_util.tree_flatten_with_path(x)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for k, (path, leaf) in zip(keys, leaves):
        name = jax.tree_util.keystr(path)
        if "ln" in name or "norm" in name:
            leaf = 0.1 * jax.random.normal(k, leaf.shape)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


def tokens(vocab: int, shape, seed: int = 0):
    t = np.random.RandomState(seed).randint(0, vocab, shape).astype(np.int32)
    return t, np.roll(t, -1, axis=-1)


def program_loss(p, cfg, tok, lab):
    return L.lm_loss(p, cfg, {"tokens": tok, "labels": lab}, remat=True,
                     q_block=16, kv_block=16)[0]


def worst_leaf(a: dict, b: dict) -> float:
    """The worst leaf's ``|a - b|`` over the larger of ``|b|`` and the
    median leaf's ``|b|`` (a leaf the round barely moves is held to the
    typical leaf's scale, as ``bench/check.py`` holds it)."""
    fa, fb = check.flatten(a), check.flatten(b)
    ref = {k: float(np.linalg.norm(np.asarray(fb[k], np.float64)))
           for k in fb}
    med = float(np.median(list(ref.values())))
    return max(float(np.linalg.norm(np.asarray(fa[k], np.float64)
                                    - np.asarray(fb[k], np.float64)))
               / max(ref[k], med) for k in fb)


@pytest.fixture(scope="module")
def small():
    file = small_file()
    return file, model_config(file), weights(file)


def test_small_config_keeps_the_period(small):
    file, cfg, x = small
    assert cfg.layer_pattern == "MMMMMAMMMM" and cfg.num_layers == 10
    assert len(B.period_spec(cfg)) == 10 and B.num_periods(cfg) == 1
    assert cfg.nope and cfg.attention_multiplier == 0.015625
    shapes = jax.eval_shape(lambda: L.init_lm_params(
        jax.random.PRNGKey(0), cfg, jnp.float32))
    assert jax.tree_util.tree_structure(shapes) == \
        jax.tree_util.tree_structure(x)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree_util.tree_leaves(shapes), jax.tree_util.tree_leaves(x)))


def test_loss_and_gradients_match_reference(small):
    file, cfg, x = small
    tok, lab = tokens(file["vocab_size"], (BATCH, SEQ))
    s = R.sizes(file)
    with jax.default_matmul_precision("highest"):
        l_ref, g_ref = jax.value_and_grad(R.hybrid_loss)(x, tok, lab, s)
    l_prog, g_prog = jax.value_and_grad(program_loss)(x, cfg, tok, lab)
    assert abs(float(l_prog) - float(l_ref)) <= LOSS_RTOL * float(l_ref)
    assert worst_leaf(g_prog, g_ref) <= GRAD_RTOL
    # the tolerance tells a bfloat16 reference from the float32 one
    xb = jax.tree.map(lambda v: v.astype(jnp.bfloat16), x)
    _, g_bf16 = jax.value_and_grad(R.hybrid_loss)(xb, tok, lab, s)
    assert worst_leaf(jax.tree.map(lambda v: v.astype(jnp.float32), g_bf16),
                      g_ref) > 100 * GRAD_RTOL


def test_round_matches_reference(small):
    """One ``MeshExecutor.build_step`` round on a ``ParamPlane`` of two
    DPUs, two local FedProx steps each, against the reference's round."""
    file, cfg, x = small
    n, gamma, eta, mu = 2, 2, 0.03, 0.01
    tok, lab = tokens(file["vocab_size"], (n, 1, 1, SEQ), seed=3)

    def loss_fn(p, micro, mask):
        return L.lm_loss(p, cfg, micro, example_mask=mask, remat=True,
                         q_block=16, kv_block=16)

    step = MeshExecutor().build_step(loss_fn, CEFLHyper(
        eta=eta, mu=mu, theta=float(gamma), gamma_max=gamma, n_micro=1))
    plane = ParamPlane.from_tree(x).broadcast(n)
    new, metrics = step(plane, {"tokens": jnp.asarray(tok),
                                "labels": jnp.asarray(lab)},
                        make_dpu_meta(n, gammas=[gamma] * n))
    x0 = check.flatten(jax.device_get(x))
    x_ref, loss_ref = R.hybrid_round(
        x0, [(tok[i, 0], lab[i, 0]) for i in range(n)], file, gamma=gamma,
        eta=eta, mu=mu, theta=float(gamma))
    x_prog = check.flatten(jax.device_get(new[0].to_tree()))
    u_prog = {k: x_prog[k] - x0[k] for k in x0}
    u_ref = {k: x_ref[k] - x0[k] for k in x0}
    assert worst_leaf(u_prog, u_ref) <= STEP_RTOL
    assert abs(float(metrics["loss"]) - loss_ref) <= LOSS_RTOL * loss_ref
    x_bf16, _ = R.hybrid_round(
        x0, [(tok[i, 0], lab[i, 0]) for i in range(n)], file, gamma=gamma,
        eta=eta, mu=mu, theta=float(gamma), dtype=jnp.bfloat16)
    u_bf16 = {k: x_bf16[k].astype(np.float32) - x0[k] for k in x0}
    assert worst_leaf(u_bf16, u_ref) > 10 * STEP_RTOL


# ------------------------------------------- defaults change nothing ----

def _seed_embed(params, cfg, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def _seed_unembed(params, cfg, x):
    table = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return jnp.einsum("...d,dv->...v", x, table,
                      preferred_element_type=jnp.float32)


def _loss_and_grad_jaxpr(cfg):
    p = L.init_lm_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    tok, lab = tokens(cfg.vocab_size, (2, 32))
    fn = jax.value_and_grad(lambda q: program_loss(q, cfg, tok, lab))
    return str(jax.make_jaxpr(fn)(p)), fn(p)


@pytest.mark.parametrize("arch", ["mamba2-130m", "qwen3-32b"])
def test_new_fields_at_defaults_add_nothing(arch, monkeypatch):
    """At their defaults the multipliers, the softmax scale and the
    positions leave the jaxpr of loss and gradients, and so every value,
    as the formulas without them give it."""
    cfg = reduced(get_config(arch))
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling, cfg.nope) == \
        (1.0, 1.0, None, 1.0, False)
    now, (loss, grads) = _loss_and_grad_jaxpr(cfg)
    monkeypatch.setattr(B, "residual_branch", lambda y, cfg: y)
    monkeypatch.setattr(L, "_token_embeddings", _seed_embed)
    monkeypatch.setattr(L, "unembed", _seed_unembed)
    monkeypatch.setattr(A, "softmax_scale", lambda scale, d: 1.0 / jnp.sqrt(
        jnp.asarray(d, jnp.float32)))
    seed, (loss0, grads0) = _loss_and_grad_jaxpr(cfg)
    assert now == seed
    assert np.array_equal(np.asarray(loss), np.asarray(loss0))
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(grads0)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_explicit_softmax_scale_is_the_default():
    """The forward pass and the flash backward take the scale from one
    argument: given as 1/sqrt(head_dim), it reads as the default."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 32, 4, 16))
    k = jax.random.normal(ks[1], (1, 32, 2, 16))
    v = jax.random.normal(ks[2], (1, 32, 2, 16))

    def f(scale):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(jnp.sin(
            A.blocked_attention(q, k, v, q_block=8, kv_block=8,
                                scale=scale))), argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(jax.tree_util.tree_leaves(f(None)),
                    jax.tree_util.tree_leaves(f(0.25))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    half = jax.tree_util.tree_leaves(f(0.125))
    assert not np.allclose(np.asarray(half[0]),
                           np.asarray(jax.tree_util.tree_leaves(f(None))[0]))


REMAT = ("remat2", "checkpoint")    # jax.checkpoint's primitive, by version


def _count(jaxpr, names) -> int:
    """Equations of a primitive in ``names`` in ``jaxpr`` and its
    sub-jaxprs."""
    def subs(v):
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(getattr(v, "jaxpr", None), "eqns"):
            yield v.jaxpr
        elif isinstance(v, (tuple, list)):
            for u in v:
                yield from subs(u)

    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name in names
        for v in eqn.params.values():
            n += sum(_count(j, names) for j in subs(v))
    return n


def _seed_backbone(params, cfg, x):
    """``lm_backbone`` as it was for a decoder with ``remat_chunk`` 1:
    each period checkpointed whole."""
    specs = B.period_spec(cfg)
    angles = L._angles(cfg, x.shape[1])

    def period_fn(x, pp):
        lb = jnp.zeros((), jnp.float32)
        rz = jnp.zeros((), jnp.float32)
        for j, spec in enumerate(specs):
            x, aux, _, _ = B.layer_forward(pp[f"layer_{j}"], x, cfg, spec,
                                           angles=angles, q_block=16,
                                           kv_block=16)
            lb = lb + aux["load_balance"]
            rz = rz + aux["router_z"]
        return x, (lb, rz)

    body = jax.checkpoint(period_fn)

    def scan_body(carry, pp):
        x, lb, rz = carry
        x, (dlb, drz) = body(x, pp)
        return (x, lb + dlb, rz + drz), None

    zero = jnp.zeros((), jnp.float32)
    (x, lb, rz), _ = jax.lax.scan(scan_body, (x, zero, zero),
                                  params["blocks"])
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    n = cfg.num_layers
    return x, {"load_balance": lb / n, "router_z": rz / n}


def test_per_layer_recomputation():
    """A one-layer period's backbone is checkpointed whole, as it was; a
    ten-layer period checkpoints each layer and not the period."""
    def jaxpr(fn, cfg):
        p = L.init_lm_params(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jnp.ones((1, 32, cfg.d_model))
        return jax.make_jaxpr(jax.grad(lambda p: jnp.sum(fn(p, cfg, x))))(p)

    def now(p, cfg, x):
        return L.lm_backbone(p, cfg, x, remat=True, q_block=16,
                             kv_block=16)[0]

    def seed(p, cfg, x):
        return _seed_backbone(p, cfg, x)[0]

    one = reduced(get_config("mamba2-130m"))
    assert str(jaxpr(now, one)) == str(jaxpr(seed, one))
    hybrid = model_config(small_file())
    per_period = _count(jaxpr(seed, hybrid).jaxpr, REMAT)
    assert per_period >= 1
    assert _count(jaxpr(now, hybrid).jaxpr, REMAT) == 10 * per_period


def test_hybrid_step_carries_the_scopes(small):
    """The compiled round step of the hybrid period labels its ops
    (``op_name``) with the attention, MLP and SSD scopes."""
    file, cfg, x = small

    def loss_fn(p, micro, mask):
        return L.lm_loss(p, cfg, micro, example_mask=mask, remat=True,
                         q_block=16, kv_block=16)

    step = MeshExecutor().build_step(loss_fn, CEFLHyper(
        eta=0.03, mu=0.01, theta=2.0, gamma_max=2, n_micro=1), jit=False)
    tok, lab = tokens(file["vocab_size"], (1, 1, 1, SEQ))
    plane = ParamPlane.from_tree(x).broadcast(1)
    text = jax.jit(step).lower(plane, {"tokens": tok, "labels": lab},
                               make_dpu_meta(1, gammas=[2])
                               ).compile().as_text()
    for scope in (tracing.ATTN, tracing.MLP, tracing.SSD):
        assert f"/{scope}/" in text, scope


# ------------------------------------------------ config and preset ----

def test_cell_file_is_the_program_preset():
    """At published widths the benchmark's file and the program's preset
    give the same model; its parameters are counted alike by the driver,
    the file and the program's layout."""
    from repro.experiments import get_experiment
    file = _real_file()
    m = get_experiment("lm_granite4_h_micro").model
    preset = dataclasses.replace(get_config(m.arch), num_layers=m.layers,
                                 vocab_size=m.vocab)
    assert model_config(file) == preset
    assert (m.batch, m.seq, m.n_dpu, m.gamma) == (1, 4096, 1, 2)
    shapes = jax.eval_shape(lambda: L.init_lm_params(
        jax.random.PRNGKey(0), preset, jnp.float32))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == DRV.hybrid_params(file) == file["params"] == 797_850_560
    published = dict(file, **{k: file["published"][k] for k in
                              ("num_hidden_layers", "vocab_size")})
    published["layer_types"] = file["layer_types"] * 4
    assert DRV.hybrid_params(published) == file["published"]["params"]
