"""Plain references of the two workloads: a CE-FL round over an MLP
classifier and over a Mamba-2 language model, in ``jax.numpy``.

Nothing here imports the program.  The references read the round's feed
(the data rows each DPU holds, its local iterations gamma_i and
mini-batch size, the round's PRNG key) and weights in the harness's own
layout, and follow the paper's equations directly:

* local FedProx at DPU i (eqs. 5-7): gamma_i steps of
  ``x <- x - eta * (grad F_i(x) + mu * (x - x^t))`` on a uniform
  mini-batch without replacement, drawn from the step key;
* eq. 10: ``d_i = sum_l a_l grad F_i(x^{t,l}) / sum_l a_l`` with
  ``a_l = (1 - eta * mu)^(gamma_i - 1 - l)``;
* eq. 11: ``x^{t+1} = x^t - theta * eta * sum_i w_i d_i``.

``dtype=float32`` runs every matmul at ``highest`` precision: that is the
reference.  ``dtype=bfloat16`` holds weights and activations in bfloat16:
that is the control, the step below the configuration's float32.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np


def _precision(dtype):
    return jax.default_matmul_precision("highest") \
        if jnp.dtype(dtype) == jnp.float32 else contextlib.nullcontext()


def a_coefficients(gamma: int, eta: float, mu: float) -> np.ndarray:
    return (1.0 - eta * mu) ** (gamma - 1.0 - np.arange(gamma))


def cast(tree, dtype):
    return jax.tree.map(lambda x: jnp.asarray(x, dtype), tree)


def altered(x_t, x_next, scale: float = 1.1):
    """The fault of an answer altered where it is produced: the round's
    update scaled by ``scale``."""
    return jax.tree.map(lambda a, b: a + scale * (b - jnp.asarray(a, b.dtype)),
                        x_t, x_next)


# ------------------------------------------------------ MLP classifier --

def mlp_logits(params, x):
    h = x.reshape(x.shape[0], -1)
    n = len(params) // 2
    for i in range(n):
        h = h @ params[f"w{i}"] + params[f"b{i}"]
        if i < n - 1:
            h = jnp.maximum(h, 0)
    return h


def mlp_loss(params, x, y, w):
    """Mean cross-entropy over the examples with weight 1 (0 where a
    padded step holds none)."""
    logits = mlp_logits(params, x).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, y[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


@functools.lru_cache(maxsize=4)
def _mlp_local(dtype_name: str):
    """Jitted local FedProx of one DPU over padded mini-batches
    ``(steps, B, ...)``; steps past gamma_i have ``active = 0``."""
    dtype = jnp.dtype(dtype_name)
    vg = jax.value_and_grad(mlp_loss)

    def local(x_t, bx, by, bw, active, a, eta, mu):
        def step(carry, inp):
            p, acc = carry
            xk, yk, wk, act, ak = inp
            loss, g = vg(p, xk, yk, wk)
            p = jax.tree.map(
                lambda pp, gg, x0: (pp - act * eta * (gg + mu * (pp - x0))
                                    ).astype(dtype), p, g, x_t)
            acc = jax.tree.map(lambda s, gg: s + act * ak * gg.astype(
                jnp.float32), acc, g)
            return (p, acc), loss * act

        acc0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), x_t)
        (_, acc), losses = jax.lax.scan(step, (x_t, acc0),
                                        (bx, by, bw, active, a))
        return acc, jnp.sum(losses)

    def traced(*args):
        with _precision(dtype):
            return local(*args)

    return jax.jit(traced)


@functools.lru_cache(maxsize=None)
def _choice(num_examples: int, bsz: int):
    return jax.jit(jax.vmap(lambda k: jax.random.choice(
        k, num_examples, (bsz,), replace=False)))


def minibatch_indices(step_keys, num_examples: int, bsz: int) -> np.ndarray:
    """Uniform without-replacement draws of ``bsz`` of ``num_examples``,
    one from each step key (``jax.random.choice``, on the host CPU)."""
    keys = jax.device_put(np.asarray(step_keys), jax.devices("cpu")[0])
    return np.asarray(_choice(num_examples, bsz)(keys))


def cefl_round(x_t: dict, feed: dict, *, eta: float, mu: float,
               dtype=jnp.float32, fault: str = ""):
    """One CE-FL round of the classifier.  ``feed``: ``dpus``, a list of
    ``{"x", "y", "gamma", "bsz", "step_keys"}`` (host arrays), and
    ``theta``.  Returns ``(x^{t+1}, weighted mean loss)``.

    ``fault`` plants one of the faults a check must catch:
    ``"half_batch"`` drops the second half of every mini-batch.
    """
    dpus = feed["dpus"]
    steps = max(d["gamma"] for d in dpus)
    width = max(d["bsz"] for d in dpus)
    width = -(-width // 256) * 256
    local = _mlp_local(jnp.dtype(dtype).name)
    xt = cast(x_t, dtype)
    sizes = np.array([len(d["y"]) for d in dpus], np.float64)
    weights = sizes / sizes.sum()
    upd = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), x_t)
    loss = 0.0
    for d, w in zip(dpus, weights):
        bsz = d["bsz"] // 2 if fault == "half_batch" else d["bsz"]
        shape = d["x"].shape[1:]
        bx = np.zeros((steps, width) + shape, np.float32)
        by = np.zeros((steps, width), np.int32)
        bw = np.zeros((steps, width), np.float32)
        draws = minibatch_indices(d["step_keys"], len(d["y"]), d["bsz"])
        for k, idx in enumerate(draws[:, :bsz]):
            bx[k, :bsz], by[k, :bsz], bw[k, :bsz] = \
                d["x"][idx], d["y"][idx], 1.0
        active = (np.arange(steps) < d["gamma"]).astype(np.float32)
        a = np.zeros(steps, np.float32)
        a[:d["gamma"]] = a_coefficients(d["gamma"], eta, mu)
        acc, loss_sum = local(xt, jnp.asarray(bx, dtype), jnp.asarray(by),
                              jnp.asarray(bw, dtype), jnp.asarray(active),
                              jnp.asarray(a), eta, mu)
        a1 = float(a.sum())
        upd = jax.tree.map(lambda u, s: u + (w / a1) * s, upd, acc)
        loss += w * float(loss_sum) / d["gamma"]
    x_next = jax.tree.map(
        lambda x, u: (jnp.asarray(x, jnp.float32)
                      - feed["theta"] * eta * u).astype(dtype), xt, upd)
    return x_next, loss


@functools.lru_cache(maxsize=4)
def _mlp_accuracy(dtype_name: str):
    dtype = jnp.dtype(dtype_name)

    def acc(params, x, y):
        with _precision(dtype):
            pred = jnp.argmax(mlp_logits(params, x), axis=-1)
        return jnp.mean((pred == y).astype(jnp.float32))

    return jax.jit(acc)


def mlp_accuracy(params, x, y, dtype=jnp.float32) -> float:
    return float(_mlp_accuracy(jnp.dtype(dtype).name)(
        cast(params, dtype), jnp.asarray(x, dtype), jnp.asarray(y)))


# ------------------------------------------------------------- Mamba-2 --

def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def segsum(a):
    """``out[..., t, s] = sum_{r = s+1}^{t} a[..., r]`` for ``s <= t``,
    ``-inf`` above the diagonal (so ``exp`` gives the SSD decay matrix
    with no overflow and no NaN gradient)."""
    T = a.shape[-1]
    rep = jnp.broadcast_to(a[..., :, None], a.shape + (T,))   # [t, s] = a_t
    rep = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), rep, 0)
    out = jnp.cumsum(rep, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), out, -jnp.inf)


def mamba2_mixer(p, h, cfg):
    """The Mamba-2 block of arXiv:2405.21060 on ``h: (B, T, d_model)``:
    in_proj to (z, x, B, C, dt), causal depthwise conv and SiLU over
    (x, B, C), the SSD recurrence in its quadratic (masked-attention)
    form with one B/C group, the D skip, gated RMSNorm, out_proj."""
    Bsz, T, _ = h.shape
    d_inner = cfg["expand"] * cfg["d_model"]
    N, P = cfg["d_state"], cfg["headdim"]
    H = d_inner // P
    dt_ = h.dtype
    zxbcdt = h @ p["w_in"]
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * N]
    dt_raw = zxbcdt[..., 2 * d_inner + 2 * N:]
    W = p["conv_w"].shape[0]
    xpad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(xpad[:, k:k + T] * p["conv_w"][k] for k in range(W))
    xbc = jax.nn.silu((conv + p["conv_b"]).astype(jnp.float32))
    x = xbc[..., :d_inner].reshape(Bsz, T, H, P)
    Bm = xbc[..., d_inner:d_inner + N]
    Cm = xbc[..., d_inner + N:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # B,T,H
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    L = jnp.exp(segsum(jnp.moveaxis(dt * A, -1, 1)))               # B,H,T,T
    cb = jnp.einsum("btn,bsn->bts", Cm.astype(dt_), Bm.astype(dt_))
    scores = cb[:, None] * L.astype(dt_) * jnp.moveaxis(
        dt, -1, 1)[:, :, None, :].astype(dt_)                      # B,H,T,S
    y = jnp.einsum("bhts,bshp->bthp", scores, x.astype(dt_))
    y = y.astype(jnp.float32) + p["d_skip"][None, None, :, None] * x
    y = y.reshape(Bsz, T, d_inner) * jax.nn.silu(z.astype(jnp.float32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg["norm_eps"])
    y = y * (1.0 + p["norm"].astype(jnp.float32))
    return y.astype(dt_) @ p["w_out"]


def mamba2_loss(params, tokens, labels, cfg):
    """Mean next-token cross-entropy of the tied-embedding Mamba-2 LM."""
    x = jnp.take(params["embed"], tokens, axis=0)
    blocks = params["blocks"]["layer_0"]

    @jax.checkpoint
    def layer(x, lp):
        h = rms_norm(x, lp["ln1"], cfg["norm_eps"])
        return x + mamba2_mixer(lp["mamba"], h, cfg), None

    x, _ = jax.lax.scan(layer, x, blocks)
    x = rms_norm(x, params["final_norm"], cfg["norm_eps"])
    logits = jnp.einsum("btd,vd->btv", x, params["embed"],
                        preferred_element_type=jnp.float32)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[..., None], -1)[..., 0]
    return jnp.mean(nll)


@functools.lru_cache(maxsize=4)
def _lm_fns(cfg_items: tuple, dtype_name: str):
    cfg = dict(cfg_items)
    dtype = jnp.dtype(dtype_name)

    def vg(p, tokens, labels):
        with _precision(dtype):
            return jax.value_and_grad(mamba2_loss)(p, tokens, labels, cfg)

    def step(p, g, acc, x_t, act_a, eta, mu):
        p = jax.tree.map(lambda pp, gg, x0: (pp - eta * (gg + mu * (pp - x0))
                                             ).astype(dtype), p, g, x_t)
        acc = jax.tree.map(lambda s, gg: s + act_a * gg.astype(jnp.float32),
                           acc, g)
        return p, acc

    return jax.jit(vg), jax.jit(step, donate_argnums=(0, 2))


def lm_round(x_t: dict, batches, cfg: dict, *, gamma: int, eta: float,
             mu: float, theta: float, dtype=jnp.float32, fault: str = ""):
    """One CE-FL round of the LM over DPUs of equal weight.  ``batches``:
    per DPU ``(tokens, labels)`` of shape ``(mb, seq)``.  Returns
    ``(x^{t+1}, mean over DPUs of the last local step's loss)``.
    ``fault="half_batch"`` drops the second half of every DPU's batch."""
    keys = ("n_layer", "d_model", "expand", "d_state", "headdim",
            "norm_eps")
    vg, step = _lm_fns(tuple((k, cfg[k]) for k in keys),
                       jnp.dtype(dtype).name)
    xt = cast(x_t, dtype)
    a = a_coefficients(gamma, eta, mu)
    upd = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), x_t)
    last = []
    w = 1.0 / len(batches)
    for tokens, labels in batches:
        if fault == "half_batch":
            tokens, labels = tokens[:len(tokens) // 2], \
                labels[:len(labels) // 2]
        tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
        p = jax.tree.map(jnp.copy, xt)
        acc = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), x_t)
        for k in range(gamma):
            loss, g = vg(p, tokens, labels)
            p, acc = step(p, g, acc, xt, float(a[k]), eta, mu)
        last.append(float(loss))
        upd = jax.tree.map(lambda u, s: u + (w / float(a.sum())) * s,
                           upd, acc)
        del p, acc
    x_next = jax.tree.map(
        lambda x, u: (jnp.asarray(x, jnp.float32) - theta * eta * u
                      ).astype(dtype), xt, upd)
    return x_next, float(np.mean(last))
