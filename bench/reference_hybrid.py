"""Plain reference of the hybrid LM cell (granite-4.0-h, ``granitemoehybrid``):
its forward pass, loss, gradients and CE-FL round in ``jax.numpy``.

Nothing here imports the program.  Weights come in the program's layout
(a nested dict; every block leaf has a leading period axis), the model's
sizes from the configuration file.  The equations, per layer ``l`` of
``layer_types``:

* ``x <- x + r * mixer_l(rmsnorm(x))``, the mixer Mamba-2 or attention;
* ``x <- x + r * W_out (silu(x' W_gate) * (x' W_in))`` with
  ``x' = rmsnorm(x)`` (SwiGLU of ``shared_intermediate_size``);

with ``r = residual_multiplier``, token embeddings times
``embedding_multiplier``, a final rmsnorm, tied logits divided by
``logits_scaling``, and the mean next-token cross-entropy.  Attention is
causal GQA with no positional embedding, the softmax scale
``attention_multiplier``, computed by query blocks.  Mamba-2 is the
masked-attention (quadratic) form of SSD (arXiv:2405.21060) with one
B/C group: ``y_t = sum_{s<=t} (C_t . B_s) exp(sum_{r=s+1}^t dt_r A) dt_s
x_s + D x_t``, then the gated rmsnorm ``rmsnorm(y * silu(z))`` and
``out_proj``.  The CE-FL round is as in ``reference.py``: local FedProx,
eq. 10, eq. 11.

Departures, each forced or a layout:

* RMSNorm weights are held as ``1 + scale`` (the program's layout);
* the decay exponent ``sum_{r=s+1}^t dt_r A`` is summed in blocks of
  ``DECAY_BLOCK`` steps (within a block, over the blocks between, and
  within the last block), each a sum of terms of one sign, so no
  difference of two long running sums loses digits at 4,096 steps;
  ``reference.segsum`` builds each block's own triangle;
* heads of the SSD and blocks of queries (attention) and tokens (the
  loss) are mapped in turn, each recomputed in the backward, and every
  layer is recomputed in the backward, so the reference fits one chip at
  4,096 tokens.  The numbers are the same as the whole-matrix form's;
* the round runs on the device leaf by leaf: ``x^t``, the local copy,
  its accumulated gradient and one gradient are held there, eq. 11 is
  applied to each leaf in float32 as it is finished.

What ``config.json`` leaves unset is the driver's, from the Mamba2
module's defaults: the dt range of the initial weights (dt_min 0.001,
dt_max 0.1, its bias the inverse softplus) and the head dim 64.

``dtype=float32`` runs every matmul at ``highest`` precision: that is the
reference.  ``dtype=bfloat16`` holds weights and activations in bfloat16:
that is the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from check import unflatten
from reference import _precision, a_coefficients, rms_norm, segsum

DECAY_BLOCK = 64        # steps per block of the SSD decay exponent
SSD_HEADS = 4           # SSD heads computed together
Q_BLOCK = 512           # attention queries per block
LOSS_BLOCK = 512        # tokens per block of the loss


def sizes(cfg: dict) -> dict:
    """The sizes the reference reads, from the configuration file."""
    d = cfg["hidden_size"]
    return {"d": d, "vocab": cfg["vocab_size"],
            "layer_types": tuple(cfg["layer_types"]),
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": d // cfg["num_attention_heads"],
            "d_inner": cfg["mamba_expand"] * d,
            "ssm_heads": cfg["mamba_n_heads"], "d_state": cfg["mamba_d_state"],
            "ssm_head_dim": cfg["mamba_d_head"],
            "eps": cfg["rms_norm_eps"],
            "emb_mult": cfg["embedding_multiplier"],
            "res_mult": cfg["residual_multiplier"],
            "attn_mult": cfg["attention_multiplier"],
            "logits_scaling": cfg["logits_scaling"]}


def period_length(layer_types) -> int:
    """The shortest period of the layer kinds (the program stacks the
    weights of layer ``l`` at ``[l // period]`` of ``layer_{l % period}``)."""
    n = len(layer_types)
    return next(p for p in range(1, n + 1) if n % p == 0 and all(
        layer_types[i] == layer_types[i % p] for i in range(n)))


# ------------------------------------------------------------- Mamba-2 --

def decay_exponent(a):
    """``out[..., t, s] = sum_{r=s+1}^{t} a[..., r]`` for ``s <= t``,
    ``-inf`` above the diagonal, for ``a: (..., T)`` of one sign, summed
    by blocks of ``DECAY_BLOCK`` (see the module's departures)."""
    T = a.shape[-1]
    Q = min(DECAY_BLOCK, T)
    nb = T // Q
    ab = a.reshape(a.shape[:-1] + (nb, Q))
    within = jnp.cumsum(ab, -1)                      # sum_{r<=i} in block
    after = jnp.flip(jnp.cumsum(jnp.flip(ab, -1), -1), -1)
    after = jnp.concatenate([after[..., 1:], jnp.zeros_like(after[..., :1])],
                            -1)                      # sum_{r>j} in block
    between = segsum(within[..., -1])                # [b, c]: blocks c+1..b
    between = jnp.concatenate(
        [jnp.full_like(between[..., :1, :], -jnp.inf), between[..., :-1, :]],
        -2)                                          # [b, c]: c+1..b-1
    off = (within[..., :, :, None, None] + between[..., :, None, :, None]
           + after[..., None, None, :, :])           # (..., b, i, c, j)
    diag = segsum(ab)                                # (..., b, i, j)
    blk = jnp.arange(nb)
    lower = (blk[:, None] > blk[None, :])[:, None, :, None]
    same = (blk[:, None] == blk[None, :])[:, None, :, None]
    out = jnp.where(lower, off, jnp.where(same, diag[..., :, :, None, :],
                                          -jnp.inf))
    return out.reshape(a.shape[:-1] + (T, T))


def ssd(x, dt, A, Bm, Cm):
    """The SSD scan in its masked-attention form.  ``x: (B, T, H, P)``,
    ``dt: (B, T, H)``, ``A: (H,)``, ``Bm, Cm: (B, T, N)``; returns
    ``(B, T, H, P)`` without the D skip."""
    dtype = x.dtype
    cb = jnp.einsum("btn,bsn->bts", Cm, Bm)                       # (B, T, S)

    @jax.checkpoint
    def head(inp):
        x_h, dt_h, a_h = inp                         # (B,T,P), (B,T), ()
        L = jnp.exp(decay_exponent(dt_h.astype(jnp.float32) * a_h))
        scores = cb * L.astype(dtype) * dt_h[:, None, :]
        return jnp.einsum("bts,bsp->btp", scores, x_h)

    y = jax.lax.map(head, (jnp.moveaxis(x, 2, 0), jnp.moveaxis(dt, 2, 0), A),
                    batch_size=min(SSD_HEADS, A.shape[0]))
    return jnp.moveaxis(y, 0, 2)


def mamba2_mixer(p, h, s: dict):
    """in_proj to (z, x, B, C, dt), causal depthwise conv with bias and
    SiLU over (x, B, C), SSD with the D skip, gated rmsnorm, out_proj."""
    Bsz, T, _ = h.shape
    d_inner, N, P = s["d_inner"], s["d_state"], s["ssm_head_dim"]
    H = s["ssm_heads"]
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
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    y = ssd(x.astype(dt_), dt.astype(dt_), A, Bm.astype(dt_), Cm.astype(dt_))
    y = y.astype(jnp.float32) + p["d_skip"][None, None, :, None] * x
    y = y.reshape(Bsz, T, d_inner) * jax.nn.silu(z.astype(jnp.float32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + s["eps"])
    y = y * (1.0 + p["norm"].astype(jnp.float32))
    return y.astype(dt_) @ p["w_out"]


# ----------------------------------------------------------- attention --

def attention(p, h, s: dict):
    """Causal GQA, no positional embedding, scale ``attention_multiplier``;
    query head ``i`` reads key/value head ``i // (heads / kv_heads)``."""
    Bsz, T, _ = h.shape
    Hkv, D = s["kv_heads"], s["head_dim"]
    G = s["heads"] // Hkv
    q = jnp.einsum("btd,dhx->bthx", h, p["wq"]).reshape(Bsz, T, Hkv, G, D)
    k = jnp.einsum("btd,dhx->bthx", h, p["wk"])
    v = jnp.einsum("btd,dhx->bthx", h, p["wv"])
    qb = min(Q_BLOCK, T)

    @jax.checkpoint
    def block(inp):
        q_blk, start = inp                           # (B, qb, Hkv, G, D)
        sc = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k,
                        preferred_element_type=jnp.float32) * s["attn_mult"]
        causal = (start + jnp.arange(qb))[:, None] >= jnp.arange(T)[None, :]
        sc = jnp.where(causal, sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1).astype(h.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", w, v)

    q_blocks = jnp.moveaxis(q.reshape(Bsz, T // qb, qb, Hkv, G, D), 1, 0)
    out = jax.lax.map(block, (q_blocks, jnp.arange(0, T, qb)))
    out = jnp.moveaxis(out, 0, 1).reshape(Bsz, T, Hkv * G, D)
    return jnp.einsum("bthx,hxd->btd", out, p["wo"])


# ------------------------------------------------------------------ LM --

def swiglu(p, h):
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_in"])) @ p["w_out"]


def layer_params(params, layer: int, period: int) -> dict:
    lp = params["blocks"][f"layer_{layer % period}"]
    return jax.tree.map(lambda a: a[layer // period], lp)


def hybrid_loss(params, tokens, labels, s: dict):
    """Mean next-token cross-entropy of the hybrid LM on ``(B, T)``."""
    period = period_length(s["layer_types"])
    x = jnp.take(params["embed"], tokens, axis=0) * s["emb_mult"]

    def layer(x, lp, kind):
        h = rms_norm(x, lp["ln1"], s["eps"])
        y = attention(lp["attn"], h, s) if kind == "attention" else \
            mamba2_mixer(lp["mamba"], h, s)
        x = x + s["res_mult"] * y
        h = rms_norm(x, lp["ln2"], s["eps"])
        return x + s["res_mult"] * swiglu(lp["mlp"], h)

    for i, kind in enumerate(s["layer_types"]):
        x = jax.checkpoint(layer, static_argnums=(2,))(
            x, layer_params(params, i, period), kind)
    x = rms_norm(x, params["final_norm"], s["eps"])
    Bsz, T, d = x.shape
    tb = min(LOSS_BLOCK, T)

    @jax.checkpoint
    def nll(inp):
        xx, ll = inp
        logits = jnp.einsum("btd,vd->btv", xx, params["embed"],
                            preferred_element_type=jnp.float32)
        logits = logits / s["logits_scaling"]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, ll[..., None], -1)[..., 0])

    xs = jnp.moveaxis(x.reshape(Bsz, T // tb, tb, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(Bsz, T // tb, tb), 1, 0)
    return jnp.sum(jax.lax.map(nll, (xs, ls))) / (Bsz * T)


@functools.lru_cache(maxsize=4)
def _fns(s_items: tuple, dtype_name: str):
    s = dict(s_items)
    dtype = jnp.dtype(dtype_name)

    def vg(p, tokens, labels):
        with _precision(dtype):
            return jax.value_and_grad(hybrid_loss)(p, tokens, labels, s)

    def leaf_step(p, g, acc, x0, a_k, eta, mu):
        p_new = (p - eta * (g + mu * (p - x0))).astype(dtype)
        return p_new, acc + a_k * g.astype(jnp.float32)

    def add_scaled(upd, acc, c):            # eq. 10's weighted sum over DPUs
        return upd + c * acc

    def eq11(x0, upd, theta_eta, altered):
        x0 = x0.astype(jnp.float32)
        x_next = (x0 - theta_eta * upd).astype(dtype)
        if altered:                          # the round's update scaled by 1.1
            x_next = (x0 + 1.1 * (x_next.astype(jnp.float32) - x0)
                      ).astype(dtype)
        return x_next

    return (jax.jit(vg), jax.jit(leaf_step, donate_argnums=(0, 2)),
            jax.jit(add_scaled, donate_argnums=(0,)),
            jax.jit(eq11, static_argnums=(3,), donate_argnums=(1,)))


def _items(s: dict) -> tuple:
    return tuple(sorted(s.items()))


def hybrid_round(x_t: dict, batches, cfg: dict, *, gamma: int, eta: float,
                 mu: float, theta: float, dtype=jnp.float32,
                 fault: str = ""):
    """One CE-FL round of the hybrid LM over DPUs of equal weight.
    ``x_t``: flat ``{"a.b.c": array}``, on the host or the device (it is
    read, never written); ``batches``: per DPU ``(tokens, labels)`` of
    shape ``(mb, seq)``.  Returns ``(x^{t+1}`` as a flat dict of device
    arrays, mean over DPUs of the last local step's loss).
    ``fault="half_batch"`` drops the second half of every DPU's batch (of
    its tokens, where it holds one sequence); ``fault="altered"`` scales
    the round's update by 1.1."""
    vg, leaf_step, add_scaled, eq11 = _fns(_items(sizes(cfg)),
                                           jnp.dtype(dtype).name)
    names = sorted(x_t)
    xt = {k: jax.device_put(x_t[k]).astype(dtype) for k in names}
    a = a_coefficients(gamma, eta, mu)
    upd, last = {}, []
    c = np.float32(1.0 / len(batches) / float(a.sum()))
    for tokens, labels in batches:
        if fault == "half_batch":    # half the sequences, or of one's
            cut = (slice(len(tokens) // 2),) if len(tokens) > 1 else \
                (slice(None), slice(tokens.shape[1] // 2))
            tokens, labels = tokens[cut], labels[cut]
        tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
        p = {k: jnp.copy(xt[k]) for k in names}
        acc = {k: jnp.zeros(xt[k].shape, jnp.float32) for k in names}
        for step in range(gamma):
            loss, g = vg(unflatten(p), tokens, labels)
            g = _flat(g)
            for k in names:
                p[k], acc[k] = leaf_step(p[k], g.pop(k), acc[k], xt[k],
                                         float(a[step]), eta, mu)
            del g
        last.append(float(loss))
        del p
        for k in names:
            upd[k] = add_scaled(upd[k] if k in upd else
                                jnp.zeros(xt[k].shape, jnp.float32),
                                acc.pop(k), c)
    theta_eta = np.float32(theta * eta)
    x_next = {k: eq11(xt[k], upd.pop(k), theta_eta, fault == "altered")
              for k in names}
    return x_next, float(np.mean(last))


def _flat(tree, prefix="") -> dict:
    """``check.flatten`` that leaves the arrays on the device."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}."))
        else:
            out[f"{prefix}{k}"] = v
    return out
