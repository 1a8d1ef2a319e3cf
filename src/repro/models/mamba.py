"""Mamba-2 (SSD: state-space duality, arXiv:2405.21060) mixer.

Training / prefill use the chunked dual form: intra-chunk attention-like
matmuls + an inter-chunk state recurrence carried by ``lax.scan``.  Decode is
the O(1) recurrent step.  ngroups=1 (B/C shared across heads), following the
130m config.

Shapes: d_inner = expand * d_model; H = d_inner / head_dim (P); state N.
State: h (B, H, P, N).  Conv state: (B, conv_width-1, d_conv) where
d_conv = d_inner + 2N (the xBC channels, as in the reference implementation).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SSMConfig


def mamba_dims(d_model: int, s: SSMConfig):
    d_inner = s.expand * d_model
    nheads = d_inner // s.head_dim
    d_conv = d_inner + 2 * s.state_dim
    return d_inner, nheads, d_conv


def init_mamba_params(key, d_model: int, s: SSMConfig, dtype) -> dict:
    d_inner, H, d_conv = mamba_dims(d_model, s)
    ks = jax.random.split(key, 4)
    scale = 1.0 / np.sqrt(d_model)
    d_in_proj = 2 * d_inner + 2 * s.state_dim + H   # z, x, B, C, dt
    dt = jnp.exp(jax.random.uniform(ks[2], (H,)) *
                 (np.log(s.dt_max) - np.log(s.dt_min)) + np.log(s.dt_min))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))          # inverse softplus
    return {
        "w_in": (jax.random.normal(ks[0], (d_model, d_in_proj)) * scale).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (s.conv_width, d_conv)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((d_conv,), dtype),
        "a_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "dt_bias": dt_bias.astype(jnp.float32),
        "d_skip": jnp.ones((H,), jnp.float32),
        "norm": jnp.zeros((d_inner,), dtype),
        "w_out": (jax.random.normal(ks[3], (d_inner, d_model))
                  * (1.0 / np.sqrt(d_inner))).astype(dtype),
    }


def _split_in_proj(proj, d_inner, N, H):
    z = proj[..., :d_inner]
    xBC = proj[..., d_inner:2 * d_inner + 2 * N]
    dt = proj[..., 2 * d_inner + 2 * N:]
    return z, xBC, dt


def _causal_conv(xBC, conv_w, conv_b, conv_state=None):
    """Depthwise causal conv over sequence.  xBC: (B,S,Cc); conv_w: (W,Cc).
    conv_state: (B,W-1,Cc) trailing context (for decode/prefill chaining)."""
    W = conv_w.shape[0]
    if conv_state is None:
        pad = jnp.zeros(xBC.shape[:1] + (W - 1,) + xBC.shape[2:], xBC.dtype)
    else:
        pad = conv_state.astype(xBC.dtype)
    xp = jnp.concatenate([pad, xBC], axis=1)
    out = sum(xp[:, i:i + xBC.shape[1]] * conv_w[i] for i in range(W))
    new_state = xp[:, xp.shape[1] - (W - 1):]
    return jax.nn.silu((out + conv_b).astype(jnp.float32)).astype(xBC.dtype), new_state


def _gated_rmsnorm(y, z, scale, eps=1e-5):
    y = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    return y * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def ssd_forward(params: dict, x_in: jnp.ndarray, s: SSMConfig,
                init_state: Optional[dict] = None,
                return_state: bool = False):
    """Chunked SSD. x_in: (B, S, d_model); S % chunk == 0.
    Returns y (B,S,d_model) and optionally {"h":..., "conv":...}."""
    B, S, d_model = x_in.shape
    d_inner, H, d_conv = mamba_dims(d_model, s)
    N, P, Q = s.state_dim, s.head_dim, s.chunk_size
    assert S % Q == 0, (S, Q)
    nc = S // Q

    proj = x_in @ params["w_in"]
    z, xBC, dt_raw = _split_in_proj(proj, d_inner, N, H)
    conv_state0 = None if init_state is None else init_state["conv"]
    xBC, conv_state = _causal_conv(xBC, params["conv_w"], params["conv_b"],
                                   conv_state0)
    x = xBC[..., :d_inner].reshape(B, S, H, P).astype(jnp.float32)
    Bm = xBC[..., d_inner:d_inner + N].astype(jnp.float32)       # (B,S,N)
    Cm = xBC[..., d_inner + N:].astype(jnp.float32)              # (B,S,N)

    # optional activation-sharding hint (batch->data, heads->model); same
    # rationale as attention.set_shard_hint (see EXPERIMENTS.md §Perf)
    from repro.models.attention import _constrain_bshd
    x = _constrain_bshd(x)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])  # (B,S,H)
    A = -jnp.exp(params["a_log"])                                # (H,)
    log_a = dt * A[None, None, :]                                # (B,S,H) <= 0

    # chunk views
    xc = x.reshape(B, nc, Q, H, P)
    Bc = Bm.reshape(B, nc, Q, N)
    Cc = Cm.reshape(B, nc, Q, N)
    dtc = dt.reshape(B, nc, Q, H)
    lac = log_a.reshape(B, nc, Q, H)
    cum = jnp.cumsum(lac, axis=2)                                # inclusive
    chunk_decay = cum[:, :, -1]                                  # (B,nc,H)

    # intra-chunk (dual / attention-like) term
    # L[t,j] = exp(cum_t - cum_j) for t >= j.  Mask before the exp: above
    # the diagonal diff > 0 overflows to inf at real widths, and the
    # gradient of a where() taken after the exp is then 0 * inf = NaN.
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]         # (B,nc,Q,Q,H)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(jnp.where(tri[None, None, :, :, None], diff, -jnp.inf))
    cb = jnp.einsum("bctn,bcjn->bctj", Cc, Bc)                   # (B,nc,Q,Q)
    scores = cb[..., None] * L * dtc[:, :, None, :, :]           # (B,nc,Q,Q,H)
    y_intra = jnp.einsum("bctjh,bcjhp->bcthp", scores, xc)

    # inter-chunk recurrence over chunk index
    # state contribution of chunk: sum_j exp(cum_end - cum_j) dt_j B_j x_j
    w_end = jnp.exp(chunk_decay[:, :, None, :] - cum) * dtc      # (B,nc,Q,H)
    chunk_state = jnp.einsum("bcjh,bcjn,bcjhp->bchpn", w_end, Bc, xc)

    def step(h, inp):
        cs, cd = inp                                             # (B,H,P,N),(B,H)
        h_new = h * jnp.exp(cd)[:, :, None, None] + cs
        return h_new, h                                          # emit previous

    if init_state is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)
    else:
        h0 = init_state["h"]
    h_final, h_prevs = jax.lax.scan(
        step, h0, (chunk_state.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    h_prevs = h_prevs.swapaxes(0, 1)                             # (B,nc,H,P,N)

    y_inter = jnp.einsum("bctn,bchpn,bcth->bcthp", Cc, h_prevs, jnp.exp(cum))
    y = (y_intra + y_inter).reshape(B, S, H, P)
    y = y + params["d_skip"][None, None, :, None] * x.reshape(B, S, H, P)
    y = y.reshape(B, S, d_inner)
    y = _gated_rmsnorm(y, z, params["norm"])
    out = (y.astype(x_in.dtype)) @ params["w_out"]
    if return_state:
        return out, {"h": h_final, "conv": conv_state}
    return out


def mamba_decode_step(params: dict, x_in: jnp.ndarray, state: dict,
                      s: SSMConfig):
    """Single-token recurrent step. x_in: (B, d_model); state h/conv."""
    B, d_model = x_in.shape
    d_inner, H, d_conv = mamba_dims(d_model, s)
    N, P = s.state_dim, s.head_dim
    proj = x_in @ params["w_in"]
    z, xBC, dt_raw = _split_in_proj(proj, d_inner, N, H)
    # conv: append token, take last W window
    conv_state = state["conv"]                                   # (B,W-1,Cc)
    window = jnp.concatenate([conv_state.astype(xBC.dtype), xBC[:, None]], axis=1)
    out = jnp.einsum("bwc,wc->bc", window, params["conv_w"]) + params["conv_b"]
    xBC = jax.nn.silu(out.astype(jnp.float32))
    new_conv = window[:, 1:]
    x = xBC[:, :d_inner].reshape(B, H, P)
    Bm = xBC[:, d_inner:d_inner + N]
    Cm = xBC[:, d_inner + N:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])  # (B,H)
    A = -jnp.exp(params["a_log"])
    a = jnp.exp(dt * A[None])                                    # (B,H)
    h = state["h"] * a[:, :, None, None] + jnp.einsum(
        "bh,bn,bhp->bhpn", dt, Bm, x)
    y = jnp.einsum("bn,bhpn->bhp", Cm, h)
    y = y + params["d_skip"][None, :, None] * x
    y = _gated_rmsnorm(y.reshape(B, d_inner), z, params["norm"])
    out = y.astype(x_in.dtype) @ params["w_out"]
    return out, {"h": h, "conv": new_conv}


def init_mamba_state(batch: int, d_model: int, s: SSMConfig, dtype):
    d_inner, H, d_conv = mamba_dims(d_model, s)
    return {
        "h": jnp.zeros((batch, H, s.head_dim, s.state_dim), jnp.float32),
        "conv": jnp.zeros((batch, s.conv_width - 1, d_conv), dtype),
    }
