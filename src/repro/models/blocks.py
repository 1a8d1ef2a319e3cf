"""Decoder layers (attention / mamba mixers + MLP / MoE) and period specs.

Layers are scanned over "periods": the smallest repeating pattern of layer
kinds (attention vs mamba) and MoE placement.  Params for one period are a
dict ``{"layer_0": {...}, ...}``; the full stack adds a leading period axis to
every leaf, consumed by ``lax.scan``.
"""
from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models.common import (ShardCtx, apply_rope, dense_init,
                                 rms_norm, rope_frequencies)
from repro.utils import tracing


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str          # 'A' | 'M'
    use_moe: bool
    has_mlp: bool      # dense MLP present (False for mamba2 pure blocks)


def period_spec(cfg: ModelConfig) -> List[LayerSpec]:
    pat = cfg.layer_pattern
    moe_n = cfg.moe.every_n_layers if cfg.moe else 1
    plen = int(np.lcm(len(pat), moe_n)) if cfg.moe else len(pat)
    specs = []
    for i in range(plen):
        kind = pat[i % len(pat)]
        use_moe = cfg.moe is not None and (i % moe_n == moe_n - 1)
        has_mlp = (cfg.d_ff > 0) and not use_moe
        specs.append(LayerSpec(kind, use_moe, has_mlp))
    return specs


def num_periods(cfg: ModelConfig) -> int:
    plen = len(period_spec(cfg))
    assert cfg.num_layers % plen == 0, (cfg.name, cfg.num_layers, plen)
    return cfg.num_layers // plen


# ---------------------------------------------------------------- init ----

def init_attn_params(key, cfg: ModelConfig, dtype, cross: bool = False):
    d, Hq, Hkv, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, (Hq, Dh), dtype),
        "wk": dense_init(ks[1], d, (Hkv, Dh), dtype),
        "wv": dense_init(ks[2], d, (Hkv, Dh), dtype),
        "wo": (jax.random.normal(ks[3], (Hq, Dh, d)) /
               np.sqrt(Hq * Dh)).astype(dtype),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((Dh,), dtype)
        p["k_norm"] = jnp.zeros((Dh,), dtype)
    return p


def init_mlp_params(key, cfg: ModelConfig, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w_in": dense_init(ks[0], d, (f,), dtype),
        "w_out": dense_init(ks[1], f, (d,), dtype),
    }
    if cfg.gated_mlp:
        p["w_gate"] = dense_init(ks[2], d, (f,), dtype)
    return p


def init_layer_params(key, cfg: ModelConfig, spec: LayerSpec, dtype):
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    p = {"ln1": jnp.zeros((d,), dtype)}
    if spec.kind == "A":
        p["attn"] = init_attn_params(ks[0], cfg, dtype)
    else:
        p["mamba"] = mamba_lib.init_mamba_params(ks[0], d, cfg.ssm, dtype)
    if spec.use_moe:
        p["ln2"] = jnp.zeros((d,), dtype)
        p["moe"] = moe_lib.init_moe_params(ks[1], d, cfg.moe, dtype)
        if cfg.moe.dense_residual or cfg.moe.shared_expert:
            p["mlp"] = init_mlp_params(ks[2], cfg, dtype)
    elif spec.has_mlp:
        p["ln2"] = jnp.zeros((d,), dtype)
        p["mlp"] = init_mlp_params(ks[1], cfg, dtype)
    return p


def init_period_params(key, cfg: ModelConfig, dtype):
    specs = period_spec(cfg)
    ks = jax.random.split(key, len(specs))
    return {f"layer_{j}": init_layer_params(ks[j], cfg, specs[j], dtype)
            for j in range(len(specs))}


def init_stacked_params(key, cfg: ModelConfig, dtype):
    """Period params with a leading ``num_periods`` axis on every leaf."""
    n = num_periods(cfg)
    ks = jax.random.split(key, n)
    per = [init_period_params(k, cfg, dtype) for k in ks]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *per)


# --------------------------------------------------------------- apply ----

def mlp_forward(p, x, cfg: ModelConfig):
    """The (gated) MLP; its ops and their gradients carry ``cefl.mlp``."""
    with jax.named_scope(tracing.MLP):
        return _mlp_forward(p, x, cfg)


def _mlp_forward(p, x, cfg: ModelConfig):
    h = x @ p["w_in"]
    if cfg.gated_mlp:
        g = x @ p["w_gate"]
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return h @ p["w_out"]


def attn_forward(p, x, cfg: ModelConfig, *, angles, causal=True,
                 kv_override=None, q_block=512, kv_block=512):
    """Full-sequence attention (train / prefill / encoder / cross): the
    projections, blocked attention and output projection, whose ops and
    gradients carry ``cefl.attn``."""
    with jax.named_scope(tracing.ATTN):
        return _attn_forward(p, x, cfg, angles=angles, causal=causal,
                             kv_override=kv_override, q_block=q_block,
                             kv_block=kv_block)


def _attn_forward(p, x, cfg: ModelConfig, *, angles, causal, kv_override,
                  q_block, kv_block):
    q = jnp.einsum("bsd,dhx->bshx", x, p["wq"])
    kv_src = x if kv_override is None else kv_override
    k = jnp.einsum("bsd,dhx->bshx", kv_src, p["wk"])
    v = jnp.einsum("bsd,dhx->bshx", kv_src, p["wv"])
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if angles is not None:
        q = apply_rope(q, angles)
        k_angles = angles if kv_override is None else None
        if k_angles is not None:
            k = apply_rope(k, k_angles)
    out = attn_lib.blocked_attention(
        q, k, v, causal=causal,
        window=cfg.sliding_window if causal else None,
        q_block=q_block, kv_block=kv_block, scale=cfg.attention_multiplier)
    return jnp.einsum("bshx,hxd->bsd", out, p["wo"]), (k, v)


def attn_decode(p, x, cfg: ModelConfig, cache, pos, *, ctx: ShardCtx,
                window=None):
    """x: (B, d) single token; cache: {'k','v'} (B,S,Hkv,D); pos scalar."""
    q = jnp.einsum("bd,dhx->bhx", x, p["wq"])
    k = jnp.einsum("bd,dhx->bhx", x, p["wk"])
    v = jnp.einsum("bd,dhx->bhx", x, p["wv"])
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if not (cfg.is_encdec or cfg.nope):   # RoPE (enc-dec: learned positions)
        angle = rope_frequencies(cfg.head_dim, cfg.rope_theta,
                                 jnp.asarray(pos)[None])      # (1, D/2)
        q = apply_rope(q[:, None], angle)[:, 0]
        k = apply_rope(k[:, None], angle)[:, 0]
    S = cache["k"].shape[1]
    if window is not None and S == window:
        # rolling window cache: write at pos % window
        slot = jnp.mod(pos, window)
    else:
        slot = pos
    kc = jax.lax.dynamic_update_slice(cache["k"], k[:, None].astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    vc = jax.lax.dynamic_update_slice(cache["v"], v[:, None].astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    cache_len = jnp.minimum(pos + 1, S)
    eff_window = None if (window is not None and S == window) else window
    if ctx.seq_shard_decode and ctx.on_mesh:
        out = attn_lib.decode_attention_seq_sharded(
            q, kc, vc, cache_len, ctx=ctx, window=eff_window,
            scale=cfg.attention_multiplier)
    else:
        out = attn_lib.decode_attention_plain(
            q, kc, vc, cache_len, window=eff_window,
            scale=cfg.attention_multiplier)
    y = jnp.einsum("bhx,hxd->bd", out, p["wo"])
    return y, {"k": kc, "v": vc}


def cross_attn_decode(p, x, cfg: ModelConfig, cross_cache):
    """Decoder cross-attention against a fixed encoder cache."""
    q = jnp.einsum("bd,dhx->bhx", x, p["wq"])
    kc, vc = cross_cache["k"], cross_cache["v"]
    out = attn_lib.decode_attention_plain(q, kc, vc, kc.shape[1],
                                          scale=cfg.attention_multiplier)
    return jnp.einsum("bhx,hxd->bd", out, p["wo"])


def residual_branch(y, cfg: ModelConfig):
    """A mixer's or MLP's output as added to the residual stream: times
    ``residual_multiplier``, which at 1 is not applied at all."""
    if cfg.residual_multiplier == 1.0:
        return y
    return y * cfg.residual_multiplier


def layer_forward(params, x, cfg: ModelConfig, spec: LayerSpec, *,
                  angles, ssm_state=None, return_ssm_state=False,
                  q_block=512, kv_block=512):
    """Full-sequence layer (train / prefill).  Returns (x, aux, kv, ssm_state)."""
    aux = {"load_balance": jnp.zeros((), jnp.float32),
           "router_z": jnp.zeros((), jnp.float32)}
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    kv = None
    new_state = None
    if spec.kind == "A":
        y, kv = attn_forward(params["attn"], h, cfg, angles=angles,
                             q_block=q_block, kv_block=kv_block)
    else:
        if return_ssm_state:
            y, new_state = mamba_lib.ssd_forward(
                params["mamba"], h, cfg.ssm, init_state=ssm_state,
                return_state=True)
        else:
            y = mamba_lib.ssd_forward(params["mamba"], h, cfg.ssm,
                                      init_state=ssm_state)
    x = x + residual_branch(y, cfg)
    if spec.use_moe:
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        y, moe_aux = moe_lib.moe_forward(params["moe"], h, cfg.moe)
        if "mlp" in params:   # arctic dense residual / llama4 shared expert
            y = y + mlp_forward(params["mlp"], h, cfg)
        aux = moe_aux
        x = x + residual_branch(y, cfg)
    elif spec.has_mlp:
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        x = x + residual_branch(mlp_forward(params["mlp"], h, cfg), cfg)
    return x, aux, kv, new_state


def layer_decode(params, x, cfg: ModelConfig, spec: LayerSpec, cache, pos, *,
                 ctx: ShardCtx, window=None):
    """Single-token layer step.  cache is the per-layer cache dict."""
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    if spec.kind == "A":
        y, new_cache = attn_decode(params["attn"], h, cfg, cache, pos,
                                   ctx=ctx, window=window)
    else:
        y, new_cache = mamba_lib.mamba_decode_step(params["mamba"], h,
                                                   cache, cfg.ssm)
    x = x + residual_branch(y, cfg)
    if spec.use_moe:
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        gs = min(1024, h.shape[0])
        y, _ = moe_lib.moe_forward(params["moe"], h[:, None], cfg.moe,
                                   group_size=gs, capacity=gs)  # drop-free
        y = y[:, 0]
        if "mlp" in params:
            y = y + mlp_forward(params["mlp"], h, cfg)
        x = x + residual_branch(y, cfg)
    elif spec.has_mlp:
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        x = x + residual_branch(mlp_forward(params["mlp"], h, cfg), cfg)
    return x, new_cache
