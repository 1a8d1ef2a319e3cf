"""Language models assembled from blocks: decoder-only LMs (dense / MoE / SSM /
hybrid / early-fusion VLM) and the Whisper-style encoder-decoder.

Layers are scanned over periods (see blocks.period_spec) with optional remat.
Loss is computed with a sequence-chunked cross-entropy so the (B, S, V)
logits tensor is never materialized.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import blocks as B
from repro.models import mamba as mamba_lib
from repro.models.common import (NO_SHARD, ShardCtx, embed_init,
                                 rms_norm, rope_frequencies)


# ------------------------------------------------------------------ init ---

def init_lm_params(key, cfg: ModelConfig, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    params = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
        "blocks": B.init_stacked_params(ks[1], cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(ks[2], cfg.vocab_size, cfg.d_model, dtype).T
    if cfg.is_encdec:
        params["enc"] = {
            "blocks": _init_encoder_params(ks[3], cfg, dtype),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }
        params["cross"] = _init_cross_params(ks[4], cfg, dtype)
        # sized for the largest decode shape Whisper runs (decode_32k)
        pos_table = max(32768, cfg.encoder_seq)
        params["pos_embed"] = (jax.random.normal(ks[5], (pos_table, cfg.d_model))
                               * 0.01).astype(dtype)
    return params


def _init_encoder_params(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, cfg.encoder_layers)
    spec = B.LayerSpec("A", False, True)
    per = [B.init_layer_params(k, cfg, spec, dtype) for k in ks]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *per)


def _init_cross_params(key, cfg: ModelConfig, dtype):
    """Cross-attention params for every decoder layer (stacked over periods)."""
    n = B.num_periods(cfg)
    plen = len(B.period_spec(cfg))
    ks = jax.random.split(key, n * plen)
    per = []
    for i in range(n):
        period = {}
        for j in range(plen):
            period[f"layer_{j}"] = {
                "xattn": B.init_attn_params(ks[i * plen + j], cfg, dtype,
                                            cross=True),
                "ln_x": jnp.zeros((cfg.d_model,), dtype),
            }
        per.append(period)
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *per)


# -------------------------------------------------------------- forward ---

def _angles(cfg: ModelConfig, S: int):
    if cfg.is_encdec or cfg.nope:
        return None
    pos = jnp.arange(S, dtype=jnp.int32)
    return rope_frequencies(cfg.head_dim, cfg.rope_theta, pos)


def _sinusoid(S: int, d: int):
    pos = np.arange(S)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * dim / d)
    return jnp.asarray(np.concatenate([np.sin(ang), np.cos(ang)], -1),
                       jnp.float32)


def lm_backbone(params, cfg: ModelConfig, x, *, remat: bool = True,
                enc_out=None, q_block=512, kv_block=512,
                remat_chunk: int = 1):
    """Run the decoder stack on embeddings x (B, S, d).  Returns (x, aux).

    ``remat_chunk``: periods per checkpoint region.  With chunk g the saved
    residual stream is n_periods/g copies instead of n_periods (activation
    memory / g at ~2x in-chunk recompute) — the coarse-remat lever used by
    the deep/wide configs (llama3-405b) and tuned in EXPERIMENTS.md §Perf.

    Recomputation is per layer: a one-layer period is checkpointed whole,
    and inside a longer period each layer is checkpointed on its own in
    place of the period, so the backward holds one layer's intermediates
    and not the whole period's.
    """
    specs = B.period_spec(cfg)
    S = x.shape[1]
    angles = _angles(cfg, S)
    per_layer = remat and len(specs) > 1

    def layer_fn(lp, x, spec):
        x, aux, _, _ = B.layer_forward(lp, x, cfg, spec, angles=angles,
                                       q_block=q_block, kv_block=kv_block)
        return x, aux

    def period_fn(x, pp):
        lb = jnp.zeros((), jnp.float32)
        rz = jnp.zeros((), jnp.float32)
        block_p, cross_p = pp if cfg.is_encdec else (pp, None)
        for j, spec in enumerate(specs):
            fn = jax.checkpoint(layer_fn, static_argnums=(2,)) \
                if per_layer else layer_fn
            x, aux = fn(block_p[f"layer_{j}"], x, spec)
            if cfg.is_encdec:
                cp = cross_p[f"layer_{j}"]
                h = rms_norm(x, cp["ln_x"], cfg.norm_eps)
                y, _ = B.attn_forward(cp["xattn"], h, cfg, angles=None,
                                      causal=False, kv_override=enc_out,
                                      q_block=q_block, kv_block=kv_block)
                x = x + y
            lb = lb + aux["load_balance"]
            rz = rz + aux["router_z"]
        return x, (lb, rz)

    xs = (params["blocks"], params["cross"]) if cfg.is_encdec \
        else params["blocks"]
    n_per = B.num_periods(cfg)
    zero = jnp.zeros((), jnp.float32)

    if remat_chunk > 1 and n_per % remat_chunk == 0:
        # two-level remat: outer scan over chunks saves only chunk inputs;
        # inside a chunk's backward, each period is rematted again, so the
        # transient working set is one period, not one chunk.
        xs = jax.tree_util.tree_map(
            lambda a: a.reshape((n_per // remat_chunk, remat_chunk)
                                + a.shape[1:]), xs)
        inner_period = jax.checkpoint(period_fn) if remat and \
            not per_layer else period_fn

        def chunk_fn(x, pp_chunk):
            def inner(carry, pp):
                x, lb, rz = carry
                x, (dlb, drz) = inner_period(x, pp)
                return (x, lb + dlb, rz + drz), None
            (x, lb, rz), _ = jax.lax.scan(inner, (x, zero, zero), pp_chunk)
            return x, (lb, rz)

        body = jax.checkpoint(chunk_fn) if remat else chunk_fn
    else:
        body = jax.checkpoint(period_fn) if remat and not per_layer \
            else period_fn

    def scan_body(carry, pp):
        x, lb, rz = carry
        x, (dlb, drz) = body(x, pp)
        return (x, lb + dlb, rz + drz), None

    (x, lb, rz), _ = jax.lax.scan(scan_body, (x, zero, zero), xs)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    n = cfg.num_layers
    return x, {"load_balance": lb / n, "router_z": rz / n}


def encoder_forward(params, cfg: ModelConfig, enc_embed, *,
                    q_block=512, kv_block=512):
    """Whisper encoder on stubbed frame embeddings (B, T_enc, d)."""
    x = enc_embed + _sinusoid(enc_embed.shape[1],
                              cfg.d_model).astype(enc_embed.dtype)[None]

    def enc_layer(x, lp):  # bidirectional self-attention + MLP
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        y, _ = B.attn_forward(lp["attn"], h, cfg, angles=None, causal=False,
                              q_block=q_block, kv_block=kv_block)
        x = x + y
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + B.mlp_forward(lp["mlp"], h, cfg)
        return x, None

    x, _ = jax.lax.scan(enc_layer, x, params["enc"]["blocks"])
    return rms_norm(x, params["enc"]["final_norm"], cfg.norm_eps)


def _token_embeddings(params, cfg: ModelConfig, tokens):
    """Rows of the embedding table, times ``embedding_multiplier`` (not
    applied at 1)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def embed_tokens(params, cfg: ModelConfig, tokens, pos_offset=0):
    x = _token_embeddings(params, cfg, tokens)
    if cfg.is_encdec:
        S = tokens.shape[-1]
        pe = jax.lax.dynamic_slice_in_dim(params["pos_embed"], pos_offset, S, 0)
        x = x + pe
    return x


def unembed(params, cfg: ModelConfig, x):
    """Logits (float32), divided by ``logits_scaling`` (not at 1)."""
    table = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("...d,dv->...v", x, table,
                        preferred_element_type=jnp.float32)
    if cfg.logits_scaling != 1.0:    # repro: noqa(RPA004) a static config field, never a tracer
        logits = logits / cfg.logits_scaling
    return logits


def chunked_loss(params, cfg: ModelConfig, x, labels, mask=None,
                 chunk: int = 512):
    """Cross-entropy without materializing (B, S, V).  x: (B,S,d)."""
    Bb, S, d = x.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    xc = x.reshape(Bb, nc, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(Bb, nc, chunk).swapaxes(0, 1)
    if mask is None:
        mask = jnp.ones((Bb, S), jnp.float32)
    mc = mask.reshape(Bb, nc, chunk).swapaxes(0, 1)

    @jax.checkpoint   # recompute chunk logits in backward (V-sized tiles)
    def chunk_nll(xx, ll, mm):
        logits = unembed(params, cfg, xx)
        logz = jax.scipy.special.logsumexp(logits.astype(jnp.float32),
                                           axis=-1)
        gold = jnp.take_along_axis(logits.astype(jnp.float32),
                                   ll[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * mm)

    def step(acc, inp):
        xx, ll, mm = inp
        return (acc[0] + chunk_nll(xx, ll, mm), acc[1] + jnp.sum(mm)), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros((), jnp.float32),
                                        jnp.zeros((), jnp.float32)),
                                 (xc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss(params, cfg: ModelConfig, batch, *, remat: bool = True,
            q_block=512, kv_block=512, example_mask=None,
            remat_chunk: int = 1):
    """batch: {'tokens','labels'} (+'enc_embed' for enc-dec).  Returns
    (loss, aux).  ``example_mask``: (B,) 0/1 — CE-FL mini-batch ratio m_i."""
    tokens = batch["tokens"]
    x = embed_tokens(params, cfg, tokens)
    enc_out = None
    if cfg.is_encdec:
        enc_out = encoder_forward(params, cfg, batch["enc_embed"],
                                  q_block=q_block, kv_block=kv_block)
    x, aux = lm_backbone(params, cfg, x, remat=remat, enc_out=enc_out,
                         q_block=q_block, kv_block=kv_block,
                         remat_chunk=remat_chunk)
    mask = None
    if example_mask is not None:
        mask = jnp.broadcast_to(example_mask[:, None],
                                tokens.shape).astype(jnp.float32)
    loss = chunked_loss(params, cfg, x, batch["labels"], mask)
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss * aux["load_balance"] \
            + cfg.moe.router_z_loss * aux["router_z"]
    return loss, aux


# ---------------------------------------------------------------- decode ---

def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None):
    """Stacked-by-period cache pytree.  For sliding-window configs the
    attention cache is a rolling buffer of size min(window, cache_len)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    specs = B.period_spec(cfg)
    n = B.num_periods(cfg)
    S = cache_len if cfg.sliding_window is None \
        else min(cfg.sliding_window, cache_len)
    period = {}
    for j, spec in enumerate(specs):
        if spec.kind == "A":
            shape = (batch, S, cfg.num_kv_heads, cfg.head_dim)
            period[f"layer_{j}"] = {"k": jnp.zeros(shape, dtype),
                                    "v": jnp.zeros(shape, dtype)}
        else:
            period[f"layer_{j}"] = mamba_lib.init_mamba_state(
                batch, cfg.d_model, cfg.ssm, dtype)
        if cfg.is_encdec:   # fixed cross-attention cache (encoder K/V)
            xshape = (batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim)
            period[f"layer_{j}"]["xk"] = jnp.zeros(xshape, dtype)
            period[f"layer_{j}"]["xv"] = jnp.zeros(xshape, dtype)
    blocks = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), period)
    return {"blocks": blocks, "pos": jnp.zeros((), jnp.int32)}


def lm_decode_step(params, cfg: ModelConfig, tokens, cache, *,
                   ctx: ShardCtx = NO_SHARD, enc_out=None):
    """tokens: (B,) int32 — one new token per sequence.  Returns
    (logits (B, V), new_cache)."""
    specs = B.period_spec(cfg)
    pos = cache["pos"]
    x = _token_embeddings(params, cfg, tokens)
    if cfg.is_encdec:
        x = x + jnp.take(params["pos_embed"], pos, axis=0).astype(x.dtype)

    def period_fn(x, pp):
        if cfg.is_encdec:
            block_p, cross_p, cache_p = pp
        else:
            block_p, cache_p = pp
            cross_p = None
        new_caches = {}
        for j, spec in enumerate(specs):
            x, nc = B.layer_decode(block_p[f"layer_{j}"], x, cfg, spec,
                                   cache_p[f"layer_{j}"], pos, ctx=ctx,
                                   window=cfg.sliding_window)
            if cfg.is_encdec:
                cp = cross_p[f"layer_{j}"]
                h = rms_norm(x, cp["ln_x"], cfg.norm_eps)
                y = B.cross_attn_decode(cp["xattn"], h, cfg,
                                        {"k": cache_p[f"layer_{j}"]["xk"],
                                         "v": cache_p[f"layer_{j}"]["xv"]})
                x = x + y
                nc = dict(nc)
                nc["xk"] = cache_p[f"layer_{j}"]["xk"]
                nc["xv"] = cache_p[f"layer_{j}"]["xv"]
            new_caches[f"layer_{j}"] = nc
        return x, new_caches

    def scan_body(x, pp):
        return period_fn(x, pp)

    xs = (params["blocks"], params["cross"], cache["blocks"]) \
        if cfg.is_encdec else (params["blocks"], cache["blocks"])
    x, new_blocks = jax.lax.scan(scan_body, x, xs)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, cfg, x)
    return logits, {"blocks": new_blocks, "pos": pos + 1}


def make_cross_cache(params, cfg: ModelConfig, enc_out):
    """Precompute per-decoder-layer cross-attention K/V from encoder output
    and merge into the cache blocks (enc-dec only)."""
    def per_period(cp):
        out = {}
        for j in range(len(B.period_spec(cfg))):
            p = cp[f"layer_{j}"]["xattn"]
            k = jnp.einsum("bsd,dhx->bshx", enc_out, p["wk"])
            v = jnp.einsum("bsd,dhx->bshx", enc_out, p["wv"])
            out[f"layer_{j}"] = {"xk": k, "xv": v}
        return out

    return jax.vmap(per_period)(params["cross"])


def prefill(params, cfg: ModelConfig, tokens, cache_len: int, *,
            ctx: ShardCtx = NO_SHARD, enc_embed=None,
            q_block=512, kv_block=512):
    """Process a prompt (B, S) and return (last_logits, cache)."""
    specs = B.period_spec(cfg)
    S = tokens.shape[1]
    x = embed_tokens(params, cfg, tokens)
    enc_out = None
    if cfg.is_encdec:
        enc_out = encoder_forward(params, cfg, enc_embed,
                                  q_block=q_block, kv_block=kv_block)
    angles = _angles(cfg, S)

    def period_fn(x, pp):
        if cfg.is_encdec:
            block_p, cross_p = pp
        else:
            block_p, cross_p = pp, None
        caches = {}
        for j, spec in enumerate(specs):
            if spec.kind == "A":
                x_res = x
                h = rms_norm(x, block_p[f"layer_{j}"]["ln1"], cfg.norm_eps)
                y, (k, v) = B.attn_forward(
                    block_p[f"layer_{j}"]["attn"], h, cfg, angles=angles,
                    q_block=q_block, kv_block=kv_block)
                x = x_res + B.residual_branch(y, cfg)
                if cfg.sliding_window is not None and S > cfg.sliding_window:
                    k = k[:, -cfg.sliding_window:]
                    v = v[:, -cfg.sliding_window:]
                caches[f"layer_{j}"] = {"k": k, "v": v}
                lp = block_p[f"layer_{j}"]
                if "moe" in lp:
                    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
                    y, _ = B.moe_lib.moe_forward(lp["moe"], h, cfg.moe)
                    if "mlp" in lp:
                        y = y + B.mlp_forward(lp["mlp"], h, cfg)
                    x = x + B.residual_branch(y, cfg)
                elif "mlp" in lp:
                    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
                    x = x + B.residual_branch(
                        B.mlp_forward(lp["mlp"], h, cfg), cfg)
            else:
                x, aux, _, st = B.layer_forward(
                    block_p[f"layer_{j}"], x, cfg, spec, angles=angles,
                    return_ssm_state=True, q_block=q_block, kv_block=kv_block)
                caches[f"layer_{j}"] = st
            if cfg.is_encdec:
                cp = cross_p[f"layer_{j}"]
                h = rms_norm(x, cp["ln_x"], cfg.norm_eps)
                y, (xk, xv) = B.attn_forward(cp["xattn"], h, cfg, angles=None,
                                             causal=False, kv_override=enc_out,
                                             q_block=q_block, kv_block=kv_block)
                x = x + y
                caches[f"layer_{j}"]["xk"] = xk
                caches[f"layer_{j}"]["xv"] = xv
        return x, caches

    xs = (params["blocks"], params["cross"]) if cfg.is_encdec \
        else params["blocks"]
    x, blocks = jax.lax.scan(period_fn, x, xs)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = x[:, -1]
    logits = unembed(params, cfg, last)
    cache = {"blocks": _pad_cache_to(blocks, cfg, cache_len),
             "pos": jnp.asarray(S, jnp.int32)}
    return logits, cache


def _pad_cache_to(blocks, cfg: ModelConfig, cache_len: int):
    """Grow attention K/V caches from prompt length to cache_len capacity."""
    target = cache_len if cfg.sliding_window is None \
        else min(cfg.sliding_window, cache_len)

    def pad(path, x):
        names = [getattr(p, "key", None) for p in path]
        if names and names[-1] in ("k", "v") and x.ndim == 5:
            n, b, s, h, d = x.shape
            if s < target:
                padding = jnp.zeros((n, b, target - s, h, d), x.dtype)
                return jnp.concatenate([x, padding], axis=2)
            return x[:, :, :target]
        return x

    return jax.tree_util.tree_map_with_path(pad, blocks)
