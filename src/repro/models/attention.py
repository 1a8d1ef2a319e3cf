"""Attention: blocked (flash-style) causal/sliding-window for train & prefill,
plus single-token decode paths (plain and sequence-sharded shard_map psum).

All softmax statistics are kept in float32 regardless of activation dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import ShardCtx

NEG_INF = -1e30

# Optional activation-sharding hint for attention tensors (B, S, H, D).
# Without it, GSPMD inherits the d_model-sharded layout from the FSDP
# weights and picks head-only (often uneven, e.g. 2-of-56) partitions for
# the attention einsums, leaving the full batch on every device — measured
# ~8-10x compute blowup on arctic/llama3 (EXPERIMENTS.md §Perf).  The
# launcher calls set_shard_hint(mesh, batch_axes, model_axis) before
# tracing; tests/CPU paths leave it unset.
_SHARD_HINT = None


def set_shard_hint(mesh=None, batch_axes=("data",), model_axis="model"):
    global _SHARD_HINT
    if mesh is None:
        _SHARD_HINT = None
    else:
        _SHARD_HINT = (mesh, tuple(batch_axes) or None, model_axis)


def _constrain_bshd(x):
    """Constrain (B, S, H, D) activations: batch->data, heads->model."""
    if _SHARD_HINT is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh, b, m = _SHARD_HINT
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(b, None, m, None)))
    except Exception:       # rank mismatch under exotic transforms: skip
        return x


def softmax_scale(scale: Optional[float], head_dim: int):
    """The factor on ``q . k``: ``scale``, or ``1/sqrt(head_dim)`` where it
    is None.  The forward passes and the flash backward all take it from
    here, so they cannot disagree."""
    if scale is None:
        return 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    return scale


def blocked_attention(q, k, v, *, causal: bool = True,
                      window: Optional[int] = None,
                      q_block: int = 512, kv_block: int = 512,
                      flash_vjp: bool = True,
                      scale: Optional[float] = None):
    """Memory-O(S*block) attention with online softmax.

    q: (B, S, Hq, D); k, v: (B, S, Hkv, D).  Returns (B, S, Hq, D).
    ``window``: sliding-window width (keys with q_pos - k_pos >= window are
    masked).  Blocks are processed fully and masked; see EXPERIMENTS.md for the
    FLOP accounting note.

    ``flash_vjp``: use the custom flash backward (recompute probabilities per
    block; saves only out+lse).  Without it, AD through the scans stores every
    (q_block x kv_block) probability tile — O(S^2) memory.

    ``scale``: the softmax scale (``softmax_scale``).
    """
    if flash_vjp:
        return _flash_attention(q, k, v, causal, window, q_block, kv_block,
                                scale)
    return _blocked_attention_fwd_only(q, k, v, causal=causal, window=window,
                                       q_block=q_block, kv_block=kv_block,
                                       scale=scale)[0]


def _blocked_attention_fwd_only(q, k, v, *, causal, window, q_block,
                                kv_block, scale=None):
    """Forward pass; returns (out, lse) with lse: (B, Hkv, G, S) f32."""
    q = _constrain_bshd(q)
    k = _constrain_bshd(k)
    v = _constrain_bshd(v)
    B, S, Hq, D = q.shape
    S_kv = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv
    def _fit(n, b):
        b = min(b, n)
        while n % b:
            b -= 1
        return b

    q_block = _fit(S, q_block)
    kv_block = _fit(S_kv, kv_block)
    nq, nk = S // q_block, S_kv // kv_block
    scale = softmax_scale(scale, D)

    qb = q.reshape(B, nq, q_block, Hkv, G, D)
    kb = k.reshape(B, nk, kv_block, Hkv, D)
    vb = v.reshape(B, nk, kv_block, Hkv, D)

    q_pos = jnp.arange(S, dtype=jnp.int32).reshape(nq, q_block)
    k_pos = jnp.arange(S_kv, dtype=jnp.int32).reshape(nk, kv_block)

    def one_q_block(_, qi):
        qq, qpos = qi  # (B, q_block, Hkv, G, D), (q_block,)

        def kv_step(carry, ki):
            acc, m, l = carry
            kk, vv, kpos = ki
            # s: (B, K, G, q_block, kv_block), f32
            s = jnp.einsum("bqkgd,bskd->bkgqs", qq, kk,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((q_block, kv_block), dtype=bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window is not None:
                mask &= qpos[:, None] - kpos[None, :] < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p, vv.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, Hkv, G, q_block, D), jnp.float32)
        m0 = jnp.full((B, Hkv, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (kb.swapaxes(0, 1), vb.swapaxes(0, 1), k_pos))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        # (B, K, G, q_block, D) -> (B, q_block, K, G, D)
        return None, (out.transpose(0, 3, 1, 2, 4), lse)

    _, (blocks, lses) = jax.lax.scan(one_q_block, None,
                                     (qb.swapaxes(0, 1), q_pos))
    out = blocks.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Hq, D)
    # lses: (nq, B, Hkv, G, q_block) -> (B, Hkv, G, S)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, Hkv, G, S)
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, window, q_block, kv_block, scale):
    out, _ = _blocked_attention_fwd_only(q, k, v, causal=causal,
                                         window=window, q_block=q_block,
                                         kv_block=kv_block, scale=scale)
    return out


def _flash_fwd(q, k, v, causal, window, q_block, kv_block, scale):
    out, lse = _blocked_attention_fwd_only(q, k, v, causal=causal,
                                           window=window, q_block=q_block,
                                           kv_block=kv_block, scale=scale)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, q_block, kv_block, scale, res, dout):
    """Recompute probabilities per (q,kv) block pair (FlashAttention-2
    backward), accumulating dq over kv blocks and dk/dv over q blocks."""
    q, k, v, out, lse = res
    q = _constrain_bshd(q)
    k = _constrain_bshd(k)
    v = _constrain_bshd(v)
    dout = _constrain_bshd(dout)
    B, S, Hq, D = q.shape
    S_kv = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv

    def _fit(n, b):
        b = min(b, n)
        while n % b:
            b -= 1
        return b

    qb_sz = _fit(S, q_block)
    kb_sz = _fit(S_kv, kv_block)
    nq, nk = S // qb_sz, S_kv // kb_sz
    scale = softmax_scale(scale, D)

    qg = q.reshape(B, nq, qb_sz, Hkv, G, D)
    kg = k.reshape(B, nk, kb_sz, Hkv, D)
    vg = v.reshape(B, nk, kb_sz, Hkv, D)
    og = out.reshape(B, nq, qb_sz, Hkv, G, D)
    dog = dout.reshape(B, nq, qb_sz, Hkv, G, D)
    lseg = lse.reshape(B, Hkv, G, nq, qb_sz)
    # delta_i = rowsum(dout * out): (B, Hkv, G, nq, qb)
    delta = jnp.einsum("bqtkgd,bqtkgd->bkgqt", dog.astype(jnp.float32),
                       og.astype(jnp.float32))
    q_pos = jnp.arange(S, dtype=jnp.int32).reshape(nq, qb_sz)
    k_pos = jnp.arange(S_kv, dtype=jnp.int32).reshape(nk, kb_sz)

    def kv_blk(dq_acc, j):
        kk = kg[:, j]                     # (B, kb, K, D)
        vv = vg[:, j]
        kpos = k_pos[j]

        def q_blk(carry, i):
            dk_a, dv_a, dq_in = carry
            qq = qg[:, i]                 # (B, qb, K, G, D)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qq, kk,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((qb_sz, kb_sz), dtype=bool)
            if causal:
                mask &= q_pos[i][:, None] >= kpos[None, :]
            if window is not None:
                mask &= q_pos[i][:, None] - kpos[None, :] < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lseg[:, :, :, i][..., None])      # (B,K,G,qb,kb)
            do = dog[:, i].astype(jnp.float32)                # (B,qb,K,G,D)
            dv_blk = jnp.einsum("bkgqs,bqkgd->bskd", p, do)
            dp = jnp.einsum("bqkgd,bskd->bkgqs", do,
                            vv.astype(jnp.float32))
            ds = p * (dp - delta[:, :, :, i][..., None]) * scale
            dq_blk = jnp.einsum("bkgqs,bskd->bqkgd", ds,
                                kk.astype(jnp.float32))
            dk_blk = jnp.einsum("bkgqs,bqkgd->bskd", ds,
                                qq.astype(jnp.float32))
            dq_in = dq_in.at[:, i].add(dq_blk)
            return (dk_a + dk_blk, dv_a + dv_blk, dq_in), None

        dk0 = jnp.zeros((B, kb_sz, Hkv, D), jnp.float32)
        dv0 = jnp.zeros((B, kb_sz, Hkv, D), jnp.float32)
        (dk_j, dv_j, dq_acc), _ = jax.lax.scan(
            q_blk, (dk0, dv0, dq_acc), jnp.arange(nq))
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((B, nq, qb_sz, Hkv, G, D), jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(kv_blk, dq0, jnp.arange(nk))
    dq = dq.reshape(B, S, Hq, D).astype(q.dtype)
    dk = dk_blocks.transpose(1, 0, 2, 3, 4).reshape(B, S_kv, Hkv, D
                                                    ).astype(k.dtype)
    dv = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(B, S_kv, Hkv, D
                                                    ).astype(v.dtype)
    return dq, dk, dv


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def decode_attention_plain(q, k_cache, v_cache, cache_len, *,
                           window: Optional[int] = None,
                           scale: Optional[float] = None):
    """Single-token decode. q: (B, Hq, D); caches: (B, S, Hkv, D);
    cache_len: () or (B,) number of valid positions (the new token's position
    is cache_len-1 after insertion).  Returns (B, Hq, D)."""
    B, S, Hkv, D = k_cache.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    scale = softmax_scale(scale, D)
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(S, dtype=jnp.int32)
    clen = jnp.asarray(cache_len)
    clen = clen[:, None] if clen.ndim == 1 else clen[None]
    valid = pos[None, :] < clen
    if window is not None:
        valid &= pos[None, :] >= clen - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p / jnp.maximum(l, 1e-30),
                     v_cache.astype(jnp.float32))
    return out.reshape(B, Hq, D).astype(q.dtype)


def decode_attention_seq_sharded(q, k_cache, v_cache, cache_len, *,
                                 ctx: ShardCtx,
                                 window: Optional[int] = None,
                                 scale: Optional[float] = None):
    """Decode attention with the KV cache sequence-sharded over
    ``ctx.cache_axes``.  Each shard computes a partial safe-softmax
    (m, l, o); partials are combined with psum/pmax over the cache axes.

    q: (B, Hq, D) — batch sharded over ctx.batch_axes, replicated over cache
    axes.  caches: (B, S, Hkv, D) with S sharded over ctx.cache_axes.
    """
    from jax.sharding import PartitionSpec as P

    axes = tuple(ctx.cache_axes)
    B, S, Hkv, D = k_cache.shape
    n_shards = 1
    for a in axes:
        n_shards *= ctx.mesh.shape[a]
    s_local = S // n_shards

    def local(q, kc, vc, clen):
        # global offset of this shard's sequence slice
        idx = jnp.zeros((), jnp.int32)
        for a in axes:
            idx = idx * ctx.mesh.shape[a] + jax.lax.axis_index(a)
        offset = idx * s_local
        Hq = q.shape[1]
        G = Hq // Hkv
        qg = q.reshape(q.shape[0], Hkv, G, D)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, kc,
                       preferred_element_type=jnp.float32) * \
            softmax_scale(scale, D)
        pos = offset + jnp.arange(s_local, dtype=jnp.int32)
        cl = jnp.asarray(clen)
        cl = cl[:, None] if cl.ndim == 1 else cl[None]
        valid = pos[None, :] < cl
        if window is not None:
            valid &= pos[None, :] >= cl - window
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)
        m = jax.lax.pmax(m_loc, axes)
        p = jnp.exp(s - m[..., None])
        l = jax.lax.psum(jnp.sum(p, axis=-1), axes)
        o = jnp.einsum("bkgs,bskd->bkgd", p, vc.astype(jnp.float32))
        o = jax.lax.psum(o, axes)
        out = o / jnp.maximum(l[..., None], 1e-30)
        return out.reshape(q.shape[0], Hq, D).astype(q.dtype)

    b_ax = tuple(ctx.batch_axes) if ctx.batch_axes else None
    in_specs = (P(b_ax, None, None), P(b_ax, axes, None, None),
                P(b_ax, axes, None, None), P())
    out_specs = P(b_ax, None, None)
    fn = jax.shard_map(local, mesh=ctx.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(q, k_cache, v_cache, jnp.asarray(cache_len))
