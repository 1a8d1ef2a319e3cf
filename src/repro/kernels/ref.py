"""Pure-jnp oracles for every Pallas kernel (shape-exact references used by
the allclose test sweeps)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fedprox_update_ref(x, g, anchor, eta, mu):
    xf = x.astype(jnp.float32)
    out = xf - eta * (g.astype(jnp.float32)
                      + mu * (xf - anchor.astype(jnp.float32)))
    return out.astype(x.dtype)


def fedprox_accum_ref(x, g, anchor, acc, coef, active, eta, mu):
    """Batched proximal step + eq.-10 accumulation (fedprox_accum_2d).
    x, g, acc: (G, R, L); anchor: (R, L) or (G, R, L); coef/active: (G,)."""
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    anc = anchor.astype(jnp.float32)
    if anc.ndim == 2:
        anc = anc[None]
    act = active.astype(jnp.float32)[:, None, None]
    ak = coef.astype(jnp.float32)[:, None, None]
    x_new = xf - act * eta * (gf + mu * (xf - anc))
    acc_new = acc.astype(jnp.float32) + act * ak * gf
    return x_new.astype(x.dtype), acc_new.astype(acc.dtype)


def nova_aggregate_ref(x, d_stack, weights, theta_eta):
    agg = jnp.einsum("n,n...->...", weights.astype(jnp.float32),
                     d_stack.astype(jnp.float32))
    return (x.astype(jnp.float32) - theta_eta * agg).astype(x.dtype)


def sort_network(slabs):
    """Sort a list of equal-shape arrays elementwise, ascending, with a
    static odd-even transposition network: n rounds of compare-exchanges
    of neighbours.  O(n^2) elementwise ops and no sort primitive, so the
    same expression lowers inside a Pallas TPU kernel, where ``sort`` has
    no lowering.  NaN sorts last, as in ``jnp.sort``, so a trimmed mean
    still trims a NaN payload."""
    v = list(slabs)
    n = len(v)
    for r in range(n):
        for i in range(r % 2, n - 1, 2):
            a, b = v[i], v[i + 1]
            swap = (a > b) | (a != a)
            v[i], v[i + 1] = jnp.where(swap, b, a), jnp.where(swap, a, b)
    return v


def robust_reduce_ref(d_stack, *, k: int = 0, median: bool = False):
    """Coordinate-wise robust location estimate over the DPU axis.

    ``median=True``: the coordinate-wise median; otherwise the k-trimmed
    mean (drop the k smallest and k largest per coordinate — requires
    2k < n).  Unweighted by design: dataset-size weights are exactly the
    lever a byzantine client can inflate.  The kernel body calls this
    same function, so the ``cpu`` and Pallas backends agree bit for bit.
    """
    n = d_stack.shape[0]
    if not median and not 0 <= 2 * k < n:
        raise ValueError(f"trim k={k} needs 0 <= 2k < n={n}")
    d = sort_network([d_stack[i].astype(jnp.float32) for i in range(n)])
    if median:
        mid = n // 2
        return d[mid] if n % 2 else 0.5 * (d[mid - 1] + d[mid])
    total = d[k]
    for x in d[k + 1:n - k]:
        total = total + x
    return total / (n - 2 * k)


def robust_aggregate_ref(x, d_stack, theta_eta, *, k: int = 0,
                         median: bool = False):
    """eq. 11 with the weighted sum replaced by a robust reduce:
    x - theta*eta*robust_reduce(d_stack)."""
    red = robust_reduce_ref(d_stack, k=k, median=median)
    return (x.astype(jnp.float32) - theta_eta * red).astype(x.dtype)


def swa_decode_attention_ref(q, k_cache, v_cache, cache_len):
    B, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg,
                   k_cache.astype(jnp.float32)) / jnp.sqrt(float(D))
    pos = jnp.arange(S)
    s = jnp.where((pos < cache_len)[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, Hq, D).astype(q.dtype)
