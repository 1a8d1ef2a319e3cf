"""Mamba-2 SSD: chunked dual form == recurrent scan; state chaining."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SSMConfig
from repro.models.mamba import (init_mamba_params, init_mamba_state,
                                mamba_decode_step, ssd_forward)

S_CFG = SSMConfig(state_dim=8, head_dim=8, expand=2, chunk_size=4,
                  conv_width=4)


def _setup(B=2, S=16, d_model=16, seed=0):
    p = init_mamba_params(jax.random.PRNGKey(seed), d_model, S_CFG,
                          jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, d_model)) * .5
    return p, x


def test_ssd_equals_recurrence():
    p, x = _setup()
    y_chunk, st = ssd_forward(p, x, S_CFG, return_state=True)
    cur = init_mamba_state(2, 16, S_CFG, jnp.float32)
    ys = []
    for t in range(x.shape[1]):
        y, cur = mamba_decode_step(p, x[:, t], cur, S_CFG)
        ys.append(y)
    y_rec = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_chunk, y_rec, atol=1e-5)
    np.testing.assert_allclose(st["h"], cur["h"], atol=1e-6)
    np.testing.assert_allclose(st["conv"], cur["conv"], atol=1e-6)


def test_ssd_state_chaining():
    p, x = _setup(S=16)
    y_full = ssd_forward(p, x, S_CFG)
    y1, st = ssd_forward(p, x[:, :8], S_CFG, return_state=True)
    y2 = ssd_forward(p, x[:, 8:], S_CFG, init_state=st)
    np.testing.assert_allclose(y_full, jnp.concatenate([y1, y2], 1),
                               atol=1e-5)


@pytest.mark.parametrize("chunk", [2, 8, 16, 64])
def test_ssd_chunk_invariance(chunk):
    """Also a chunk longer than the sequence (64 > 16): one chunk."""
    import dataclasses
    p, x = _setup(S=16)
    cfg2 = dataclasses.replace(S_CFG, chunk_size=chunk)
    y1 = ssd_forward(p, x, S_CFG)
    y2 = ssd_forward(p, x, cfg2)
    np.testing.assert_allclose(y1, y2, atol=1e-5)


def test_ssd_grads_finite():
    p, x = _setup()
    g = jax.grad(lambda pp: jnp.sum(ssd_forward(pp, x, S_CFG) ** 2))(p)
    for leaf in jax.tree_util.tree_leaves(g):
        assert jnp.all(jnp.isfinite(leaf))


def test_ssd_grads_finite_under_strong_decay():
    """A 64-step chunk with fast decay, as at mamba2-130m's widths: the
    intra-chunk exp above the diagonal overflows unless it is masked
    before the exp, and its gradient is then NaN."""
    import dataclasses
    cfg = dataclasses.replace(S_CFG, chunk_size=64)
    p, x = _setup(S=64)
    p = dict(p, a_log=jnp.full_like(p["a_log"], np.log(24.0)),
             dt_bias=jnp.full_like(p["dt_bias"], 2.0))
    g = jax.grad(lambda pp: jnp.sum(ssd_forward(pp, x, cfg) ** 2))(p)
    for leaf in jax.tree_util.tree_leaves(g):
        assert jnp.all(jnp.isfinite(leaf))
