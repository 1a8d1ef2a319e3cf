"""Sharding spec rules + HLO cost walker + dry-run plumbing (small mesh)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st  # hypothesis or fallback
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch import steps as ST
from repro.models import lm as L
from repro.sharding.specs import param_specs, sanitize_spec
from repro.utils.hlo import collective_bytes, shape_bytes
from repro.utils.hlo_walk import amplified_costs


def _mesh22():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_param_specs_cover_all_leaves():
    for arch in ["qwen3-32b", "jamba-v0.1-52b", "whisper-medium"]:
        cfg = get_config(arch)
        p = jax.eval_shape(lambda c=cfg: L.init_lm_params(
            jax.random.PRNGKey(0), c))
        specs = param_specs(cfg, p)
        leaves_p = jax.tree_util.tree_leaves(p)
        leaves_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(leaves_p) == len(leaves_s)


@settings(max_examples=30, deadline=None)
@given(dim=st.integers(1, 64), axis=st.sampled_from(["data", "model"]))
def test_sanitize_spec_divisibility(dim, axis):
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    spec = sanitize_spec(P(axis), (dim,), mesh)
    size = mesh.shape[axis]
    if dim % size == 0:
        assert spec == P(axis)
    else:
        assert spec == P(None)


def test_shape_bytes():
    assert shape_bytes("bf16[128,1024]") == 128 * 1024 * 2
    assert shape_bytes("f32[]") == 4
    assert shape_bytes("pred[8]") == 8


def test_walker_amplifies_nested_scans():
    def f(a):
        def body(c, _):
            def inner(c2, _):
                return jnp.tanh(c2 @ a), None
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, None
        c, _ = jax.lax.scan(body, jnp.eye(128), None, length=8)
        return c

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    res = amplified_costs(comp.as_text())
    expect = 32 * 2 * 128 ** 3
    assert abs(res["flops"] - expect) / expect < 0.05
    assert not res["unknown_trip_counts"]


def test_collective_parsers_on_hlo_text():
    # single-device compiles elide collectives, so test on crafted HLO.
    # hlo.collective_bytes reads inline operand shapes (quick diagnostic);
    # hlo_walk.amplified_costs resolves %name operands via symbol tables
    # (the authoritative path used by the roofline).
    hlo = """
ENTRY %main (p: f32[128,64]) -> f32[128,64] {
  %p = f32[128,64]{1,0} parameter(0)
  %ar = f32[128,64]{1,0} all-reduce(%p), replica_groups={}
  %ag = bf16[256,64]{1,0} all-gather(bf16[128,64]{1,0} %x), dimensions={0}
  ROOT %r = f32[128,64]{1,0} copy(%ar)
}
"""
    cb = collective_bytes(hlo)
    assert cb["all-gather"] == 128 * 64 * 2     # inline shape counted
    amp = amplified_costs(hlo)
    assert amp["collectives"]["all-reduce"] == 128 * 64 * 4  # via table


def test_make_plan_rules():
    # whisper skips long_500k; dense gets a window variant; ssm native
    p = ST.make_plan("whisper-medium", "long_500k", multi_pod=False)
    assert p.skip
    p = ST.make_plan("qwen3-32b", "long_500k", multi_pod=False)
    assert p.cfg.sliding_window == ST.SW_LONG and not p.skip
    p = ST.make_plan("starcoder2-15b", "long_500k", multi_pod=False)
    assert p.cfg.sliding_window == 4096
    p = ST.make_plan("mamba2-130m", "long_500k", multi_pod=False)
    assert not p.seq_shard_decode and not p.skip
    p = ST.make_plan("jamba-v0.1-52b", "long_500k", multi_pod=False)
    assert p.wide_cache
    # train microbatching keeps per-microbatch examples = data axis
    p = ST.make_plan("llama3-405b", "train_4k", multi_pod=False)
    assert p.mb * p.n_micro * p.n_dpu == 256
    assert p.remat_chunk > 1


def test_input_specs_are_abstract():
    p = ST.make_plan("whisper-medium", "train_4k", multi_pod=True)
    spec = ST.input_specs(p)
    assert set(spec) == {"tokens", "labels", "enc_embed"}
    for leaf in jax.tree_util.tree_leaves(spec):
        assert isinstance(leaf, jax.ShapeDtypeStruct)
    assert spec["tokens"].shape[0] == 2      # 2 DPUs on the multi-pod mesh


@pytest.mark.slow
def test_dryrun_subprocess_one_combo(tmp_path):
    """Full dry-run path in its own process (512 host devices)."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "mamba2-130m", "--shape", "decode_32k", "--mesh", "single",
         "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=560,
        env=dict(os.environ, PYTHONPATH="src"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert (tmp_path / "mamba2-130m_decode_32k_single.json").exists()
