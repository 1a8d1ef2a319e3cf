"""Unit tests for the PartitionSpec rule layer (sharding/specs.py): the
path-keyed param/cache rules, the sanitize divisibility degradation, and
the ShardCtx presets.  Mesh-free (specs only inspect ``mesh.shape``), so
these run on the single-device tier-1 lane too."""
import types

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.specs import (batch_spec, cache_specs, param_specs,
                                  sanitize_spec, sanitize_tree,
                                  shard_ctx_for)


def _mesh(**axes):
    # sanitize_spec / plane_axes only read mesh.shape[name]
    return types.SimpleNamespace(shape=dict(axes))


MESH = _mesh(data=2, model=16)


# ----------------------------------------------------- sanitize_spec -----

def test_sanitize_spec_degrades_non_dividing_axis():
    # kv-heads = 8 on a 16-way model axis: the classic non-divisible case
    assert sanitize_spec(P(None, None, "model", None),
                         (4, 128, 8, 64), MESH) == \
        P(None, None, None, None)
    # 32 heads divide 16: kept
    assert sanitize_spec(P(None, None, "model", None),
                         (4, 128, 32, 64), MESH) == \
        P(None, None, "model", None)


def test_sanitize_spec_tuple_entries_use_axis_product():
    big = _mesh(data=2, model=4)
    assert sanitize_spec(P(("data", "model")), (32,), big) == \
        P(("data", "model"))
    assert sanitize_spec(P(("data", "model")), (12,), big) == P(None)


def test_sanitize_spec_short_spec_pads_with_replication():
    out = sanitize_spec(P("data"), (8, 16, 32), MESH)
    assert out == P("data", None, None)


def test_sanitize_tree_maps_over_pytrees():
    specs = {"a": P("model"), "b": P("data", None)}
    shapes = {"a": np.zeros((48,)), "b": np.zeros((7, 3))}
    out = sanitize_tree(specs, shapes, MESH)
    assert out["a"] == P("model")       # 48 % 16 == 0
    assert out["b"] == P(None, None)    # 7 % 2 != 0


def test_plane_axes_divisibility_degradation():
    from repro.sharding.plane import plane_axes
    mesh = _mesh(dpu=4, rows=2)
    assert plane_axes(mesh, 8, 16) == ("dpu", "rows")
    # ragged DPU group: dpu degrades, rows survive
    assert plane_axes(mesh, 7, 16) == (None, "rows")
    # no leading axis at all (master plane)
    assert plane_axes(mesh, None, 16) == (None, "rows")
    # rows not divisible by the rows axis
    assert plane_axes(_mesh(dpu=4, rows=3), 8, 16) == ("dpu", None)
    # rows split only into whole 8-row tiles: the 176-row paper_table1
    # plane (22 tiles) splits 2 ways, not 4 (44 rows per shard)
    assert plane_axes(_mesh(dpu=1, rows=2), 4, 176) == ("dpu", "rows")
    assert plane_axes(_mesh(dpu=1, rows=4), 4, 176) == ("dpu", None)
    assert plane_axes(_mesh(dpu=2, rows=2), 8, 8) == ("dpu", None)


# -------------------------------------------------------- param rules ----

def _fake_params():
    """Path-named pytree exercising every rule family: top-level embeds,
    stacked attention / mlp / mamba / moe blocks, final norm."""
    z = np.zeros
    return {
        "embed": z((512, 64)),
        "pos_embed": z((128, 64)),
        "blocks": {
            "attn": {"wq": z((2, 64, 8, 16)), "wo": z((2, 8, 16, 64)),
                     "ln": z((2, 64))},
            "mlp": {"w_in": z((2, 64, 256)), "w_out": z((2, 256, 64))},
            "mamba": {"w_in": z((2, 64, 128)), "w_out": z((2, 128, 64)),
                      "conv_w": z((2, 4, 128)), "norm": z((2, 128))},
            "moe": {"router": z((2, 64, 8)),
                    "w_in": z((2, 8, 64, 256)),
                    "w_out": z((2, 8, 256, 64))},
        },
        "final_norm": z((64,)),
        "unembed": z((64, 512)),
    }


def test_param_specs_cover_attention_mlp_mamba_moe():
    specs = param_specs(None, _fake_params())
    assert specs["embed"] == P("model", "data")
    assert specs["unembed"] == P("data", "model")
    assert specs["pos_embed"] == P(None, "data")
    blocks = specs["blocks"]
    # stacked blocks get a replicated leading layer axis
    assert blocks["attn"]["wq"] == P(None, "data", "model", None)
    assert blocks["attn"]["wo"] == P(None, "model", None, "data")
    assert blocks["attn"]["ln"] == P(None, None)
    assert blocks["mlp"]["w_in"] == P(None, "data", "model")
    assert blocks["mlp"]["w_out"] == P(None, "model", "data")
    assert blocks["mamba"]["w_in"] == P(None, "data", "model")
    assert blocks["mamba"]["w_out"] == P(None, "model", "data")
    assert blocks["mamba"]["conv_w"] == P(None, None, "model")
    assert blocks["mamba"]["norm"] == P(None, "model")
    assert blocks["moe"]["router"] == P(None, "data", None)
    assert blocks["moe"]["w_in"] == P(None, "model", "data", None)
    assert blocks["moe"]["w_out"] == P(None, "model", None, "data")
    assert specs["final_norm"] == P(None)


def test_param_specs_custom_axis_names():
    specs = param_specs(None, {"embed": np.zeros((8, 8))},
                        data="rows", model="dpu")
    assert specs["embed"] == P("dpu", "rows")


# -------------------------------------------------------- cache rules ----

def _fake_cache():
    z = np.zeros
    return {"layers": {"k": z((2, 4, 128, 8, 64)),
                       "v": z((2, 4, 128, 8, 64)),
                       "xk": z((2, 4, 128, 8, 64)),
                       "h": z((2, 4, 8, 64, 16)),
                       "conv": z((2, 4, 3, 128))},
            "pos": z(())}


def test_cache_specs_default_and_wide():
    specs = cache_specs(None, _fake_cache())
    lay = specs["layers"]
    assert lay["k"] == P(None, ("data",), ("model",), None, None)
    assert lay["v"] == P(None, ("data",), ("model",), None, None)
    # xk/xv: cross-attention keys are not sequence-sharded
    assert lay["xk"] == P(None, ("data",), None, None, None)
    assert lay["h"] == P(None, ("data",), None, None, None)
    assert lay["conv"] == P(None, ("data",), None, None)
    assert specs["pos"] == P()

    wide = cache_specs(None, _fake_cache(), batch_axes=(),
                       seq_axes=("model", "data"))
    assert wide["layers"]["k"] == P(None, (), ("model", "data"), None,
                                    None)
    off = cache_specs(None, _fake_cache(), seq_shard=False)
    assert off["layers"]["k"] == P(None, ("data",), None, None, None)


def test_shard_ctx_for_wide_cache_moves_data_axis():
    mesh = _mesh(data=2, model=4)
    ctx = shard_ctx_for(mesh, multi_pod=False, seq_shard_decode=True)
    assert ctx.batch_axes == ("data",)
    assert ctx.cache_axes == ("model",)

    wide = shard_ctx_for(mesh, multi_pod=False, seq_shard_decode=True,
                         wide_cache=True)
    # long-context b=1: the data axis leaves batch and joins the cache seq
    assert wide.batch_axes == ()
    assert wide.cache_axes == ("model", "data")

    pod = shard_ctx_for(mesh, multi_pod=True, seq_shard_decode=False,
                        wide_cache=True)
    assert pod.batch_axes == ("pod",)


def test_batch_spec():
    assert batch_spec(True) == ("pod", "data")
    assert batch_spec(False) == ("data",)
