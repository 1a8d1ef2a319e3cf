"""Sharded parameter-plane parity: the ('dpu', 'rows') shard_map path
must give params BITWISE identical to the single-device plane round (the
per-DPU losses are allclose; see docs/sharding.md).

These tests need a multi-device mesh; the `shard-parity` CI lane provides
8 virtual CPU devices via XLA_FLAGS=--xla_force_host_platform_device_count=8
(tests/conftest.py deliberately sets no device-count flags, so under plain
tier-1 the module skips on the single real device).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fedprox
from repro.kernels import ops
from repro.models.classifier import (ClassifierConfig, classifier_loss,
                                     init_classifier_params)
from repro.sharding import plane as sp

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8); the shard-parity CI lane sets this")

MESH_SHAPES = [(8, 1), (4, 2), (2, 4), (1, 8), (2, 2)]


# --------------------------------------------------- fixtures / data -----

# 64,090 params -> a 64-row plane = 8 whole SUBLANE tiles, so the 'rows'
# axis of every mesh above really splits the plane (2, 4 or 8 ways)
IN_SHAPE = (16, 16, 1)
CCFG = ClassifierConfig(input_shape=IN_SHAPE, hidden=(240,))


def _round_inputs(G=4, examples=64):
    params = init_classifier_params(jax.random.PRNGKey(0), CCFG)
    rng = np.random.RandomState(0)
    datasets = [
        {"x": jnp.asarray(rng.normal(size=(examples,) + IN_SHAPE),
                          jnp.float32),
         "y": jnp.asarray(rng.randint(0, 10, size=(examples,)), jnp.int32)}
        for _ in range(G)]
    keys = [jax.random.PRNGKey(i + 1) for i in range(G)]
    kw = dict(gamma=3, m_frac=0.25, eta=0.05, mu=0.1, theta=1.0)
    return params, datasets, keys, kw


def _op_inputs(G=8, R=64):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.normal(size=(R, 1024)), jnp.float32)
    d = jnp.asarray(rng.normal(size=(G, R, 1024)), jnp.float32)
    w = jnp.asarray(np.abs(rng.normal(size=(G,))), jnp.float32)
    return x, d, w / w.sum(), rng


def _assert_rows_split(mesh, G, R):
    """Guard against a silent fallback to replication: on a mesh with a
    rows axis the plane must really be row-sharded."""
    if mesh.shape[sp.ROW_AXIS] > 1:
        assert sp.plane_axes(mesh, G, R)[1] == sp.ROW_AXIS, \
            f"rows replicated on mesh {dict(mesh.shape)} (R={R})"


# ------------------------------------------------ standalone plane ops ---

def test_plane_mesh_shapes_and_validation():
    mesh = sp.plane_mesh((4, 2))
    assert mesh.shape == {"dpu": 4, "rows": 2}
    assert sp.plane_mesh(None).shape["dpu"] == jax.device_count()
    with pytest.raises(ValueError, match="devices"):
        sp.plane_mesh((jax.device_count(), 2))
    with pytest.raises(ValueError):
        sp.plane_mesh((0, 1))


def test_nova_aggregate_sharded_exact_is_bitwise():
    x, d, w, _ = _op_inputs()
    ref = ops.nova_aggregate_plane(x, d, w, 0.3)
    for shape in MESH_SHAPES:
        _assert_rows_split(sp.plane_mesh(shape), d.shape[0], x.shape[0])
        out = sp.nova_aggregate_plane_sharded(
            x, d, w, 0.3, mesh=sp.plane_mesh(shape))
        assert bool(jnp.all(ref == out)), f"mesh {shape} not bitwise"


def test_nova_aggregate_sharded_psum_is_allclose():
    x, d, w, _ = _op_inputs()
    ref = ops.nova_aggregate_plane(x, d, w, 0.3)
    out = sp.nova_aggregate_plane_sharded(
        x, d, w, 0.3, mesh=sp.plane_mesh((4, 2)), reduce="psum")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)
    with pytest.raises(ValueError, match="reduce"):
        sp.nova_aggregate_plane_sharded(
            x, d, w, 0.3, mesh=sp.plane_mesh((4, 2)), reduce="mean")


def test_nova_aggregate_sharded_ragged_group_degrades_bitwise():
    # G=7 divides no 8/4/2-way dpu axis: the spec degrades that dim to
    # replication (sanitize rule) and the result stays bitwise
    x, d, w, _ = _op_inputs(G=7)
    ref = ops.nova_aggregate_plane(x, d, w, 0.3)
    out = sp.nova_aggregate_plane_sharded(
        x, d, w, 0.3, mesh=sp.plane_mesh((4, 2)))
    assert bool(jnp.all(ref == out))


def test_robust_aggregate_sharded_is_bitwise():
    x, d, _, _ = _op_inputs()
    for mode in ("trimmed_mean", "median"):
        ref = ops.robust_aggregate_plane(x, d, 0.3, mode=mode,
                                         trim_frac=0.2)
        out = sp.robust_aggregate_plane_sharded(
            x, d, 0.3, mesh=sp.plane_mesh((4, 2)), mode=mode,
            trim_frac=0.2)
        assert bool(jnp.all(ref == out)), mode


def test_fedprox_accum_sharded_is_bitwise():
    x, d, _, rng = _op_inputs()
    G, R = d.shape[0], x.shape[0]
    xs = jnp.asarray(rng.normal(size=(G, R, 1024)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(G, R, 1024)), jnp.float32)
    coef = jnp.asarray(np.abs(rng.normal(size=(G,))), jnp.float32)
    act = jnp.ones((G,), jnp.float32)
    ref = ops.fedprox_accum_plane(xs, g, x, jnp.zeros_like(xs), coef, act,
                                  0.05, 0.1)
    out = sp.fedprox_accum_plane_sharded(
        xs, g, x, jnp.zeros_like(xs), coef, act, 0.05, 0.1,
        mesh=sp.plane_mesh((4, 2)))
    for a, b in zip(ref, out):
        assert bool(jnp.all(a == b))


# ------------------------------------------------- fused sharded round ---

def test_sharded_round_bitwise_across_mesh_shapes():
    params, datasets, keys, kw = _round_inputs()
    ref_plane, ref_loss, _ = fedprox.local_round_plane(
        params, classifier_loss, datasets, keys=keys, **kw)
    for shape in MESH_SHAPES:
        _assert_rows_split(sp.plane_mesh(shape), len(datasets),
                           ref_plane.data.shape[0])
        out_plane, out_loss, _ = sp.local_round_plane_sharded(
            params, classifier_loss, datasets, keys=keys,
            mesh=sp.plane_mesh(shape), **kw)
        assert bool(jnp.all(out_plane.data == ref_plane.data)), \
            f"params diverge on mesh {shape}"
        # losses are allclose, not bitwise: a mesh that leaves one DPU
        # per device vmaps the loss over a size-1 axis, which XLA may
        # reduce in another order (1 ulp on (4, 2))
        np.testing.assert_allclose(out_loss, ref_loss, rtol=1e-6, atol=0,
                                   err_msg=f"losses diverge on mesh {shape}")


def test_sharded_round_psum_mode_allclose():
    params, datasets, keys, kw = _round_inputs()
    ref_plane, _, _ = fedprox.local_round_plane(
        params, classifier_loss, datasets, keys=keys, **kw)
    out_plane, _, _ = sp.local_round_plane_sharded(
        params, classifier_loss, datasets, keys=keys,
        mesh=sp.plane_mesh((4, 2)), reduce="psum", **kw)
    np.testing.assert_allclose(np.asarray(out_plane.data),
                               np.asarray(ref_plane.data),
                               atol=1e-6, rtol=1e-6)


def test_sharded_round_ragged_group_bitwise():
    params, datasets, keys, kw = _round_inputs()
    ref_plane, ref_loss, _ = fedprox.local_round_plane(
        params, classifier_loss, datasets[:3], keys=keys[:3], **kw)
    out_plane, out_loss, _ = sp.local_round_plane_sharded(
        params, classifier_loss, datasets[:3], keys=keys[:3],
        mesh=sp.plane_mesh((8, 1)), **kw)
    assert bool(jnp.all(out_plane.data == ref_plane.data))
    assert np.all(out_loss == ref_loss)


def test_sharded_round_warm_no_retrace(assert_no_retrace):
    params, datasets, keys, kw = _round_inputs()
    mesh = sp.plane_mesh((4, 2))
    sp.local_round_plane_sharded(params, classifier_loss, datasets,
                                 keys=keys, mesh=mesh, **kw)
    with assert_no_retrace():
        for i in range(3):
            keys2 = [jax.random.PRNGKey(100 + i) for _ in keys]
            sp.local_round_plane_sharded(params, classifier_loss, datasets,
                                         keys=keys2, mesh=mesh, **kw)


# ------------------------------------------------------ engine parity ----

def _engine_run(**opt_kw):
    from repro.core import Engine, EngineOptions, MLConstants
    from repro.data import make_image_dataset, make_online_ues
    from repro.models.classifier import classifier_accuracy
    from repro.network import NetworkConfig, make_network
    from repro.solver import ObjectiveWeights

    net = make_network(NetworkConfig(num_ue=4, num_bs=2, num_dc=2))
    (trx, tr_y), (tex, te_y) = make_image_dataset(2000, IN_SHAPE)
    p0 = init_classifier_params(jax.random.PRNGKey(0), CCFG)
    consts = MLConstants(L=5.0, theta_i=np.ones(6) * 2,
                         sigma_i=np.ones(6) * 3, zeta1=2.0, zeta2=1.0)
    eng = Engine(net, "fednova", consts=consts, ow=ObjectiveWeights(T=3),
                 opts=EngineOptions(rounds=3, seed=0, **opt_kw))
    ues = make_online_ues(trx, tr_y, num_ue=4, mean_arrivals=200,
                          std_arrivals=20)

    def eval_fn(p):
        return classifier_accuracy(p, jnp.asarray(tex[:300]),
                                   jnp.asarray(te_y[:300]))

    return eng.run(ues, init_params=p0, loss_fn=classifier_loss,
                   eval_fn=eval_fn)


def test_engine_sharded_matches_single_device_bitwise():
    """EngineOptions.mesh_shape end to end: accuracy, loss AND final
    params of the sharded engine equal the single-device run bitwise."""
    ref = _engine_run()
    for shape in [(4, 2), (2, 2)]:
        out = _engine_run(mesh_shape=shape)
        assert [r.acc for r in out.reports] == [r.acc for r in ref.reports]
        assert [r.loss for r in out.reports] == \
            [r.loss for r in ref.reports]
        for a, b in zip(jax.tree_util.tree_leaves(ref.params),
                        jax.tree_util.tree_leaves(out.params)):
            assert bool(jnp.all(a == b)), f"params diverge on mesh {shape}"


def test_mesh_executor_sharded_plane_allclose():
    """MeshExecutor.mesh_shape device_puts the plane stack with a
    NamedSharding; GSPMD may re-partition reductions, so the contract is
    allclose (not bitwise)."""
    from repro.core import (Engine, EngineOptions, MeshExecutor,
                            MLConstants)
    from repro.data import make_image_dataset, make_online_ues
    from repro.network import NetworkConfig, make_network
    from repro.solver import ObjectiveWeights

    from repro.models.classifier import classifier_accuracy

    net = make_network(NetworkConfig(num_ue=4, num_bs=2, num_dc=2))
    (trx, tr_y), (tex, te_y) = make_image_dataset(1200, (8, 8, 1))
    ccfg = ClassifierConfig(input_shape=(8, 8, 1), hidden=(16,))
    p0 = init_classifier_params(jax.random.PRNGKey(0), ccfg)
    consts = MLConstants(L=5.0, theta_i=np.ones(6) * 2,
                         sigma_i=np.ones(6) * 3, zeta1=2.0, zeta2=1.0)

    def run(executor):
        eng = Engine(net, "fixed:0", consts=consts,
                     ow=ObjectiveWeights(T=2),
                     opts=EngineOptions(rounds=2, seed=0, solver_outer=2),
                     executor=executor)
        ues = make_online_ues(trx, tr_y, num_ue=4, mean_arrivals=120,
                              std_arrivals=12, seed=0)
        return eng.run(ues, init_params=p0, loss_fn=classifier_loss,
                       eval_fn=lambda p: classifier_accuracy(
                           p, jnp.asarray(tex[:100]),
                           jnp.asarray(te_y[:100])))

    ref = run(MeshExecutor())
    out = run(MeshExecutor(mesh_shape=(4, 2)))
    np.testing.assert_allclose(out.series("loss"), ref.series("loss"),
                               atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(out.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)
