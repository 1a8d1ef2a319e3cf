"""Flat parameter-plane layer: FlatSpec round-trips, batched-kernel parity
vs the pure-jnp oracles, the weight-semantics contract, degenerate
mini-batch sampling, and tree-path vs plane-path engine equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.cefl_paper import ClassifierConfig
from repro.core import aggregation, fedprox
from repro.core.round_step import CEFLHyper, build_cefl_round_step, \
    make_dpu_meta
from repro.kernels import ops, ref
from repro.kernels.fedprox_update import LANE, fedprox_accum_2d
from repro.kernels.plane import ParamPlane, as_tree, spec_of
from repro.models.classifier import classifier_loss, init_classifier_params
from repro.utils import tracing

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------- FlatSpec round-trip -----

def _assert_tree_equal(a_tree, b_tree):
    for a, b in zip(jax.tree_util.tree_leaves(a_tree),
                    jax.tree_util.tree_leaves(b_tree)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("tree", [
    # odd leaf shapes
    {"w": jax.random.normal(KEY, (37, 13)),
     "b": jax.random.normal(KEY, (7,)),
     "nested": {"u": jax.random.normal(KEY, (2, 3, 5))}},
    # scalar + empty leaves
    {"s": jnp.asarray(1.5), "e": jnp.zeros((0, 4)),
     "v": jnp.arange(11, dtype=jnp.float32)},
    # bf16 params (f32 plane holds bf16 exactly)
    {"w": jax.random.normal(KEY, (33, 9)).astype(jnp.bfloat16),
     "b": jax.random.normal(KEY, (129,)).astype(jnp.bfloat16)},
], ids=["odd-shapes", "empty-and-scalar", "bf16"])
def test_flatspec_roundtrip(tree):
    spec = spec_of(tree)
    assert spec.rows % 8 == 0
    _assert_tree_equal(spec.unflatten(spec.flatten(tree)), tree)
    # ParamPlane view round-trips too, batched included
    plane = ParamPlane.from_tree(tree)
    _assert_tree_equal(plane.to_tree(), tree)
    stacked = plane.broadcast(3)
    batched = stacked.to_tree()
    _assert_tree_equal(
        jax.tree_util.tree_map(lambda x: x[1], batched), tree)


def _flat_view(spec, plane):
    """The reference tree view: one flat view of the whole plane, each leaf
    a dynamic slice of it, differentiated by autodiff."""
    flat = plane.reshape(-1)
    out = []
    for shape, dtype, off in zip(spec.shapes, spec.dtypes, spec.offsets):
        k = int(np.prod(shape)) if shape else 1
        out.append(jax.lax.dynamic_slice_in_dim(flat, off, k)
                   .reshape(shape).astype(dtype))
    return jax.tree_util.tree_unflatten(spec.treedef, out)


def _sin_loss(view):
    """A weighted sum of ``sin`` of every leaf of ``view(plane)``."""
    def loss(plane):
        leaves = jax.tree_util.tree_leaves(view(plane))
        return sum((i + 1.0) * jnp.sum(jnp.sin(x.astype(jnp.float32)))
                   for i, x in enumerate(leaves))
    return loss


def _classifier_shapes():
    cfg = ClassifierConfig(input_shape=(28, 28, 1), hidden=(200, 100))
    return jax.eval_shape(lambda: init_classifier_params(KEY, cfg))


@pytest.mark.parametrize("shapes", [
    # 3 x 700 straddles rows 0-2, the next leaf starts mid-row
    {"a": ((3, 700), jnp.float32), "b": ((1500,), jnp.float32),
     "c": ((2, 9), jnp.float32)},
    # "b" ends on the row boundary 2048, "c" starts on it
    {"a": ((3, 512), jnp.float32), "b": ((512,), jnp.float32),
     "c": ((7, 3), jnp.float32)},
    # scalars around a leaf longer than a row, and an empty leaf
    {"s": ((), jnp.float32), "v": ((1030,), jnp.float32),
     "e": ((0, 4), jnp.float32), "t": ((), jnp.float32)},
    # bf16 leaves beside an f32 one
    {"w": ((33, 40), jnp.bfloat16), "b": ((129,), jnp.bfloat16),
     "f": ((1000,), jnp.float32)},
    "classifier",
], ids=["straddle", "row-end", "scalar", "bf16", "classifier"])
def test_row_window_view_equals_flat_view(shapes):
    """The row-window view and its ``flatten`` backward give, bit for bit,
    the leaves and gradients of the flat view, and so under ``vmap`` over
    a (G, R, LANE) stack."""
    if shapes == "classifier":
        tree = _classifier_shapes()
    else:
        tree = {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in shapes.items()}
    spec = spec_of(tree)
    stack = jax.random.normal(jax.random.PRNGKey(1), (3, spec.rows, LANE))
    plane = stack[0]

    _assert_tree_equal(jax.jit(spec.unflatten)(plane),
                       jax.jit(lambda p: _flat_view(spec, p))(plane))
    new, ref = _sin_loss(spec.unflatten), _sin_loss(
        lambda p: _flat_view(spec, p))
    np.testing.assert_array_equal(jax.jit(jax.grad(new))(plane),
                                  jax.jit(jax.grad(ref))(plane))
    np.testing.assert_array_equal(
        jax.jit(jax.vmap(jax.grad(new)))(stack),
        jax.jit(jax.vmap(jax.grad(ref)))(stack))
    # batched views are vmap(unflatten): leaves carry the leading G axis
    _assert_tree_equal(spec.unflatten_batched(stack),
                       jax.vmap(lambda p: _flat_view(spec, p))(stack))


def test_view_ops_carry_the_unflatten_scope():
    """The view's forward and its backward label their ops (``op_name``)
    with the ``cefl.unflatten`` scope, which the trace reader keys on."""
    spec = spec_of({"a": jnp.zeros((3, 700)), "b": jnp.zeros((1500,))})
    text = jax.jit(jax.grad(_sin_loss(spec.unflatten))).lower(
        jnp.zeros((spec.rows, LANE))).compile().as_text()
    assert f"jvp({tracing.UNFLATTEN})/" in text
    assert f"transpose(jvp({tracing.UNFLATTEN}))/" in text


def test_spec_is_cached_and_hashable():
    t1 = {"w": jnp.zeros((5, 5))}
    t2 = {"w": jnp.ones((5, 5))}
    assert spec_of(t1) is spec_of(t2)       # same structure, one spec
    assert hash(spec_of(t1)) == hash(spec_of(t2))
    assert spec_of(t1) != spec_of({"w": jnp.zeros((5, 6))})


# -------------------------------------------- batched kernel vs oracle -----

@pytest.mark.parametrize("anchor_kind", ["shared", "per_group"])
@pytest.mark.parametrize("G,R", [(1, 8), (3, 16), (5, 64)])
def test_fedprox_accum_kernel_vs_ref(anchor_kind, G, R):
    x = jax.random.normal(KEY, (G, R, LANE))
    g = jax.random.normal(jax.random.PRNGKey(1), (G, R, LANE))
    acc = jax.random.normal(jax.random.PRNGKey(2), (G, R, LANE))
    anc2 = jax.random.normal(jax.random.PRNGKey(3), (R, LANE))
    anc = anc2 if anchor_kind == "shared" else \
        jnp.broadcast_to(anc2[None], x.shape) * 1.1
    coef = jnp.linspace(1.0, 0.5, G)
    active = (jnp.arange(G) % 2).astype(jnp.float32)
    out = fedprox_accum_2d(x, g, anc, acc, coef, active, 0.1, 0.05,
                           interpret=True)
    exp = ref.fedprox_accum_ref(x, g, anc, acc, coef, active, 0.1, 0.05)
    np.testing.assert_allclose(out[0], exp[0], atol=1e-6)
    np.testing.assert_allclose(out[1], exp[1], atol=1e-6)


def test_nova_stacked_kernel_vs_ref():
    n, R = 4, 16
    x = jax.random.normal(KEY, (n, R, LANE))
    d = jax.random.normal(jax.random.PRNGKey(1), (n, R, LANE))
    w = jnp.asarray([0.1, 0.2, 0.3, 0.4])
    out = ops.nova_aggregate_plane(x, d, w, 0.07)
    exp = ref.nova_aggregate_ref(x, d, w, 0.07)
    np.testing.assert_allclose(out, exp, atol=1e-5)


# ------------------------------------------------ weight contract -----

def test_weight_contract_absolute_sizes_one_normalization():
    """All tree-level aggregation entry points take ABSOLUTE D_i and
    normalize once; scaling the weights must not change the result
    (regression for ops.nova_aggregate silently re-normalizing while
    round_step expected pre-normalized weights)."""
    params = {"w": jax.random.normal(KEY, (33, 9))}
    ds = [jax.tree_util.tree_map(lambda x: (i + 1) * 0.1 * x, params)
          for i in range(3)]
    for w_abs in ([100.0, 300.0, 100.0], [0.2, 0.6, 0.2]):
        out_ops = ops.nova_aggregate(params, ds, w_abs, 0.02)
        out_agg = aggregation.aggregate(params, ds, w_abs, theta=1.0,
                                        eta=0.02)
        np.testing.assert_allclose(out_ops["w"], out_agg["w"], atol=1e-5)
    # scaled vs normalized weights: identical everywhere
    a = aggregation.aggregate(params, ds, [1.0, 3.0, 1.0], theta=2.0,
                              eta=0.1)
    b = aggregation.aggregate(params, ds, [0.2, 0.6, 0.2], theta=2.0,
                              eta=0.1)
    np.testing.assert_allclose(a["w"], b["w"], atol=1e-6)


def test_round_step_accepts_absolute_weights():
    cfg = ClassifierConfig(input_shape=(6, 6, 1), hidden=(16,))
    p0 = init_classifier_params(KEY, cfg)
    n_dpu, mb = 2, 8
    x = jax.random.normal(KEY, (n_dpu, 1, mb, 6, 6, 1))
    y = jax.random.randint(KEY, (n_dpu, 1, mb), 0, 10)

    def loss_fn(p, micro, mask):
        return classifier_loss(p, {"x": micro["x"], "y": micro["y"]},
                               mask), {}

    step = jax.jit(build_cefl_round_step(
        loss_fn, CEFLHyper(eta=0.05, mu=0.01, gamma_max=2)))
    stacked = jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l[None], (n_dpu,) + l.shape), p0)
    meta_abs = make_dpu_meta(n_dpu, gammas=[2, 2], weights=[300.0, 100.0])
    meta_norm = make_dpu_meta(n_dpu, gammas=[2, 2], weights=[0.75, 0.25])
    out_abs, _ = step(stacked, {"x": x, "y": y}, meta_abs)
    out_norm, _ = step(stacked, {"x": x, "y": y}, meta_norm)
    for k in out_abs:
        np.testing.assert_allclose(out_abs[k], out_norm[k], atol=1e-7)


# ------------------------------------- degenerate mini-batch sampling -----

def test_sample_minibatch_clamps_and_handles_empty():
    idx = fedprox.sample_minibatch(KEY, 4, 1.0)
    assert len(idx) == 4 and len(set(np.asarray(idx).tolist())) == 4
    # m*D rounds above D -> clamped to D (used to fault in choice)
    idx = fedprox.sample_minibatch(KEY, 3, 1.2)
    assert len(idx) == 3
    # D == 0 (degenerate offloading split) -> empty, no fault
    idx = fedprox.sample_minibatch(KEY, 0, 0.5)
    assert idx.shape == (0,)
    # tiny m still yields one example
    assert len(fedprox.sample_minibatch(KEY, 50, 1e-6)) == 1


@pytest.mark.parametrize("backend", ["plane", "tree"])
def test_local_train_handles_empty_dataset(backend):
    """A D == 0 DPU (degenerate offloading split) trains nothing instead
    of faulting: params unchanged, d_i = 0, nan loss."""
    cfg = ClassifierConfig(input_shape=(6, 6, 1), hidden=(16,))
    p0 = init_classifier_params(KEY, cfg)
    empty = {"x": jnp.zeros((0, 6, 6, 1)), "y": jnp.zeros((0,), jnp.int32)}
    data = {"x": jax.random.normal(KEY, (8, 6, 6, 1)),
            "y": jax.random.randint(KEY, (8,), 0, 10)}
    r = fedprox.local_train(p0, classifier_loss, empty, gamma=2,
                            m_frac=0.5, eta=0.05, mu=0.01, key=KEY,
                            backend=backend)
    assert r.num_examples == 0 and np.isnan(r.loss)
    _assert_tree_equal(as_tree(r.params), p0)
    assert all(not np.any(np.asarray(x))
               for x in jax.tree_util.tree_leaves(as_tree(r.d_i)))
    # mixed batch: empty DPUs skipped, live ones match an all-live run
    keys = list(jax.random.split(KEY, 3))
    mixed = fedprox.local_train_batched(
        p0, classifier_loss, [data, empty, data], gamma=2, m_frac=1.0,
        eta=0.05, mu=0.01, keys=keys, backend=backend)
    assert mixed[1].num_examples == 0
    alive = fedprox.local_train_batched(
        p0, classifier_loss, [data, data], gamma=2, m_frac=1.0,
        eta=0.05, mu=0.01, keys=[keys[0], keys[2]], backend=backend)
    for a, b in zip(jax.tree_util.tree_leaves(as_tree(mixed[2].params)),
                    jax.tree_util.tree_leaves(as_tree(alive[1].params))):
        np.testing.assert_allclose(a, b, atol=1e-6)


# ----------------------------------------- engine plane/tree parity -----

def _mini_engine(executor):
    from repro.core import (Engine, EngineOptions, MLConstants)
    from repro.data import make_image_dataset, make_online_ues
    from repro.models.classifier import classifier_accuracy
    from repro.network import NetworkConfig, make_network
    from repro.solver import ObjectiveWeights
    net = make_network(NetworkConfig(num_ue=4, num_bs=2, num_dc=2))
    (trx, tr_y), (tex, te_y) = make_image_dataset(1200, (8, 8, 1))
    ccfg = ClassifierConfig(input_shape=(8, 8, 1), hidden=(16,))
    p0 = init_classifier_params(KEY, ccfg)
    consts = MLConstants(L=5.0, theta_i=np.ones(6) * 2,
                         sigma_i=np.ones(6) * 3, zeta1=2.0, zeta2=1.0)
    eng = Engine(net, "fixed:0", consts=consts, ow=ObjectiveWeights(),
                 opts=EngineOptions(rounds=3, eta=0.1, solver_outer=2),
                 executor=executor)
    ues = make_online_ues(trx, tr_y, num_ue=4, mean_arrivals=120,
                          std_arrivals=12, seed=0)

    def eval_fn(p):
        return classifier_accuracy(p, jnp.asarray(tex[:200]),
                                   jnp.asarray(te_y[:200]))

    return eng.run(ues, init_params=p0, loss_fn=classifier_loss,
                   eval_fn=eval_fn)


def test_engine_plane_path_matches_tree_path():
    """SimExecutor loss/params series on the plane path must match the
    pre-refactor tree path within float tolerance."""
    from repro.core import SimExecutor
    res_plane = _mini_engine(SimExecutor(use_plane=True))
    res_tree = _mini_engine(SimExecutor(use_plane=False))
    np.testing.assert_allclose(res_plane.series("loss"),
                               res_tree.series("loss"), atol=1e-4)
    np.testing.assert_allclose(res_plane.series("acc"),
                               res_tree.series("acc"), atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(res_plane.params),
                    jax.tree_util.tree_leaves(res_tree.params)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_engine_mesh_plane_path_matches_tree_path():
    from repro.core import MeshExecutor
    res_plane = _mini_engine(MeshExecutor(use_plane=True))
    res_tree = _mini_engine(MeshExecutor(use_plane=False))
    np.testing.assert_allclose(res_plane.series("loss"),
                               res_tree.series("loss"), atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(res_plane.params),
                    jax.tree_util.tree_leaves(res_tree.params)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_local_train_plane_results_unflatten_at_boundary():
    """keep_planes=True returns ParamPlane-backed results; as_tree is the
    API-boundary conversion and matches the default tree output."""
    cfg = ClassifierConfig(input_shape=(6, 6, 1), hidden=(16,))
    p0 = init_classifier_params(KEY, cfg)
    data = {"x": jax.random.normal(KEY, (16, 6, 6, 1)),
            "y": jax.random.randint(KEY, (16,), 0, 10)}
    kw = dict(gamma=2, m_frac=1.0, eta=0.05, mu=0.01, key=KEY)
    r_plane = fedprox.local_train(p0, classifier_loss, data,
                                  keep_planes=True, **kw)
    r_tree = fedprox.local_train(p0, classifier_loss, data, **kw)
    assert isinstance(r_plane.params, ParamPlane)
    for a, b in zip(jax.tree_util.tree_leaves(as_tree(r_plane.params)),
                    jax.tree_util.tree_leaves(r_tree.params)):
        np.testing.assert_allclose(a, b, atol=1e-6)
