"""Adversarial client models (scenario.adversary), the robust-aggregation
counter (kernels + core.aggregation + EngineOptions.robust_agg), their
engine threading (corruption between training and aggregation, straggler
cost accounting), and the ISSUE-8 robustness acceptance gate: under a 20%
sign-flip byzantine population, trimmed-mean CE-FL retains >= 80% of its
clean final accuracy while plain FedAvg demonstrably degrades."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.cefl_paper import ClassifierConfig
from repro.core import Engine, EngineOptions, MLConstants, aggregation
from repro.data import make_image_dataset, make_online_ues
from repro.kernels import ops
from repro.kernels.plane import as_plane
from repro.kernels.ref import robust_aggregate_ref, robust_reduce_ref
from repro.models.classifier import (classifier_accuracy, classifier_loss,
                                     init_classifier_params)
from repro.network import NetworkConfig, make_network
from repro.scenario import (ByzantineUpdate, Dropout, DynamicScenario,
                            LabelPoison, Straggler)
from repro.scenario.adversary import resolve_ues
from repro.solver import ObjectiveWeights

from _hypothesis_compat import given, settings, st

NET = make_network(NetworkConfig(num_ue=6, num_bs=3, num_dc=2))
(TRX, TRY), (TEX, TEY) = make_image_dataset(2500, (8, 8, 1))
CCFG = ClassifierConfig(input_shape=(8, 8, 1), hidden=(16,))
P0 = init_classifier_params(jax.random.PRNGKey(0), CCFG)
CONSTS = MLConstants(L=5.0, theta_i=np.ones(8) * 2, sigma_i=np.ones(8) * 3,
                     zeta1=2.0, zeta2=1.0)
OW = ObjectiveWeights()


def _eval_fn(p):
    return classifier_accuracy(p, np.asarray(TEX[:400]), np.asarray(TEY[:400]))


def _run(strategy, scenario, *, robust="none", trim_frac=0.2, rounds=4,
         seed=0, arrivals=120):
    ues = make_online_ues(TRX, TRY, num_ue=6, mean_arrivals=arrivals,
                          std_arrivals=arrivals / 10, seed=seed)
    eng = Engine(NET, strategy, consts=CONSTS, ow=OW, scenario=scenario,
                 opts=EngineOptions(rounds=rounds, eta=0.1, solver_outer=2,
                                    seed=seed, robust_agg=robust,
                                    trim_frac=trim_frac))
    return eng.run(ues, init_params=P0, loss_fn=classifier_loss,
                   eval_fn=_eval_fn)


# ------------------------------------------------------- unit: models --

def test_resolve_ues_frac_is_deterministic_and_spread():
    assert resolve_ues(10, 0.2, None) == resolve_ues(10, 0.2, None)
    assert len(resolve_ues(10, 0.2, None)) == 2
    assert resolve_ues(10, 0.0, None) == ()
    assert resolve_ues(6, 0.2, None) == (0,)        # round(1.2) == 1
    got = resolve_ues(10, 1.0, None)
    assert got == tuple(range(10))                   # frac=1 -> everyone
    # explicit set wins, is clamped to range, deduped and sorted
    assert resolve_ues(5, 0.9, (4, 1, 1, 7, -2)) == (1, 4)


def test_byzantine_update_events_and_start_gating():
    adv = ByzantineUpdate(mode="gauss", frac=0.5, scale=2.5, start=3)
    adv.reset(4)
    assert adv.corrupted(2) == ()                    # not started yet
    got = adv.corrupted(3)
    assert got == ((0, "gauss", 2.5), (3, "gauss", 2.5))
    data = {"x": np.zeros((3, 1)), "y": np.arange(3)}
    rng = np.random.RandomState(0)
    assert adv.apply(5, 0, data, rng) is data        # data untouched
    with pytest.raises(ValueError, match="unknown corruption mode"):
        ByzantineUpdate(mode="zero_out")


def test_label_poison_flips_only_compromised_ues():
    adv = LabelPoison(frac=0.5, num_classes=10, ues=(1,), start=1)
    adv.reset(4)
    data = {"x": np.zeros((4, 1)), "y": np.array([0, 3, 5, 9])}
    rng = np.random.RandomState(0)
    assert adv.apply(0, 1, data, rng) is data        # before start
    np.testing.assert_array_equal(adv.apply(1, 1, data, rng)["y"],
                                  np.array([9, 6, 4, 0]))
    assert adv.apply(1, 0, data, rng) is data        # honest UE untouched
    empty = {"x": np.zeros((0, 1)), "y": np.zeros(0, int)}
    assert adv.apply(1, 1, empty, rng) is empty      # no-data round


def test_straggler_compute_scale_shape_and_values():
    adv = Straggler(frac=0.5, slowdown=4.0)
    adv.reset(4)
    assert adv.compute_scale(0, 4) == (0.25, 1.0, 1.0, 0.25)
    with pytest.raises(ValueError, match="slowdown"):
        Straggler(slowdown=0.0)


def test_dropout_respects_min_active_floor():
    adv = Dropout(p=1.0, min_active=2)
    adv.reset(5)
    rng = np.random.RandomState(0)
    adv.begin_round(0, 5, rng)
    data = {"x": np.zeros((3, 1)), "y": np.arange(3)}
    alive = [len(adv.apply(0, u, data, rng)["y"]) > 0 for u in range(5)]
    assert sum(alive) == 2 and alive[:2] == [True, True]  # lowest indices
    _, left = adv.events()
    assert len(left) == 3


# --------------------------------------------------- robust reduction --

@given(st.integers(min_value=3, max_value=9),
       st.integers(min_value=0, max_value=10 ** 6))
@settings(max_examples=20, deadline=None)
def test_robust_reduce_ref_matches_numpy_sort_oracle(n, seed):
    rng = np.random.RandomState(seed)
    stack = rng.randn(n, 4, 8).astype(np.float32) * 3
    k = (n - 1) // 2 if n > 2 else 0
    srt = np.sort(stack, axis=0)
    np.testing.assert_allclose(
        np.asarray(robust_reduce_ref(jnp.asarray(stack), k=k)),
        srt[k:n - k].mean(axis=0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(robust_reduce_ref(jnp.asarray(stack), median=True)),
        np.median(stack, axis=0), rtol=1e-5, atol=1e-6)


def test_robust_reduce_rejects_overtrim():
    with pytest.raises(ValueError, match="2k < n"):
        robust_reduce_ref(jnp.zeros((4, 2, 2)), k=2)


def test_trim_count_properties():
    assert ops.trim_count(10, 0.1) == 1
    assert ops.trim_count(10, 0.0) == 0
    assert ops.trim_count(3, 0.49) == 1
    for n in range(1, 12):
        for f in (0.0, 0.1, 0.2, 0.3, 0.49):
            k = ops.trim_count(n, f)
            assert 0 <= 2 * k < n                    # survivor guarantee
    with pytest.raises(ValueError, match="trim_frac"):
        ops.trim_count(10, 0.5)


def test_trimmed_mean_ignores_a_planted_outlier():
    rng = np.random.RandomState(0)
    honest = rng.randn(5, 2, 16).astype(np.float32)
    evil = np.concatenate([honest, np.full((1, 2, 16), 1e4, np.float32)])
    out = np.asarray(robust_reduce_ref(jnp.asarray(evil), k=1))
    assert np.abs(out).max() < 10                    # outlier trimmed away
    # the plain mean is swamped
    assert np.abs(evil.mean(axis=0)).max() > 1e3


def test_trimmed_mean_trims_a_nan_payload():
    rng = np.random.RandomState(0)
    honest = rng.randn(5, 2, 16).astype(np.float32)
    evil = np.concatenate([honest, np.full((1, 2, 16), np.nan, np.float32)])
    out = np.asarray(robust_reduce_ref(jnp.asarray(evil[::-1]), k=1))
    np.testing.assert_allclose(
        out, np.sort(honest, axis=0)[1:].mean(axis=0), rtol=1e-6)


def test_robust_aggregate_plane_cpu_matches_interpret():
    rng = np.random.RandomState(1)
    x = rng.randn(8, 1024).astype(np.float32)
    stack = rng.randn(6, 8, 1024).astype(np.float32)
    for mode in ops.ROBUST_MODES:
        cpu = ops.robust_aggregate_plane(x, stack, 0.2, mode=mode,
                                         trim_frac=0.2, backend="cpu")
        itp = ops.robust_aggregate_plane(x, stack, 0.2, mode=mode,
                                         trim_frac=0.2, backend="interpret")
        np.testing.assert_allclose(np.asarray(cpu), np.asarray(itp),
                                   rtol=1e-5, atol=1e-5)
        ref = robust_aggregate_ref(
            jnp.asarray(x), jnp.asarray(stack), 0.2,
            k=0 if mode == "median" else ops.trim_count(6, 0.2),
            median=(mode == "median"))
        np.testing.assert_allclose(np.asarray(cpu), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_robust_aggregate_tree_and_plane_paths_agree():
    rng = np.random.RandomState(2)
    tree = {"w": rng.randn(8, 4).astype(np.float32),
            "b": rng.randn(4).astype(np.float32)}
    ds = [{"w": rng.randn(8, 4).astype(np.float32),
           "b": rng.randn(4).astype(np.float32)} for _ in range(5)]
    out_tree = aggregation.robust_aggregate(
        tree, ds, theta=2.0, eta=0.1, mode="trimmed_mean", trim_frac=0.2)
    plane = as_plane(tree)
    out_plane = aggregation.robust_aggregate(
        plane, [as_plane(d) for d in ds], theta=2.0, eta=0.1,
        mode="trimmed_mean", trim_frac=0.2).to_tree()
    for k in tree:
        np.testing.assert_allclose(np.asarray(out_tree[k]),
                                   np.asarray(out_plane[k]),
                                   rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="unknown robust mode"):
        aggregation.robust_aggregate(tree, ds, theta=2.0, eta=0.1,
                                     mode="krum")


def test_robust_fedavg_reduces_to_plain_mean_without_trim():
    rng = np.random.RandomState(3)
    ps = [as_plane({"w": rng.randn(4, 4).astype(np.float32)})
          for _ in range(3)]
    out = aggregation.robust_fedavg_aggregate(ps, mode="trimmed_mean",
                                              trim_frac=0.0)
    mean = np.mean([np.asarray(p.data) for p in ps], axis=0)
    np.testing.assert_allclose(np.asarray(out.data), mean,
                               rtol=1e-5, atol=1e-6)


# ----------------------------------------------------- engine threading --

def test_byzantine_scenario_changes_the_run():
    """byzantine:0 is the rng-identical clean twin; any nonzero fraction
    must actually alter the training trajectory."""
    clean = _run("cefl", "byzantine:0.0")
    byz = _run("cefl", "byzantine")
    assert clean.series("loss") != byz.series("loss")
    # and the corrupted events surface in staged rounds, not reports: the
    # clean twin's accuracy series must also differ
    assert clean.series("acc") != byz.series("acc")


def test_gauss_corruption_is_seed_deterministic():
    def mk():
        return DynamicScenario(
            mobility=None,
            schedules=(ByzantineUpdate(mode="gauss", frac=0.34, scale=1.0),))
    a = _run("greedy_data", mk(), rounds=3)
    b = _run("greedy_data", mk(), rounds=3)
    assert a.series("loss") == b.series("loss")
    assert a.series("acc") == b.series("acc")


def test_straggler_slowdown_raises_round_delay():
    """f_n scaling rides through network_costs: the straggler run's
    cumulative delay must exceed the identically-seeded clean run's."""
    def mk(slowdown):
        return DynamicScenario(
            mobility=None,
            schedules=(Straggler(frac=0.5, slowdown=slowdown),))
    slow = _run("greedy_data", mk(8.0), rounds=3)
    clean = _run("greedy_data", mk(1.0), rounds=3)
    assert clean.series("loss") == slow.series("loss")   # learning equal
    assert slow.reports[-1].cum_delay > 1.5 * clean.reports[-1].cum_delay


def test_robust_agg_flag_threads_through_spec():
    from repro.experiments import get_experiment
    spec = get_experiment("quickstart").override(**{
        "engine.robust_agg": "median", "engine.trim_frac": 0.25})
    opts = spec.engine_options(0)
    assert opts.robust_agg == "median" and opts.trim_frac == 0.25
    from repro.experiments.spec import from_json, to_json
    assert from_json(to_json(spec)) == spec


# ------------------------------------------------- acceptance (ISSUE 8) --

def test_robust_cefl_survives_byzantine_population():
    """THE robustness gate: 20% sign-flip byzantines (byzantine preset,
    scale 4).  Trimmed-mean CE-FL keeps >= 80% of the clean twin's final
    accuracy; plain FedAvg and unprotected CE-FL demonstrably degrade.
    byzantine:0.0 consumes identical rng, so the comparison is exact."""
    rounds, arrivals = 8, 150
    clean_cefl = _run("cefl", "byzantine:0.0", rounds=rounds,
                      arrivals=arrivals).reports[-1].acc
    robust_byz = _run("cefl", "byzantine", robust="trimmed_mean",
                      trim_frac=0.2, rounds=rounds,
                      arrivals=arrivals).reports[-1].acc
    naked_byz = _run("cefl", "byzantine", rounds=rounds,
                     arrivals=arrivals).reports[-1].acc
    clean_avg = _run("fedavg", "byzantine:0.0", rounds=rounds,
                     arrivals=arrivals).reports[-1].acc
    byz_avg = _run("fedavg", "byzantine", rounds=rounds,
                   arrivals=arrivals).reports[-1].acc
    # the counter works: >= 80% of clean accuracy retained
    assert robust_byz >= 0.8 * clean_cefl, (robust_byz, clean_cefl)
    # the attack works: unprotected runs visibly degrade
    assert byz_avg < clean_avg - 0.1, (byz_avg, clean_avg)
    assert naked_byz < robust_byz - 0.05, (naked_byz, robust_byz)
