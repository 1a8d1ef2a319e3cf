"""The plane kernels compile for a TPU v5e chip at real plane sizes.

Interpret mode never applies Mosaic's block rules, so these tests compile
each kernel through its ``ops.*_plane(..., backend="tpu")`` entry point
(the production ``TilePlan``) for a described ``v5e:2x2`` topology: no
chip is attached, the TPU compiler runs here.  The topology is described
inside a module fixture, never at import, because only one process at a
time may load the TPU library (see the on-chip measurement notes in
README.md).

Two plane sizes: the ``paper_table1`` classifier (28x28 MLP 200-100,
178,110 params -> 176 rows, G = 25 DPUs: 20 UEs + 5 DCs) and
mamba2-130m at published widths (~1.3e5 rows, G = 2 DPUs as in the
``lm_mamba2_130m`` preset).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.plane import LANE, spec_of

SIZES = ("paper_table1", "mamba2_130m")
KERNELS = ("fedprox_update", "fedprox_accum", "nova_aggregate",
           "nova_aggregate_stacked", "robust_trimmed_mean", "robust_median")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip, so keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _plane_dims(size: str):
    """(rows R, DPU count G) of the size's parameter plane."""
    if size == "paper_table1":
        from repro.models.classifier import (ClassifierConfig,
                                             init_classifier_params)
        cfg = ClassifierConfig(input_shape=(28, 28, 1), hidden=(200, 100))
        shapes = jax.eval_shape(
            lambda: init_classifier_params(jax.random.PRNGKey(0), cfg))
        return spec_of(shapes).rows, 25
    from repro.configs import get_config
    from repro.models import lm as L
    cfg = get_config("mamba2-130m")
    shapes = jax.eval_shape(
        lambda: L.init_lm_params(jax.random.PRNGKey(0), cfg, jnp.float32))
    return spec_of(shapes).rows, 2


def _program(kernel: str, R: int, G: int):
    """(fn, argument shapes) calling one kernel through its ops entry."""
    plane, stack, per_dpu, scalar = (R, LANE), (G, R, LANE), (G,), ()
    tpu = dict(backend="tpu")
    if kernel == "fedprox_update":
        return (lambda x, g, a, eta, mu: ops.fedprox_plane(
            x, g, a, eta, mu, **tpu),
            (plane, plane, plane, scalar, scalar))
    if kernel == "fedprox_accum":
        return (lambda x, g, a, acc, coef, act, eta, mu:
                ops.fedprox_accum_plane(x, g, a, acc, coef, act, eta, mu,
                                        **tpu),
                (stack, stack, plane, stack, per_dpu, per_dpu, scalar,
                 scalar))
    if kernel == "nova_aggregate":
        return (lambda x, d, w, te: ops.nova_aggregate_plane(
            x, d, w, te, **tpu), (plane, stack, per_dpu, scalar))
    if kernel == "nova_aggregate_stacked":
        return (lambda x, d, w, te: ops.nova_aggregate_plane(
            x, d, w, te, **tpu), (stack, stack, per_dpu, scalar))
    mode = kernel.removeprefix("robust_")
    return (lambda x, d, te: ops.robust_aggregate_plane(
        x, d, te, mode=mode, trim_frac=0.2, **tpu), (plane, stack, scalar))


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel, size):
    R, G = _plane_dims(size)
    fn, shapes = _program(kernel, R, G)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
