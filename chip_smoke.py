"""Smoke run of the CE-FL main path on TPU — a smoke run, not a benchmark.

    python chip_smoke.py              # one chip: kernels, phases A and B
    python chip_smoke.py --chips 4    # four chips: the sharded plane round

First each Pallas plane kernel is checked against the jnp reference
path on the chip at the paper_table1 plane size.

Phase A runs the ``paper_table1`` preset (28x28 MLP 200-100, 20 UE /
10 BS / 5 DC, estimated constants) under ``strategy=cefl``, seed 0, for 3
rounds through ``repro.experiments.run``: solver, offloading, local
FedProx, floating aggregation and eval.  It then compiles the round
programs that run used again and checks that they hold the Pallas
kernels (``tpu_custom_call``).

Phase B runs the ``lm_mamba2_130m`` preset at published widths (24
layers, d_model 768, vocab 50280) for 3 rounds through the same ``run``
entry; its loss must be finite and must decrease.

``--chips 4`` runs only the fused sharded round
(``repro.sharding.plane.local_round_plane_sharded``) on meshes (4, 1),
(2, 2) and (1, 4) against the single-device ``fedprox.local_round_plane``:
``reduce="exact"`` must give bitwise equal params and allclose losses,
``reduce="psum"`` allclose params.  Its classifier is the paper's MLP
with the first hidden layer widened from 200 to 256, so that the plane
(224 rows, 28 tiles of 8) splits 4 ways over the rows axis.

The script refuses to run unless JAX finds a TPU and the kernel backend
is ``tpu`` (the compiled Pallas kernels): no CPU fallback.  Any failure
exits non-zero.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed are wall-clock times of a single smoke run.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import sys
import time

ROUNDS = 3
MESHES = ((4, 1), (2, 2), (1, 4))


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(chips: int):
    """The devices, or exit naming what was found instead of a TPU."""
    import jax

    from repro.kernels import ops
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found platform {dev.platform!r} "
                 f"({dev.device_kind}), not a TPU; refusing to run")
    backend = ops.current_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: kernel backend is {backend!r} "
                 f"(REPRO_KERNEL_BACKEND="
                 f"{os.environ.get('REPRO_KERNEL_BACKEND')!r}), not the "
                 f"compiled 'tpu' kernels; refusing to run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"JAX found {len(devices)}")
    return devices


def record_round_programs():
    """Wrap ``repro.core.fedprox``'s two round-program factories (the fused
    round and the per-group training scan) so that the programs a run
    calls can be compiled again and read afterwards.  Returns the dict
    the first call of each program is recorded into."""
    import jax

    from repro.core import fedprox
    seen = {}

    def spy(factory):
        def make(*a, **kw):
            fn = factory(*a, **kw)

            def call(*args):
                seen.setdefault(id(fn), (factory.__name__, fn, jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    args)))
                return fn(*args)
            return call
        return make

    fedprox._plane_round_fn = spy(fedprox._plane_round_fn)
    fedprox._plane_train_fn = spy(fedprox._plane_train_fn)
    return seen


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def kernel_parity(backend: str) -> None:
    """Each plane kernel on ``backend`` against the jnp reference path
    (backend ``cpu``, run on the same device, its weighted sum at f32
    matmul precision as the kernels accumulate) at the paper_table1 plane
    size: 176 rows, G = 25 DPUs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.plane import LANE
    R, G = 176, 25
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x, g, anchor = (jax.random.normal(k, (R, LANE)) for k in ks[:3])
    d = jax.random.normal(ks[3], (G, R, LANE))
    w = jax.random.uniform(ks[4], (G,))
    w = w / jnp.sum(w)
    active = (jax.random.uniform(ks[5], (G,)) > 0.3).astype(jnp.float32)
    cases = {
        "fedprox_update": lambda b: ops.fedprox_plane(
            x, g, anchor, 0.05, 0.1, backend=b),
        "fedprox_accum": lambda b: ops.fedprox_accum_plane(
            d, 0.5 * d, anchor, 0.1 * d, w, active, 0.05, 0.1, backend=b),
        "nova_aggregate": lambda b: ops.nova_aggregate_plane(
            x, d, w, 0.1, backend=b),
        "nova_aggregate_stacked": lambda b: ops.nova_aggregate_plane(
            d, d, w, 0.1, backend=b),
        "robust_trimmed_mean": lambda b: ops.robust_aggregate_plane(
            x, d, 0.1, mode="trimmed_mean", trim_frac=0.2, backend=b),
        "robust_median": lambda b: ops.robust_aggregate_plane(
            x, d, 0.1, mode="median", backend=b),
    }
    for name, fn in cases.items():
        got = [np.asarray(a) for a in jax.tree.leaves(fn(backend))]
        with jax.default_matmul_precision("highest"):
            want = [np.asarray(a) for a in jax.tree.leaves(fn("cpu"))]
        err = max(float(np.max(np.abs(a - b))) for a, b in zip(got, want))
        log(f"kernel {name} ({backend}) vs jnp reference: max|diff| {err!r}")
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-5,
                                       err_msg=name)


def phase_a(dev) -> None:
    from repro import experiments
    from repro.kernels import ops
    spec = experiments.get_experiment("paper_table1").override(
        **{"engine.rounds": ROUNDS, "strategy": "cefl", "seeds": (0,)})
    seen = record_round_programs()
    t0 = time.perf_counter()
    experiments.build_context(spec)     # cached; run() reuses it
    setup = time.perf_counter() - t0
    log(f"phase A: paper_table1 strategy=cefl seed=0 rounds={ROUNDS} "
        f"kernel backend={ops.current_backend()}")

    def on_round(r):
        log(f"phase A round {r.round}: loss {r.loss!r} acc {r.acc!r} "
            f"aggregator DC{r.aggregator} wall {r.wall_time!r} s")

    t0 = time.perf_counter()
    res = experiments.run(spec, seed=0, callbacks=(on_round,))
    wall = time.perf_counter() - t0
    bad = [r.round for r in res.reports
           if not (math.isfinite(r.loss) and math.isfinite(r.acc))]
    if len(res.reports) != ROUNDS or bad:
        raise RuntimeError(f"phase A: {len(res.reports)} rounds, "
                           f"non-finite loss/acc in rounds {bad}")
    if not seen:
        raise RuntimeError("phase A ran no plane round program")
    for name, fn, shapes in seen.values():
        text = fn.lower(*shapes).compile().as_text()
        n = text.count("tpu_custom_call")
        log(f"phase A compiled round program (fedprox.{name}): "
            f"{n} tpu_custom_call")
        if not n:
            raise RuntimeError(f"fedprox.{name} holds no Pallas TPU kernel")
    log(f"phase A time (smoke run, not a benchmark): set-up "
        f"(data, network, constants) {setup!r} s, 3 rounds {wall!r} s "
        f"(round 0 includes compilation); peak_bytes_in_use "
        f"{peak_bytes(dev)}")


def phase_b(dev) -> None:
    import jax

    from repro import experiments
    spec = experiments.get_experiment("lm_mamba2_130m").override(
        **{"engine.rounds": ROUNDS, "seeds": (0,)})
    m = spec.model
    if m.reduced:
        raise RuntimeError("phase B must run at published widths")
    log(f"phase B: {m.arch} published widths, {m.n_dpu} DPUs x "
        f"gamma={m.gamma}, batch {m.batch} x seq {m.seq}, rounds={ROUNDS}")
    t0 = time.perf_counter()
    res = experiments.run(spec, seed=0)
    wall = time.perf_counter() - t0
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(res.params))
    losses = [r.loss for r in res.reports]
    log(f"phase B parameters: {n_params}")
    for r in res.reports:
        log(f"phase B round {r.round}: loss {r.loss!r} wall "
            f"{r.wall_time!r} s")
    if not all(math.isfinite(x) for x in losses) or \
            not losses[-1] < losses[0]:
        raise RuntimeError(f"phase B loss not finite and decreasing: "
                           f"{losses}")
    log(f"phase B time (smoke run, not a benchmark): 3 rounds incl. "
        f"set-up {wall!r} s (round 0 includes compilation); "
        f"peak_bytes_in_use {peak_bytes(dev)}")


def sharded_phase() -> None:
    """The fused sharded round on four chips vs the single-device round,
    G = 20 DPUs, on a 28x28 MLP 256-100: 227,670 params, a 224-row plane
    of 28 whole 8-row tiles, so the rows axis really shards it 2 and 4
    ways (the 176-row paper classifier would replicate on (1, 4))."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fedprox
    from repro.models.classifier import (ClassifierConfig, classifier_loss,
                                         init_classifier_params)
    from repro.sharding import plane as sp

    cfg = ClassifierConfig(input_shape=(28, 28, 1), hidden=(256, 100))
    params = init_classifier_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    G, D = 20, 256
    datasets = [{"x": jnp.asarray(rng.normal(size=(D, 28, 28, 1)),
                                  jnp.float32),
                 "y": jnp.asarray(rng.randint(0, 10, size=(D,)), jnp.int32)}
                for _ in range(G)]
    keys = [jax.random.PRNGKey(i + 1) for i in range(G)]
    kw = dict(gamma=3, m_frac=0.25, eta=0.05, mu=0.1, theta=1.0, keys=keys)
    t0 = time.perf_counter()
    ref, ref_loss, _ = fedprox.local_round_plane(
        params, classifier_loss, datasets, **kw)
    ref_data = np.asarray(ref.data)
    log(f"sharded: single-device reference round {time.perf_counter() - t0!r}"
        f" s (incl. compilation), G={G}, plane {ref_data.shape}")
    for shape in MESHES:
        mesh = sp.plane_mesh(shape)
        g_ax, r_ax = sp.plane_axes(mesh, G, ref_data.shape[0])
        if (shape[0] > 1 and g_ax is None) or (shape[1] > 1 and r_ax is None):
            raise RuntimeError(f"mesh {shape}: plane axes {(g_ax, r_ax)}, "
                               f"a fallback to replication")
        for reduce in ("exact", "psum"):
            t0 = time.perf_counter()
            out, loss, _ = sp.local_round_plane_sharded(
                params, classifier_loss, datasets, mesh=mesh, reduce=reduce,
                **kw)
            data = np.asarray(out.data)
            wall = time.perf_counter() - t0
            devs = sorted(d.id for d in out.data.sharding.device_set)
            bitwise = bool(np.array_equal(data, ref_data))
            err = float(np.max(np.abs(data - ref_data)))
            log(f"sharded mesh {shape} reduce={reduce}: dpu axis {g_ax}, "
                f"rows axis {r_ax}, output on devices {devs} "
                f"({out.data.sharding.spec}); params bitwise={bitwise} "
                f"max|diff|={err!r}; loss max|diff|="
                f"{float(np.max(np.abs(loss - ref_loss)))!r}; "
                f"{wall!r} s incl. compilation")
            if reduce == "exact":
                if not bitwise:
                    raise RuntimeError(f"mesh {shape}: exact params differ")
                np.testing.assert_allclose(loss, ref_loss, rtol=1e-6,
                                           atol=0)
            else:
                np.testing.assert_allclose(data, ref_data, rtol=1e-6,
                                           atol=1e-6)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded plane round on 4 chips")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    import jax

    from repro.kernels import ops
    from repro.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    events = collections.Counter()     # JAX's compile-cache hit/miss events
    jax.monitoring.register_event_listener(
        lambda event, **_kw: events.update((event,)))
    devices = require_tpu(args.chips)
    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase()
    else:
        kernel_parity(ops.current_backend())
        phase_a(dev)
        phase_b(dev)
    log(f"total {time.perf_counter() - t0!r} s; compile cache hits "
        f"{events['/jax/compilation_cache/cache_hits']}, misses "
        f"{events['/jax/compilation_cache/cache_misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
