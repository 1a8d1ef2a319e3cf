"""The classifier cells: the paper's CE-FL rounds through the
program's ``Engine`` on the context ``experiments.build_context`` builds.

Set-up builds the context (data, network, constants estimation), the
engine and the UE streams, makes the initial weights from the seed, and
drives the first ``window_multiple`` rounds (one re-solve period) through
``begin_round`` / ``execute_round`` / ``finish_round``, keeping the feed
of the first ``checked_rounds`` for the reference.  Round 0 compiles the
solver and the round programs; every round compiles the programs keyed
on its data sizes.  The window replays that period from a fresh
``init_loop``, fresh UE streams and the same weights, again and again:
the same sizes, plans and solver rounds, so it compiles nothing and
measures the same work whatever the compile cache held.  It ends on a
whole period.

The seed sets the initial weights and the engine's JAX key (which
examples each mini-batch draws).  The program's numpy streams (arrivals,
scenario, offloading) keep the mix's ``stream_seed``: the seed changes no
size, plan or solver iteration, so every seed runs the same programs, and
only a checkout's first run compiles the ones the cache keeps.
"""
from __future__ import annotations

import copy
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import check
import costs
import reference
from harness import log


def deep_merge(a: dict, b: dict) -> dict:
    out = copy.deepcopy(a)
    for k, v in b.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def init_mlp(key, dims):
    """He-normal weights and zero biases, in float32, on the device."""
    keys = jax.random.split(key, len(dims) - 1)
    params = {}
    for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        params[f"w{i}"] = jax.random.normal(keys[i], (din, dout)) * \
            math.sqrt(2.0 / din)
        params[f"b{i}"] = jnp.zeros((dout,), jnp.float32)
    return params


def plan_settings(plan, i: int, D: int):
    """(gamma_i, mini-batch size) of DPU ``i`` under ``plan``: gamma
    rounded to a whole step (at least 1), m clipped to [0.05, 1], the
    batch ``round(m * D)`` clamped to [1, D]."""
    gamma = max(int(np.rint(np.asarray(plan.gamma)[i])), 1)
    m = float(np.clip(np.asarray(plan.m), 0.05, 1.0)[i])
    return gamma, max(1, min(D, int(round(m * D))))


def plan_violation(plan, f_min: float, f_max: float) -> float:
    """The largest violation of the plan's constraints (paper eqs. 55-62):
    offloading shares non-negative, UE rows at most 1, BS rows on the
    simplex, one-hot associations and aggregator, gamma > 0, m in (0, 1],
    f_n in [f_min, f_max] (relative to f_max)."""
    w = {k: np.asarray(v, np.float64) for k, v in plan.to_w().items()}
    v = [max(0.0, -w["rho_nb"].min()),
         max(0.0, (w["rho_nb"].sum(1) - 1.0).max()),
         max(0.0, -w["rho_bs"].min()),
         np.abs(w["rho_bs"].sum(1) - 1.0).max(),
         max(0.0, -w["gamma"].min()), max(0.0, -w["m"].min()),
         max(0.0, w["m"].max() - 1.0),
         max(0.0, (f_min - w["f_n"].min()) / f_max),
         max(0.0, (w["f_n"].max() - f_max) / f_max)]
    for name, axis in (("I_s", 0), ("I_nb", 1), ("I_bn", 0)):
        x = w[name]
        v += [np.abs(x.sum(axis) - 1.0).max(), np.abs(x * (1.0 - x)).max()]
    return float(max(v))


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, rec):
        self.cfg, self.traffic, self.seed, self.rec = cfg, traffic, seed, rec
        self.stream_seed = traffic["stream_seed"]
        self.params = costs.mlp_params(cfg)
        self.feeds, self.prog = [], None

    # ------------------------------------------------------------ set-up --
    def setup(self):
        from repro.experiments import build_context, get_experiment
        from repro.kernels.plane import as_tree
        self._as_tree = as_tree
        spec = deep_merge(self.cfg["spec"], self.traffic["spec"])
        spec["name"] = f"{self.cfg['name']}.{self.traffic['name']}"
        spec["seeds"] = [self.stream_seed]
        t0 = time.perf_counter()
        self.ctx = build_context(get_experiment(spec))
        log(f"context (data, network, constants) {time.perf_counter() - t0!r} s")
        self.engine = self.ctx.make_engine(self.stream_seed)
        self.ues = self.ctx.make_ues(self.stream_seed)
        decide = self.engine.decide

        def timed_decide(*args, **kw):
            with self.rec.span("decide"):
                return decide(*args, **kw)

        self.engine.decide = timed_decide
        m = self.cfg["spec"]["model"]
        dims = (math.prod(m["input_shape"]),) + tuple(m["hidden"]) + \
            (m["num_classes"],)
        self.x0 = init_mlp(jax.random.PRNGKey(self.seed), dims)
        self.on_built()
        self._start()
        self.prog = {"x": [self._host_params()], "loss": [], "acc": []}
        checked = self.traffic["checked_rounds"]
        for t in range(max(checked, self.traffic["window_multiple"])):
            t0 = time.perf_counter()
            staged, loss, acc = self.round(capture=t < checked)
            log(f"set-up round {t}{' (checked)' if t < checked else ''}: "
                f"{time.perf_counter() - t0!r} s")
            if t < checked:
                self.prog["x"].append(self._host_params())
                self.prog["loss"].append(loss)
                self.prog["acc"].append(acc)
            self.rec.round_done()

    def on_built(self):
        """Called once the engine exists, before its first round (tests
        break the timed path here)."""

    def _start(self):
        """Round 0 of the schedule: a new loop state from the seed's
        weights and key (the UE streams are made anew by the caller)."""
        self.state = self.engine.init_loop(
            self.ues, init_params=self.x0, loss_fn=self.ctx.loss_fn,
            eval_fn=self.ctx.eval_fn)
        self.state.key = jax.random.PRNGKey(self.seed)

    def _host_params(self) -> dict:
        return check.flatten(self._as_tree(self.state.params))

    # ------------------------------------------------------------- round --
    def round(self, capture: bool = False):
        """One whole round through the engine's three calls.  Returns
        ``(staged, loss, acc)``; with ``capture`` the round's feed is kept
        for the reference."""
        eng, st, rec = self.engine, self.state, self.rec
        with rec.span("stage"):
            staged = eng.begin_round(st, self.ues)
        if capture:
            self.feeds.append(self._feed(staged))
        with rec.span("execute"):
            loss, acc = eng.execute_round(st, staged)
        with rec.span("finish"):
            report = eng.finish_round(st, staged, loss, acc)
        return staged, float(report.loss), float(report.acc)

    def _live(self, staged):
        return [(i, d) for i, d in enumerate(staged.datasets)
                if d is not None and len(d["y"])]

    def _feed(self, staged) -> dict:
        live = self._live(staged)
        keys = np.asarray(jax.random.split(staged.key, len(live)))
        dpus, sizes, gammas = [], [], []
        for (i, d), k in zip(live, keys):
            D = len(d["y"])
            gamma, bsz = plan_settings(staged.plan, i, D)
            dpus.append({"x": np.asarray(d["x"]), "y": np.asarray(d["y"]),
                         "gamma": gamma, "bsz": bsz,
                         "step_keys": np.asarray(jax.random.split(
                             jnp.asarray(k), gamma))})
            sizes.append(D)
            gammas.append(gamma)
        w = np.asarray(sizes, float) / sum(sizes)
        return {"dpus": dpus, "theta": float(np.sum(w * np.asarray(gammas))),
                "plan": staged.plan}

    def _round_flops(self, staged) -> float:
        examples = 0
        for i, d in self._live(staged):
            gamma, bsz = plan_settings(staged.plan, i, len(d["y"]))
            examples += gamma * bsz
        return costs.train_flops(self.params, examples)

    # ------------------------------------------------------------ window --
    def window(self, seconds: float) -> dict:
        multiple = self.traffic["window_multiple"]
        t0 = time.perf_counter()
        rounds, flops = 0, 0.0
        while True:
            if rounds % multiple == 0:
                with self.rec.span("restart"):
                    self.ues = self.ctx.make_ues(self.stream_seed)
                    self._start()
            staged, _, _ = self.round()
            flops += self._round_flops(staged)
            rounds += 1
            if time.perf_counter() - t0 >= seconds and \
                    rounds % multiple == 0:
                break
        jax.block_until_ready(self.state.params)
        return {"rounds": rounds, "seconds": time.perf_counter() - t0,
                "model_flops": flops}

    # ------------------------------------------------------------- check --
    def trajectory(self, dtype=jnp.float32, fault: str = "") -> dict:
        """The reference over the checked rounds' feeds; in a lower
        precision, the control; with ``fault`` (``half_batch``,
        ``altered``), the reference with that fault planted."""
        e = self.cfg["spec"]["engine"]
        x = reference.cast(self.x0, dtype)
        out = {"x": [check.flatten(self.x0)], "loss": []}
        for feed in self.feeds:
            x_t = x
            x, loss = reference.cefl_round(x, feed, eta=e["eta"], mu=e["mu"],
                                           dtype=dtype, fault=fault)
            if fault == "altered":
                x = reference.altered(x_t, x)
            out["x"].append(check.flatten(jax.device_get(x)))
            out["loss"].append(loss)
        return out

    def release(self):
        """Drop the program's device state before the reference runs."""
        self.state.params = None

    def acc_gap(self, dtype=None) -> float:
        """The worst checked round's gap between the program's eval and
        the reference's eval of the same weights (with ``dtype``, between
        the reference in ``dtype`` and in float32: the control's)."""
        d = self.ctx.spec.data
        ex = self.ctx.test_x[:d.eval_examples]
        ey = self.ctx.test_y[:d.eval_examples]
        gaps = []
        for acc, x in zip(self.prog["acc"], self.prog["x"][1:]):
            ref = reference.mlp_accuracy(x, ex, ey)
            if dtype is not None:
                acc = reference.mlp_accuracy(x, ex, ey, dtype)
            gaps.append(abs(acc - ref))
        return max(gaps)

    def check(self) -> dict:
        self.release()
        numbers = check.compare(self.prog, self.trajectory())
        numbers["acc_gap"] = self.acc_gap()
        net = self.ctx.net.cfg
        numbers["plan_violation"] = max(
            plan_violation(f["plan"], net.f_min, net.f_max)
            for f in self.feeds)
        return numbers
