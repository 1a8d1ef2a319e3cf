"""The LM cells: the mesh-native CE-FL round of an LM at
published widths, the jitted step that ``experiments.lm.run_lm`` builds
(``MeshExecutor.build_step`` on a ``ParamPlane`` of ``n_dpu`` replicas,
with the same loss, remat and hyper-parameters), fed one token batch per
round.

Set-up makes the weights from the seed on the device in one jitted call,
keeps a host copy for the reference, builds the plane and frees the
tree, builds and compiles the step, and drives the first
``checked_rounds`` rounds, keeping their batches and the weights at each
round boundary.  The window drives the same step on, one batch draw
(``batch`` span) and one step with its loss read back (``execute`` span)
per round, as ``run_lm`` does.

The check runs the reference one round from each of the program's round
boundaries (``check.compare_steps``): at its default matmul precision
the program drifts, over a few rounds, away from a reference that runs
on by itself, so each round is judged from the program's own weights.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import check
import costs
import reference


def token_batches(vocab: int, n_dpu: int, n_micro: int, mb: int, seq: int,
                  zipf_a: float, seed: int) -> dict:
    """Token ids ``(n_dpu, n_micro, mb, seq)``: zipf(a) draws folded into
    the vocabulary; labels are the ids shifted by one (the same draw as
    the program's ``data.make_token_batches``)."""
    rng = np.random.RandomState(seed)
    base = rng.zipf(zipf_a, (n_dpu, n_micro, mb, seq)).astype(np.int64)
    tokens = (base % vocab).astype(np.int32)
    return {"tokens": tokens, "labels": np.roll(tokens, -1, axis=-1)}


def small_seed(seed: int) -> int:
    """A 22-bit seed drawn from the run seed (which may exceed the 32 bits
    a numpy stream takes), for the per-round draws that add to it."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               % (2 ** 22))


# built steps, by configuration and mix: a process that runs several seeds
# of one cell (bench/probe.py) builds and compiles the step once
_STEPS = {}


@functools.partial(jax.jit, static_argnums=(1,))
def init_mamba2(key, dims):
    """Random Mamba-2 weights in the program's layout (float32, layers
    stacked on a leading axis), on the device: normal embedding (std
    0.02), fan-in scaled projections, A in [1, 16], dt log-uniform in
    [dt_min, dt_max] (its bias the inverse softplus), D = 1, norm scales 0
    (the program stores RMSNorm weights as 1 + scale)."""
    (n_layer, d, vocab, d_inner, n_state, heads, conv_w, dt_min,
     dt_max) = dims
    ks = jax.random.split(key, 6)
    conv_ch = d_inner + 2 * n_state
    d_proj = 2 * d_inner + 2 * n_state + heads
    dt = jnp.exp(jax.random.uniform(ks[3], (n_layer, heads))
                 * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
    mamba = {
        "w_in": jax.random.normal(ks[1], (n_layer, d, d_proj))
        / math.sqrt(d),
        "conv_w": jax.random.normal(ks[2], (n_layer, conv_w, conv_ch)) * 0.1,
        "conv_b": jnp.zeros((n_layer, conv_ch)),
        "a_log": jnp.log(jax.random.uniform(ks[4], (n_layer, heads),
                                            minval=1.0, maxval=16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "d_skip": jnp.ones((n_layer, heads)),
        "norm": jnp.zeros((n_layer, d_inner)),
        "w_out": jax.random.normal(ks[5], (n_layer, d_inner, d))
        / math.sqrt(d_inner),
    }
    return {"embed": jax.random.normal(ks[0], (vocab, d)) * 0.02,
            "final_norm": jnp.zeros((d,)),
            "blocks": {"layer_0": {"ln1": jnp.zeros((n_layer, d)),
                                   "mamba": mamba}}}


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, rec):
        self.cfg, self.traffic, self.seed, self.rec = cfg, traffic, seed, rec
        self.data_seed = small_seed(seed)
        self.params = costs.mamba2_params(cfg)
        t = traffic
        self.mb = t["batch"] // (t["n_dpu"] * t["n_micro"])
        self.t = 0

    def model_config(self):
        from repro.configs import get_config
        from repro.configs.base import SSMConfig
        c = self.cfg
        return dataclasses.replace(
            get_config(c["program_config"]), num_layers=c["n_layer"],
            d_model=c["d_model"], vocab_size=costs.padded_vocab(c),
            tie_embeddings=c["tie_embeddings"], norm_eps=c["norm_eps"],
            ssm=SSMConfig(state_dim=c["d_state"], head_dim=c["headdim"],
                          expand=c["expand"], chunk_size=c["chunk_size"],
                          conv_width=c["d_conv"], dt_min=c["dt_min"],
                          dt_max=c["dt_max"]))

    # ------------------------------------------------------------ set-up --
    def setup(self):
        from repro.core.engine import MeshExecutor
        from repro.core.round_step import CEFLHyper, make_dpu_meta
        from repro.kernels.plane import ParamPlane
        from repro.models import lm as L
        c, t = self.cfg, self.traffic
        mcfg = self.model_config()
        x0 = init_mamba2(jax.random.PRNGKey(self.seed), self.dims())
        self.x0_host = check.flatten(jax.device_get(x0))
        # the weights live on as the host copy only: the device holds the
        # planes the program trains
        plane = ParamPlane.from_tree(x0)
        del x0
        jax.block_until_ready(plane.data)
        self.plane = plane.broadcast(t["n_dpu"])
        del plane
        jax.block_until_ready(self.plane.data)
        seq = t["seq"]

        def loss_fn(p, micro, mask):
            return L.lm_loss(p, mcfg, micro, example_mask=mask, remat=True,
                             q_block=min(512, seq), kv_block=min(512, seq))

        hyper = CEFLHyper(eta=t["eta"], mu=t["mu"], theta=float(t["gamma"]),
                          gamma_max=t["gamma"], n_micro=t["n_micro"])
        key = json.dumps([c, t], sort_keys=True)
        if key not in _STEPS:
            _STEPS[key] = MeshExecutor().build_step(loss_fn, hyper)
        self.step = _STEPS[key]
        self.meta = make_dpu_meta(t["n_dpu"], gammas=[t["gamma"]] * t["n_dpu"])
        self.on_built()
        self.prog = {"x": [self.x0_host], "loss": []}
        self.batches = []
        for _ in range(t["checked_rounds"]):
            b, loss = self.round()
            self.batches.append(b)
            self.prog["x"].append(check.flatten(jax.device_get(
                self.plane[0].to_tree())))
            self.prog["loss"].append(loss)
            self.rec.round_done()

    def dims(self) -> tuple:
        c = self.cfg
        d_inner = c["expand"] * c["d_model"]
        return (c["n_layer"], c["d_model"], costs.padded_vocab(c), d_inner,
                c["d_state"], d_inner // c["headdim"], c["d_conv"],
                c["dt_min"], c["dt_max"])

    def on_built(self):
        """Called once the step exists, before its first round (tests break
        the timed path here)."""

    # ------------------------------------------------------------- round --
    def draw(self) -> dict:
        t = self.traffic
        return token_batches(self.cfg["vocab_size"], t["n_dpu"],
                             t["n_micro"], self.mb, t["seq"], t["zipf_a"],
                             (self.data_seed + 7919 * self.t) % (2 ** 32))

    def round(self):
        with self.rec.span("batch"):
            b = self.draw()
            dev = {k: jnp.asarray(v) for k, v in b.items()}
        with self.rec.span("execute"):
            self.plane, metrics = self.step(self.plane, dev, self.meta)
            loss = float(metrics["loss"])
        self.t += 1
        return b, loss

    # ------------------------------------------------------------ window --
    def window(self, seconds: float) -> dict:
        t = self.traffic
        t0 = time.perf_counter()
        rounds = 0
        while True:
            self.round()
            rounds += 1
            if time.perf_counter() - t0 >= seconds and \
                    rounds % t["window_multiple"] == 0:
                break
        jax.block_until_ready(self.plane.data)
        tokens = t["batch"] * t["seq"] * t["gamma"]
        return {"rounds": rounds, "seconds": time.perf_counter() - t0,
                "model_flops": rounds * costs.train_flops(self.params, tokens)}

    # ------------------------------------------------------------- check --
    def ref_round(self, x, b, dtype=jnp.float32, fault: str = ""):
        """The reference's round on batch ``b`` from weights ``x``."""
        t = self.traffic
        per_dpu = [(b["tokens"][i].reshape(-1, t["seq"]),
                    b["labels"][i].reshape(-1, t["seq"]))
                   for i in range(t["n_dpu"])]
        x_next, loss = reference.lm_round(
            x, per_dpu, self.cfg, gamma=t["gamma"], eta=t["eta"], mu=t["mu"],
            theta=float(t["gamma"]), dtype=dtype, fault=fault)
        if fault == "altered":
            x_next = reference.altered(x, x_next)
        return x_next, loss

    def trajectory(self, dtype=jnp.float32, fault: str = "") -> dict:
        """The reference running on by itself over the checked rounds; in
        a lower precision, the control; with ``fault`` (``half_batch``,
        ``altered``), the reference with that fault planted."""
        x = jax.device_put(check.unflatten(self.x0_host))
        out = {"x": [self.x0_host], "loss": []}
        for b in self.batches:
            x, loss = self.ref_round(x, b, dtype, fault)
            out["x"].append(check.flatten(jax.device_get(x)))
            out["loss"].append(loss)
        return out

    def steps(self, traj: dict) -> list:
        """The reference's round from each round boundary of ``traj``."""
        out = []
        for x_r, b in zip(traj["x"], self.batches):
            x, loss = self.ref_round(
                jax.device_put(check.unflatten(x_r)), b)
            out.append({"x": check.flatten(jax.device_get(x)),
                        "loss": loss})
        return out

    def release(self):
        """Drop the program's device state before the reference runs."""
        self.plane = None
        self.step = None

    def check(self) -> dict:
        self.release()
        self.ref_steps = self.steps(self.prog)
        return check.compare_steps(self.prog, self.ref_steps)
