"""The hybrid LM cells: the mesh-native CE-FL round of an LM whose period
mixes Mamba-2 and attention layers, each with a SwiGLU MLP (granite-4.0-h),
at published widths.  The step is the one ``experiments.lm.run_lm``
builds (``MeshExecutor.build_step`` on a ``ParamPlane`` of ``n_dpu``
replicas, with the same loss, remat and hyper-parameters), fed one token
batch per round, as in ``drivers/lm.py``.

The program's configuration is looked up first, so a checkout whose
program lacks it stops at once.  Set-up makes the weights from the seed
on the device in one jitted call, in the program's layout, keeps a host
copy for the reference, builds the plane, builds and compiles the step
and drives the first ``checked_rounds`` rounds, keeping their batches and
the weights at each round boundary.  The window drives the same step on.

The check runs the reference (``reference_hybrid.py``) one round from
each of the program's round boundaries and compares round by round
(``check.compare_steps``'s numbers), holding one reference round at a
time on the device and comparing leaf by leaf there: at the cell's size a
plane is 3.2 GB, and the host holds the program's round boundaries.  The
rounds, the window and the token draws are ``drivers/lm.py``'s.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

import check
import reference_hybrid
from drivers import lm

# built steps, by configuration and mix (one compile for many seeds)
_STEPS = {}


def hybrid_params(cfg: dict) -> int:
    """Tied embedding, final norm, and per layer: two norms, the SwiGLU
    MLP, and the mixer: attention's q, k, v, o (no bias), or Mamba-2's
    in_proj (z, x, B, C, dt), conv weight and bias over (x, B, C), A, dt
    bias, D, gated norm and out_proj."""
    d, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    d_inner = cfg["mamba_expand"] * d
    heads = cfg["mamba_n_heads"]
    bc = 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    conv_ch = d_inner + bc
    mamba = (d * (2 * d_inner + bc + heads) + cfg["mamba_d_conv"] * conv_ch
             + conv_ch + 3 * heads + d_inner + d_inner * d)
    attn = 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])
    total = cfg["vocab_size"] * d * (1 if cfg["tie_word_embeddings"] else 2)
    for kind in cfg["layer_types"]:
        total += 2 * d + 3 * d * f + (attn if kind == "attention" else mamba)
    return total + d


@functools.partial(jax.jit, static_argnums=(1,))
def init_hybrid(key, dims):
    """Random weights in the program's layout (float32, every block leaf
    on a leading axis of periods), on the device: normal
    embedding (std 0.02), fan-in scaled projections, A in [1, 16], dt
    log-uniform in [dt_min, dt_max] (its bias the inverse softplus), D =
    1, norm scales 0 (the program stores RMSNorm weights as 1 + scale)."""
    (kinds, d, vocab, f, hq, hkv, hd, d_inner, n_state, heads, conv_w,
     dt_min, dt_max) = dims
    n = len(kinds) // reference_hybrid.period_length(kinds)
    kinds = kinds[:len(kinds) // n]            # one period
    keys = iter(jax.random.split(key, 1 + 8 * len(kinds)))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), (n,) + shape) / math.sqrt(fan_in)

    def mamba():
        conv_ch = d_inner + 2 * n_state
        dt = jnp.exp(jax.random.uniform(next(keys), (n, heads))
                     * (math.log(dt_max) - math.log(dt_min))
                     + math.log(dt_min))
        return {"w_in": normal((d, 2 * d_inner + 2 * n_state + heads), d),
                "conv_w": jax.random.normal(next(keys),
                                            (n, conv_w, conv_ch)) * 0.1,
                "conv_b": jnp.zeros((n, conv_ch)),
                "a_log": jnp.log(jax.random.uniform(
                    next(keys), (n, heads), minval=1.0, maxval=16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "d_skip": jnp.ones((n, heads)),
                "norm": jnp.zeros((n, d_inner)),
                "w_out": normal((d_inner, d), d_inner)}

    def attn():
        return {"wq": normal((d, hq, hd), d), "wk": normal((d, hkv, hd), d),
                "wv": normal((d, hkv, hd), d),
                "wo": normal((hq, hd, d), hq * hd)}

    blocks = {}
    for j, kind in enumerate(kinds):
        layer = {"ln1": jnp.zeros((n, d)), "ln2": jnp.zeros((n, d))}
        if kind == "attention":
            layer["attn"] = attn()
        else:
            layer["mamba"] = mamba()
        layer["mlp"] = {"w_in": normal((d, f), d), "w_gate": normal((d, f), d),
                        "w_out": normal((f, d), f)}
        blocks[f"layer_{j}"] = layer
    return {"embed": jax.random.normal(next(keys), (vocab, d)) * 0.02,
            "final_norm": jnp.zeros((d,)), "blocks": blocks}


@jax.jit
def _sq_updates(x, a, b):
    """``|a - x|^2`` and ``|b - x|^2`` of one leaf, in float32."""
    x = x.astype(jnp.float32)
    ua, vb = a.astype(jnp.float32) - x, b.astype(jnp.float32) - x
    return jnp.sum(ua * ua), jnp.sum(vb * vb)


@jax.jit
def _angle_terms(x, a, b, nu, nv):
    """``|u - v|^2`` and ``|u + v|^2`` of one leaf, for the unit updates
    ``u = (a - x) / nu`` and ``v = (b - x) / nv``."""
    x = x.astype(jnp.float32)
    u = (a.astype(jnp.float32) - x) / nu
    v = (b.astype(jnp.float32) - x) / nv
    return jnp.sum(jnp.square(u - v)), jnp.sum(jnp.square(u + v))


def update_norms_angle(x_r: dict, a: dict, b: dict):
    """For the updates ``a - x_r`` and ``b - x_r`` (flat dicts, on the
    host or the device): ``check.leaf_norms`` of each and ``check.angle``
    between them, leaf by leaf on the device.  Each leaf's differences and
    sums of squares are float32 (a difference of two nearby float32 values
    is exact; a sum of squares is good to about 1e-6), the sums over
    leaves float64.  ``check`` holds whole planes in float64 on the host:
    tens of GB and minutes of one core at a 3.2 GB plane."""
    dev = {k: (jax.device_put(x_r[k]), jax.device_put(a[k]),
               jax.device_put(b[k])) for k in b}
    sq = {k: _sq_updates(*dev[k]) for k in b}
    norm_a = {k: math.sqrt(float(sq[k][0])) for k in b}
    norm_b = {k: math.sqrt(float(sq[k][1])) for k in b}
    nu = math.sqrt(sum(float(sq[k][0]) for k in b))
    nv = math.sqrt(sum(float(sq[k][1]) for k in b))
    if nu == 0 or nv == 0:              # no update has no direction
        return norm_a, norm_b, float("inf")
    terms = [_angle_terms(*dev[k], np.float32(nu), np.float32(nv))
             for k in b]
    minus = sum(float(t[0]) for t in terms)
    plus = sum(float(t[1]) for t in terms)
    return norm_a, norm_b, 2.0 * math.atan2(math.sqrt(minus),
                                            math.sqrt(plus))


def host(x: dict) -> dict:
    """A flat dict of arrays, on the host."""
    return {k: np.asarray(v) for k, v in x.items()}


def device(x: dict) -> dict:
    """A flat dict of arrays, on the device."""
    return {k: jax.device_put(v) for k, v in x.items()}


def combine(rows: list) -> dict:
    """``check.compare_steps``'s numbers from ``judge_round``'s rows."""
    out = {"grad_gap": rows[0]["gap"],
           "step_gap": max(r["gap"] for r in rows),
           "step_angle": max(r["angle"] for r in rows),
           "step_loss_gap": max(r["loss_gap"] for r in rows)}
    return {k: (float(v) if np.isfinite(v) else float("inf"))
            for k, v in out.items()}


class Cell(lm.Cell):
    """``drivers/lm.py``'s cell with the hybrid model, its weights and its
    reference."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, rec):
        self.cfg, self.traffic, self.seed, self.rec = cfg, traffic, seed, rec
        self.mcfg = self.model_config()
        self.data_seed = lm.small_seed(seed)
        self.params = hybrid_params(cfg)
        t = traffic
        self.mb = t["batch"] // (t["n_dpu"] * t["n_micro"])
        self.t = 0

    def model_config(self):
        """The program's configuration, at this cell's depth and with the
        widths and multipliers of the cell's file (at published widths the
        file and the program's preset agree: a test checks it)."""
        from repro.configs import get_config
        from repro.configs.base import SSMConfig
        c = self.cfg
        if c["mamba_n_groups"] != 1:
            raise SystemExit("bench: the program's Mamba-2 has one B/C group")
        d = c["hidden_size"]
        return dataclasses.replace(
            get_config(c["program_config"]),
            num_layers=c["num_hidden_layers"], d_model=d,
            vocab_size=c["vocab_size"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            head_dim=d // c["num_attention_heads"],
            d_ff=c["shared_intermediate_size"],
            layer_pattern="".join("A" if k == "attention" else "M"
                                  for k in c["layer_types"]),
            tie_embeddings=c["tie_word_embeddings"],
            norm_eps=c["rms_norm_eps"],
            embedding_multiplier=float(c["embedding_multiplier"]),
            residual_multiplier=float(c["residual_multiplier"]),
            attention_multiplier=float(c["attention_multiplier"]),
            logits_scaling=float(c["logits_scaling"]),
            nope=c["position_embedding_type"] == "nope",
            ssm=SSMConfig(state_dim=c["mamba_d_state"],
                          head_dim=c["mamba_d_head"],
                          expand=c["mamba_expand"],
                          chunk_size=c["mamba_chunk_size"],
                          conv_width=c["mamba_d_conv"], dt_min=c["dt_min"],
                          dt_max=c["dt_max"]))

    def dims(self) -> tuple:
        c = self.cfg
        d = c["hidden_size"]
        return (tuple(c["layer_types"]), d, c["vocab_size"],
                c["shared_intermediate_size"], c["num_attention_heads"],
                c["num_key_value_heads"], d // c["num_attention_heads"],
                c["mamba_expand"] * d, c["mamba_d_state"], c["mamba_n_heads"],
                c["mamba_d_conv"], c["dt_min"], c["dt_max"])

    # ------------------------------------------------------------ set-up --
    def setup(self):
        from repro.core.engine import MeshExecutor
        from repro.core.round_step import CEFLHyper, make_dpu_meta
        from repro.kernels.plane import ParamPlane
        from repro.models import lm as L
        c, t = self.cfg, self.traffic
        mcfg = self.mcfg
        x0 = init_hybrid(jax.random.PRNGKey(self.seed), self.dims())
        self.x0_host = check.flatten(jax.device_get(x0))
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

    # ------------------------------------------------------------- check --
    def ref_round(self, x: dict, b, dtype=jnp.float32, fault: str = ""):
        """The reference's round on batch ``b`` from the flat weights
        ``x`` (host or device); returns flat device weights and the
        loss."""
        t = self.traffic
        per_dpu = [(b["tokens"][i].reshape(-1, t["seq"]),
                    b["labels"][i].reshape(-1, t["seq"]))
                   for i in range(t["n_dpu"])]
        return reference_hybrid.hybrid_round(
            x, per_dpu, self.cfg, gamma=t["gamma"], eta=t["eta"], mu=t["mu"],
            theta=float(t["gamma"]), dtype=dtype, fault=fault)

    def trajectory(self, dtype=jnp.float32, fault: str = "") -> dict:
        """The reference running on by itself over the checked rounds; in
        a lower precision, the control; with ``fault`` (``half_batch``,
        ``altered``), the reference with that fault planted."""
        x = self.x0_host
        out = {"x": [x], "loss": []}
        for b in self.batches:
            x, loss = self.ref_round(x, b, dtype, fault)
            x = host(x)
            out["x"].append(x)
            out["loss"].append(loss)
        return out

    def steps(self, traj: dict) -> list:
        """The reference's round from each round boundary of ``traj``."""
        out = []
        for x_r, b in zip(traj["x"], self.batches):
            x, loss = self.ref_round(x_r, b)
            out.append({"x": host(x), "loss": loss})
        return out

    def judge_round(self, x_r: dict, x_next: dict, loss: float, b):
        """``check.compare_steps``'s numbers for one round: the update
        ``x_r -> x_next`` and its loss against the reference's round from
        ``x_r``, holding that one reference round on the device.  Returns
        them and ``x_next`` on the device, the next round's ``x_r``."""
        x_r = device(x_r)
        ref, ref_loss = self.ref_round(x_r, b)
        x_next = device(x_next)     # once the reference's round is done
        norms, ref_norms, angle = update_norms_angle(x_r, x_next, ref)
        return {"gap": check.worst_leaf_gap(norms, ref_norms),
                "angle": angle,
                "loss_gap": abs(loss - ref_loss) / abs(ref_loss)}, x_next

    def compare_stepwise(self, traj: dict) -> dict:
        """``check.compare_steps(traj, self.steps(traj))``, one reference
        round held at a time."""
        rows, x_r = [], traj["x"][0]
        for r, b in enumerate(self.batches):
            row, x_r = self.judge_round(x_r, traj["x"][r + 1],
                                        traj["loss"][r], b)
            rows.append(row)
        return combine(rows)

    def compare_trajectory(self, dtype=jnp.float32, fault: str = "") -> dict:
        """``compare_stepwise(trajectory(dtype, fault))`` holding two of the
        trajectory's boundaries at a time (``bench/probe_stepwise.py``)."""
        x, rows = self.x0_host, []
        for b in self.batches:
            x_next, loss = self.ref_round(x, b, dtype, fault)
            x_next = host(x_next)       # the device holds the judged round
            row, x = self.judge_round(x, x_next, loss, b)
            rows.append(row)
        return combine(rows)

    def check(self) -> dict:
        self.release()
        return self.compare_stepwise(self.prog)
