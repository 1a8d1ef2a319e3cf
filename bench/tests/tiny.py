"""Cells small enough for a CPU test run: a 14x14 MLP on a 4-UE network
and a 2-layer Mamba-2 of narrow widths, each under a short mix, written
with their BENCHMARK.json, configs, traffic and limits into a directory
the harness reads as a checkout."""
from __future__ import annotations

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
MLP, LM = "tiny_mlp.tiny_static", "tiny_mamba2.tiny_local"


def _load(rel: str) -> dict:
    with open(BENCH / rel) as f:
        return json.load(f)


def configs():
    mlp = copy.deepcopy(_load("configs/cefl_mlp_paper.json"))
    mlp["name"] = "tiny_mlp"
    s = mlp["spec"]
    s["model"].update(input_shape=[14, 14, 1], hidden=[32])
    s["data"].update(pool=3000, eval_examples=200)
    s["network"].update(num_ue=4, num_bs=2, num_dc=2)
    s["consts"].update(mode="fixed")
    s["engine"].update(rounds=6, solver_outer=2)
    mlp_t = copy.deepcopy(_load("traffic/static_cefl.json"))
    mlp_t["name"] = "tiny_static"
    mlp_t["spec"]["data"].update(mean_arrivals=150.0, std_arrivals=15.0)
    lm = copy.deepcopy(_load("configs/mamba2_130m.json"))
    lm.update(name="tiny_mamba2", n_layer=2, d_model=64, vocab_size=256,
              d_state=16, headdim=16, chunk_size=8)
    lm_t = copy.deepcopy(_load("traffic/local_heavy.json"))
    lm_t.update(name="tiny_local", batch=4, seq=32)
    return {MLP: (mlp, mlp_t), LM: (lm, lm_t)}


def write_root(root: Path, limits=None) -> Path:
    """A checkout-like directory holding the tiny cells.  ``limits``: per
    workload, or the real cells' limits by default."""
    cells = configs()
    real = {MLP: "cefl_mlp_paper.static_cefl",
            LM: "mamba2_130m.local_heavy"}
    for sub in ("configs", "traffic", "limits"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    bench = _load("../BENCHMARK.json")
    bench["workloads"] = []
    for name, (cfg, traffic) in cells.items():
        bench["workloads"].append({"name": name, "config": cfg["name"],
                                   "traffic": traffic["name"], "chips": 1,
                                   "why": "test"})
        (root / "bench/configs" / f"{cfg['name']}.json").write_text(
            json.dumps(cfg))
        (root / "bench/traffic" / f"{traffic['name']}.json").write_text(
            json.dumps(traffic))
        lim = (limits or {}).get(name) or _load(f"limits/{real[name]}.json")
        (root / "bench/limits" / f"{name}.json").write_text(json.dumps(lim))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
