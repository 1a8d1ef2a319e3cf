import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination on the production meshes, print memory/cost analysis, and
dump roofline inputs as JSON.

Usage:
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
  python -m repro.launch.dryrun --all --out results/dryrun

The XLA host-device-count flag above MUST precede every other import (jax
locks the device count at first init), which is why this module sets it in
its first two lines and why nothing else in the repo sets it globally.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES
from repro.core.round_step import make_dpu_meta
from repro.launch import steps as ST
from repro.launch.mesh import make_production_mesh
from repro.sharding.specs import sanitize_spec
from repro.utils.hlo import collective_bytes
from repro.utils.hlo_walk import amplified_costs
from repro.utils.roofline import model_flops_for


def _flt(d):
    return {k: (float(v) if isinstance(v, (int, float)) else v)
            for k, v in (d or {}).items()}


def dryrun_combo(arch: str, shape_name: str, *, multi_pod: bool,
                 gamma_max: int = 1, verbose: bool = True,
                 keep_hlo: bool = False, plan_overrides=None,
                 attn_hint: bool = True) -> dict:
    """Lower + compile one combination; returns the roofline record."""
    plan = ST.make_plan(arch, shape_name, multi_pod=multi_pod,
                        gamma_max=gamma_max, **(plan_overrides or {}))
    rec = {"arch": arch, "shape": shape_name, "mesh": plan.mesh_name,
           "chips": plan.chips, "mode": plan.shape.mode,
           "n_micro": plan.n_micro, "remat_chunk": plan.remat_chunk,
           "seq_shard_decode": plan.seq_shard_decode,
           "wide_cache": plan.wide_cache,
           "sliding_window": plan.cfg.sliding_window}
    if plan.skip:
        rec["status"] = "skipped"
        rec["reason"] = plan.skip
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    # constrain attention activations (batch->data, heads->model); see
    # repro.models.attention.set_shard_hint
    # Measured: the constraint is a ~10-30x win for train (GSPMD otherwise
    # leaves full batch on every device in the attention backward) but hurts
    # prefill memory, where GSPMD's free layout is better — so train only.
    from repro.models import attention as attn_mod
    use_hint = attn_hint and plan.shape.mode == "train"
    attn_mod.set_shard_hint(mesh if use_hint else None, ("data",), "model")
    params = ST.abstract_params(plan)
    p_shard = ST.param_shardings(plan, mesh)
    b_spec = ST.input_specs(plan)
    b_shard = ST.batch_shardings(plan, mesh)

    # NamedShardings carry the mesh; no ambient mesh context needed
    if True:
        if plan.shape.mode == "train":
            step = ST.build_train_step(plan)
            meta = make_dpu_meta(plan.n_dpu)
            meta_shard = jax.tree_util.tree_map(
                lambda x: NamedSharding(mesh, P(*((None,) * x.ndim))), meta)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, b_shard, meta_shard),
                             out_shardings=(p_shard, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(params, b_spec, meta)
        elif plan.shape.mode == "prefill":
            step = ST.build_prefill_step(plan, mesh)
            c_shard = ST.cache_shardings(plan, mesh)
            logit_shard = NamedSharding(mesh, sanitize_spec(
                P(("data",), "model"),
                (plan.shape.global_batch, plan.cfg.vocab_size), mesh))
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard),
                             out_shardings=(logit_shard, c_shard))
            lowered = jitted.lower(params, b_spec)
        else:
            step = ST.build_serve_step(plan, mesh)
            cache = ST.abstract_cache(plan)
            c_shard = ST.cache_shardings(plan, mesh)
            ctx = ST.shard_ctx(plan, mesh)
            b_ax = tuple(ctx.batch_axes) or None
            logit_shard = NamedSharding(mesh, sanitize_spec(
                P(b_ax, "model"),
                (plan.shape.global_batch, plan.cfg.vocab_size), mesh))
            jitted = jax.jit(step,
                             in_shardings=(p_shard, c_shard, b_shard),
                             out_shardings=(logit_shard, c_shard),
                             donate_argnums=(1,))
            lowered = jitted.lower(params, cache, b_spec)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    attn_mod.set_shard_hint(None)

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    amp = amplified_costs(hlo)          # trip-count-aware totals
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # NOTE: compiled HLO is the per-device SPMD program; *_device values
        # are per-chip, the headline values are global (x chips).
        "flops_raw_device": flops,             # XLA, loop bodies once
        "bytes_accessed_raw_device": nbytes,
        "flops_device": amp["flops"],          # trip-count amplified
        "bytes_device": amp["bytes"],
        "flops": amp["flops"] * plan.chips,
        "bytes_accessed": amp["bytes"] * plan.chips,
        "collectives_raw": {k: v for k, v in coll.items() if k != "counts"},
        "collectives": {k: v * plan.chips
                        for k, v in amp["collectives"].items()},
        "collective_bytes": amp["collective_bytes_total"] * plan.chips,
        "unknown_trip_counts": amp["unknown_trip_counts"][:8],
        "collective_counts": coll.get("counts", {}),
        "model_flops": model_flops_for(plan.cfg, plan.shape,
                                       gamma=gamma_max),
        "memory_analysis": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_size_bytes":
                getattr(mem, "generated_code_size_in_bytes", 0),
        },
    })
    # memory_analysis of the SPMD module is already per-device
    arg_b = rec["memory_analysis"]["argument_size_bytes"]
    tmp_b = rec["memory_analysis"]["temp_size_bytes"]
    rec["bytes_per_device"] = arg_b + tmp_b
    if keep_hlo:
        rec["hlo"] = hlo
    if verbose:
        mf = rec["model_flops"]
        print(f"[{arch} x {shape_name} x {plan.mesh_name}] "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s | "
              f"global flops {rec['flops']:.3e} "
              f"(model/hlo {mf/max(rec['flops'],1):.2f}) "
              f"bytes {rec['bytes_accessed']:.3e} "
              f"coll {rec['collective_bytes']/1e9:.2f}GB | "
              f"args+tmp/device {rec['bytes_per_device']/1e9:.2f}GB")
        print(f"  memory_analysis: {rec['memory_analysis']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--gamma-max", type=int, default=1)
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'multi' if mp else 'single'}"
                path = outdir / f"{tag}.json"
                if path.exists():
                    print(f"[skip cached] {tag}")
                    continue
                try:
                    rec = dryrun_combo(arch, shape, multi_pod=mp,
                                       gamma_max=args.gamma_max,
                                       keep_hlo=True)
                    if "hlo" in rec:     # archive compressed HLO next to it
                        import gzip
                        (outdir / f"{tag}.hlo.txt.gz").write_bytes(
                            gzip.compress(rec.pop("hlo").encode()))
                except Exception as e:   # noqa: BLE001
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": repr(e)}
                    failures.append(tag)
                path.write_text(json.dumps(rec, indent=1))
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("dry-run complete.")


if __name__ == "__main__":
    main()
