"""CLI for the declarative experiment API.

    python -m repro.experiments list
    python -m repro.experiments show quickstart
    python -m repro.experiments run quickstart
    python -m repro.experiments run sweep_smoke --executor vmap \
        --trace out/trace.jsonl
    python -m repro.experiments run campus_walk_vs_fixed \
        --set strategy=fixed:0 --seeds 0,1 --set engine.rounds=10
    python -m repro.experiments run sweep_smoke --checkpoint out/ck \
        --checkpoint-every 1 --resume

``NAME`` is a preset (``list`` shows them) or a path to a spec JSON
(written by ``show`` / ``--dump``).  ``--set`` takes dotted spec paths.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from repro.experiments import (TraceSink, available_experiments,
                               build_context, from_json, get_experiment,
                               run as run_one, sweep, to_json)
from repro.utils.compile_cache import enable_compile_cache


def _load_spec(name: str):
    if name.endswith(".json") or os.path.sep in name:
        with open(name) as f:
            return from_json(f.read())
    return get_experiment(name)


def _apply_overrides(spec, args):
    updates = {}
    for kv in args.set or []:
        k, _, v = kv.partition("=")
        if not _:
            raise SystemExit(f"--set needs key=value, got {kv!r}")
        updates[k] = v
    if args.seeds:
        updates["seeds"] = tuple(
            int(s) for s in args.seeds.replace(",", " ").split())
    if args.rounds is not None:
        updates["engine.rounds"] = args.rounds
    if args.strategy:
        updates["strategy"] = args.strategy
    if args.scenario:
        updates["scenario"] = args.scenario
    return spec.override(**updates) if updates else spec


def _cmd_list(args):
    for name in available_experiments():
        spec = get_experiment(name)
        print(f"{name:22s} kind={spec.model.kind:10s} "
              f"strategy={spec.strategy:12s} scenario={spec.scenario:16s} "
              f"rounds={spec.engine.rounds:<4d} seeds={list(spec.seeds)}")
    return 0


def _cmd_show(args):
    spec = _apply_overrides(_load_spec(args.name), args)
    print(to_json(spec))
    return 0


def _cmd_run(args):
    spec = _apply_overrides(_load_spec(args.name), args)
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(to_json(spec))
    # append on resume: the pre-kill rounds are already in the file
    trace = TraceSink(args.trace, append=args.resume) if args.trace \
        else None
    if spec.model.kind == "lm":
        if args.checkpoint or args.resume or args.stop_after:
            raise SystemExit(
                "--checkpoint/--resume/--stop-after apply to classifier "
                "sweeps; for lm specs use repro.experiments.lm.run_lm("
                "spec, checkpoint=...) directly")
        res = run_one(spec, trace=trace)
        if trace:
            trace.close()
        print(f"final loss {res.final.loss:.4f}")
        return 0
    if (args.checkpoint_every or args.stop_after or args.resume) \
            and not args.checkpoint:
        raise SystemExit("--checkpoint-every/--stop-after/--resume need "
                         "--checkpoint <dir>")
    if len(spec.run_seeds) == 1 and not (args.checkpoint or args.resume
                                         or args.stop_after
                                         or args.executor == "vmap"):
        _print_header()
        res = run_one(spec, trace=trace, callbacks=(_print_round,))
        _print_final(spec.name, spec.run_seeds[0], res)
        if trace:
            trace.close()
        return 0
    result = sweep(spec, executor=args.executor, trace=trace,
                   checkpoint_dir=args.checkpoint,
                   checkpoint_every=args.checkpoint_every,
                   resume=args.resume, stop_after=args.stop_after)
    if trace:
        trace.close()
    for key, res in result.runs:
        _print_final(key.experiment, key.seed, res)
    print("\naggregate stats:")
    print(json.dumps(result.stats(), indent=1))
    return 0


def _print_header():
    print("round  acc    loss   aggregator  energy(J)  delay(s)")


def _print_round(r):
    print(f"{r.round:5d}  {r.acc:.3f}  {r.loss:6.3f}  DC{r.aggregator:<9d}"
          f" {r.energy:9.2f} {r.delay:9.2f}")


def _print_final(name, seed, res):
    f = res.final
    print(f"[{name} seed={seed}] rounds={len(res)} acc={f.acc:.3f} "
          f"loss={f.loss:.3f} E={f.cum_energy:.1f}J "
          f"delay={f.cum_delay:.1f}s aggregators={res.series('aggregator')}")


def _cmd_validate(args):
    spec = _apply_overrides(_load_spec(args.name), args)
    back = from_json(to_json(spec))
    assert back == spec, "spec JSON round-trip failed"
    build_context(spec)
    print(f"spec {spec.name!r} OK (json round-trip + context build)")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m repro.experiments")
    sub = ap.add_subparsers(dest="cmd", required=True)
    sub.add_parser("list", help="available presets")
    for cmd, fn in (("show", _cmd_show), ("run", _cmd_run),
                    ("validate", _cmd_validate)):
        p = sub.add_parser(cmd)
        p.add_argument("name", help="preset name or spec JSON path")
        p.add_argument("--set", action="append", metavar="PATH=VALUE",
                       help="dotted spec override, e.g. engine.rounds=4")
        p.add_argument("--seeds", help="comma-separated seed list")
        p.add_argument("--rounds", type=int)
        p.add_argument("--strategy")
        p.add_argument("--scenario")
        if cmd == "run":
            p.add_argument("--executor", default="vmap",
                           choices=("vmap", "sequential"))
            p.add_argument("--trace", help="JSONL trace output path")
            p.add_argument("--checkpoint", help="full-state snapshot dir")
            p.add_argument("--checkpoint-every", type=int, default=0)
            p.add_argument("--resume", action="store_true")
            p.add_argument("--stop-after", type=int, default=None,
                           help="stop (with snapshot) after N rounds")
            p.add_argument("--dump", help="write the resolved spec JSON")
    args = ap.parse_args(argv)
    if args.cmd == "list":
        return _cmd_list(args)
    return {"show": _cmd_show, "run": _cmd_run,
            "validate": _cmd_validate}[args.cmd](args)


if __name__ == "__main__":
    # the command line, not main(): tests call main() and keep JAX's
    # default cache settings
    enable_compile_cache()
    sys.exit(main())
