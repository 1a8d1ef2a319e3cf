"""The benchmark harness: one cell, one run, one JSON line.

A cell is a workload entry of ``BENCHMARK.json``: a configuration file
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  The configuration's ``kind`` names
the module ``bench/drivers/<kind>.py``, which builds the system under
test, drives its first rounds in set-up, runs the measured window and
checks the checked rounds against the plain reference.  Per-layer
metrics are read by ``bench/metrics/<metric>.py``, each a ``read(run)``
over the window's host spans, compile counts and the reduced device
trace.  Every one of these is found by name, so a new cell, mix or metric
is a new file and a new ``BENCHMARK.json`` entry.
"""
from __future__ import annotations

import collections
import contextlib
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / "bench_out" / "trace"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file of ``bench/`` by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(workload: str, root: Path = ROOT):
    """(benchmark, workload entry, config dict, traffic dict)."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    cfg = load_json(root / "bench" / "configs" / f"{w['config']}.json")
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return bench, w, cfg, traffic


class Recorder:
    """Host spans (their host-clock totals, and a ``TraceAnnotation`` each,
    so a traced run sees them beside the device ops) and JAX's compile
    events, counted by phase: ``setup`` up to the end of set-up's first
    round, ``rounds`` for set-up's later rounds (``round_done`` counts
    them), ``window`` for the measured window."""

    def __init__(self):
        import jax
        self.totals = collections.defaultdict(float)
        self.names = set()                        # every span name used
        self.phase = "setup"
        self.setup_rounds = 0            # set-up rounds after the first
        self.compiles = collections.Counter()     # phase -> backend compiles
        self.cache = collections.Counter()        # hits / misses
        self._annotate = jax.profiler.TraceAnnotation

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles[self.phase] += 1

        def on_event(event, **_kw):
            if event.startswith("/jax/compilation_cache/cache_"):
                self.cache[event.rsplit("_", 1)[-1]] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def round_done(self):
        """A driver's set-up finished a round: compiles after the first
        one are the per-round compiles a round's new data costs."""
        if self.phase == "rounds":
            self.setup_rounds += 1
        self.phase = "rounds"

    @contextlib.contextmanager
    def span(self, name: str):
        self.names.add(name)
        t0 = time.perf_counter()
        with self._annotate(name):
            try:
                yield
            finally:
                if self.phase == "window":
                    self.totals[name] += time.perf_counter() - t0


def require_tpu(chips: int):
    """The devices, or exit naming what was found instead of a TPU."""
    import jax

    from repro.kernels import ops
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found {devices[0].platform!r} "
                         f"({devices[0].device_kind}), not a TPU")
    if ops.current_backend() != "tpu":
        raise SystemExit(f"bench: kernel backend {ops.current_backend()!r}, "
                         f"not the compiled 'tpu' kernels")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, root: Path = ROOT,
        cell_hook=None) -> tuple:
    """Run one cell once.  Returns ``(result, check_lines)``: the JSON
    object of the last stdout line and the compared numbers, each beside
    its limit.  ``cell_hook(cell)``, for tests, may break the timed path
    after the cell is built."""
    bench, w, cfg, traffic = find_cell(workload, root)
    import jax

    from repro.utils.compile_cache import enable_compile_cache
    rec = Recorder()
    devices = require_tpu(w["chips"]) if require_chip else jax.devices()
    device = devices[0]
    cache_dir = enable_compile_cache()
    driver = load_module(BENCH / "drivers" / f"{cfg['kind']}.py",
                         f"bench_driver_{cfg['kind']}")
    limits = load_json(root / "bench" / "limits" / f"{workload}.json")
    cell = driver.Cell(cfg, traffic, seed, rec)
    if cell_hook is not None:
        cell_hook(cell)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    log(f"{workload} seed {seed}: set-up {setup_s!r} s (compile cache "
        f"{cache_dir}: {dict(rec.cache)}; backend compiles: "
        f"{rec.compiles['setup']} to the end of the first round, "
        f"{rec.compiles['rounds']} in {rec.setup_rounds} later rounds)")

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    rec.phase = "window"
    window = cell.window(seconds)
    rec.phase = "check"
    if trace:
        jax.profiler.stop_trace()
    peak = peak_bytes(device)
    rounds, window_s = window["rounds"], window["seconds"]
    log(f"window: {rounds} rounds in {window_s!r} s, "
        f"{rec.compiles['window']} backend compiles, host spans "
        f"{dict(rec.totals)}")

    t_check = time.perf_counter()
    numbers = cell.check()          # frees the program's state first
    log(f"check against the reference: {time.perf_counter() - t_check!r} s")
    check_lines, correct = [], True
    for name, limit in limits.items():     # the cell's limits name what
        value = numbers[name]              # it compares
        ok = math.isfinite(value) and value <= limit
        correct &= ok
        check_lines.append((name, value, limit, ok))

    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if trace:
        import trace_reduce
        reduced = trace_reduce.reduce_dir(TRACE_DIR, rec.names)
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        breakdown = reduced["breakdown"]
        peaks = load_json(BENCH / "peaks.json")
        if device.device_kind not in peaks:
            raise SystemExit(f"bench: no peaks for {device.device_kind!r} "
                             f"in bench/peaks.json")
        ctx = RunData(rounds=rounds, window_s=window_s, spans=rec.totals,
                      compiles=rec.compiles, setup_rounds=rec.setup_rounds,
                      model_flops=window["model_flops"], trace=reduced,
                      peaks=peaks[device.device_kind])
        for m in bench["per_layer"]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"round_s": window_s / rounds, "peak_hbm_gb": peak / 1e9,
               "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": rounds,
              "failed": sum(not ok for *_, ok in check_lines),
              "metrics": metrics,
              "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit, _ in check_lines}
    return result, check_lines


class RunData:
    """What a per-layer metric reader sees of one traced run."""

    def __init__(self, *, rounds, window_s, spans, compiles, setup_rounds,
                 model_flops, trace, peaks):
        self.rounds = rounds
        self.window_s = window_s
        self.spans = dict(spans)
        self.compiles = dict(compiles)      # phase -> backend compiles
        self.setup_rounds = setup_rounds
        self.model_flops = model_flops
        self.trace = trace
        self.peaks = peaks


def main(argv, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    result, checks = run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=t_start)
    log(f"run {time.perf_counter() - t_start!r} s in all")
    for name, value, limit, ok in checks:
        print(f"check {name}: {value!r} (limit {limit!r}) "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
