"""Device self time under the program's named scopes, for scopes beyond
``program_spans.SCOPES``.

Each ``XLA Ops`` event of a device plane is given the first scope of
``SCOPES`` that its ``tf_op`` stat (the HLO ``op_name``) holds, its self
time is taken as ``program_spans`` takes it (a ``while`` op less the ops
of its body), and each scope's time is averaged over the device planes
that ran a program.  :func:`of` reads the newest ``.xplane.pb`` under
``harness.TRACE_DIR`` once per run and keeps the result on the run's
``RunData``.  A run of a program that labels no op with a scope reads
None for it.
"""
from __future__ import annotations

import collections
from pathlib import Path

import harness
import program_spans
import trace_reduce

SCOPES = ("cefl.attn", "cefl.mlp")


def _scope_of(stats) -> str:
    name = str(stats.get(program_spans.OP_NAME_STAT, ""))
    return next((s for s in SCOPES if s in name), "")


def from_planes(planes) -> dict:
    """Scope -> seconds, from ``program_spans.read_pb``'s planes."""
    devices = []
    for pname, lines in planes:
        if pname.startswith("/device:TPU:") and lines.get("XLA Modules"):
            devices.append([(s, e, _scope_of(st))
                            for s, e, _, st in lines.get("XLA Ops", ())])
    out = collections.Counter()
    for ops in devices:
        for scope, ns in trace_reduce.self_times(ops).items():
            if scope:
                out[scope] += ns / len(devices) / 1e9
    return dict(out)


def of(run) -> dict:
    times = getattr(run, "_scope_times", None)
    if times is None:
        pbs = sorted(Path(harness.TRACE_DIR).rglob("*.xplane.pb"),
                     key=lambda p: p.stat().st_mtime)
        times = from_planes(program_spans.read_pb(pbs[-1])[0]) if pbs \
            else {}
        run._scope_times = times
    return times


def ms_per_round(run, scope: str):
    """Device self time under ``scope`` per round of the window, in ms;
    None where no op carries it."""
    s = of(run).get(scope)
    return None if s is None else 1e3 * s / run.rounds
