"""From a JAX profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Three
parts of it are read:

* each ``/device:TPU:<n>`` plane's ``XLA Modules`` line: one event per
  program execution.  Their union is the device's busy time.
* the same plane's ``XLA Ops`` line: one event per HLO op, named by its
  HLO text (``%fedprox_accum_2d.8 = (f32[3,176,1024]...) custom-call(...)``),
  so a kernel's operand and result shapes come with its time.  Every
  custom call is kept; a roofline reader picks its kernels by name.
* the host's ``TraceAnnotation`` spans (the names the run's drivers gave
  their spans) and compile events, which label the device's idle gaps by
  what the host was doing.

All times on these lines are nanoseconds from the start of the trace.
"""
from __future__ import annotations

import collections
import re
from pathlib import Path

SHAPE = re.compile(r"\b(f64|f32|bf16|f16|s32|u32|s8|u8|pred)\[([0-9,]*)\]"
                   r"(\{[^}]*\})?")


def op_name(event_name: str) -> str:
    """``%fedprox_accum_2d.8 = ...`` -> ``fedprox_accum_2d.8``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def kernel_of(event_name: str):
    """The kernel a custom-call op event runs (its op name without the
    instance number), or None for any other op."""
    if " custom-call(" not in event_name:
        return None
    return op_name(event_name).split(".", 1)[0]


def shapes(text: str):
    """[(dtype, dims, in_hbm)] of every array written in ``text``.  An
    array whose layout names another memory space (``S(1)``: on-chip
    memory XLA may place small buffers in) moves no HBM bytes."""
    return [(dt, tuple(int(x) for x in dims.split(",") if x),
             "S(" not in layout)
            for dt, dims, layout in SHAPE.findall(text)]


def call_arrays(event_name: str):
    """(result arrays, operand arrays) of a custom-call op's HLO text."""
    head, _, rest = event_name.partition(" custom-call(")
    operands = rest.split("), custom_call_target", 1)[0]
    return shapes(head.split(" = ", 1)[-1]), shapes(operands)


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``[start, end)`` intervals clipped to
    ``[lo, hi)``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float):
    """The idle ``(start, end)`` gaps of ``[lo, hi)`` outside the union of
    ``intervals``."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(events):
    """Exclusive time of each event on one line, where events nest
    (a ``while`` op holds the ops of its body): name -> ns."""
    out = collections.defaultdict(float)
    stack = []          # (end, name)
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out[name] += e - s
        if stack:
            out[stack[-1][1]] -= e - s
        stack.append((e, name))
    return out


def label_at(t: float, host_events, host_spans) -> str:
    """What the host was doing at ``t``: the innermost harness span that
    covers it, with ``+compile`` where a compile covers it too."""
    spans = [(e - s, n) for s, e, n in host_events
             if n in host_spans and s <= t < e]
    label = min(spans)[1] if spans else "outside-spans"
    if any(s <= t < e for s, e, n in host_events if "compile" in n.lower()):
        label += "+compile"
    return label


def reduce_profile(planes, window_ns: float, host_spans) -> dict:
    """``planes``: ``[(plane name, {line name: [(start_ns, end_ns, name)]})]``
    as :func:`read_planes` gives them; ``host_spans``: the names of the
    harness's host spans."""
    busy, kernels, ops, host = [], [], collections.defaultdict(float), []
    idle = None       # the first device's gaps label the breakdown
    for pname, lines in planes:
        if pname.startswith("/device:TPU:"):
            mods = [(s, e) for s, e, _ in lines.get("XLA Modules", ())]
            if not mods:
                continue
            busy.append(union_ns(mods, 0.0, window_ns))
            if idle is None:
                idle = gaps(mods, 0.0, window_ns)
            evs = lines.get("XLA Ops", [])
            for name, ns in self_times(
                    [(s, e, op_name(n)) for s, e, n in evs]).items():
                ops[name] += ns
            for s, e, n in evs:
                k = kernel_of(n)
                if k is not None:
                    res, opnd = call_arrays(n)
                    kernels.append({"kernel": k, "ns": e - s,
                                    "results": res, "operands": opnd})
        elif pname == "/host:CPU":
            for evs in lines.values():
                host.extend((s, e, n) for s, e, n in evs
                            if n in host_spans or "compile" in n.lower())
    if not busy:
        raise RuntimeError("the trace holds no device program execution")
    busy_ns = sum(busy) / len(busy)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "kernels": kernels,
        "breakdown": {
            "device_ops": [[n, ns / 1e9] for n, ns in sorted(
                ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[label_at((s + e) / 2, host, host_spans),
                           (e - s) / 1e9]
                          for s, e in longest],
        },
    }


def read_planes(pb_path):
    """(planes as :func:`reduce_profile` takes them, trace length in ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(pb_path))
    planes, window_ns = [], None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = {k: float(v) for k, v in plane.stats}
            window_ns = st["profile_stop_time"] - st["profile_start_time"]
        if not (plane.name.startswith("/device:TPU:")
                or plane.name == "/host:CPU"):
            continue
        lines = {}
        for line in plane.lines:
            if plane.name.startswith("/device:") and line.name not in (
                    "XLA Modules", "XLA Ops"):
                continue
            lines[line.name] = [(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events]
        planes.append((plane.name, lines))
    return planes, window_ns


def reduce_dir(trace_dir, host_spans) -> dict:
    """Reduce the newest trace under ``trace_dir``."""
    pbs = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                 key=lambda p: p.stat().st_mtime)
    if not pbs:
        raise RuntimeError(f"no trace under {trace_dir}")
    planes, window_ns = read_planes(pbs[-1])
    return reduce_profile(planes, window_ns, host_spans)
