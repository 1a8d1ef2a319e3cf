"""The program's own spans, counters and device scopes in a traced run.

The program opens host spans named ``cefl/<name>`` whose keyword counters
come back as the event's stats (``src/repro/utils/tracing.py``), and
labels device ops with the named scopes in ``SCOPES``, which reach the
trace in each ``XLA Ops`` event's ``OP_NAME_STAT`` stat (the HLO
``op_name``).  :func:`of` reads the newest ``.xplane.pb`` under
``harness.TRACE_DIR`` once per run and keeps the result on the run's
``RunData``, so every metric reader of the run shares one parse.

A span's self time is its duration less the ``cefl/*`` spans directly
inside it on the same host thread line.  A scope's device time is the
self time of the ``XLA Ops`` events whose op name holds it, averaged over
the device planes as ``trace_reduce`` averages busy time.  A run of a
program that opens no such span, or labels no op, reads None.
"""
from __future__ import annotations

import bisect
import collections
import functools
from pathlib import Path

import harness
import trace_reduce

PREFIX = "cefl/"
SCOPES = ("cefl.eq10", "cefl.eq11", "cefl.ssd")
OP_NAME_STAT = "tf_op"


def _scope_of(stats) -> str:
    name = str(stats.get(OP_NAME_STAT, ""))
    return next((s for s in SCOPES if s in name), "")


class Spans:
    """``host``: ``[(line, start_ns, end_ns, name, stats)]`` of the
    ``cefl/*`` events, the prefix taken off the name; ``devices``:
    ``[(XLA Modules [(start, end)], XLA Ops [(start, end, scope)])]``, one
    per device plane that ran a program; ``window_ns``: the trace's
    length."""

    def __init__(self, host, devices, window_ns):
        self.host, self.devices, self.window_ns = host, devices, window_ns
        self._self_ns = collections.Counter()
        by_line = collections.defaultdict(list)
        for line, s, e, name, _ in host:
            by_line[line].append((s, e, name))
        for events in by_line.values():
            self._self_ns.update(trace_reduce.self_times(events))
        self._scope_ns = collections.Counter()
        for _mods, ops in devices:
            for scope, ns in trace_reduce.self_times(ops).items():
                self._scope_ns[scope] += ns / len(devices)

    def count(self, name: str) -> int:
        return sum(ev[3] == name for ev in self.host)

    def self_s(self, name: str):
        """Self time of the spans ``name`` in seconds, None without one."""
        if not self.count(name):
            return None
        return self._self_ns[name] / 1e9

    def total_s(self, name: str):
        if not self.count(name):
            return None
        return sum(e - s for _, s, e, n, _ in self.host if n == name) / 1e9

    def stat(self, key: str, name: str = None):
        """Sum of the numeric stat ``key`` over the spans ``name`` (every
        span where ``name`` is None); None where no span carries it."""
        vals = [st[key] for _, _, _, n, st in self.host
                if (name is None or n == name) and key in st]
        return float(sum(vals)) if vals else None

    def scope_s(self, *scopes: str):
        """Device self time of the ops under any of ``scopes``, in
        seconds; None where no op carries one."""
        if not any(s in self._scope_ns for s in scopes):
            return None
        return sum(self._scope_ns[s] for s in scopes) / 1e9

    def idle_under(self, name: str):
        """Seconds of the first device's idle time (outside every ``XLA
        Modules`` interval, as ``trace_reduce`` finds its gaps) that fall
        inside the spans ``name``; None without such a span or device."""
        iv = sorted((s, e) for _, s, e, n, _ in self.host if n == name)
        if not iv or not self.devices:
            return None
        gaps = trace_reduce.gaps(self.devices[0][0], 0.0, self.window_ns)
        starts = [g[0] for g in gaps]
        total, reach = 0.0, float("-inf")
        for s, e in iv:                 # spans of one name may nest
            s = max(s, reach)
            if e <= s:
                continue
            reach = e
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            for gs, ge in gaps[i:]:
                if gs >= e:
                    break
                total += max(0.0, min(ge, e) - max(gs, s))
        return total / 1e9


def from_planes(planes, window_ns) -> Spans:
    """``planes``: ``[(plane name, {line name: [(start_ns, end_ns, name,
    stats dict)]})]``, as :func:`read_pb` gives them."""
    host, devices = [], []
    for pname, lines in planes:
        if pname == "/host:CPU":
            for line, evs in lines.items():
                host.extend((line, s, e, n[len(PREFIX):], st)
                            for s, e, n, st in evs if n.startswith(PREFIX))
        elif pname.startswith("/device:TPU:"):
            mods = [(s, e) for s, e, _, _ in lines.get("XLA Modules", ())]
            if mods:
                devices.append((mods, [(s, e, _scope_of(st)) for s, e, _, st
                                       in lines.get("XLA Ops", ())]))
    return Spans(host, devices, window_ns)


# The part of the profiler's ``XSpace`` message (``xplane.proto``) read
# here, with its field numbers: a map field is a repeated key/value entry
# on the wire.  ``jax.profiler.ProfileData`` gives each event its own
# stats but not those of its metadata, where a device op's ``tf_op`` is.
XSPACE = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True),
               ("stats", 6, "XStat", True)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False),
               ("stats", 4, "XStat", True)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("double_value", 2, "double", False),
              ("uint64_value", 3, "uint64", False),
              ("int64_value", 4, "int64", False),
              ("str_value", 5, "string", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadata": [("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string", False)],
}


@functools.lru_cache(maxsize=None)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    fd = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": fd.TYPE_INT64, "uint64": fd.TYPE_UINT64,
              "double": fd.TYPE_DOUBLE, "string": fd.TYPE_STRING}
    proto = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace.proto", package="bench_xspace")
    for mname, fields in XSPACE.items():
        msg = proto.message_type.add(name=mname)
        for fname, number, kind, repeated in fields:
            f = msg.field.add(name=fname, number=number, label=(
                fd.LABEL_REPEATED if repeated else fd.LABEL_OPTIONAL))
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type, f.type_name = fd.TYPE_MESSAGE, ".bench_xspace." + kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.XSpace"))


def _stats(xstats, names) -> dict:
    out = {}
    for st in xstats:
        for field in ("int64_value", "uint64_value", "double_value",
                      "str_value"):
            if st.HasField(field):
                out[names.get(st.metadata_id)] = getattr(st, field)
                break
        else:
            if st.HasField("ref_value"):
                out[names.get(st.metadata_id)] = names.get(st.ref_value)
    return out


def read_pb(pb_path):
    """(planes as :func:`from_planes` takes them, trace length in ns), with
    stats kept only where a reader looks at them: a host event's own and
    a device op's metadata stats."""
    space = _xspace_class()()
    space.ParseFromString(Path(pb_path).read_bytes())
    planes, window_ns = [], None
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        if plane.name == "Task Environment":
            st = _stats(plane.stats, names)
            window_ns = float(st["profile_stop_time"] -
                              st["profile_start_time"])
        host = plane.name == "/host:CPU"
        if not (host or plane.name.startswith("/device:TPU:")):
            continue
        meta = {e.key: e.value for e in plane.event_metadata}
        lines = {}
        for line in plane.lines:
            if not host and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            evs = []
            for e in line.events:
                m = meta.get(e.metadata_id)
                name = m.name if m is not None else ""
                if host and not name.startswith(PREFIX):
                    continue
                s = line.timestamp_ns + e.offset_ps / 1e3
                if host:
                    st = _stats(e.stats, names)
                elif line.name == "XLA Ops" and m is not None:
                    st = _stats(m.stats, names)
                else:
                    st = {}
                evs.append((s, s + e.duration_ps / 1e3, name, st))
            lines[line.name] = evs
        planes.append((plane.name, lines))
    return planes, window_ns


def of(run) -> Spans:
    """The run's spans, parsed once and kept on ``run``."""
    spans = getattr(run, "_program_spans", None)
    if spans is None:
        pbs = sorted(Path(harness.TRACE_DIR).rglob("*.xplane.pb"),
                     key=lambda p: p.stat().st_mtime)
        spans = from_planes(*read_pb(pbs[-1])) if pbs else \
            Spans([], [], 0.0)
        run._program_spans = spans
    return spans
