"""Round program: device self time per round of the window of the ops
under the ``cefl.unflatten`` named scope (``kernels/plane.FlatSpec.
unflatten``: each leaf's row window of the parameter plane, and
``flatten`` of the cotangents as the view's backward), in ms.

Each ``XLA Ops`` event of a device plane that ran a program counts where
its ``tf_op`` stat (the HLO ``op_name``) holds the scope; its self time is
taken as ``program_spans`` takes it, and averaged over those planes.  A
program that labels no op with the scope reads None.
"""
from pathlib import Path

import harness
import program_spans
import trace_reduce

SCOPE = "cefl.unflatten"


def scope_s(planes):
    """Seconds under ``SCOPE``, from ``program_spans.read_pb``'s planes;
    None where no op carries it."""
    devices = [lines.get("XLA Ops", ()) for pname, lines in planes
               if pname.startswith("/device:TPU:")
               and lines.get("XLA Modules")]
    found, ns = False, 0.0
    for ops in devices:
        marked = [(s, e, SCOPE in str(st.get(program_spans.OP_NAME_STAT,
                                              "")))
                  for s, e, _, st in ops]
        found = found or any(m for _, _, m in marked)
        ns += trace_reduce.self_times(marked).get(True, 0.0) / len(devices)
    return ns / 1e9 if found else None


def read(run):
    pbs = sorted(Path(harness.TRACE_DIR).rglob("*.xplane.pb"),
                 key=lambda p: p.stat().st_mtime)
    s = scope_s(program_spans.read_pb(pbs[-1])[0]) if pbs else None
    return None if s is None else 1e3 * s / run.rounds
