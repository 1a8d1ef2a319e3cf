"""Host spans, counters and device read-backs of the CE-FL round, as the
JAX profiler records them (``docs/tracing.md``).

Every host span is a ``jax.profiler.TraceAnnotation`` named ``cefl/<name>``;
its keyword counters come back as the event's stats in the trace.  A span
given ``round=`` hands that round to every span opened inside it, so all
spans of one round carry the same ``round``.  With no trace running a span
does nothing, and a counter that costs work to compute is computed only
``if enabled()``.  The named scopes ``EQ10``, ``EQ11``, ``SSD``, ``ATTN``
and ``MLP`` label the device ops of those computations in the HLO
``op_name`` metadata.

Two rules hold for every span name: it never contains ``compile`` (trace
readers take such host events for compiles), and it is never one of a
benchmark driver's own span names (``decide``, ``stage``, ``execute``,
``finish``, ``restart``, ``batch``).
"""
from __future__ import annotations

import threading

import jax
import numpy as np
from jax.profiler import TraceAnnotation

PREFIX = "cefl/"
EQ10, EQ11, SSD = "cefl.eq10", "cefl.eq11", "cefl.ssd"
ATTN, MLP = "cefl.attn", "cefl.mlp"

_local = threading.local()


def enabled() -> bool:
    """Whether a trace is recording host spans now."""
    return TraceAnnotation.is_enabled()


def _open() -> list:
    if not hasattr(_local, "open"):
        _local.open = []
    return _local.open


class span:
    """``with span("offload", rows=n) as s: ...; s.set(h2d_bytes=b)``."""
    __slots__ = ("name", "counters", "_tm")

    def __init__(self, name: str, **counters):
        self.name, self.counters, self._tm = name, counters, None

    def __enter__(self):
        if TraceAnnotation.is_enabled():
            open_ = _open()
            if "round" not in self.counters and open_ and \
                    "round" in open_[-1].counters:
                self.counters["round"] = open_[-1].counters["round"]
            self._tm = TraceAnnotation(PREFIX + self.name, **self.counters)
            self._tm.__enter__()
            open_.append(self)
        return self

    def __exit__(self, *exc):
        if self._tm is not None:
            _open().pop()
            self._tm.__exit__(*exc)
        return False

    def set(self, **counters):
        """Counters known only once the span's work is done."""
        if self._tm is not None:
            self._tm.set_metadata(**counters)


def add(name: str, **counters) -> None:
    """Counters for the innermost open span ``cefl/<name>`` (none: none)."""
    for s in reversed(_open()):
        if s.name == name:
            s.set(**counters)
            return


def sync(x, site: str) -> np.ndarray:
    """``np.asarray(x)``: the blocking read-back of a device value, inside
    a ``cefl/sync`` span that carries ``site``; a host value passes
    through without a span."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    with span("sync", site=site):
        return np.asarray(x)


def nbytes(*trees) -> int:
    """Bytes of the device arrays among the leaves of ``trees``."""
    return sum(int(a.nbytes) for t in trees
               for a in jax.tree_util.tree_leaves(t)
               if isinstance(a, jax.Array))
