"""Kernels: device self time per round of the window of the ops under the
``cefl.eq10`` and ``cefl.eq11`` named scopes (the proximal step with the
eq.-10 accumulation and the eq.-11 aggregation, whatever implements
them), in ms."""
import program_spans


def read(run):
    s = program_spans.of(run).scope_s("cefl.eq10", "cefl.eq11")
    return None if s is None else 1e3 * s / run.rounds
