"""Solver layer: SCA outer iterations per solve in the window: the sum of
the ``outer_iters`` counters over the count of the program's
``cefl/solve`` spans; None with no solve in the window."""
import program_spans


def read(run):
    spans = program_spans.of(run)
    n = spans.count("solve")
    iters = spans.stat("outer_iters", "solve")
    return None if not n or iters is None else iters / n
