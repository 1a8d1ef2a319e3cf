"""Round program: device self time per round of the window of the ops
under the ``cefl.ssd`` named scope (``models/mamba.ssd_forward``: the
Mamba2 mixer's projections, convolution, chunked SSD scan and gated norm,
forward and backward), in ms."""
import program_spans


def read(run):
    s = program_spans.of(run).scope_s("cefl.ssd")
    return None if s is None else 1e3 * s / run.rounds
