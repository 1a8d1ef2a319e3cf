"""Round program: device self time per round of the window of the ops
under the ``cefl.attn`` named scope (``models/blocks.attn_forward``: the
q/k/v projections, blocked attention and the output projection, forward
and backward), in ms."""
import scope_times


def read(run):
    return scope_times.ms_per_round(run, "cefl.attn")
