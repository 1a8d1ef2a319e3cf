"""Round program: device self time per round of the window of the ops
under the ``cefl.mlp`` named scope (``models/blocks.mlp_forward``: the
gated MLP's three projections and its SiLU gate, forward and backward),
in ms."""
import scope_times


def read(run):
    return scope_times.ms_per_round(run, "cefl.mlp")
