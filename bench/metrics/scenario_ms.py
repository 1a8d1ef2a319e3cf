"""Engine host layer: self time of the program's ``cefl/scenario`` span per
round of the window, in ms: the scenario tick (the network's resampled
rates and each UE's new rows, handed over as device arrays), read from
the traced window by ``program_spans``."""
import program_spans


def read(run):
    s = program_spans.of(run).self_s("scenario")
    return None if s is None else 1e3 * s / run.rounds
