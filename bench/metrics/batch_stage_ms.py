"""Engine host layer: self time of the program's ``cefl/stage_batches``
spans per round of the window, in ms: ``fedprox._stage_group_batches``
padding and stacking each DPU group's rows and drawing its mini-batch
indices, one eager dispatch each."""
import program_spans


def read(run):
    s = program_spans.of(run).self_s("stage_batches")
    return None if s is None else 1e3 * s / run.rounds
