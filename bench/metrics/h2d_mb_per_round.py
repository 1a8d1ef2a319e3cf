"""Engine host layer: bytes the program moves from host to device per
round of the window, in MB (1e6 bytes): the sum of the ``h2d_bytes``
counters of its spans (the scenario's new rows, the offloaded rows, the
mini-batch weights)."""
import program_spans


def read(run):
    b = program_spans.of(run).stat("h2d_bytes")
    return None if b is None else b / 1e6 / run.rounds
