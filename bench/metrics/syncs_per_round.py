"""Engine host layer: blocking device read-backs per round of the window:
the count of the program's ``cefl/sync`` spans (``tracing.sync``), each of
which waits for the device value it reads."""
import program_spans


def read(run):
    n = program_spans.of(run).count("sync")
    return n / run.rounds if n else None
