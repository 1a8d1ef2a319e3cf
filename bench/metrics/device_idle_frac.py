"""Device: the share of the traced window in which no program ran on the
chip (1 - the union of ``XLA Modules`` intervals over the window), in
percent."""


def read(run):
    t = run.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
