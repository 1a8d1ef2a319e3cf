"""Round program: model operations of the window (6 per parameter per
example or token a local step processes, recomputation not counted) over
the window's length times the chip's bf16 peak (``bench/peaks.json``),
in percent."""


def read(run):
    return 100.0 * run.model_flops / (run.window_s * run.peaks["bf16_flops"])
