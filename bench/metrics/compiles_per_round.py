"""Compilation layer: JAX backend compiles per set-up round after the
first (``/jax/core/compile/backend_compile_duration`` events; programs
loaded from the persistent cache are not compiles).  These are the
programs keyed on a round's new data sizes, which a user pays every
round; the window replays shapes that set-up warmed and compiles
nothing, so they show in ``setup_s``."""


def read(run):
    if not run.setup_rounds:
        return None
    return run.compiles.get("rounds", 0) / run.setup_rounds
