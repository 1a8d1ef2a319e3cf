"""Solver layer: host time in ``Engine.decide`` (the SCA solver of
``solver/sca.py``) per round of the window, on the host clock."""


def read(run):
    if "decide" not in run.spans:
        return None
    return 1e3 * run.spans["decide"] / run.rounds
