"""Engine host layer: host time per round outside the solver and the
device round: ``begin_round`` less ``decide`` (scenario tick, offloading)
plus ``finish_round`` (costs, report) plus the new UE streams and
``init_loop`` at the start of each replayed period, or for the LM the
token-batch draw and its transfer, on the host clock."""


def read(run):
    s = run.spans
    host = s.get("stage", 0.0) - s.get("decide", 0.0) + \
        s.get("finish", 0.0) + s.get("batch", 0.0) + s.get("restart", 0.0)
    return 1e3 * host / run.rounds
