"""Engine host layer: self time of the program's ``cefl/offload`` span per
round of the window, in ms: ``realize_offloading`` splitting each UE's rows
over base stations and data centres (numpy permutations, each UE's rows
read back and every DPU's rows transferred anew), less the read-backs'
own ``cefl/sync`` spans."""
import program_spans


def read(run):
    s = program_spans.of(run).self_s("offload")
    return None if s is None else 1e3 * s / run.rounds
