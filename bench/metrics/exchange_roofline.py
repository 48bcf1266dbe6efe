"""The mesh exchange's device programs' share of one chip's HBM roofline,
in percent: the layer must read each sorted word with its length (20 B) and
write it to its destination (20 B), whatever implements it; over the
device time, summed over the chips, of the programs that sample, bound and
cut the runs into their slots. Compare lanes and slot padding are the
implementation's own; the copies between chips are not device programs."""

import mesh

PROGRAMS = ("jit__run_samples", "jit__run_bounds", "jit__split_slots")
BYTES_PER_WORD = 20 + 20


def read(run):
    return mesh.roofline(run, PROGRAMS, BYTES_PER_WORD)
