"""The mesh gather's share of one chip's HBM roofline, in percent: the
layer must read each merged word with its length (20 B) and write it into
the result (20 B); over the device time of the program that writes the
destinations' blocks end to end, ``_gather_blocks``."""

import mesh

PROGRAMS = ("jit__gather_blocks",)
BYTES_PER_WORD = 20 + 20


def read(run):
    return mesh.roofline(run, PROGRAMS, BYTES_PER_WORD)
