"""Ingest's share of its HBM roofline, in percent: the least time the
layer's bytes take at the chip's peak bandwidth over its device time per
job. The layer must read each packed word (16 B) and write it sorted with
its length (16 B + 4 B), whatever implements it; the padded bucket tensor
and the rank-key lanes are the implementation's own and do not count."""

import tracereduce

PROGRAMS = ("jit__fused_sort_packed",)
BYTES_PER_WORD = 16 + 20


def read(run):
    if run.trace is None or not run.latencies_s:
        return None
    seconds = tracereduce.program_seconds(run.trace, PROGRAMS)
    return tracereduce.roofline_share(
        BYTES_PER_WORD * run.words_per_job, seconds / len(run.latencies_s),
        run.peaks["hbm_bytes_per_s"])
