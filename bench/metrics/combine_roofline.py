"""The combine's share of its HBM roofline, in percent: the least time the
layer's bytes take at the chip's peak bandwidth over its device time per
job. The layer must read each sorted word with its length (20 B) and write
it merged (20 B); rank keys and padding are the implementation's own."""

import tracereduce

PROGRAMS = ("jit__kway_merge_jit", "jit__kway_take_jit")
BYTES_PER_WORD = 20 + 20


def read(run):
    if run.trace is None or not run.latencies_s:
        return None
    seconds = tracereduce.program_seconds(run.trace, PROGRAMS)
    return tracereduce.roofline_share(
        BYTES_PER_WORD * run.words_per_job, seconds / len(run.latencies_s),
        run.peaks["hbm_bytes_per_s"])
