"""Device milliseconds per job of the combine layer: the runs of the k-way
merge (``pipeline.merge.merge_runs`` -> the streaming Pallas kernel's
program, or the jnp ``take`` tier where the router picks it), summed over
the chips."""

import tracereduce

PROGRAMS = ("jit__kway_merge_jit", "jit__kway_take_jit")


def read(run):
    if run.trace is None or not run.latencies_s:
        return None
    seconds = tracereduce.program_seconds(run.trace, PROGRAMS)
    return seconds / len(run.latencies_s) * 1e3 if seconds > 0 else None
