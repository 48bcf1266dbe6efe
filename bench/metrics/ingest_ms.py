"""Device milliseconds per job of the ingest layer: the runs of the fused
per-chunk program (``core/bucketing._fused_sort_packed``: distribute ->
segmented sort -> compaction -> rank keys), summed over the chips."""

import tracereduce

PROGRAMS = ("jit__fused_sort_packed",)


def read(run):
    if run.trace is None or not run.latencies_s:
        return None
    seconds = tracereduce.program_seconds(run.trace, PROGRAMS)
    return seconds / len(run.latencies_s) * 1e3 if seconds > 0 else None
