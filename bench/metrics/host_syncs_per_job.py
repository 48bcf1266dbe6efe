"""Blocking device-to-host reads per job: the ``sort.sync`` spans of the
job's thread that start inside the traced window (one per chunk's count
read in ``core/bucketing.sorted_packed``, and the other reads of that
path), over the jobs."""

import spans


def read(run):
    return spans.per_job(run, "sync")
