"""Reductions for the mesh cell's readers, over what ``spans`` keeps on the
trace: the job thread's spans of one name inside the window, their time
per job, and a device program's share of one chip's HBM roofline. Each is
``None`` where the trace has no device or lacks what it reads, as for a
program from before the spans it reads existed."""

from __future__ import annotations

import spans
import tracereduce


def window_spans(run, name: str):
    """The job thread's ``sort.<name>`` spans that start inside the traced
    window; ``None`` where there are none."""
    job = spans._job_spans(run)
    if job is None:
        return None
    lo, hi = run.trace.window
    return [s for s in job if s.name == spans.SPAN_PREFIX + name
            and lo <= s.start_ns < hi] or None


def span_ms(run, name: str):
    """Milliseconds per job inside the job thread's ``sort.<name>`` spans
    that start in the window, on the host's clock."""
    found = window_spans(run, name)
    if found is None:
        return None
    return sum(s.dur_ns for s in found) / 1e6 / len(run.latencies_s)


def roofline(run, programs, bytes_per_word: int):
    """Percent of one chip's HBM peak: the least time ``bytes_per_word``
    for every word of a job takes at the peak, over the device time per job
    of ``programs``, summed over the chips."""
    if run.trace is None or not run.latencies_s:
        return None
    seconds = tracereduce.program_seconds(run.trace, programs)
    return tracereduce.roofline_share(
        bytes_per_word * run.words_per_job, seconds / len(run.latencies_s),
        run.peaks["hbm_bytes_per_s"])
