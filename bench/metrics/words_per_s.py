"""Sorted words per second: the words of every job completed in the window
over the window's seconds, on the host's clock."""


def read(run):
    if not run.latencies_s or run.window_s <= 0:
        return None
    return run.words_per_job * len(run.latencies_s) / run.window_s
