"""Seconds from the process's start to the window's: imports and backend,
the corpus pool, and the warm-up jobs with every compile or cache load."""


def read(run):
    return run.setup_s
