"""The 90th percentile of the latencies of all jobs in the window, in
milliseconds: each from the call until the sorted lengths and keys are on
the host."""

import numpy as np


def read(run):
    if not run.latencies_s:
        return None
    return float(np.percentile(run.latencies_s, 90)) * 1e3
