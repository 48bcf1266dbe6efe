"""The share of the traced window in which no operation ran on a chip:
1 - (union of the ``XLA Ops`` intervals / window), averaged over the
cell's chips."""

import tracereduce


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    lo, hi = run.trace.window
    if hi <= lo:
        return None
    idle = [1 - tracereduce.busy_ns(c, lo, hi) / (hi - lo)
            for c in run.trace.chips]
    return sum(idle) / len(idle)
