"""Milliseconds per job that the job's thread spends inside the mesh
exchange, ``sort.run_exchange`` (the destination slots cut on each chip and
copied to their chips), on the host's clock, for the spans that start
inside the traced window."""

import mesh


def read(run):
    return mesh.span_ms(run, "run_exchange")
