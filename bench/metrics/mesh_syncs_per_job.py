"""Blocking device-to-host reads per job of the mesh path: the job thread's
``sort.sync`` spans that start inside the traced window, over the jobs (a
count read per chunk, the splitter samples and the run boundaries); none
where the window holds no mesh exchange."""

import mesh
import spans


def read(run):
    if mesh.window_spans(run, "run_exchange") is None:
        return None
    return spans.per_job(run, "sync")
