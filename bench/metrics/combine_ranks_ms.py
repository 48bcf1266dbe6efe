"""Device milliseconds per job of the k-way combine's rank tournament: the
operations of ``_kway_merge_jit`` under its named scope ``kway_ranks``
(``kernels/kway_kernel.kway_ranks`` over the runs' compare lanes), summed
over the chips."""

import spans


def read(run):
    return spans.scope_ms(run, spans.COMBINE, "kway_ranks")
