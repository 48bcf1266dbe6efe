"""Device milliseconds per job of the ingest's compaction: the operations
of ``_fused_sort_packed`` under its named scope ``compact`` (the scatter of
every bucket's sorted words, and their lengths, into one dense run over all
padded bucket slots), summed over the chips."""

import spans


def read(run):
    return spans.scope_ms(run, spans.INGEST, "compact")
