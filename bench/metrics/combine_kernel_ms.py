"""Device milliseconds per job of the k-way combine's streaming Pallas
kernel: the operations of ``_kway_merge_jit`` under its named scope
``kway_kernel`` (the kernel's launch and the slices of its output), summed
over the chips."""

import spans


def read(run):
    return spans.scope_ms(run, spans.COMBINE, "kway_kernel")
