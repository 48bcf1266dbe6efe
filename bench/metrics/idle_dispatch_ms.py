"""Chip idle milliseconds per job while the job's thread is inside a
``sort.dispatch`` span, its innermost: the host enqueueing a device program
(the fused chunk program, the output and lane slices, the k-way combine)
while the chip has nothing to run; averaged over the chips."""

import spans


def read(run):
    return spans.idle_ms(run, "dispatch")
