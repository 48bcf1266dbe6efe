"""Chip idle milliseconds per job while the job's thread is inside a
``sort.sync`` span, its innermost: a blocking device-to-host read (each
chunk's count read) with the chip waiting on the round trip; averaged over
the chips."""

import spans


def read(run):
    return spans.idle_ms(run, "sync")
