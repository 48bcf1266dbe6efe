"""Host spans of the sort pipeline, written into the JAX profiler's trace.

A span is a ``jax.profiler.TraceAnnotation`` named ``sort.<name>``: it
lands in the profiler's own trace, on the same clock as the device planes,
so a reader of one ``.xplane.pb`` sees what the host was doing while the
chip ran or idled. Spans nest by time on one thread; a job's spans all lie
inside the ``sort.job`` that holds them. Attributes are the span's event
stats. There are no counters and no buffers: a count is the number of a
span's events, or an attribute. With no profiler running a span costs one
object construction.

Operators trace a job with ``jax.profiler.trace(dir)`` and open the trace
in Perfetto or TensorBoard; the README lists every span with its
attributes, and the device-side ``jax.named_scope`` names of the fused
ingest and k-way combine programs.
"""

from __future__ import annotations

import jax

__all__ = ["SPANS", "span"]

# every span the program emits, without the ``sort.`` prefix; the names of
# the supervisor's stages where one exists
SPANS = (
    "job",                 # one call of a chunked entry point, the mesh
                           # one included: rows, chunks
    "stage",               # one chunk's host->device copy, on the prefetch
                           # worker thread: chunk, bytes
    "ingest_chunk",        # one chunk's fused sort: chunk, rows, capacity
    "dispatch",            # a host enqueue of a device program: program
    "sync",                # a blocking device->host read: what
    "streaming_combine",   # the one-pass k-way combine: runs, rows
    "merge_round",         # one round of the pairwise tournament: runs, rows
    "run_exchange",        # the mesh exchange's slices and copies: slices,
                           # bytes, rows (real), slots (padded rows handed
                           # to the combines)
    "gather",              # the mesh result written onto one device: rows
)


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """A context manager that records the span ``sort.<name>`` with
    ``attrs`` while the profiler runs. ``name`` must be one of
    :data:`SPANS`."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; known: {SPANS}")
    return jax.profiler.TraceAnnotation(f"sort.{name}", **attrs)
