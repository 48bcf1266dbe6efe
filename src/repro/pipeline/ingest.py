"""Chunked ingest: stream datasets larger than one device launch through the
fused bucketize + segmented-sort program.

The shape is the MPI follow-up's (*Parallelize Bubble and Merge Sort
Algorithms Using MPI*): produce locally sorted runs, combine them by merge.
Here a "processor" is one device launch — each fixed-size chunk of packed
words runs ``core.bucketing.sorted_packed`` (on-device distribute ->
segmented in-bucket sort -> shortlex compaction) to yield a
:class:`SortedRun`, and runs combine with the packed rank-key merge path of
``pipeline.merge`` / ``kernels.ops.merge_sorted_lex``. The *per-launch*
working set is bounded by the chunk size — the fused program's bucket
tensor is ``O(num_buckets * chunk_capacity)`` regardless of total input
length, and every chunk reuses the same compiled executable (chunks share
one static shape; only the tail chunk re-traces). The run merge is bounded
the same way per compare: each tournament round ranks by binary search over
the packed shortlex keys (O(n log n) gathers — the fused program emits the
keys during compaction, see ``SortedRun.cmp_lanes``), never by the
O(|a|·|b|·L) broadcast the jnp-level combine used to pay.

Runs carry an explicit length lane so the merge key is the shortlex tuple
``(length, lane_0, ..., lane_L-1)`` — packed keys alone order
byte-lexicographically ("aa" < "z"), not shortlex ("z" < "aa").

Both front-ends overlap their host work with the device through the same
single-worker double buffer (:func:`_prefetch_map`): the words path packs
chunk ``i+1`` on the worker thread while chunk ``i``'s fused launch is in
flight, and the packed path stages chunk ``i+1``'s host->device transfer
the same way (:func:`_stage_chunk`) — so neither packing nor H2D copies
sit on the critical path between launches.
"""

from __future__ import annotations

import logging
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import packing
from ..core.bucketing import sorted_packed
from ..kernels.keypack import cmp_from_packed, packed_cmp_lanes, shortlex_max_values
from ..runtime.trace import span
from .manifest import RunManifest
from .merge import merge_runs
from .validate import check_chunked, keys_digest

__all__ = ["DEFAULT_CHUNK", "SortedRun", "sorted_run",
           "chunked_sort_packed", "chunked_sort_words"]

log = logging.getLogger("repro.pipeline")

_VALIDATE_MODES = ("off", "cheap", "full")

# Chunk size balancing launch count against the fused program's bucket
# tensor footprint (num_buckets * capacity * lanes uint32 slots; capacity
# <= chunk). Any multiple of the 128-lane tile works.
DEFAULT_CHUNK = 4096


@dataclass
class SortedRun:
    """One shortlex-sorted run: ``lengths[i]`` is the byte length of the
    word packed in ``keys[i]``; rows ascend by ``(length, bytes)``.
    ``packed`` optionally holds the 1-2 uint32 rank-key lanes of the
    shortlex tuples (``kernels/keypack.py``), emitted for free by the fused
    per-chunk program."""

    lengths: jnp.ndarray   # (m,) int32
    keys: jnp.ndarray      # (m, lanes) uint32
    packed: Optional[Tuple] = None

    def lanes(self):
        """The run as a merge-ready lex tuple (length lane first)."""
        return (self.lengths,
                *(self.keys[:, l] for l in range(self.keys.shape[1])))

    def cmp_lanes(self):
        """The minimal compare-lane list for ranking this run in a merge:
        the precomputed rank keys + keypack's tie-break suffix, or a fresh
        packing when the run was built without one."""
        lanes = list(self.lanes())
        mv = shortlex_max_values(self.keys.shape[1])
        if self.packed is None:
            return packed_cmp_lanes(lanes, mv)
        return cmp_from_packed(list(self.packed), lanes, mv)

    @classmethod
    def from_lanes(cls, lanes):
        return cls(lengths=lanes[0], keys=jnp.stack(lanes[1:], axis=1))


def sorted_run(keys, algorithm: str = "pallas",
               capacity: int | None = None,
               on_overflow: str = "raise") -> SortedRun:
    """Sort one packed (n, lanes) chunk on device into a :class:`SortedRun`
    (the per-chunk fused bucketize + segmented-sort launch, rank keys
    included). ``on_overflow`` forwards to ``core.bucketing.sorted_packed``
    ('raise' | 'retry' | 'clip')."""
    lengths, sorted_keys, packed = sorted_packed(
        keys, algorithm=algorithm, capacity=capacity, return_packed=True,
        on_overflow=on_overflow)
    return SortedRun(lengths=lengths, keys=sorted_keys, packed=packed)


def _run_from_arrays(lengths, keys, packed) -> SortedRun:
    return SortedRun(
        lengths=jnp.asarray(lengths), keys=jnp.asarray(keys),
        packed=tuple(jnp.asarray(p) for p in packed) if packed else None)


def _ingest_chunk(chunk, chunk_id: int, *, algorithm: str, capacity,
                  on_overflow: str, store, supervisor, need_manifest: bool):
    """Produce one (run, manifest) for a chunk — by resuming it from the
    store when an intact matching run is already persisted, else by
    launching the fused per-chunk sort (through the supervisor's
    ``ingest_chunk`` stage when one is given) and persisting it."""
    if store is not None:
        from ..checkpoint.manager import CorruptSnapshotError
        try:
            man = store.manifest(chunk_id)
        except CorruptSnapshotError as e:
            log.warning("run store: chunk %d manifest unreadable (%s) — "
                        "re-ingesting", chunk_id, e)
            man = None
        if man is not None:
            # A stored run matches iff it holds the same multiset as the
            # incoming chunk — the digest is order-independent, so the
            # *input* chunk digests straight against the *sorted* run's
            # manifest. A mismatch means the store is stale (same path,
            # different dataset): recompute instead of merging foreign data.
            if (man.count == int(chunk.shape[0])
                    and man.digest == keys_digest(chunk)):
                try:
                    loaded = _run_from_arrays(*store.load(chunk_id))
                except CorruptSnapshotError as e:
                    # torn/truncated artifact (kill mid-write never produces
                    # this — the rename is atomic — but disk damage can):
                    # the chunk is still in hand, so recompute, don't fail
                    log.warning("run store: chunk %d unreadable (%s) — "
                                "re-ingesting", chunk_id, e)
                else:
                    if int(loaded.lengths.shape[0]) == man.count:
                        return loaded, man
                    log.warning(
                        "run store: chunk %d loaded %d row(s) but manifest "
                        "records %d — re-ingesting", chunk_id,
                        int(loaded.lengths.shape[0]), man.count)
            else:
                log.warning(
                    "run store: chunk %d manifest does not match incoming "
                    "data (stale store?) — re-ingesting", chunk_id)

    def launch():
        return sorted_run(chunk, algorithm=algorithm, capacity=capacity,
                          on_overflow=on_overflow)

    with span("ingest_chunk", chunk=chunk_id, rows=int(chunk.shape[0]),
              capacity=capacity):
        if supervisor is not None:
            run = supervisor.run_stage("ingest_chunk", launch)
        else:
            run = launch()
    man = (RunManifest.from_run(run, chunk_id)
           if (store is not None or need_manifest) else None)
    if store is not None:
        store.put(man, run)
    return run, man


def _merged_run(runs, manifests=None, supervisor=None,
                merge_engine: str = "auto") -> SortedRun:
    if len(runs) == 1:
        return runs[0]
    with span("dispatch", program="run_lanes"):
        lanes = [r.lanes() for r in runs]
        cmp_runs = [r.cmp_lanes() for r in runs]
    merged = merge_runs(lanes, engine=merge_engine, cmp_runs=cmp_runs,
                        manifests=manifests, supervisor=supervisor)
    with span("dispatch", program="stack_lanes"):
        return SortedRun.from_lanes(merged)


def _stage_chunk(chunk):
    """Stage one pre-packed chunk onto the device. Runs on the prefetch
    worker thread, so chunk ``i+1``'s host->device transfer overlaps chunk
    ``i``'s fused launch — the device half of the ingest double buffer (the
    words front-end overlaps host packing through the same worker)."""
    return jax.device_put(jnp.asarray(chunk, jnp.uint32))


def chunked_sort_packed(keys, chunk_size: int = DEFAULT_CHUNK,
                        algorithm: str = "pallas",
                        capacity: int | None = None,
                        store=None, supervisor=None,
                        validate: str = "off",
                        on_overflow: str = "raise",
                        merge_engine: str = "auto") -> SortedRun:
    """Shortlex-sort a packed (n, lanes) uint32 tensor of any length by
    streaming ``chunk_size`` rows per launch and merging the sorted runs.

    ``capacity`` (per-bucket slots of the fused program) defaults to
    ``chunk_size`` for full chunks — the worst case (every word one length),
    so all full chunks share one compiled executable with no histogram sync;
    pass a smaller value to shrink the bucket tensor when the length
    distribution is known. Returns the full-input :class:`SortedRun`.

    Robustness knobs:

    * ``store`` — a :class:`~repro.pipeline.manifest.RunStore`. Every
      completed run persists atomically before the next chunk launches, and
      chunks whose intact runs are already stored are *loaded, not re-sorted*
      — a killed job resumes from its completed runs.
    * ``supervisor`` — a ``runtime.SortSupervisor``; chunk launches run as
      its ``ingest_chunk`` stage and merge rounds as ``merge_round``, with
      bounded retry on transient :class:`~repro.runtime.sortfault.
      StageFailure`.
    * ``validate`` — ``'off' | 'cheap' | 'full'`` invariant gate
      (``pipeline.validate.check_chunked``): per-run manifest reconciliation
      + merge count/histogram/sortedness conservation; ``'full'`` adds
      order-independent content digests.
    * ``on_overflow`` — bucket-capacity overflow policy for the per-chunk
      fused program ('raise' | 'retry' | 'clip').
    * ``merge_engine`` — run-combine strategy, forwarded to
      ``pipeline.merge.merge_runs``: 'auto'/'kway' (one streaming k-way
      pass), 'kway_kernel' (force the Pallas tier), or 'tournament' (the
      legacy pairwise tree).

    Host (NumPy) input stays host-side until its chunk stages: each chunk's
    H2D transfer runs on the prefetch worker while the previous chunk's
    launch is in flight (:func:`_stage_chunk`).
    """
    if validate not in _VALIDATE_MODES:
        raise ValueError(f"validate must be one of {_VALIDATE_MODES}")
    if not isinstance(keys, jax.Array):
        keys = np.asarray(keys, dtype=np.uint32)
    n = keys.shape[0]
    if chunk_size < 1:
        raise ValueError("chunk_size must be >= 1")
    if n == 0:
        return SortedRun(lengths=jnp.zeros((0,), jnp.int32),
                         keys=jnp.asarray(keys, jnp.uint32))
    track = store is not None or validate != "off"
    runs, manifests = [], []
    host_chunks = [keys[start: start + chunk_size]
                   for start in range(0, n, chunk_size)]

    def stage(item):
        ci, chunk = item
        with span("stage", chunk=ci, bytes=int(chunk.nbytes)):
            return _stage_chunk(chunk)

    with span("job", rows=n, chunks=len(host_chunks)):
        for ci, chunk in enumerate(_prefetch_map(stage,
                                                 enumerate(host_chunks))):
            cap = capacity if capacity is not None else int(chunk.shape[0])
            run, man = _ingest_chunk(
                chunk, ci, algorithm=algorithm, capacity=cap,
                on_overflow=on_overflow, store=store, supervisor=supervisor,
                need_manifest=validate != "off")
            runs.append(run)
            manifests.append(man)
        merged = _merged_run(runs, manifests=manifests if track else None,
                             supervisor=supervisor, merge_engine=merge_engine)
        if validate != "off":
            check_chunked(runs, manifests, merged, mode=validate)
    return merged


def _prefetch_map(fn, items):
    """Yield ``fn(item)`` in order, computing the *next* call on a worker
    thread while the consumer processes the current result — the
    double-buffering that keeps host packing off the critical path between
    device launches."""
    items = list(items)
    if not items:
        return
    with ThreadPoolExecutor(max_workers=1) as ex:
        fut = ex.submit(fn, items[0])
        for nxt in items[1:]:
            cur = fut.result()
            fut = ex.submit(fn, nxt)
            yield cur
        yield fut.result()


def chunked_sort_words(words, chunk_size: int = DEFAULT_CHUNK,
                       algorithm: str = "pallas",
                       capacity: int | None = None,
                       store=None, supervisor=None,
                       validate: str = "off",
                       on_overflow: str = "raise",
                       merge_engine: str = "auto") -> list:
    """Words front-end: chunked device sort + packed-rank-key run merge,
    unpack once (egress). Returns the words in shortlex order —
    bit-identical to ``core.bucketed_sort_words`` but with per-launch device
    memory bounded by ``chunk_size``, and with each chunk packed (at the
    global width, so all runs share one lane count) on a worker thread while
    the previous chunk's fused launch is in flight.

    ``store`` / ``supervisor`` / ``validate`` / ``on_overflow`` /
    ``merge_engine`` behave as on :func:`chunked_sort_packed` —
    persisted-run resume, supervised stage retry, the invariant-validation
    gate, the bucket-overflow policy, and the run-combine strategy."""
    if validate not in _VALIDATE_MODES:
        raise ValueError(f"validate must be one of {_VALIDATE_MODES}")
    words = list(words)
    if not words:
        return []
    if chunk_size < 1:
        raise ValueError("chunk_size must be >= 1")
    width = max(packing.byte_length(w) for w in words)
    chunks = [words[i: i + chunk_size]
              for i in range(0, len(words), chunk_size)]
    track = store is not None or validate != "off"
    runs, manifests = [], []
    with span("job", rows=len(words), chunks=len(chunks)):
        for ci, keys in enumerate(_prefetch_map(
                lambda ws: jnp.asarray(packing.pack_words(ws, width=width)),
                chunks)):
            cap = capacity if capacity is not None else int(keys.shape[0])
            run, man = _ingest_chunk(
                keys, ci, algorithm=algorithm, capacity=cap,
                on_overflow=on_overflow, store=store, supervisor=supervisor,
                need_manifest=validate != "off")
            runs.append(run)
            manifests.append(man)
        run = _merged_run(runs, manifests=manifests if track else None,
                          supervisor=supervisor, merge_engine=merge_engine)
        if validate != "off":
            check_chunked(runs, manifests, run, mode=validate)
        with span("sync", what="unpack_keys"):
            host_keys = np.asarray(run.keys)
    return packing.unpack_words(host_keys)
