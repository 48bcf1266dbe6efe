"""Length-bucketed segmented sort — the paper's core decomposition.

"The main idea of the proposed algorithm is distributing the elements of the
input datasets into many additional temporary sub-arrays according to a
number of characters in each word" — buckets are independent, so they sort
in parallel. On CPU the paper assigns one bucket per OpenMP thread; on TPU we
pad buckets to a common capacity and either ``vmap`` the traced comparator
sort across the bucket axis (the 'oets'/'bitonic' algorithms) or — the
production path — hand the whole (num_buckets, capacity, lanes) tensor to
``kernels.ops.segmented_sort`` ('pallas'), one batched lexicographic kernel
launch over all buckets at any lane count and capacity. Both are SPMD
renderings of the same decomposition.

The concatenation of sorted buckets in increasing length order yields
*shortlex* order (length-major, then alphabetic) — exactly the order the
paper's phases 2+3 produce.

The distribute step itself (phases 1-2) also runs on device:
``bucketize_packed``/``sorted_packed`` route through
``kernels.ops.distribute``/``bucketize`` — the Pallas length-histogram +
stable-rank pass plus one scatter — so ``bucketed_sort_words`` has **zero
host-side per-word Python loops between packing and unpacking**:
bytes pack in (host ingress), one distribute launch + one jitted
scatter→segmented-sort→compaction program, bytes unpack out (host egress).
``bucketize_words`` below is kept as the host reference implementation the
differential tests compare against. Device buckets are *dense per-length*
(bucket id = byte length, empty lengths hold count 0), whereas the host
reference only materializes lengths that occur; the sorted concatenations
agree exactly.

Chunked ingest of inputs larger than one launch lives one layer up in
``repro.pipeline`` (per-chunk ``sorted_packed`` runs + k-way lex merge).
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.trace import span
from . import packing
from .bitonic import bitonic_sort
from .oets import oets_sort

__all__ = ["Buckets", "bucketize_words", "bucketize_packed", "sort_buckets",
           "sorted_packed", "bucketed_sort_words"]

log = logging.getLogger("repro.core")


@dataclass
class Buckets:
    """Dense bucket storage: the paper's 3-D array (bucket, slot, packed lanes)."""

    keys: np.ndarray        # (num_buckets, capacity, lanes) uint32; sentinel padded
    counts: np.ndarray      # (num_buckets,) int32 — real elements per bucket
    lengths: np.ndarray     # (num_buckets,) int32 — word length of each bucket
    dropped: int = 0        # elements clipped under on_overflow='clip'


def bucketize_packed(keys, capacity: int | None = None,
                     on_overflow: str = "raise") -> Buckets:
    """Device counterpart of :func:`bucketize_words`: distribute an already
    packed (n, lanes) uint32 word tensor into the dense per-length bucket
    tensor via ``kernels.ops.bucketize`` (Pallas histogram/rank pass + one
    scatter) — no host per-word loop. Bucket ``l`` holds the words of byte
    length ``l`` in arrival order; ``lengths`` is ``arange(4*lanes+1)``.

    ``on_overflow`` — the degrade policy when an explicit ``capacity`` is
    exceeded (``kernels.ops.bucketize`` semantics): ``'raise'`` (default —
    the host reference's contract; raises ``repro.runtime.CapacityOverflow``,
    a ``ValueError``), ``'retry'`` (one exact-count re-scatter at the true
    max, lossless), or ``'clip'`` (keep the static tensor, report the loss
    in ``Buckets.dropped`` and a warning log)."""
    from ..kernels.ops import bucketize  # lazy: core imports kernels
    keys = jnp.asarray(keys, jnp.uint32)
    if keys.ndim != 2:
        raise ValueError("keys must be (n, lanes) packed words")
    bucket_keys, counts, dropped = bucketize(keys, capacity=capacity,
                                             on_overflow=on_overflow)
    return Buckets(keys=bucket_keys, counts=counts,
                   lengths=jnp.arange(bucket_keys.shape[0], dtype=jnp.int32),
                   dropped=dropped)


def bucketize_words(words, capacity: int | None = None) -> Buckets:
    """Phase 2 of the paper's pre-processing: distribute words into
    per-length sub-arrays sized by the length histogram.

    Host reference implementation (the original Python dict loop) — the
    production path is :func:`bucketize_packed` / ``kernels.ops.bucketize``
    on device; the differential tests compare the two. Length is the
    *encoded byte* length (the unit the packed lanes sort by — multi-byte
    UTF-8 words bucket by their byte width), matching the device kernel and
    the tests' byte-shortlex oracle."""
    by_len: dict[int, list] = {}
    for w in words:
        by_len.setdefault(packing.byte_length(w), []).append(w)
    if not by_len:
        return Buckets(
            keys=np.zeros((0, 0, 1), np.uint32),
            counts=np.zeros((0,), np.int32),
            lengths=np.zeros((0,), np.int32),
        )
    lengths = sorted(by_len)
    cap = capacity or max(len(v) for v in by_len.values())
    lanes = packing.lanes_for_width(max(lengths))
    keys = np.full((len(lengths), cap, lanes), packing.SENTINEL_U32, dtype=np.uint32)
    counts = np.zeros((len(lengths),), np.int32)
    for i, ln in enumerate(lengths):
        bucket = by_len[ln]
        if len(bucket) > cap:
            raise ValueError(f"bucket for length {ln} exceeds capacity {cap}")
        keys[i, : len(bucket)] = packing.pack_words(bucket, width=lanes * 4)
        counts[i] = len(bucket)
    return Buckets(keys=keys, counts=counts, lengths=np.asarray(lengths, np.int32))


def sort_buckets(keys: jax.Array, algorithm: str = "oets",
                 counts: jax.Array | None = None) -> jax.Array:
    """Sort every bucket independently (vmap over the bucket axis).

    ``keys``: (num_buckets, capacity, lanes) uint32, sentinel padded.
    ``algorithm``: 'oets' (paper-faithful parallel bubble sort), 'bitonic'
    (beyond-paper network), 'pallas' (the fused ``kernels.ops.segmented_sort``
    pipeline — one batched lex kernel launch over all buckets, any lane
    count and any capacity including the multi-block blocksort tier), or
    'xla' (production baseline). ``counts`` (optional, (num_buckets,)) lets
    the 'pallas' path re-mask slots beyond each bucket's count to the
    sentinel; ``None`` trusts the tensor's existing sentinel padding.
    """
    if algorithm == "oets":
        return jax.vmap(oets_sort)(keys)
    if algorithm == "bitonic":
        return jax.vmap(bitonic_sort)(keys)
    if algorithm == "pallas":
        from ..kernels.ops import segmented_sort
        return segmented_sort(keys, counts)
    if algorithm == "xla":
        # lexicographic sort of multi-lane keys via XLA's variadic sort
        def one(bucket):
            lanes = [bucket[:, l] for l in range(bucket.shape[1])]
            sorted_lanes = jax.lax.sort(lanes, num_keys=len(lanes))
            return jnp.stack(sorted_lanes, axis=1)

        return jax.vmap(one)(keys)
    raise ValueError(f"unknown algorithm {algorithm!r}")


@functools.partial(jax.jit, static_argnames=("capacity", "algorithm"))
def _fused_sort_packed(keys, *, capacity: int, algorithm: str):
    """One jitted program: distribute scatter -> segmented bucket sort ->
    shortlex compaction -> packed rank keys. ``keys`` (n, lanes) uint32 in;
    out come ``(lengths (n,), sorted (n, lanes), counts (B,), packed)``:
    the kept words, ``min(counts, cap).sum()`` of them (all n unless a
    bucket overflows ``cap``), fill the leading rows in exact shortlex
    order, and the rows past them hold length 0 and the sentinel (the
    caller slices). ``packed`` is the tuple of 1-2 uint32 (n,) rank-key
    lanes of the compacted shortlex tuples
    (``kernels.keypack.pack_shortlex`` — a few bit ops fused into the same
    program), which the run-merge tier ranks on instead of re-packing."""
    from ..kernels.keypack import pack_shortlex
    from ..kernels.ops import _scatter_to_buckets, distribute
    n, lanes = keys.shape
    num_buckets = 4 * lanes + 1
    # one named scope per stage: the device trace's ops carry these names
    with jax.named_scope("distribute"):
        dest, rank, counts = distribute(keys)
    with jax.named_scope("bucket_scatter"):
        buckets = _scatter_to_buckets(keys, dest, rank,
                                      num_buckets=num_buckets,
                                      capacity=capacity)
        counts_c = jnp.minimum(counts, capacity)
    with jax.named_scope("bucket_sort"):
        sorted_keys = sort_buckets(buckets, algorithm, counts=counts_c)
    # compaction: bucket b's i-th real word lands at offset[b] + i — the
    # concatenation-in-length-order of the paper's phase 4, as one
    # contiguous copy a bucket. In increasing length order each whole
    # sorted block lands at its offset, and the next bucket's head
    # overwrites its sentinel tail; ``capacity`` rows of headroom keep
    # every start in range, so no copy is clamped
    with jax.named_scope("compact"):
        ends = jnp.cumsum(counts_c)
        offsets = ends - counts_c
        flat_keys = jnp.full((n + capacity, lanes), packing.SENTINEL_U32,
                             jnp.uint32)
        for b in range(num_buckets):
            flat_keys = jax.lax.dynamic_update_slice(
                flat_keys, sorted_keys[b], (offsets[b], 0))
        flat_keys = flat_keys[:n]
        # a row's length is its bucket: the number of buckets ending at or
        # before it; 0 past the kept rows
        row = jnp.arange(n, dtype=jnp.int32)
        flat_lens = jnp.sum(ends[None, :] <= row[:, None], axis=1,
                            dtype=jnp.int32)
        flat_lens = jnp.where(row < ends[-1], flat_lens, 0)
    with jax.named_scope("rank_keys"):
        packed = pack_shortlex(flat_lens, flat_keys)
    return flat_lens, flat_keys, counts, tuple(packed.lanes)


def sorted_packed(keys, algorithm: str = "pallas",
                  capacity: int | None = None, return_packed: bool = False,
                  on_overflow: str = "raise"):
    """Shortlex-sort a packed (n, lanes) uint32 word tensor entirely on
    device: distribute -> segmented in-bucket sort -> compact, zero host
    per-word loops. Returns ``(lengths (n,), sorted_keys (n, lanes))``
    device arrays in exact shortlex order (length-major, then byte-wise);
    with ``return_packed`` a third element carries the tuple of packed
    shortlex rank-key lanes (``kernels/keypack.py``) the fused program
    computed during compaction — the merge-ready key the ``repro.pipeline``
    run tier ranks on.

    ``capacity``: per-bucket slots for the fused program (static under jit);
    ``None`` sizes it at the histogram max (one extra distribute launch +
    one scalar sync). ``on_overflow`` — policy for a too-small explicit
    capacity: ``'raise'`` (default; ``repro.runtime.CapacityOverflow``, a
    ``ValueError``), ``'retry'`` (re-run the fused program at the true
    histogram max — lossless, one extra launch), or ``'clip'`` (drop the
    overflow: the outputs shrink to the surviving element count, with a
    warning log). The fused program's outputs have n rows, the kept words
    first and rows of length 0 and the sentinel past them; the host slices
    them to the kept count. The per-chunk producer of the
    ``repro.pipeline`` sorted-run tier."""
    from ..runtime.failure import CapacityOverflow
    if on_overflow not in ("raise", "retry", "clip"):
        raise ValueError(f"unknown on_overflow policy {on_overflow!r}")
    keys = jnp.asarray(keys, jnp.uint32)
    n = keys.shape[0]
    if n == 0:
        lens = jnp.zeros((0,), jnp.int32)
        if not return_packed:
            return lens, keys
        from ..kernels.keypack import pack_shortlex
        return lens, keys, tuple(pack_shortlex(lens, keys).lanes)
    if capacity is None:
        from ..kernels.ops import distribute
        with span("dispatch", program="distribute"):
            _, _, counts = distribute(keys)
        with span("sync", what="capacity"):
            capacity = max(1, int(jnp.max(counts)))
    with span("dispatch", program="_fused_sort_packed"):
        flat_lens, flat_keys, counts, packed = _fused_sort_packed(
            keys, capacity=capacity, algorithm=algorithm)
    with span("sync", what="max_count"):
        true_max = int(jnp.max(counts))
    if true_max > capacity:
        with span("sync", what="overflow"):
            ln = int(jnp.argmax(counts))
            dropped = int(jnp.sum(jnp.maximum(counts - capacity, 0)))
        if on_overflow == "raise":
            raise CapacityOverflow(
                f"bucket for length {ln} exceeds capacity {capacity}",
                capacity, required=true_max, dropped=dropped)
        if on_overflow == "retry":
            log.warning("sorted_packed overflow: capacity %d -> %d "
                        "(lossless retry of the fused program)",
                        capacity, true_max)
            with span("dispatch", program="_fused_sort_packed"):
                flat_lens, flat_keys, counts, packed = _fused_sort_packed(
                    keys, capacity=true_max, algorithm=algorithm)
        else:
            log.warning("sorted_packed overflow: dropping %d element(s) "
                        "past capacity %d (bucket for length %d needs %d)",
                        dropped, capacity, ln, true_max)
            n = n - dropped
    with span("dispatch", program="slice_outputs"):
        if not return_packed:
            return flat_lens[:n], flat_keys[:n]
        return flat_lens[:n], flat_keys[:n], tuple(p[:n] for p in packed)


def bucketed_sort_words(words, algorithm: str = "oets") -> list:
    """End-to-end paper pipeline: pack -> on-device distribute -> parallel
    in-bucket sort -> on-device shortlex compaction -> unpack. Returns words
    in shortlex order. Between ``pack_words`` (ingress) and ``unpack_words``
    (egress) every per-word step runs on device — the host reference
    ``bucketize_words`` is never called (pinned by a mock-patch test)."""
    words = list(words)
    if not words:
        return []
    keys = jnp.asarray(packing.pack_words(words))
    _, sorted_keys = sorted_packed(keys, algorithm=algorithm)
    return packing.unpack_words(np.asarray(sorted_keys))
