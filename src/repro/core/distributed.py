"""Mesh-scale distributed sort engines: the paper's distribute step across
devices, as a multi-engine subsystem.

OpenMP's ``parallel for`` over buckets has no analogue across TPU pods — there
is no shared memory. But the paper's decomposition generalizes two ways, and
this module ships both behind one front-end (``distributed_sort`` /
``distributed_sort_lex``), mirroring ``kernels.ops.sort``'s engine tiers:

  * ``'odd_even'`` — treat each device's shard as one "element"; neighbouring
    devices compare-exchange (merge their sorted blocks and split low/high
    halves) over the ICI ring via ``lax.ppermute``. P alternating odd/even
    rounds sort P blocks — odd-even transposition at block granularity,
    i.e. *bubble sort across the mesh*. O(P) rounds, O(P·B) bytes/device.
  * ``'sample'`` — splitter-based one-shot (sample sort, the MPI follow-up's
    design, arXiv:1411.5283): sample splitters globally (one ``all_gather``),
    partition every block by splitter bucket — exactly the paper's
    distribute-into-sub-arrays step keyed by value range instead of word
    length — exchange with ONE ``all_to_all``, sort locally. O(1) rounds,
    O(B) bytes/device, independent of P.

``choose_engine(P, B)`` is the cost model: odd_even only wins at P <= 2
(where its <= 2 merge rounds undercut the splitter machinery); sample wins
beyond because its round count does not grow with the mesh.

Both engines are variadic over lexicographic tuples (``kernels/lex.py``
conventions: lane 0 most significant, trailing lanes are payload/tie-break,
all lanes travel through one permutation), so key-only and kv sorting are
the 1-/2-tuple special cases. Device-local sorting routes through
``kernels.ops.sort_lex`` (the Pallas front-end) on TPU and XLA's variadic
sort on other backends (``local_sort='auto'``).

Exact-count exchange protocol (no silent data loss): alongside the data
``all_to_all``, the sample engine ``all_gather``s the *true* per-destination
count vectors (one tiny (P, P) matrix, replicated everywhere), so receivers
know exactly how many real elements arrived from each source — validity is
never inferred from sentinel comparisons (real
``iinfo.max`` ints and sentinel-bit floats count correctly), capacity overflow
is reported in an explicit flag instead of silently dropping, and the
host-facing wrappers always size capacity at the per-source worst case B so
nothing can overflow. Non-divisible inputs are sentinel-padded to the next
multiple of P and sliced back — no caller-visible shape constraint.

Merge strategies for the odd_even engine (the hillclimb axis recorded in
EXPERIMENTS.md §Perf), all full-tuple lex now:
  * 'resort'  — re-sort the 2B concatenation (paper-faithful baseline:
                dumb local work, like re-running bubble sort)
  * 'bitonic' — O(log B) bitonic merge of the two sorted blocks
  * 'take'    — merge-path selection via packed rank-key binary search
                (``kernels/keypack.py``: O(B log B) gathers + one scatter —
                the shared run-merge primitive of the pipeline tier)

Communication note: each odd_even round sends the full block both ways so
the merge is computed redundantly on both partners — this trades 2x ICI
bytes for zero additional latency-bound round trips, the right trade at
50 GB/s links when blocks fit VMEM.
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels.keypack import (lex_searchsorted, merge_take_packed,
                               packed_searchsorted)
from ..kernels.lex import LANE_TILE
from ..kernels.ops import _sentinel
from ..parallel.compat import axis_size
from ..runtime.trace import span
from .bitonic import bitonic_merge, bitonic_merge_lex

__all__ = [
    "choose_engine", "local_merge",
    "odd_even_block_sort", "odd_even_block_sort_lex",
    "sample_sort", "sample_sort_lex", "sample_sort_exact", "SampleSortResult",
    "distributed_sort", "distributed_sort_kv", "distributed_sort_lex",
    "distributed_chunked_sort_lex",
]

log = logging.getLogger("repro.core")


# --------------------------------------------------------------------------
# local sort / merge building blocks
# --------------------------------------------------------------------------

def _local_sort_fn(local_sort):
    """Resolve the device-local tuple sort: 'pallas' (the unified
    ``kernels.ops.sort_lex`` front-end), 'xla' (XLA's variadic sort — the
    same full-tuple compare, compiled), 'auto' (pallas on TPU, where the
    kernels are the point; xla elsewhere, where pallas runs in interpret
    mode), or a callable ``lanes -> lanes``."""
    if callable(local_sort):
        return local_sort
    if local_sort == "auto":
        local_sort = "pallas" if jax.default_backend() == "tpu" else "xla"
    if local_sort == "pallas":
        from ..kernels.ops import sort_lex  # lazy: avoid import-time cycle
        return lambda lanes: list(sort_lex(list(lanes)))
    if local_sort == "xla":
        return lambda lanes: list(lax.sort(list(lanes), num_keys=len(lanes)))
    raise ValueError(f"unknown local_sort {local_sort!r}")


def _merge_resort_lex(mine, theirs, sort_fn):
    return sort_fn([jnp.concatenate([m, t]) for m, t in zip(mine, theirs)])


def _merge_bitonic_lex(mine, theirs, sort_fn):
    return bitonic_merge_lex(mine, theirs)


def _merge_take_lex(mine, theirs, sort_fn):
    # merge-path rank + scatter — the shared run-merge primitive
    # (kernels/keypack.merge_take_packed: packed rank-key binary search, the
    # same combine the pipeline tier uses on its chunked sorted runs), never
    # the O(B^2) lane-wise broadcast.
    return merge_take_packed(mine, theirs)


_MERGES_LEX = {"resort": _merge_resort_lex, "bitonic": _merge_bitonic_lex,
               "take": _merge_take_lex}


def _merge_sorted_rows_lex(rows):
    """Merge the rows of parallel (r, L) lane arrays — each row-tuple lex
    ascending, r a power of two — into one sorted lane tuple of (r*L,)
    arrays via a merge-path tree: log2(r) vmapped rounds of packed rank-key
    searchsorted + scatter (``kernels/keypack.py``), O(n log r) instead of a
    full O(n log n) re-sort. Any arity — key-only is the 1-lane case, and
    multi-lane tuples rank by binary search instead of the broadcast they
    used to need."""
    def mpair(a_rows, b_rows):
        return list(merge_take_packed(a_rows, b_rows))

    rows = list(rows)
    while rows[0].shape[0] > 1:
        rows = jax.vmap(mpair)([x[0::2] for x in rows],
                               [x[1::2] for x in rows])
    return [x[0] for x in rows]


def local_merge(mine, theirs, strategy: str = "bitonic"):
    """Merge two sorted key-only blocks (the 1-tuple view of the lex merge)."""
    if strategy == "bitonic":
        return bitonic_merge(mine, theirs)  # keeps the key-only fast path
    (out,) = _MERGES_LEX[strategy]([mine], [theirs],
                                   lambda ls: [jnp.sort(ls[0])])
    return out


# --------------------------------------------------------------------------
# engine 1: odd-even block sort (bubble sort across the mesh)
# --------------------------------------------------------------------------

def odd_even_block_sort_lex(lanes, axis_name: str, merge: str = "bitonic",
                            local_sort="auto"):
    """Sort lex tuples distributed along mesh axis ``axis_name``.

    To be called *inside* ``shard_map``. ``lanes``: list of this device's
    same-shape (B,) shards — key lanes first, payload/tie-break lanes last
    (``kernels/lex.py`` conventions). Returns the sorted lane tuple
    (globally ascending across the axis). ``merge``: 'resort' | 'bitonic'
    ('bitonic' needs pow2 B) | 'take'; ``local_sort``: see
    :func:`distributed_sort_lex`.
    """
    if merge not in _MERGES_LEX:
        raise ValueError(f"unknown merge strategy {merge!r}")
    lanes = list(lanes)
    num = axis_size(axis_name)
    me = lax.axis_index(axis_name)
    sort_fn = _local_sort_fn(local_sort)
    lanes = sort_fn(lanes)
    bsz = lanes[0].shape[0]
    fwd = [(i, (i + 1) % num) for i in range(num)]
    bwd = [(i, (i - 1) % num) for i in range(num)]

    def round_body(r, lanes_t):
        blk = list(lanes_t)
        # round parity decides pairing: even r -> (0,1)(2,3)..; odd -> (1,2)(3,4)..
        left_of_pair = (me % 2) == (r % 2)
        partner = jnp.where(left_of_pair, me + 1, me - 1)
        has_partner = (partner >= 0) & (partner < num)

        # The pairing depends on the traced round index, so a static perm per
        # round is impossible; exchange with both ring neighbours and select.
        # from_left[j] = block of device j-1; from_right[j] = block of j+1.
        from_left = [lax.ppermute(a, axis_name, fwd) for a in blk]
        from_right = [lax.ppermute(a, axis_name, bwd) for a in blk]
        theirs = [jnp.where(left_of_pair, fr, fl)
                  for fl, fr in zip(from_left, from_right)]

        merged = _MERGES_LEX[merge](blk, theirs, sort_fn)
        new = [jnp.where(left_of_pair, m[:bsz], m[bsz:]) for m in merged]
        return tuple(jnp.where(has_partner, n_, a) for n_, a in zip(new, blk))

    return lax.fori_loop(0, num, round_body, tuple(lanes))


def odd_even_block_sort(block, axis_name: str, merge: str = "bitonic",
                        local_sort=jnp.sort):
    """Key-only odd-even block sort (the 1-tuple view). To be called inside
    ``shard_map``; ``block`` is this device's (B,) shard. ``local_sort``
    keeps its historical array->array signature (default ``jnp.sort``)."""
    if callable(local_sort):
        one = local_sort
        fn = lambda ls: [one(ls[0])]  # noqa: E731 — adapt array fn to lanes
    else:
        fn = local_sort
    (out,) = odd_even_block_sort_lex([block], axis_name, merge=merge,
                                     local_sort=fn)
    return out


# --------------------------------------------------------------------------
# engine 2: sample sort (splitter one-shot with exact-count exchange)
# --------------------------------------------------------------------------

class SampleSortResult(NamedTuple):
    """Per-device result of :func:`sample_sort_lex`.

    ``lanes``: tuple of (P*capacity,) sorted arrays — real elements occupy
    the prefix ``[0, count)``; slots beyond hold sentinel fill. ``count`` is
    exact (from the exchanged counts, never inferred from values).
    ``overflow`` is True iff some source had more than ``capacity`` elements
    destined for *this* device and the excess was clipped (each device flags
    its own inbound overflow — OR the flags across the axis for a global
    verdict) — impossible when capacity is the default worst case B."""

    lanes: Tuple[jax.Array, ...]
    count: jax.Array
    overflow: jax.Array


def _sample_partition_exchange(lanes, axis_name, n_valid, capacity,
                               oversample, local_sort):
    """Shared sample-sort core: local sort -> global splitters -> ONE
    all_to_all of data + one all_gather of the true count vectors. Returns
    ``(out_lanes, count_matrix, overflow, b, cap)``: ``out_lanes`` are this
    device's (P*cap,) arrays with the real elements sorted in the prefix,
    whose length is ``min(count_matrix[:, me], cap).sum()``;
    ``count_matrix[s, d]`` is the TRUE number of elements source s holds
    for destination d (pre-clip, replicated on every device)."""
    lanes = list(lanes)
    num = axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b = lanes[0].shape[0]
    cap = capacity if capacity is not None else b
    sort_fn = _local_sort_fn(local_sort)
    sentinels = [_sentinel(a.dtype) for a in lanes]

    # validity from construction, not from values: the host wrapper pads the
    # global tail, so device me's real elements are a prefix of its shard.
    if n_valid is None:
        local_valid = jnp.int32(b)
    else:
        local_valid = jnp.clip(n_valid - me * b, 0, b).astype(jnp.int32)

    # Invalid tail slots are overwritten with the all-sentinel tuple BEFORE
    # the sort: that tuple is lex-maximal under the full-tuple compare, so
    # fills sink to the tail and the first local_valid slots hold exactly
    # the real multiset (a real element equal to the fill in every lane is
    # interchangeable with it). Key-only sorting thus stays on the fast
    # single-operand path — no flag lane — while *counts* still come only
    # from the protocol, never from value comparisons.
    if n_valid is not None:
        idx = jnp.arange(b)
        lanes = [jnp.where(idx < local_valid, a, s)
                 for a, s in zip(lanes, sentinels)]
    local = sort_fn(lanes)
    vmask = jnp.arange(b) < local_valid

    # evenly spaced local quantiles -> global splitters (invalid samples are
    # masked to the lex-maximal sentinel tuple so they sort past every real
    # sample and never skew the low splitters)
    stride = max(1, b // oversample)
    pos = jnp.minimum(jnp.arange(oversample) * stride, b - 1)
    sample_ok = pos < local_valid
    samples = [jnp.where(sample_ok, a[pos], s) for a, s in zip(local, sentinels)]
    gathered = [lax.all_gather(s, axis_name).reshape(-1) for s in samples]
    all_samples = list(lax.sort(gathered, num_keys=len(gathered)))
    take = [(i + 1) * oversample for i in range(num - 1)]
    splitters = [s[jnp.asarray(take, jnp.int32)] for s in all_samples]

    # bucket by splitter (the paper's phase-2 distribution step):
    # dest = #splitters lex<= element — the packed rank-key binary search
    # (splitters are slices of the lex-sorted gathered samples, so they are
    # sorted tuples), the same rank primitive the run merges use
    if num > 1:
        dest = packed_searchsorted(splitters, local,
                                   side="right").astype(jnp.int32)
    else:
        dest = jnp.zeros((b,), jnp.int32)
    # rank within destination bucket via stable order (the valid prefix is
    # sorted, so same-destination elements are contiguous); invalid slots go
    # to the discard bucket ``num`` and never enter the counts.
    dest_eff = jnp.where(vmask, dest, num)
    counts = jnp.bincount(dest_eff, length=num + 1)[:num].astype(jnp.int32)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(b) - offsets[jnp.minimum(dest_eff, num - 1)]
    keep = vmask & (rank < cap)
    slot = jnp.where(keep, dest * cap + rank, num * cap)
    buckets = [
        jnp.full((num * cap + 1,), s, a.dtype).at[slot].set(a)[: num * cap]
        .reshape(num, cap)
        for a, s in zip(local, sentinels)
    ]

    # ONE all_to_all for the data, plus ONE tiny all_gather for the TRUE
    # counts: every device learns the full (source, destination) count
    # matrix, so the validity mask comes from these counts — never from
    # comparing values against the sentinel — and the exact-placement step
    # can compute every device's global offset with no further collective.
    received = [lax.all_to_all(bk, axis_name, split_axis=0, concat_axis=0,
                               tiled=False) for bk in buckets]
    count_matrix = lax.all_gather(counts, axis_name)  # [src, dst] true counts
    recv_counts = count_matrix[:, me]
    overflow = jnp.any(recv_counts > cap)

    # Final combine: unfilled bucket slots already hold the all-sentinel
    # fill tuple by construction, so any order-preserving combine leaves the
    # real multiset in the count-sized prefix (same argument as the local
    # sort). Each received row is a slice of a sorted block, hence sorted —
    # pow2 row counts take a merge-path tree (log P rounds of packed
    # rank-key searchsorted gathers, any lane arity) instead of re-sorting
    # all P·cap elements; non-pow2 falls back to the full-tuple sort.
    if num & (num - 1) == 0:
        out = _merge_sorted_rows_lex(received)
    else:
        out = sort_fn([r.reshape(-1) for r in received])
    return out, count_matrix, overflow, b, cap


def sample_sort_lex(lanes, axis_name: str, n_valid: Optional[int] = None,
                    capacity: Optional[int] = None, oversample: int = 8,
                    local_sort="auto") -> SampleSortResult:
    """Splitter-based distributed lex sort — the paper's *bucketing* idea at
    mesh scale, and the fix for odd-even block sort's O(P)-round wall.

    To be called inside ``shard_map``. ``lanes``: list of this device's
    same-shape (B,) shards (key lanes first, payload last). ``n_valid``:
    global count of real elements when the caller padded the tail of the
    *last* shards (as :func:`distributed_sort_lex` does); None = all real.
    ``capacity`` bounds the per-source-per-destination bucket; the default B
    is the worst case, so no element can ever be dropped. Returns
    :class:`SampleSortResult` — the concatenation of every device's valid
    prefix (in axis order) is the globally sorted sequence.
    """
    me = lax.axis_index(axis_name)
    out, count_matrix, overflow, _, cap = _sample_partition_exchange(
        lanes, axis_name, n_valid, capacity, oversample, local_sort)
    count = jnp.sum(jnp.minimum(count_matrix[:, me], cap))
    return SampleSortResult(tuple(out), count, overflow)


def sample_sort_exact(lanes, axis_name: str, n_valid: Optional[int] = None,
                      capacity: Optional[int] = None, oversample: int = 8,
                      local_sort="auto"):
    """Sample sort returning *exactly placed* (B,) shards: a second
    ``all_to_all`` moves every element to the device and slot of its global
    rank, so the ``out_specs``-concatenated result is the globally sorted
    array with all padding at the tail — no host-side compaction (which
    XLA's partitioner would otherwise render as a storm of all-gathers).

    Global ranks come from the gathered count matrix (already on every
    device — no extra collective), never from values. Placement ships an
    explicit occupancy flag through the exchange, so receivers select real
    elements per slot without comparing against the sentinel. Returns
    ``(out_lanes, overflow, kept)``: ``overflow`` is this device's inbound
    overflow flag (OR across the axis for the global verdict); ``kept`` is
    the *global* number of elements that survived capacity clipping
    (``sum(min(count_matrix, capacity))``, replicated — equals the real
    element count whenever ``overflow`` is False everywhere). Unfilled
    slots (input padding) hold the lex-maximal sentinel tuple.
    """
    num = axis_size(axis_name)
    me = lax.axis_index(axis_name)
    out, count_matrix, overflow, b, cap = _sample_partition_exchange(
        lanes, axis_name, n_valid, capacity, oversample, local_sort)
    sentinels = [_sentinel(a.dtype) for a in out]
    m = out[0].shape[0]

    # my elements' global ranks: offset of my valid run + local index
    all_counts = jnp.sum(jnp.minimum(count_matrix, cap), axis=0)
    kept = jnp.sum(all_counts)
    cnt = all_counts[me]
    my_off = (jnp.cumsum(all_counts) - all_counts)[me]
    i = jnp.arange(m)
    pos = my_off + i
    valid = i < cnt
    # bucket row = destination device (pos // b), column = in-shard slot
    # (pos % b) — i.e. the flat bucket index IS the global rank
    slot = jnp.where(valid, pos, num * b)
    buckets = [
        jnp.full((num * b + 1,), s, a.dtype).at[slot].set(a)[: num * b]
        .reshape(num, b)
        for a, s in zip(out, sentinels)
    ]
    occupied = jnp.zeros((num * b + 1,), jnp.int32).at[slot].set(1)[: num * b] \
        .reshape(num, b)
    recv = [lax.all_to_all(bk, axis_name, split_axis=0, concat_axis=0,
                           tiled=False) for bk in buckets]
    rocc = lax.all_to_all(occupied, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)
    # global positions are unique, so each slot has at most one occupied
    # source; empty slots keep source 0's sentinel fill
    src = jnp.argmax(rocc, axis=0)
    cols = jnp.arange(b)
    return tuple(r[src, cols] for r in recv), overflow, kept


def sample_sort(block, axis_name: str, capacity: int | None = None,
                oversample: int = 8, local_sort="auto"):
    """Key-only sample sort (the 1-tuple view). Returns ``(values, count)``
    per device: ``values`` is (P*capacity,) with the real elements sorted in
    the prefix ``[0, count)``; ``count`` is exact even when real elements
    equal the padding sentinel (``iinfo.max`` / the all-ones-bits NaN —
    ``kernels.lex.sentinel_for``)."""
    res = sample_sort_lex([block], axis_name, capacity=capacity,
                          oversample=oversample, local_sort=local_sort)
    return res.lanes[0], res.count


# --------------------------------------------------------------------------
# engine selection + host-facing front-end
# --------------------------------------------------------------------------

def choose_engine(num_devices: int, block: int, engine: str = "auto") -> str:
    """Pick the mesh engine for P devices of B-element blocks — the
    ``kernels.ops.choose_plan`` cost model lifted to mesh granularity.

    odd_even moves O(P·B) bytes per device over P latency-bound rounds;
    sample moves O(B) bytes in one all_to_all plus an O(P·oversample)
    splitter all_gather. The splitter machinery only loses when the round
    count is already trivial: P <= 2 (<= 2 merge rounds). Beyond that the
    one-shot wins and keeps winning as P grows — block size scales both
    engines' local work equally, so the boundary is P-driven only. Explicit
    ``engine`` overrides."""
    if engine != "auto":
        if engine not in ("odd_even", "sample"):
            raise ValueError(f"unknown engine {engine!r}")
        return engine
    return "odd_even" if num_devices <= 2 else "sample"


def _pad_tail(a, npad):
    if a.shape[0] == npad:
        return a
    fill = jnp.full((npad - a.shape[0],), _sentinel(a.dtype), a.dtype)
    return jnp.concatenate([a, fill])


@functools.lru_cache(maxsize=128)
def _build_host_fn(mesh, axis, eng, merge, local_sort, oversample, n,
                   dtypes, capacity=None):
    """Jitted host function for one (mesh, config, shape) combination —
    cached so repeated calls (serving admission waves, benchmarks) reuse the
    compiled executable instead of re-tracing per call. Returns
    ``run(*padded) -> (data_lanes, overflow_flags, kept)``: for the sample
    engine ``overflow_flags`` is the (P,) per-device inbound overflow vector
    and ``kept`` the global surviving-element count (replicated); for
    odd_even — which has no capacity to overflow — both are ``None``."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.compat import shard_map_norep

    spec_in = tuple([P(axis)] * len(dtypes))

    if eng == "odd_even":
        body = functools.partial(odd_even_block_sort_lex, axis_name=axis,
                                 merge=merge, local_sort=local_sort)
        fn = shard_map_norep(lambda *ls: body(list(ls)), mesh=mesh,
                             in_specs=spec_in, out_specs=spec_in)

        @jax.jit
        def run(*padded):
            # Sorted in place across the axis: padding tuples (all-sentinel,
            # hence lex-maximal) sort to the global tail, so the leading-n
            # slice is exact.
            return tuple(o[:n] for o in fn(*padded)), None, None
    else:
        def body(*ls):
            out, ovf, kept = sample_sort_exact(
                list(ls), axis_name=axis, n_valid=n, capacity=capacity,
                oversample=oversample, local_sort=local_sort)
            return (*out, ovf[None].astype(jnp.int32), kept[None])

        fn = shard_map_norep(body, mesh=mesh, in_specs=spec_in,
                             out_specs=spec_in + (P(axis), P(axis)))

        @jax.jit
        def run(*padded):
            # Exact rank placement puts every surviving element at its
            # global rank and sentinel-fills unassigned tail slots, so the
            # leading-n slice is exact whenever nothing overflowed.
            res = fn(*padded)
            return (tuple(o[:n] for o in res[:-2]), res[-2], res[-1])

    return run


def distributed_sort_lex(keys_lanes, mesh, axis: str = "data", vals=None,
                         engine: str = "auto", merge: str = "bitonic",
                         local_sort="auto", oversample: int = 8,
                         capacity: int | None = None,
                         on_overflow: str = "raise", validate: str = "off"):
    """Sort 1-D lex tuples sharded over ``axis`` of ``mesh``. Host-facing.

    ``keys_lanes``: sequence of same-shape 1-D arrays, lane 0 most
    significant; optional ``vals`` rides the keys' permutation as the final
    tie-break lane (``kernels.ops.sort_lex`` semantics). ``engine``: 'auto'
    (:func:`choose_engine`), 'odd_even', or 'sample'; ``merge`` applies to
    odd_even only. Any length: non-divisible inputs are sentinel-padded to
    the next multiple of the axis size and sliced back.

    ``capacity`` (sample engine only) bounds the per-source-per-destination
    exchange bucket; the default ``None`` sizes it at the worst-case block
    so zero elements can ever be dropped. A smaller explicit capacity
    shrinks the exchange tensor ``P * capacity``-fold but can overflow on
    skew; ``on_overflow`` is then the degrade policy:
      * ``'raise'`` — raise ``repro.runtime.CapacityOverflow``;
      * ``'retry'`` — double the capacity and re-run until the exchange
        fits (bounded: the worst-case block size always fits), logging each
        escalation — the supervisor-friendly lossless policy;
      * ``'clip'``  — return only the surviving elements (the output
        shortens to the exchanged count) with a warning log.

    ``validate``: ``'off'`` | ``'cheap'`` (host check that the output is
    lex-sorted and, on lossless paths, conserves the element count) |
    ``'full'`` (adds multiset conservation via the order-independent content
    digest of ``pipeline.validate``) — raises
    ``pipeline.validate.ValidationError`` on violation.

    Returns a tuple of sorted lanes, or ``(lanes, sorted_vals)`` when
    ``vals`` is given.
    """
    from ..runtime.failure import CapacityOverflow
    if on_overflow not in ("raise", "retry", "clip"):
        raise ValueError(f"unknown on_overflow policy {on_overflow!r}")
    arrs = list(keys_lanes) + ([vals] if vals is not None else [])
    if not arrs or any(a.ndim != 1 for a in arrs):
        raise ValueError("need 1-D lanes")
    if any(a.shape != arrs[0].shape for a in arrs[1:]):
        raise ValueError("all lanes (and vals) must have identical shapes")
    n = arrs[0].shape[0]
    num = mesh.shape[axis]
    b = -(-n // num) if n else 1
    npad = b * num
    eng = choose_engine(num, b, engine)
    if eng == "odd_even" and merge == "bitonic" and b & (b - 1):
        merge = "resort"  # bitonic merge needs pow2 blocks; stay exact
    dtypes = tuple(jnp.asarray(a).dtype for a in arrs)
    cap = capacity if eng == "sample" else None
    padded = [_pad_tail(a, npad) for a in arrs]
    clipped = False
    while True:
        if callable(local_sort):  # unhashable config: build uncached
            run = _build_host_fn.__wrapped__(mesh, axis, eng, merge,
                                             local_sort, oversample, n,
                                             dtypes, cap)
        else:
            run = _build_host_fn(mesh, axis, eng, merge, local_sort,
                                 oversample, n, dtypes, cap)
        out, ovf, kept = run(*padded)
        if ovf is None or cap is None or not bool(jnp.any(ovf)):
            break
        if on_overflow == "raise":
            # the exchange reports the flag, not the exact need: required
            # defaults to the always-sufficient worst-case block size
            raise CapacityOverflow(
                f"sample-sort exchange overflowed capacity {cap} "
                f"(block size {b} always fits)", cap, required=b)
        if on_overflow == "clip":
            kept_n = int(kept[0])
            log.warning("sample-sort exchange overflow: clipping %d "
                        "element(s) past capacity %d", n - kept_n, cap)
            out = tuple(o[:kept_n] for o in out)
            clipped = True
            break
        new_cap = min(cap * 2, b)
        log.warning("sample-sort exchange overflow: capacity %d -> %d "
                    "(retry)", cap, new_cap)
        cap = new_cap
    if validate != "off":
        from ..pipeline.validate import check_lanes_sorted, check_multiset
        check_lanes_sorted(out, what="distributed_sort_lex output")
        if not clipped:
            if out[0].shape[0] != n:
                from ..pipeline.validate import ValidationError
                raise ValidationError(
                    f"distributed_sort_lex lost elements: {out[0].shape[0]}"
                    f" != {n}")
            if validate == "full":
                check_multiset(arrs, out,
                               what="distributed_sort_lex multiset")
    if vals is None:
        return out
    return out[:-1], out[-1]


def distributed_sort(x, mesh, axis: str = "data", engine: str = "auto",
                     merge: str = "bitonic", local_sort="auto"):
    """Sort a 1-D array sharded over ``axis`` of ``mesh`` (key-only view of
    :func:`distributed_sort_lex`); any length, any engine."""
    (out,) = distributed_sort_lex((x,), mesh, axis=axis, engine=engine,
                                  merge=merge, local_sort=local_sort)
    return out


def distributed_sort_kv(keys, vals, mesh, axis: str = "data",
                        engine: str = "auto", merge: str = "bitonic",
                        local_sort="auto"):
    """Key-value view of :func:`distributed_sort_lex`: ``vals`` rides the
    keys' permutation as the final tie-break lane."""
    if keys.shape != vals.shape:
        raise ValueError("keys and vals must have identical shapes")
    lanes, ov = distributed_sort_lex((keys,), mesh, axis=axis, vals=vals,
                                     engine=engine, merge=merge,
                                     local_sort=local_sort)
    return lanes[0], ov


# --------------------------------------------------------------------------
# out-of-core: chunk-per-device ingest + run exchange + streaming combine
# --------------------------------------------------------------------------

def _chunk_devices(mesh, axis, devices):
    if devices is not None:
        return list(devices)
    if mesh is not None:
        # the mesh's devices in axis-major flat order (1-D meshes: the ring)
        return list(np.asarray(mesh.devices).reshape(-1))
    return list(jax.devices())


def _slot_rows(n: int, num: int) -> int:
    """Rows of one (run, destination) slot of the mesh exchange: a quarter
    over a piece's even share, ``ceil(ceil(n / num) / num)``, rounded up to
    the lane tile. It follows from ``n`` and the device count alone, so
    every input of one size runs the same programs. On 24 paper-ds2 jobs
    over 4 devices the fullest piece held 1.157x its even share."""
    chunk = -(-n // num)
    share = -(-chunk // num)
    rows = -(-share * 5 // 4)
    return -(-rows // LANE_TILE) * LANE_TILE


@functools.partial(jax.jit, static_argnames=("oversample",))
def _run_samples(cmp_lanes, oversample: int):
    """``oversample`` evenly spaced rows of one sorted run's compare
    lanes."""
    n_r = cmp_lanes[0].shape[0]
    pos = np.minimum(np.arange(oversample) * max(1, n_r // oversample),
                     n_r - 1)
    return tuple(lane[pos] for lane in cmp_lanes)


def _run_splitters(cmp_runs, num: int, oversample: int):
    """Global splitter tuples for a ``num``-way partition of k sorted runs:
    evenly spaced per-run quantile samples of the compare lanes, read to
    the host in one transfer, pooled and lex-sorted there (a few
    k*oversample rows), then ``num - 1`` evenly spaced picks, as NumPy
    lanes. The splitters only steer *balance*; correctness never depends
    on them because the per-run boundaries are exact searchsorted
    positions."""
    samples = jax.device_get([_run_samples(tuple(c), oversample)
                              for c in cmp_runs if c[0].shape[0]])
    pooled = [np.concatenate(s) for s in zip(*samples)]
    order = np.lexsort(tuple(reversed(pooled)))
    take = order[[(d + 1) * len(order) // num for d in range(num - 1)]]
    return tuple(p[take] for p in pooled)


@jax.jit
def _run_bounds(splitters, cmp_lanes):
    """One sorted run's destination boundaries, (num + 1,) int32: 0, each
    splitter's ``side='right'`` position, the run's length."""
    pos = lex_searchsorted(list(cmp_lanes), list(splitters), side="right")
    return jnp.concatenate([
        jnp.zeros((1,), jnp.int32), pos.astype(jnp.int32),
        jnp.full((1,), cmp_lanes[0].shape[0], jnp.int32)])


@functools.partial(jax.jit, static_argnames=("num", "slot"))
def _split_slots(bnd, lanes, *, num: int, slot: int):
    """One sorted run cut at its boundaries ``bnd`` into ``num`` slots of
    ``slot`` rows, ``out[d][i]`` lane i of slot d: rows ``bnd[d]:bnd[d +
    1]`` at the slot's head, then padding (the next piece's rows, or zeros
    past the run), which the combine masks by count."""
    padded = [jnp.concatenate([x, jnp.zeros((slot,), x.dtype)])
              for x in lanes]
    return tuple(tuple(lax.dynamic_slice(x, (bnd[d],), (slot,))
                       for x in padded) for d in range(num))


@functools.partial(jax.jit, static_argnames=("n",))
def _gather_blocks(offsets, blocks, *, n: int):
    """The destinations' merged blocks end to end on one device, as
    ``(lengths, keys)`` of ``n`` rows: block d is written at
    ``offsets[d]``, in order, so the padding after its real rows is
    overwritten by block d + 1 or lies past ``n``."""
    size = n + max(b[0].shape[0] for b in blocks)
    out = []
    for lane in zip(*blocks):
        buf = jnp.zeros((size,), lane[0].dtype)
        for d, x in enumerate(lane):
            buf = lax.dynamic_update_slice(buf, x, (offsets[d],))
        out.append(buf[:n])
    return out[0], jnp.stack(out[1:], axis=1)


def distributed_chunked_sort_lex(keys, mesh=None, axis: str = "data",
                                 devices=None, algorithm: str = "pallas",
                                 capacity: int | None = None,
                                 store=None, supervisor=None,
                                 validate: str = "off",
                                 on_overflow: str = "raise",
                                 merge_engine: str = "auto",
                                 oversample: int = 8,
                                 shard_store=None,
                                 gather: bool | None = None):
    """Out-of-core mesh sort of packed shortlex words — the MPI follow-up's
    bucket->distribute->merge-across-ranks shape composed from the pipeline
    and kernel tiers, host-orchestrated over explicit device placement (so
    it runs identically on a TPU mesh and on fake CPU devices):

      1. **chunk-per-device ingest**: row-shard ``keys`` into one chunk per
         device, ``device_put`` each onto its device, and run the fused
         per-chunk bucketize + segmented-sort (``pipeline.ingest``'s
         ``_ingest_chunk`` — PR 6's ``RunStore`` resume, manifests, and
         ``on_overflow`` forward untouched) to get local ``SortedRun``s.
      2. **one exact-count sample-sort exchange of whole runs** (supervisor
         stage ``'run_exchange'``): splitters come from pooled per-run
         quantile samples; each run's destination boundaries are *exact*
         ``lex_searchsorted`` positions over its packed compare lanes, so
         destination d receives precisely its key range as k contiguous
         sorted sub-runs — counts derive from the boundaries, never from
         sentinel comparisons, and nothing can be silently lost. Each
         (run, destination) piece travels in a slot of fixed rows
         (:func:`_slot_rows`, from n and the device count), padded past
         its count; a piece too large for it doubles the slot (new
         shapes, so new programs) and is never cut.
      3. **streaming combine** (stage ``'streaming_combine'`` inside
         ``pipeline.merge.merge_runs``): each destination merges its k
         slots in ONE k-way pass (``kernels/kway_kernel.py``), masked by
         their counts; the destinations' blocks written end to end at
         offsets from the counts are the global sort. Every shape follows
         from n and the device count, so a new input of the same size
         compiles nothing.

    ``keys``: packed (n, lanes) uint32 words, host or device. Devices come
    from ``devices`` (explicit list), else ``mesh``'s flat device order,
    else all local devices. ``capacity`` bounds each destination's combine
    input; ``on_overflow`` is then the degrade policy — 'raise'
    (``CapacityOverflow`` with the required size), 'retry' (double until it
    fits; always terminates at the worst-case destination count), or 'clip'
    (each overflowing destination keeps its ``capacity`` smallest elements,
    with a warning; conservation checks are skipped for the clipped
    output). ``validate``: 'off' | 'cheap' | 'full' — the PR 6 gate
    (``pipeline.validate.check_chunked``: per-run manifest reconciliation +
    count/histogram/sortedness conservation, 'full' adds content digests)
    applied across ingest, exchange, and combine end to end.

    **Sharded spill** (``shard_store``, a ``pipeline.shards.ShardStore``):
    each destination's merged output lands as an atomic disk shard the
    moment its combine completes — per-shard ``RunManifest`` (count,
    min/max key, additive digest) in the snapshot metadata, so (a) a killed
    job resumes at shard granularity (a stored shard whose count and summed
    sub-run digest match the re-exchanged destination *loads* instead of
    re-merging; torn or mismatched shards recompute), and (b) with
    ``validate != 'off'`` the ``check_sharded`` gate proves cross-shard
    boundary ordering + count/histogram(/digest) conservation from
    manifests alone, no rescan. ``gather`` controls the result form:
    ``True`` (default without a shard store) writes the destinations onto
    the default device and returns a ``SortedRun``; ``False`` (default
    *with* a shard store) skips the gather entirely — for results that
    don't fit the home device either — and returns the
    ``pipeline.shards.ShardedRun`` handle.

    When the ``supervisor`` carries a ``SpeculationPolicy``, each
    destination combine runs through ``run_speculative`` — a straggling
    merge gets a backup replica, first successful completion wins, the
    loser is discarded only after its output digest matches.

    Returns the globally sorted :class:`~repro.pipeline.ingest.SortedRun`,
    or a :class:`~repro.pipeline.shards.ShardedRun` when ``gather=False``.
    """
    from ..pipeline.ingest import SortedRun
    if on_overflow not in ("raise", "retry", "clip"):
        raise ValueError(f"unknown on_overflow policy {on_overflow!r}")
    if validate not in ("off", "cheap", "full"):
        raise ValueError("validate must be one of ('off', 'cheap', 'full')")
    if gather is None:
        gather = shard_store is None
    if not gather and shard_store is None:
        raise ValueError("gather=False requires a shard_store to spill to")
    devs = _chunk_devices(mesh, axis, devices)
    if not isinstance(keys, jax.Array):
        keys = np.asarray(keys, dtype=np.uint32)
    n = int(keys.shape[0])
    if n == 0:
        if not gather:
            from ..pipeline.shards import ShardedRun
            return ShardedRun(store=shard_store, manifests=())
        return SortedRun(lengths=jnp.zeros((0,), jnp.int32),
                         keys=jnp.zeros(keys.shape, jnp.uint32))
    b = -(-n // len(devs))
    with span("job", rows=n, chunks=-(-n // b)):
        return _mesh_sort(keys, devs, b, algorithm=algorithm,
                          capacity=capacity, store=store,
                          supervisor=supervisor, validate=validate,
                          on_overflow=on_overflow, merge_engine=merge_engine,
                          oversample=oversample, shard_store=shard_store,
                          gather=gather)


def _mesh_sort(keys, devs, b, *, algorithm, capacity, store, supervisor,
               validate, on_overflow, merge_engine, oversample, shard_store,
               gather):
    """The body of :func:`distributed_chunked_sort_lex` for non-empty
    ``keys`` in chunks of ``b`` rows, one per device of ``devs``."""
    from ..checkpoint.manager import CorruptSnapshotError
    from ..pipeline.ingest import SortedRun, _ingest_chunk, _run_from_arrays
    from ..pipeline.manifest import RunManifest
    from ..pipeline.merge import merge_runs
    from ..pipeline.validate import (ValidationError, check_chunked,
                                     check_lanes_sorted, check_run,
                                     check_sharded, multiset_digest)
    from ..runtime.failure import CapacityOverflow
    num, n = len(devs), int(keys.shape[0])

    # 1. chunk-per-device ingest (resume/manifests/overflow via the
    # pipeline's own chunk stage)
    runs, manifests = [], []
    for d, start in enumerate(range(0, n, b)):
        chunk = jax.device_put(keys[start:start + b], devs[d])
        run, man = _ingest_chunk(
            chunk, d, algorithm=algorithm, capacity=int(chunk.shape[0]),
            on_overflow=on_overflow, store=store, supervisor=supervisor,
            need_manifest=validate != "off")
        runs.append(run)
        manifests.append(man)

    lanes_rs = [r.lanes() for r in runs]
    cmp_rs = [r.cmp_lanes() for r in runs]
    n_cmp, k = len(cmp_rs[0]), len(runs)
    row_bytes = np.asarray([sum(x.dtype.itemsize for x in lanes + tuple(c))
                            for lanes, c in zip(lanes_rs, cmp_rs)])
    slot = _slot_rows(n, num)

    # 2. exact-count exchange of whole sorted sub-runs, in fixed slots
    def exchange(oversample):
        nonlocal slot
        if num == 1 or k == 1:
            bnds = [np.asarray([0] + [int(r[0].shape[0])] * num, np.int32)
                    for r in lanes_rs]
            counts = np.diff(np.stack(bnds), axis=1)
        else:
            with span("sync", what="splitter_samples"):
                splitters = _run_splitters(cmp_rs, num, oversample)
            bnds = [_run_bounds(splitters, tuple(c)) for c in cmp_rs]
            with span("sync", what="run_boundaries"):
                counts = np.diff(np.stack(jax.device_get(bnds)), axis=1)
        # counts[r, d]: rows of run r bound for destination d
        while slot < counts.max():
            log.warning("run exchange: a piece of %d row(s) outgrows its "
                        "slot of %d: slot -> %d", counts.max(), slot,
                        2 * slot)
            slot *= 2
        with span("run_exchange", slices=int(np.count_nonzero(counts)),
                  bytes=int(counts.sum(axis=1) @ row_bytes),
                  rows=int(counts.sum()), slots=num * k * slot):
            pieces = [_split_slots(bnd, tuple(c) + tuple(x), num=num,
                                   slot=slot)
                      for bnd, c, x in zip(bnds, cmp_rs, lanes_rs)]
            # destination d's k slots onto device d, in one call
            per_dest = jax.device_put([[p[d] for p in pieces]
                                       for d in range(num)], devs)
        return per_dest, counts

    while True:
        if supervisor is not None:
            per_dest, counts = supervisor.run_stage("run_exchange",
                                                    exchange, oversample)
        else:
            per_dest, counts = exchange(oversample)
        incoming = [int(c) for c in counts.sum(axis=0)]
        worst = max(incoming)
        if capacity is None or worst <= capacity:
            clipped = False
            break
        if on_overflow == "raise":
            raise CapacityOverflow(
                f"run exchange: destination needs {worst} > capacity "
                f"{capacity}", capacity, required=worst)
        if on_overflow == "clip":
            clipped = True
            break
        # retry rebalances as well as grows: denser samples usually shrink
        # the worst destination, and the capacity doubling guarantees the
        # loop terminates even under unsplittable skew (duplicate keys)
        new_cap = min(capacity * 2, n)
        log.warning("run exchange overflow (worst destination %d): "
                    "capacity %d -> %d, oversample %d -> %d (retry)",
                    worst, capacity, new_cap, oversample, oversample * 2)
        capacity = new_cap
        oversample *= 2

    # 3. one streaming k-way combine per destination — each output spilled
    # as an atomic shard (when a shard_store is given) the moment it lands,
    # so a kill between destinations loses only the in-flight one
    speculative = (supervisor is not None
                   and getattr(supervisor, "speculation", None) is not None)
    merged_dests = []        # (gather path) per-destination lane tuples
    kept = []                # their real rows, at the head of each
    shard_manifests = []     # (spill path) destination-ordered manifests
    for d, pieces in enumerate(per_dest):
        sub_cmps = [p[:n_cmp] for p in pieces]
        sub_lanes = [p[n_cmp:] for p in pieces]
        cnt = counts[:, d].astype(np.int32)
        keep = min(incoming[d], capacity) if clipped else incoming[d]
        # expected shard identity from the exchange alone: incoming count +
        # summed sub-run key digest (additive, so the merged output's digest
        # equals the sum — no merge needed to know what "done" looks like)
        want_digest = None
        if shard_store is not None:
            want_digest = sum(
                multiset_digest([np.asarray(x)[:c] for x in s[1:]])
                for s, c in zip(sub_lanes, cnt)) % (1 << 64)

        merged = None
        if shard_store is not None:
            try:
                man_d = shard_store.manifest(d)
            except CorruptSnapshotError as e:
                log.warning("shard store: shard %d manifest unreadable "
                            "(%s) — recomputing", d, e)
                man_d = None
            if (man_d is not None and man_d.count == incoming[d]
                    and man_d.digest == want_digest):
                try:
                    loaded = _run_from_arrays(*shard_store.load(d))
                    if validate != "off":
                        check_run(loaded, man_d, mode=validate)
                    elif int(loaded.lengths.shape[0]) != man_d.count:
                        raise ValidationError(
                            f"shard {d}: loaded {int(loaded.lengths.shape[0])} "
                            f"row(s) but manifest records {man_d.count}")
                except (CorruptSnapshotError, ValidationError) as e:
                    log.warning("shard store: shard %d failed its load "
                                "gate (%s) — recomputing", d, e)
                    shard_store.drop(d)
                else:
                    merged = loaded.lanes()
                    shard_manifests.append(man_d)
            elif man_d is not None:
                log.warning("shard store: shard %d manifest does not match "
                            "the exchanged destination (stale or clipped "
                            "shard) — recomputing", d)

        if merged is None:
            if speculative:
                # the backup replica re-runs the same pure combine; the
                # inner merge skips its own stage probe so the speculative
                # wrapper owns the injector/retry bookkeeping
                merged = supervisor.run_speculative(
                    "streaming_combine",
                    lambda sl=sub_lanes, sc=sub_cmps, c=cnt: merge_runs(
                        sl, engine=merge_engine, cmp_runs=sc,
                        supervisor=None, counts=c),
                    digest_of=lambda lanes: multiset_digest(list(lanes)))
            else:
                merged = merge_runs(sub_lanes, engine=merge_engine,
                                    cmp_runs=sub_cmps, supervisor=supervisor,
                                    counts=cnt)
            log.info("destination %d: %d row(s) merged on %s", d,
                     incoming[d], sorted(merged[0].devices(), key=str))
            if keep < incoming[d]:
                log.warning("run exchange overflow: destination %d clipped "
                            "%d element(s) past capacity %d", d,
                            incoming[d] - keep, capacity)
            if shard_store is not None:
                merged = tuple(x[:keep] for x in merged)
                run_d = SortedRun.from_lanes(merged)
                man_d = RunManifest.from_run(run_d, d)
                shard_store.put(man_d, run_d)
                shard_manifests.append(man_d)
        merged_dests.append(merged)
        kept.append(keep)

    if shard_store is not None and validate != "off":
        if clipped:
            # conservation cannot hold for a clipped output; still prove
            # the shards concatenate in order (each is internally sorted —
            # its own merge or load gate proved that)
            occ = [m for m in shard_manifests if m.count]
            for a, b in zip(occ, occ[1:]):
                if tuple(a.max_key) > tuple(b.min_key):
                    raise ValidationError(
                        f"shard boundary disorder: shard {a.chunk_id} max "
                        f"key {a.max_key} > shard {b.chunk_id} min key "
                        f"{b.min_key}")
        else:
            check_sharded(manifests, shard_manifests, mode=validate)

    if not gather:
        from ..pipeline.shards import ShardedRun
        return ShardedRun(store=shard_store,
                          manifests=tuple(shard_manifests))

    # destinations live on their own devices; the host-facing result is
    # written onto the default device (committed arrays never concatenate
    # across devices), each block at its offset in one program
    total = sum(kept)
    with span("gather", rows=total):
        offsets = np.cumsum([0] + kept[:-1]).astype(np.int32)
        lengths, out_keys = _gather_blocks(
            offsets, jax.device_put(merged_dests, jax.devices()[0]), n=n)
        if total < n:
            lengths, out_keys = lengths[:total], out_keys[:total]
    result = SortedRun(lengths=lengths, keys=out_keys)

    if validate != "off":
        if clipped:
            check_lanes_sorted(result.lanes(),
                               what="distributed_chunked output")
        else:
            check_chunked(runs, manifests, result, mode=validate)
    return result
