"""Pallas TPU kernel: ONE-launch streaming k-way merge of sorted runs.

``pipeline/merge.py``'s tournament combines k runs in ceil(log2 k) pairwise
rounds — every round is a full pass over all the data, so the combine costs
~log2(k)x the HBM traffic of a single streaming pass (the multi-way merge
payoff the parallel-sorting survey calls out, and the merge profile of the
authors' MPI follow-up). This module collapses the combine to one launch:

  1. **k-way diagonal split** (:func:`kway_ranks`, host jnp inside the same
     jit): the merge-path ranks are the inverse of the merge permutation,
     through one scatter. A bitonic merge network over the runs' compare
     lanes plus a source-index lane computes the permutation: ceil(log2 k)
     rounds of compare-exchange stages over a (rows, 128) layout, each
     partner one static roll away, with no gather and no sort. Only the
     compare lanes move through it (the data lanes move exactly once,
     later). Ties resolve by run index, then in-run index (the source
     index is the last compare lane), so the ranks are exactly a
     permutation of ``[0, total)``. On a TPU v5e the network replaced a
     tournament of pairwise binary-search rounds whose ~2,400 dependent
     small gathers took 443 ms a job at 7 x 32768 + 624 rows, against the
     kernel's 20 ms; one ``lax.sort`` of the same lanes would take the
     TPU compiler minutes at that size. One ``searchsorted`` of
     each run's ranks over the block boundaries turns them into per-block
     segment cursors, and those cursors ride into the kernel as SMEM blocks
     of 128 columns of the (run, block) table (grid step k reads columns k
     and k+1; the whole table would outgrow SMEM): the split is consumed
     *in-kernel*, there is no host-side gather/scatter of the data lanes at
     all.
  2. **2-slot double-buffered segment DMA**: each grid step starts the async
     copies for output block ``k+1`` into the alternate scratch slot before
     waiting on block ``k``'s, so the k segment fetches for the next block
     overlap the merge network of the current one and HBM latency hides
     behind compute. A DMA may only start on a 128-lane tile boundary, so
     each fetch is the ``block + 128``-lane window from the boundary below
     its segment, rotated into place in VMEM (``lex.segment_window``).
  3. **Block-granularity loser tree**: the per-run cursor state lives in
     SMEM (the blocks of the starts table); selection runs as a pairwise
     elimination tree over the k resident VMEM segments — each round merges
     two block-sorted windows with ``merge_kernel._merge_network`` and
     keeps the low ``block`` (the "winners"), so after ceil(log2 k) rounds
     the surviving window IS the output block. Tails mask to the lex-maximal
     sentinel tuple, which keeps every window sorted and makes fills
     interchangeable with sentinel-valued real elements — the output is
     bit-identical to the NumPy/tournament oracle.

Variadic over lex lane tuples like every engine here (lane 0 most
significant, trailing lanes payload tie-breaks). ``n_cmp`` ranks the split
on pre-packed leading compare lanes only; callers must pass a compare
prefix that is an order-preserving refinement of the full tuple (equal
prefix => equal tuple), which the pipeline's exact packings guarantee.

:func:`merge_runs_kway_take` is the jnp tier of the same contract: off-TPU
there is no DMA pipeline to hide latency behind, so op count is what rules —
ONE fused ``lax.sort`` over the canonical order bits of the compare lanes
(+ an iota lane whose stable order encodes the run-index tie protocol)
yields the merge permutation of item 1 in a single dispatch, then ONE
gather per lane.
The data lanes move exactly once, versus the tournament's log2(k) passes of
~k separate jits over every lane. That is the engine
``ops.merge_runs_lex`` routes to off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime.trace import span
from .keypack import packed_cmp_lanes
from .lex import (LANE_TILE, concat_lanes, lex_gt_lanes, pad_run,
                  segment_window, to_order_bits, window_start)
from .merge_kernel import _merge_network

__all__ = ["DEFAULT_KWAY_BLOCK", "kway_ranks", "merge_runs_kway_take",
           "kway_kernel_call", "merge_runs_kway_pallas", "merge_kway_pallas"]

# one output tile per grid step; 2 slots x k segments of every lane in VMEM
DEFAULT_KWAY_BLOCK = 256


def _merge_perm(cmp_runs, counts=None):
    """Source index, in the concatenation of the sorted runs ``cmp_runs``,
    of every slot of their k-way merge, ties broken by run index then in-run
    index (the k-way tie protocol), as a bitonic merge network in plain jnp:
    no gather and no sort.

    ``counts`` (k,) int32, where given, holds each run's real rows; the rows
    past them are padding, which takes the all-ones order bits and a source
    index past ``total``, so it follows every real row (one with all-ones
    bits too) in run order, and maps back to its own index.

    Each run pads to ``P`` (a power of two >= 128) with the all-ones order
    bits, the run count to a power of two ``K`` with all-pad runs, and every
    slot carries its source index as a last compare lane (``total`` on
    pads, above every real index), so real tuples are distinct and the
    network's order is the stable one. Odd runs are reversed, so the blocks
    alternate ascending and descending, and ceil(log2 k) bitonic merge
    rounds sort the (K*P/128, 128) layout: one compare-exchange stage per
    partner distance, the partner fetched by a static roll along lanes
    (distance < 128) or rows. Compiled for a TPU v5e, one ``lax.sort`` of
    the same 6 lanes at 7 x 32768 + 624 rows takes minutes, this network
    seconds."""
    nc = len(cmp_runs[0])
    ns = [c[0].shape[0] for c in cmp_runs]
    k, total = len(cmp_runs), sum(ns)
    P = max(LANE_TILE, 1 << (max(ns) - 1).bit_length())
    K = 1 << (k - 1).bit_length()
    rows = (K * P) // LANE_TILE

    def layout(runs, fill):
        blocks = [jnp.pad(x, (0, P - x.shape[0]), constant_values=fill)
                  for x in runs] + [jnp.full((P,), fill)] * (K - k)
        blocks = [x[::-1] if r % 2 else x for r, x in enumerate(blocks)]
        return jnp.concatenate(blocks).reshape(rows, LANE_TILE)

    top = jnp.uint32(0xFFFFFFFF)  # the largest order bits
    pads = None if counts is None else [
        jnp.arange(n, dtype=jnp.int32) >= counts[r] for r, n in enumerate(ns)]

    def bits(i):
        xs = [to_order_bits(c[i]) for c in cmp_runs]
        return xs if pads is None else [jnp.where(p, top, x)
                                        for p, x in zip(pads, xs)]

    lanes = [layout(bits(i), top) for i in range(nc)]
    src = jnp.arange(total, dtype=jnp.int32)
    bases = [sum(ns[:r]) for r in range(k)]
    srcs = [src[b:b + n] for b, n in zip(bases, ns)]
    fill = total
    if pads is not None:
        srcs = [jnp.where(p, s + total, s) for p, s in zip(pads, srcs)]
        fill = 2 * total
    lanes.append(layout(srcs, jnp.int32(fill)))
    idx = (lax.broadcasted_iota(jnp.int32, (rows, LANE_TILE), 0) * LANE_TILE
           + lax.broadcasted_iota(jnp.int32, (rows, LANE_TILE), 1))
    size = 2 * P
    while size <= K * P:
        asc = (idx & size) == 0
        d = size // 2
        while d:
            lower = (idx & d) == 0
            shift, axis = (d, 1) if d < LANE_TILE else (d // LANE_TILE, 0)
            part = [jnp.where(lower, jnp.roll(x, -shift, axis),
                              jnp.roll(x, shift, axis)) for x in lanes]
            # the lower slot of an ascending pair keeps the smaller tuple
            take = (lower == asc) == lex_gt_lanes(lanes, part)
            lanes = [jnp.where(take, y, x) for x, y in zip(lanes, part)]
            d //= 2
        size *= 2
    perm = lanes[-1].reshape(-1)[:total]
    return perm if pads is None else jnp.where(perm >= total, perm - total,
                                                perm)


def kway_ranks(cmp_runs, counts=None):
    """Merge-path rank of every element of every sorted run: a list of int32
    arrays (one per run) that together form a permutation of ``[0, total)``.

    ``cmp_runs[r]`` is run r's compare-lane tuple. Compare-equal elements
    order by run index (then by in-run index), so the ranks collide nowhere.

    The ranks are the inverse of :func:`_merge_perm`'s permutation, through
    one scatter. A tournament of pairwise binary-search rounds gives the
    same permutation, but on a TPU v5e its ~2,400 dependent small gathers
    took 443 ms a job at 7 x 32768 + 624 rows, 63% of the device time of
    the whole sort; the merge network has no gather at all.

    ``counts``: as :func:`_merge_perm`'s; the real rows then take the ranks
    ``[0, sum(counts))``, and each run's padding ranks above them, in
    order."""
    cmp_runs = [tuple(c) for c in cmp_runs]
    ns = [c[0].shape[0] for c in cmp_runs]
    total = sum(ns)
    if len(cmp_runs) == 1:
        return [jnp.arange(total, dtype=jnp.int32)]
    iota = jnp.arange(total, dtype=jnp.int32)
    ranks_flat = jnp.zeros((total,), jnp.int32).at[
        _merge_perm(cmp_runs, counts)].set(iota, unique_indices=True)
    bases = [sum(ns[:r]) for r in range(len(ns))]
    return [ranks_flat[b:b + n_r] for b, n_r in zip(bases, ns)]


def _cmp_runs(runs, n_cmp, max_values):
    if n_cmp is None:
        return [packed_cmp_lanes(list(r), max_values) for r in runs]
    return [tuple(r[:n_cmp]) for r in runs]


def merge_runs_kway_take(runs, n_cmp=None, max_values=None, counts=None):
    """jnp k-way merge: ONE fused key sort + ONE gather per lane (a single
    data pass; the tournament re-gathers every lane log2(k) times).

    The merge permutation comes from a stable ``lax.sort`` of the
    concatenated compare lanes — each mapped through ``lex.to_order_bits``
    so unsigned sort order IS the canonical lex order (floats included:
    ``-0.0`` collapses onto ``+0.0`` and every NaN onto the canonical slot
    above ``+inf``, exactly the comparator the oracle uses) — with an iota
    lane riding along: stable ties keep concatenation order, which is run
    index then in-run index, the k-way tie protocol. It is the order the
    Pallas tier's :func:`kway_ranks` computes with a merge network, which
    compiles in seconds on a TPU where this sort would take minutes at its
    sizes; off-TPU, where per-op dispatch dominates, one fused sort is the
    cheaper program. Traceable; runs are sequences of equal-arity lane
    tuples, any lengths.

    ``counts`` (k,) int32, where given, holds each run's real rows: a
    leading padding flag keeps the rows past them behind every real row,
    so the merge's first ``sum(counts)`` rows are the real ones."""
    runs = [list(r) for r in runs]
    cmp_runs = _cmp_runs(runs, n_cmp, max_values)
    nc = len(cmp_runs[0])
    total = sum(r[0].shape[0] for r in runs)
    keys = tuple(to_order_bits(concat_lanes([c[i] for c in cmp_runs]))
                 for i in range(nc))
    if counts is not None:
        keys = (concat_lanes([
            (jnp.arange(r[0].shape[0], dtype=jnp.int32) >= counts[i])
            .astype(jnp.uint32) for i, r in enumerate(runs)]),) + keys
    src = jnp.arange(total, dtype=jnp.int32)
    perm = lax.sort(keys + (src,), num_keys=len(keys), is_stable=True)[-1]
    return tuple(concat_lanes([r[i] for r in runs])[perm]
                 for i in range(len(runs[0])))


def _kway_kernel(cur_ref, nxt_ref, *refs, n_arr, n_runs, block):
    in_refs = refs[:n_arr]
    out_refs = refs[n_arr:2 * n_arr]
    scr = refs[2 * n_arr:3 * n_arr]
    sem = refs[3 * n_arr]
    k = pl.program_id(0)
    nb = pl.num_programs(0)

    # cur_ref[r, k % 128] / nxt_ref[r, (k + 1) % 128] are the ABSOLUTE
    # offsets of run r's segments for output blocks k and k+1 inside the
    # flat (run || sentinel-pad) concatenation, so the segment count is the
    # plain difference and every read stays in bounds. Each DMA fetches the
    # lane-tile-aligned window that holds the segment (segment_window: a DMA
    # may only start on a 128-lane tile boundary).
    def copy(i, r, start, slot):
        return pltpu.make_async_copy(
            in_refs[i].at[:, pl.ds(window_start(start), block + LANE_TILE)],
            scr[i].at[slot * n_runs + r], sem.at[slot, i, r])

    cur = lax.rem(k, LANE_TILE)
    nxt = lax.rem(k + 1, LANE_TILE)

    def stage(starts_ref, col, slot):
        for i in range(n_arr):
            for r in range(n_runs):
                copy(i, r, starts_ref[r, col], slot).start()

    # 2-slot double buffer: block k+1's k segment DMAs start into the
    # alternate slot before this block's are awaited, so the fetches for the
    # next block run under this block's merge network.
    slot = lax.rem(k, 2)

    @pl.when(k == 0)
    def _():
        stage(cur_ref, cur, 0)

    @pl.when(k + 1 < nb)
    def _():
        stage(nxt_ref, nxt, lax.rem(k + 1, 2))

    for i in range(n_arr):
        for r in range(n_runs):
            copy(i, r, cur_ref[r, cur], slot).wait()

    # Resident segments, tails masked to the lex-maximal sentinel tuple so
    # every window is sorted ascending and fills sink past real elements.
    segs = []
    for r in range(n_runs):
        start = cur_ref[r, cur]
        cnt = nxt_ref[r, nxt] - start
        segs.append(tuple(
            segment_window(scr[i][slot * n_runs + r], start, cnt, block)
            for i in range(n_arr)))

    # Loser tree at block granularity: pairwise elimination rounds; each
    # keeps the low `block` of an asc++asc merge. Real (non-fill) elements
    # of this output block number <= block in total, so no round's
    # truncation can drop one (anything truncated is sentinel fill or
    # interchangeable with it).
    while len(segs) > 1:
        nxt = []
        for j in range(0, len(segs) - 1, 2):
            cat = tuple(jnp.concatenate([a, b], axis=1)
                        for a, b in zip(segs[j], segs[j + 1]))
            nxt.append(tuple(m[:, :block]
                             for m in _merge_network(cat, block)))
        if len(segs) % 2:
            nxt.append(segs[-1])
        segs = nxt
    for ref, m in zip(out_refs, segs[0]):
        ref[...] = m


def kway_kernel_call(starts, *flat, nblocks, block, interpret=False):
    """The streaming kernel launch alone. ``starts``: (n_runs, C) int32,
    column j the runs' segment starts for output block j (C >= nblocks + 1,
    a multiple of 128); ``flat``: per lane, the (1, L) concatenation of the
    runs, each followed by ``block + 128`` sentinels. Returns one
    (1, nblocks * block) output per lane."""
    n_arr, n_runs = len(flat), starts.shape[0]
    col_blk = (n_runs, LANE_TILE)
    return pl.pallas_call(
        functools.partial(_kway_kernel, n_arr=n_arr, n_runs=n_runs,
                          block=block),
        out_shape=tuple(jax.ShapeDtypeStruct((1, nblocks * block), x.dtype)
                        for x in flat),
        grid=(nblocks,),
        in_specs=[pl.BlockSpec(col_blk, lambda k: (0, k // LANE_TILE),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(col_blk,
                               lambda k: (0, (k + 1) // LANE_TILE),
                               memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * n_arr,
        out_specs=tuple(pl.BlockSpec((1, block), lambda k: (0, k))
                        for _ in range(n_arr)),
        scratch_shapes=[pltpu.VMEM((2 * n_runs, 1, block + LANE_TILE),
                                   x.dtype) for x in flat]
        + [pltpu.SemaphoreType.DMA((2, n_arr, n_runs))],
        interpret=interpret,
        # the custom call takes its HLO name from the innermost name scope
        # around it: this one keeps it ``_kway_merge_jit.N`` inside the
        # caller's ``kway_kernel`` scope, the name device traces have always
        # shown for the kernel
        name="_kway_merge_jit",
    )(starts, starts, *flat)


@functools.partial(jax.jit, static_argnames=("n_arr", "n_runs", "n_cmp",
                                             "max_values", "block",
                                             "interpret"))
def _kway_merge_jit(*arrs, counts=None, n_arr, n_runs, n_cmp, max_values,
                    block, interpret):
    runs = [list(arrs[r * n_arr:(r + 1) * n_arr]) for r in range(n_runs)]
    ns = [r[0].shape[0] for r in runs]
    total = sum(ns)
    nblocks = -(-total // block)

    # one named scope per stage: the device trace's ops carry these names
    with jax.named_scope("kway_ranks"):
        ranks = kway_ranks(_cmp_runs(runs, n_cmp, max_values), counts)
    # flat layout: run r's lane at [base_r, base_r + ns[r]), then
    # `block + 128` sentinel fill slots — every segment DMA reads a full
    # in-bounds window (runmerge_kernel.pad_run).
    bases, off = [], 0
    for n_r in ns:
        bases.append(off)
        off += n_r + block + LANE_TILE
    # (n_runs, nblocks + 1): column j holds every run's segment start for
    # output block j. Step k reads columns k and k+1 through two SMEM blocks
    # of 128 columns of the same table (the whole table, prefetched into
    # SMEM, outgrows it past ~2^22 output elements).
    with jax.named_scope("kway_starts"):
        bounds = jnp.arange(nblocks + 1, dtype=jnp.int32) * block
        if counts is not None:
            # no block reaches past the real rows, so padding, which ranks
            # after them, never enters the kernel: the blocks beyond are
            # sentinel fill
            bounds = jnp.minimum(bounds, jnp.sum(counts))
        starts = jnp.stack([
            jnp.int32(bases[r]) + jnp.searchsorted(
                ranks[r], bounds, side="left").astype(jnp.int32)
            for r in range(n_runs)])
        starts = jnp.pad(starts, ((0, 0), (0, -(nblocks + 1) % LANE_TILE)))
    with jax.named_scope("kway_pad"):
        flat = [concat_lanes([pad_run(run[i], block) for run in runs], axis=1)
                for i in range(n_arr)]
    with jax.named_scope("kway_kernel"):
        out = kway_kernel_call(starts, *flat, nblocks=nblocks, block=block,
                               interpret=interpret)
        return tuple(o[0, :total] for o in out)


def merge_runs_kway_pallas(runs, n_cmp=None, max_values=None,
                           block: int | None = None,
                           interpret: bool = False, counts=None):
    """Merge k sorted lex-tuple runs (sequences of equal-arity tuples of
    parallel 1-D arrays, any lengths) in ONE kernel launch.

    ``n_cmp``: rank the split on the leading pre-packed compare lanes
    (``None`` packs rank keys from all lanes here); ``max_values``: per-lane
    bounds for that packing (hashable tuple). ``block`` must be a power of
    two >= 128. Empty runs drop host-side (static shapes); k == 1 returns
    the run as-is. VMEM holds 2*k segments per lane — practical k is a few
    dozen; past that, chunk the combine.

    ``counts``: each run's real rows (k,), as runs of a fixed shape whose
    tails are padding; the merge then holds ``sum(counts)`` real rows first
    and sentinel fill after them, and no run is dropped for its shape."""
    runs = [tuple(r) for r in runs]
    if max_values is not None:
        max_values = tuple(max_values)  # static under jit: must be hashable
    if not runs or not runs[0] or any(len(r) != len(runs[0]) for r in runs):
        raise ValueError("runs must share a non-zero lane arity")
    if any(x.ndim != 1 for r in runs for x in r):
        raise ValueError("runs must be tuples of 1-D arrays")
    block = DEFAULT_KWAY_BLOCK if block is None else block
    if block < 128 or block & (block - 1):
        raise ValueError("block must be a power of two >= 128")
    nonempty = runs if counts is not None else [r for r in runs
                                                if r[0].shape[0]]
    if not nonempty:
        return runs[0]
    if len(nonempty) == 1:
        return nonempty[0]
    with span("dispatch", program="_kway_merge_jit"):
        return _kway_merge_jit(*[x for r in nonempty for x in r],
                               counts=counts,
                               n_arr=len(runs[0]), n_runs=len(nonempty),
                               n_cmp=n_cmp, max_values=max_values,
                               block=block, interpret=interpret)


def merge_kway_pallas(runs, block: int | None = None,
                      interpret: bool = False):
    """Key-only special case of :func:`merge_runs_kway_pallas`."""
    (out,) = merge_runs_kway_pallas([(r,) for r in runs], block=block,
                                    interpret=interpret)
    return out
