"""Pallas TPU kernel: block-parallel merge-path combine of two sorted runs.

The MPI follow-up paper's profile (merge dominating once local sorts are
fast) is exactly our stack post-PR 4: chunked ingest produces kernel-sorted
runs, but the run *combine* was a jnp-level rank + one HBM-wide scatter.
This kernel keeps the combine in VMEM tiles instead:

  1. **Diagonal partition** (host jnp, inside the same jit): merge-path
     ranks of run ``a`` against run ``b`` come from the packed rank-key
     binary search (``kernels/keypack.py`` — O(n log n) gathers, never the
     O(|a|·|b|) broadcast), and one ``searchsorted`` over those ranks yields
     for every output block of ``block`` slots the exact source segments
     ``a[sa:ea)`` / ``b[sb:eb)`` with ``(ea-sa) + (eb-sb) == block``.
  2. **Per-block VMEM merge**: each grid step DMAs its two segments (via
     scalar-prefetched starts — the segments land at data-dependent offsets
     no BlockSpec can express), masks the tails to the lex-maximal sentinel
     tuple, and runs the same asc++asc merge network the cross-block kernel
     uses (``merge_kernel._merge_network``) on the ``2*block`` window;
     the low half is the finished output block. No HBM scatter anywhere.

Variadic like every engine in this package: lanes merge as one lex tuple
(lane 0 most significant, trailing lanes are payload tie-breaks). ``n_cmp``
lets a caller that pre-packed rank keys (the pipeline tournament) rank the
diagonal on the leading compare lanes only; the in-block network still
compares the full tuple, which is consistent because the compare prefix is
an order-preserving refinement.

A DMA may only start on a 128-lane tile boundary, so each segment is fetched
as the ``block + 128``-lane window that starts at the tile boundary below it
and rotated into place in VMEM. Both runs are padded with ``block + 128``
sentinel elements so every such window stays in bounds; output blocks beyond
``|a|+|b|`` hold sentinel fill and are sliced off. Equal tuples are
interchangeable values, so the output is bit-identical to the lane-wise
``lex_merge_take`` oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .keypack import lex_searchsorted, packed_cmp_lanes
from .lex import LANE_TILE, pad_run, segment_window, window_start
from .merge_kernel import _merge_network

__all__ = ["DEFAULT_MERGE_BLOCK", "merge_runs_lex_pallas", "merge_runs_pallas"]

# one output tile per grid step; 2*block lanes of every array live in VMEM
DEFAULT_MERGE_BLOCK = 256


def _runmerge_kernel(starts_ref, *refs, n_arr, block):
    a_refs = refs[:n_arr]
    b_refs = refs[n_arr:2 * n_arr]
    out_refs = refs[2 * n_arr:3 * n_arr]
    scr = refs[3 * n_arr:4 * n_arr]
    sem = refs[4 * n_arr]
    k = pl.program_id(0)
    sa, ea = starts_ref[0, k], starts_ref[0, k + 1]
    sb, eb = starts_ref[1, k], starts_ref[1, k + 1]

    copies = []
    for i in range(n_arr):
        for side, (refs_, s) in enumerate(((a_refs, sa), (b_refs, sb))):
            c = pltpu.make_async_copy(
                refs_[i].at[:, pl.ds(window_start(s), block + LANE_TILE)],
                scr[i].at[side], sem.at[2 * i + side])
            c.start()
            copies.append(c)
    for c in copies:
        c.wait()

    # window layout: a-segment in cols [0, block), b-segment in [block, 2B).
    # Positions past each segment's count are masked to the sentinel tuple
    # (lex-maximal under the full-tuple compare), so both halves stay sorted
    # ascending and the fills sink past every real element of the block.
    arrs = tuple(
        jnp.concatenate([segment_window(s[0], sa, ea - sa, block),
                         segment_window(s[1], sb, eb - sb, block)], axis=1)
        for s in scr)
    merged = _merge_network(arrs, block)
    for r, m in zip(out_refs, merged):
        r[...] = m[:, :block]


@functools.partial(jax.jit, static_argnames=("n_arr", "n_cmp", "max_values",
                                             "block", "interpret"))
def _merge_runs_jit(*arrs, n_arr, n_cmp, max_values, block, interpret):
    a_lanes = list(arrs[:n_arr])
    b_lanes = list(arrs[n_arr:])
    na, nb = a_lanes[0].shape[0], b_lanes[0].shape[0]
    total = na + nb
    nblocks = -(-total // block)

    if n_cmp is None:
        cmp_a = packed_cmp_lanes(a_lanes, max_values)
        cmp_b = packed_cmp_lanes(b_lanes, max_values)
    else:
        cmp_a, cmp_b = a_lanes[:n_cmp], b_lanes[:n_cmp]
    # merge-path ranks of a (a wins ties, mirroring lex_merge_take), then the
    # diagonal: a_starts[k] = #a-elements among the first k*block outputs.
    # rank_a ascends, so this is one searchsorted over the block boundaries.
    rank_a = jnp.arange(na, dtype=jnp.int32) + lex_searchsorted(
        cmp_b, cmp_a, side="left").astype(jnp.int32)
    bounds = jnp.arange(nblocks + 1, dtype=jnp.int32) * block
    a_starts = jnp.searchsorted(rank_a, bounds, side="left").astype(jnp.int32)
    b_starts = jnp.clip(bounds - a_starts, 0, nb).astype(jnp.int32)
    starts = jnp.stack([a_starts, b_starts])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (2 * n_arr),
        out_specs=tuple(pl.BlockSpec((1, block), lambda k, s: (0, k))
                        for _ in range(n_arr)),
        scratch_shapes=[pltpu.VMEM((2, 1, block + LANE_TILE), a.dtype)
                        for a in a_lanes]
        + [pltpu.SemaphoreType.DMA((2 * n_arr,))],
    )
    out = pl.pallas_call(
        functools.partial(_runmerge_kernel, n_arr=n_arr, block=block),
        out_shape=tuple(jax.ShapeDtypeStruct((1, nblocks * block), a.dtype)
                        for a in a_lanes),
        grid_spec=grid_spec,
        interpret=interpret,
    )(starts, *[pad_run(a, block) for a in a_lanes],
      *[pad_run(b, block) for b in b_lanes])
    return tuple(o[0, :total] for o in out)


def merge_runs_lex_pallas(a_lanes, b_lanes, n_cmp=None, max_values=None,
                          block: int | None = None, interpret: bool = False):
    """Merge two sorted lex-tuple runs (tuples of parallel 1-D arrays, any
    lengths) into one sorted run with the block-parallel merge-path kernel.

    ``n_cmp``: rank the diagonal on the leading ``n_cmp`` pre-packed compare
    lanes (``None`` packs rank keys from all lanes here); ``max_values``:
    per-lane bounds for the packing (hashable tuple). ``block`` must be a
    power of two >= 128 (the merge network and lane tile demand it)."""
    a_lanes, b_lanes = list(a_lanes), list(b_lanes)
    if max_values is not None:
        max_values = tuple(max_values)  # static under jit: must be hashable
    if len(a_lanes) != len(b_lanes) or not a_lanes:
        raise ValueError("runs must share a non-zero lane arity")
    if any(a.ndim != 1 for a in a_lanes + b_lanes):
        raise ValueError("runs must be tuples of 1-D arrays")
    block = DEFAULT_MERGE_BLOCK if block is None else block
    if block < 128 or block & (block - 1):
        raise ValueError("block must be a power of two >= 128")
    if a_lanes[0].shape[0] == 0:
        return tuple(b_lanes)
    if b_lanes[0].shape[0] == 0:
        return tuple(a_lanes)
    return _merge_runs_jit(*a_lanes, *b_lanes, n_arr=len(a_lanes),
                           n_cmp=n_cmp, max_values=max_values, block=block,
                           interpret=interpret)


def merge_runs_pallas(a, b, block: int | None = None,
                      interpret: bool = False):
    """Key-only special case of :func:`merge_runs_lex_pallas`."""
    (out,) = merge_runs_lex_pallas([a], [b], block=block, interpret=interpret)
    return out
