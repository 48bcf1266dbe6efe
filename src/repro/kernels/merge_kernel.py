"""Pallas TPU kernel: cross-block odd-even *merge* passes.

This is the block-level analogue of one OETS compare-exchange: where the
in-block kernels swap neighbouring *lanes*, this kernel "swaps" neighbouring
*blocks* — each grid step loads two adjacent sorted blocks of ``block`` lanes
into VMEM and merges them, leaving the smaller half in the left block and the
larger half in the right. ``core/blocksort.py`` alternates even/odd pairings
of this kernel until the whole row is globally sorted, exactly as OETS
alternates even/odd lane pairings.

The merge itself is Batcher's odd-even merge network, which takes asc++asc
input as it is: one compare-exchange at distance ``B`` (lane ``i`` against
``i + B``), then ``log2(B)`` stages at distances ``B/2 .. 1`` (the same
two-rotate partner select as the bitonic sort kernel, with the lanes that
have no partner in the window left alone). ``log2(2B)`` phases total, all
lane-parallel VPU work, no gather/scatter and no lane reversal (a reverse
has no TPU lowering). ``block`` must be a power of two (the orchestrator
guarantees it).

Variadic like the in-block kernels: ``merge_adjacent_lex_pallas(*arrs)``
merges tuples of same-shape arrays by lexicographic compare
(``kernels/lex.py``); key-only and key-value are the 1- and 2-tuple cases.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lex import (lane_partners, lane_roll, lex_gt_lanes, map_lanes,
                  select_lanes)

__all__ = [
    "VMEM_LIMIT",
    "merge_rows_lex_kernel",
    "merge_adjacent_lex_pallas",
    "merge_adjacent_pallas",
    "merge_adjacent_kv_pallas",
]


# Scoped VMEM the kernel may use. At blocksort's largest blocks (8 rows x
# 2 * 32Ki lanes key-only, 2 * 8Ki lanes for 4 arrays) the double-buffered
# in/out blocks plus one stage's temporaries take 16.6-17 MiB, just over the
# compiler's default 16 MiB scope; a TPU v5e core has 128 MiB of VMEM.
VMEM_LIMIT = 32 * 1024 * 1024


def _first_stage(arrs, col, block):
    """Stage k = B: lane i < B against lane i + B, min to the low lane. The
    compare is full-tuple lex (see kernels/lex.py): trailing payload lanes
    break ties, so padding tuples (sentinel, ..., sentinel) stay strictly
    maximal and can never displace a real payload that shares the sentinel
    key."""
    partners = map_lanes(lambda a: lane_roll(a, block), arrs)
    lower = col < block
    swap = ((lower & lex_gt_lanes(arrs, partners))
            | (~lower & lex_gt_lanes(partners, arrs)))
    return tuple(select_lanes(swap, partners, arrs))


def _stage(arrs, col, block, t):
    """Stage t of k = B/2 .. 1 (k = B >> (t + 1), t may be a loop value):
    lane i with bit k set takes the min against i + k, lane i + k the max;
    lanes with no partner in the window (the first k and the last k) stay.
    Swaps combine as boolean ops: a select between boolean vectors has no
    TPU lowering."""
    k = block >> (t + 1)
    bit_set = (col & k) != 0
    low = bit_set & (col < 2 * block - k)
    high = ~bit_set & (col >= 2 * k)
    partners = lane_partners(arrs, bit_set, k)
    swap = ((low & lex_gt_lanes(arrs, partners))
            | (high & lex_gt_lanes(partners, arrs)))
    return tuple(select_lanes(swap, partners, arrs))


def _merge_network(arrs, block):
    """Merge (RB, 2*block) rows whose halves are each sorted ascending —
    Batcher's odd-even merge, ``log2(2B)`` stages of lane rotates. The
    stages after the first run as a loop with k a loop value, which keeps
    one stage in the kernel's code instead of log2(B)."""
    col = lax.broadcasted_iota(jnp.int32, arrs[0].shape, 1)
    # Float tuples can compare equal with different bits (-0.0 and +0.0,
    # NaN payloads). The window position then breaks the tie, so those keep
    # the merge oracle's order (left half first, each half in order).
    floats = any(jnp.issubdtype(a.dtype, jnp.floating) for a in arrs)
    arrs = tuple(arrs) + ((col,) if floats else ())
    arrs = _first_stage(arrs, col, block)
    arrs = lax.fori_loop(0, block.bit_length() - 1,
                         lambda t, a: _stage(a, col, block, t), arrs)
    return list(arrs[:-1] if floats else arrs)


def merge_rows_lex_kernel(*refs, block):
    """The network of :func:`_merge_network`, run in place on the output
    refs: a loop that carried the (RB, 2*block) windows as values would need
    VMEM for two copies of them."""
    n = len(refs) // 2
    outs = refs[n:]
    col = lax.broadcasted_iota(jnp.int32, outs[0].shape, 1)

    def store(arrs):
        for o, a in zip(outs, arrs):
            o[...] = a

    store(_first_stage(tuple(r[...] for r in refs[:n]), col, block))

    def body(t, carry):
        store(_stage(tuple(o[...] for o in outs), col, block, t))
        return carry

    lax.fori_loop(0, block.bit_length() - 1, body, 0)


def _row_block(rows: int) -> int:
    return min(rows, 8)


def _check(rows, cols, block, row_block):
    if block < 1 or block & (block - 1):
        raise ValueError("block must be a power of two")
    if cols % (2 * block):
        raise ValueError("cols must cover whole pairs of blocks")
    rb = row_block or _row_block(rows)
    if rows % rb:
        raise ValueError("rows must be a multiple of the row block")
    return rb, cols // (2 * block)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "row_block"))
def merge_adjacent_lex_pallas(*arrs, block: int, interpret: bool = False,
                              row_block: int | None = None):
    """One merge round over (R, npairs * 2 * block): pair p (cols
    [2pB, 2pB+2B)) is merged in place, comparing full lexicographic tuples.
    Each pair's halves must be sorted ascending; the caller slices the row to
    select even or odd pairing. Returns the merged tuple."""
    rows, cols = arrs[0].shape
    rb, npairs = _check(rows, cols, block, row_block)
    kern = functools.partial(merge_rows_lex_kernel, block=block)
    spec = pl.BlockSpec((rb, 2 * block), lambda i, j: (i, j))
    return pl.pallas_call(
        kern,
        out_shape=tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrs),
        grid=(rows // rb, npairs),
        in_specs=[spec] * len(arrs),
        out_specs=tuple([spec] * len(arrs)),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*arrs)


def merge_adjacent_pallas(x, *, block: int, interpret: bool = False,
                          row_block: int | None = None):
    """Key-only special case."""
    (out,) = merge_adjacent_lex_pallas(x, block=block, interpret=interpret,
                                       row_block=row_block)
    return out


def merge_adjacent_kv_pallas(keys, vals, *, block: int, interpret: bool = False,
                             row_block: int | None = None):
    """Key-value special case: the payload is the 2nd (tie-break) lane."""
    return merge_adjacent_lex_pallas(keys, vals, block=block,
                                     interpret=interpret, row_block=row_block)
