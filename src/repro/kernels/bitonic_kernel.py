"""Pallas TPU kernel: bitonic network sort along vector lanes (beyond-paper).

Same layout as the OETS kernel ((ROW_BLOCK, cols) in VMEM, one bucket per
sublane row) but O(log^2 cols) phases instead of cols. The XOR-partner
shuffle is expressed as two lane ``roll``s + a bit-select, which lowers to
cheap lane permutes on the VPU — no gather. cols must be a power of two
(ops.py pads with the dtype's max sentinel).

Variadic like the OETS kernel: ``bitonic_rows_lex_pallas(*arrs)`` sorts
tuples of same-shape arrays by lexicographic compare (``kernels/lex.py``);
key-only and key-value are the 1- and 2-tuple special cases.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .lex import lane_partners, lex_gt_lanes, select_lanes

__all__ = [
    "bitonic_rows_lex_kernel",
    "bitonic_rows_lex_pallas",
    "bitonic_rows_pallas",
    "bitonic_rows_kv_pallas",
]


def _stage(arrs, col, j, direction_asc):
    """Compare-exchange with partner col ^ j; ascending where mask True."""
    bit_unset = (col & j) == 0
    # partner value: col+j for bit-unset lanes, col-j otherwise.
    partners = lane_partners(arrs, bit_unset, j)
    # Full-tuple lex compare (trailing payload lanes are the tie-break):
    # keeps the all-sentinel padding tuple strictly maximal so it cannot
    # displace a real payload when a real key equals the sentinel
    # (long-distance swaps are not stable).
    gt = lex_gt_lanes(arrs, partners)
    lt = lex_gt_lanes(partners, arrs)
    # the lane keeps the min iff its direction agrees with its side; boolean
    # ops, since a select between boolean vectors has no TPU lowering
    take_min = ~(direction_asc ^ bit_unset)
    swap = (take_min & gt) | (~take_min & lt)
    return select_lanes(swap, partners, arrs)


def bitonic_rows_lex_kernel(*refs):
    """The network runs in place on the output refs as two nested loops
    (merge stage, then partner distance) with the distance a loop value, so
    the kernel's code holds one compare-exchange, not log^2(cols) unrolled
    copies of it — unrolled, an 8192-lane block took minutes to compile."""
    n = len(refs) // 2
    outs = refs[n:]
    for r, o in zip(refs[:n], outs):
        o[...] = r[...]
    ncols = outs[0].shape[1]
    col = lax.broadcasted_iota(jnp.int32, outs[0].shape, 1)

    def stage(s, carry):
        direction_asc = (col & (1 << s)) == 0

        def sub(t, carry):
            j = 1 << (s - 1 - t)
            arrs = _stage(tuple(o[...] for o in outs), col, j, direction_asc)
            for o, a in zip(outs, arrs):
                o[...] = a
            return carry

        return lax.fori_loop(0, s, sub, carry)

    lax.fori_loop(1, int(math.log2(ncols)) + 1, stage, 0)


def _row_block(rows: int) -> int:
    return min(rows, 8)


@functools.partial(jax.jit, static_argnames=("interpret", "row_block"))
def bitonic_rows_lex_pallas(*arrs, interpret: bool = False,
                            row_block: int | None = None):
    """Sort each row of the (R, C) tuple ``arrs`` ascending by lexicographic
    tuple compare; C must be a power of two (pad in ops.py)."""
    rows, cols = arrs[0].shape
    if cols & (cols - 1):
        raise ValueError("cols must be a power of two (pad in ops.py)")
    rb = row_block or _row_block(rows)
    spec = pl.BlockSpec((rb, cols), lambda i: (i, 0))
    return pl.pallas_call(
        bitonic_rows_lex_kernel,
        out_shape=tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrs),
        grid=(rows // rb,),
        in_specs=[spec] * len(arrs),
        out_specs=tuple([spec] * len(arrs)),
        interpret=interpret,
    )(*arrs)


def bitonic_rows_pallas(x, *, interpret: bool = False, row_block: int | None = None):
    """Key-only special case."""
    (out,) = bitonic_rows_lex_pallas(x, interpret=interpret, row_block=row_block)
    return out


def bitonic_rows_kv_pallas(keys, vals, *, interpret: bool = False,
                           row_block: int | None = None):
    """Key-value special case: the payload is the 2nd (tie-break) lane."""
    return bitonic_rows_lex_pallas(keys, vals, interpret=interpret,
                                   row_block=row_block)
