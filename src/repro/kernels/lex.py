"""The canonical total-order key plane shared by every comparator tier.

Every comparator engine in this package (OETS, bitonic, cross-block merge,
merge-path run merge) reduces to the same primitive: compare two tuples of
per-lane arrays lane-by-lane and swap *all* lanes together. The paper's
multi-character words pack into multiple uint32 lanes (``core/packing.py``),
so the compare-exchange must break ties lane-by-lane — exactly the
``(key, val)`` compare the kv kernels already did, generalised to any
number of lanes.

There is exactly ONE definition of "less than" in this codebase, and it
lives here: :func:`to_order_bits` maps each lane into uint32 *order bits*
whose unsigned order is the lane's total order — unsigned ints pass
through, signed ints flip the sign bit (or shift, for narrow dtypes), and
float32 takes the IEEE total-order flip with ``-0.0`` normalised to
``+0.0`` and **every NaN canonicalised strictly above ``+inf``** (the
all-ones bit pattern, which is the float padding sentinel, sits strictly
above the other NaNs). ``lex_gt_lanes`` compares order bits but engines
swap the *raw* values, so outputs conserve the input bit multiset exactly
while NaNs sink to the tail — ``jnp.sort``-equivalent semantics. The
packed rank keys of ``kernels/keypack.py`` are the concatenated-bits
special case of this same representation.

Conventions shared by all engines:

  * A sort operates on a tuple ``arrs = (k0, k1, ..., v...)`` of same-shape
    2-D arrays. *Every* array participates in the compare, in tuple order:
    leading entries are key lanes (most-significant first), trailing entries
    are payloads that double as final tie-breaks. Payloads therefore ride
    the exact permutation the keys choose, and the all-sentinel padding
    tuple stays strictly lex-maximal unless a real element equals the
    sentinel in **every** lane (see ``ops.sort_lex`` for the contract).
  * Partner selection (roll / flip / XOR-shuffle) is applied identically to
    every lane before comparing, so the helpers here take *lists* of arrays
    and return element-wise boolean masks ready for ``jnp.where``.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax

__all__ = ["to_order_bits", "from_order_bits", "order_view",
           "lex_gt_lanes", "lex_rank_count", "lex_merge_take", "map_lanes",
           "lane_roll", "lane_partners", "select_lanes", "sentinel_for",
           "where_sentinel", "concat_lanes", "LANE_TILE", "window_start",
           "segment_window", "pad_run"]

# lanes of one TPU vector tile; a DMA may only start on a multiple of it
LANE_TILE = 128

# Plain python ints, NOT module-level jnp scalars: these helpers run inside
# Pallas kernel bodies, which refuse closed-over array constants. The
# ``jnp.uint32(...)`` wrapping happens inside each function, where a 0-d
# scalar traces as a jaxpr literal.
_TOP = 0x80000000
# float32 order-bit layout above +inf (0xFF800000): every NaN bit pattern
# canonicalises to one slot, except the all-ones pattern — the float padding
# sentinel — which owns the strict maximum. A bijection with all ~2^24 NaN
# patterns above +inf is impossible in 32 bits, so the transform is
# compare-only for NaNs: engines compare order bits and swap raw values,
# which is exactly what conserves the bit-level multiset.
_F32_NAN_ORDER = 0xFFFFFFFE
_F32_SENTINEL_ORDER = 0xFFFFFFFF
_F32_SENTINEL_BITS = 0xFFFFFFFF
_F32_CANONICAL_NAN_BITS = 0x7FC00000  # quiet NaN, for unpacking


def sentinel_for(dtype):
    """The lex-maximal padding value of ``dtype``: ``iinfo.max`` for ints —
    including signed, where it is the positive max — and for floats the
    all-ones-bits NaN, which :func:`to_order_bits` places strictly above
    every other value *including* other NaNs, so padding can never strand
    inside a row that holds real NaNs. The padding contract every engine in
    this package shares; see ``ops.sort_lex`` for the full discussion."""
    if jnp.dtype(dtype) == jnp.dtype(jnp.float32):
        # constructed by bitcast, never via a float literal (a python-level
        # float() round-trip would canonicalise the NaN payload)
        return lax.bitcast_convert_type(jnp.uint32(_F32_SENTINEL_BITS),
                                        jnp.float32)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.nan, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def _is_f32(x):
    return jnp.dtype(x.dtype) == jnp.dtype(jnp.float32)


def where_sentinel(keep, x):
    """``jnp.where(keep, x, sentinel_for(x.dtype))`` that conserves float32
    bits: the select runs on the uint32 bits, because inside a TPU kernel a
    scalar float bitcast has no lowering and a float NaN scalar may be
    canonicalised on its way into the vector."""
    if _is_f32(x):
        bits = lax.bitcast_convert_type(x, jnp.uint32)
        return lax.bitcast_convert_type(
            jnp.where(keep, bits, jnp.uint32(_F32_SENTINEL_BITS)), jnp.float32)
    return jnp.where(keep, x, sentinel_for(x.dtype))


def concat_lanes(xs, axis: int = 0):
    """``jnp.concatenate`` that conserves float32 bits. XLA on TPU may lower
    a float concatenate to padded operands joined by ``maximum``, which
    canonicalises NaN payloads; the uint32 bits concatenate instead. The
    barrier keeps the compiler from folding the bitcasts back into a float
    concatenate."""
    if _is_f32(xs[0]):
        bits = lax.optimization_barrier(
            [lax.bitcast_convert_type(x, jnp.uint32) for x in xs])
        return lax.bitcast_convert_type(jnp.concatenate(bits, axis=axis),
                                        jnp.float32)
    return jnp.concatenate(xs, axis=axis)


def to_order_bits(x, max_value: Optional[int] = None):
    """Order-preserving uint32 embedding of one lane — the canonical key
    transform every comparator tier shares (the packed rank keys of
    ``kernels/keypack.py`` concatenate these same bits).

    ``max_value`` asserts a ``[0, max_value]`` range on an integer lane
    (values cast directly); otherwise signed ints shift by 2^(bits-1),
    unsigned ints pass through, and float32 maps via the IEEE total-order
    flip with ``-0.0`` normalised to ``+0.0`` (order-bit equality coincides
    with ``==`` on non-NaN values) and every NaN canonicalised above
    ``+inf`` — the all-ones pattern (the padding sentinel) strictly above
    the rest. The NaN collapse makes the float transform compare-only:
    engines compare order bits but always swap the raw lanes."""
    dt = jnp.dtype(x.dtype)
    if max_value is not None:
        if not jnp.issubdtype(dt, jnp.integer):
            raise TypeError("max_values only applies to integer lanes")
        return x.astype(jnp.uint32)
    if dt == jnp.dtype(jnp.float32):
        top = jnp.uint32(_TOP)
        b = lax.bitcast_convert_type(x, jnp.uint32)
        xn = jnp.where(x == 0, jnp.zeros_like(x), x)  # -0.0 -> +0.0
        bn = lax.bitcast_convert_type(xn, jnp.uint32)
        flipped = jnp.where((bn & top) != 0, ~bn, bn | top)
        nan_slot = jnp.where(b == jnp.uint32(_F32_SENTINEL_BITS),
                             jnp.uint32(_F32_SENTINEL_ORDER),
                             jnp.uint32(_F32_NAN_ORDER))
        return jnp.where(jnp.isnan(x), nan_slot, flipped)
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return x.astype(jnp.uint32)
    if jnp.issubdtype(dt, jnp.signedinteger):
        if dt.itemsize == 4:
            return lax.bitcast_convert_type(x, jnp.uint32) ^ jnp.uint32(_TOP)
        # int8/int16: shift into [0, 2^bits) so the value fits `bits` bits
        half = 1 << (dt.itemsize * 8 - 1)
        return (x.astype(jnp.int32) + half).astype(jnp.uint32)
    raise TypeError(f"cannot order-transform lanes of dtype {dt}")


def from_order_bits(v, dtype, max_value: Optional[int] = None):
    """Invert :func:`to_order_bits` — exactly for integer lanes; for float32
    the inverse is *canonical*, not bijective: ``-0.0`` comes back as
    ``+0.0``, the sentinel order slot returns the all-ones-bits NaN, and
    the collapsed NaN slot returns the canonical quiet NaN. Callers that
    must conserve float bits carry the original lanes through the
    permutation instead of unpacking (see ``ops.sort_lex``)."""
    dt = jnp.dtype(dtype)
    if max_value is not None:
        return v.astype(dt)
    if dt == jnp.dtype(jnp.float32):
        top = jnp.uint32(_TOP)
        b = jnp.where((v & top) != 0, v ^ top, ~v)
        b = jnp.where(v == jnp.uint32(_F32_NAN_ORDER),
                      jnp.uint32(_F32_CANONICAL_NAN_BITS), b)
        b = jnp.where(v == jnp.uint32(_F32_SENTINEL_ORDER),
                      jnp.uint32(_F32_SENTINEL_BITS), b)
        return lax.bitcast_convert_type(b, jnp.float32)
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return v.astype(dt)
    if dt.itemsize == 4:
        return lax.bitcast_convert_type(v ^ jnp.uint32(_TOP), jnp.int32)
    half = 1 << (dt.itemsize * 8 - 1)
    return (v.astype(jnp.int32) - half).astype(dt)


def order_view(a):
    """The comparator's view of one lane: order bits for float lanes (NaN
    total order), the raw values for integer lanes (already totally ordered
    — the transform would only add work)."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        return to_order_bits(a)
    return a


def lex_gt_lanes(a_lanes, b_lanes):
    """Element-wise lexicographic ``a > b`` over parallel lane lists —
    THE "less than" of this codebase.

    ``a_lanes``/``b_lanes``: equal-length sequences of same-shape arrays.
    Lane 0 is most significant; later lanes break ties. Returns a boolean
    array of the common shape. Dtypes may differ per lane; each lane
    compares within its own :func:`order_view`, so float lanes follow the
    canonical total order (NaNs above ``+inf``, ``-0.0 == +0.0``, padding
    sentinel strictly maximal) while integer lanes compare raw.
    """
    a_lanes = [order_view(a) for a in a_lanes]
    b_lanes = [order_view(b) for b in b_lanes]
    a0, b0 = a_lanes[0], b_lanes[0]
    gt = a0 > b0
    if len(a_lanes) == 1:
        return gt
    eq = a0 == b0
    for a, b in zip(a_lanes[1:-1], b_lanes[1:-1]):
        gt = gt | (eq & (a > b))
        eq = eq & (a == b)
    a, b = a_lanes[-1], b_lanes[-1]
    return gt | (eq & (a > b))


def lex_rank_count(a_lanes, b_lanes, strict):
    """For each element of ``b``: how many elements of ``a`` are lex-below
    it (``strict``) or lex-at-or-below it (``not strict``). O(|a|·|b|)
    broadcast compare — the merge-path rank at block granularity, kept as
    the *differential oracle* for the packed rank-key fast path
    (``kernels/keypack.py``: ``lex_searchsorted`` computes the same counts
    in O(|b| log |a|) gathers; the production merges all route there)."""
    a2 = [a[:, None] for a in a_lanes]
    b2 = [b[None, :] for b in b_lanes]
    cmp = lex_gt_lanes(b2, a2) if strict else ~lex_gt_lanes(a2, b2)
    return jnp.sum(cmp, axis=0)


def lex_merge_take(a_lanes, b_lanes):
    """Merge two *sorted* lex-tuple runs into one sorted run of length
    ``|a| + |b|`` via merge-path rank + scatter (no re-sort).

    Each element's output position is its rank in the merged sequence:
    own index + count of smaller elements in the other run — strict one way,
    non-strict the other, so equal tuples get distinct ranks and every
    output slot is written exactly once. Key-only runs rank in O(n log n)
    via ``searchsorted``; wider tuples pay the O(|a|·|b|) broadcast compare
    here — this is the lane-wise *oracle*; production merges use
    ``keypack.merge_take_packed`` / ``ops.merge_sorted_lex``, which rank
    every arity in O(n log n). Runs may have different lengths.
    """
    a_lanes, b_lanes = list(a_lanes), list(b_lanes)
    na, nb = a_lanes[0].shape[0], b_lanes[0].shape[0]
    if len(a_lanes) == 1:
        a0, b0 = order_view(a_lanes[0]), order_view(b_lanes[0])
        rank_a = jnp.arange(na) + jnp.searchsorted(b0, a0, side="left")
        rank_b = jnp.arange(nb) + jnp.searchsorted(a0, b0, side="right")
    else:
        rank_a = jnp.arange(na) + lex_rank_count(b_lanes, a_lanes, strict=True)
        rank_b = jnp.arange(nb) + lex_rank_count(a_lanes, b_lanes,
                                                 strict=False)
    out = []
    for a, b in zip(a_lanes, b_lanes):
        o = jnp.zeros((na + nb,), a.dtype)
        out.append(o.at[rank_a].set(a).at[rank_b].set(b))
    return out


def map_lanes(fn, arrs):
    """Apply ``fn`` (a partner shuffle: roll/flip/...) to every lane."""
    return [fn(a) for a in arrs]


def lane_roll(a, shift: int):
    """``jnp.roll(a, shift, axis=-1)`` as the TPU's lane rotate
    (``pltpu.roll``, which takes non-negative shifts only; ``shift`` may be
    a loop value). Interpret mode evaluates it as ``jnp.roll``."""
    from jax.experimental.pallas import tpu as pltpu
    axis = a.ndim - 1
    return pltpu.roll(a, shift % a.shape[axis], axis)


def window_start(start):
    """The lane-tile boundary at or below ``start``, where the DMA of a
    segment that starts at ``start`` begins: a DMA may only start on a tile
    boundary. ``start`` is non-negative."""
    from jax.experimental import pallas as pl
    return pl.multiple_of(start - (start & (LANE_TILE - 1)), LANE_TILE)


def segment_window(win, start, cnt, block: int):
    """The segment ``[start, start + cnt)`` out of the ``(1, block + 128)``
    window fetched from :func:`window_start`: rotated to lane 0, cut to
    ``block`` lanes, and masked past ``cnt`` to the sentinel."""
    seg = lane_roll(win, -(start & (LANE_TILE - 1)))[:, :block]
    col = lax.broadcasted_iota(jnp.int32, (1, block), 1)
    return where_sentinel(col < cnt, seg)


def pad_run(a, block: int):
    """Run ``a`` as a ``(1, n + block + 128)`` row with sentinel fill: a
    segment window starts up to one lane tile before its segment and spans
    ``block + 128`` lanes, so the last one reaches that far past the run."""
    fill = jnp.full((block + LANE_TILE,), sentinel_for(a.dtype), a.dtype)
    return concat_lanes([a, fill])[None, :]


def lane_partners(arrs, up, j: int):
    """Every lane's partner at distance ``j``: lane ``i`` reads lane
    ``i + j`` where ``up`` is True and lane ``i - j`` elsewhere."""
    return [jnp.where(up, lane_roll(a, -j), lane_roll(a, j)) for a in arrs]


def select_lanes(mask, on_true, on_false):
    """``jnp.where`` broadcast across parallel lane lists (the swap step)."""
    return [jnp.where(mask, t, f) for t, f in zip(on_true, on_false)]
