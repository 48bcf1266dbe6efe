"""Pallas TPU kernel: the paper's *distribute* phase (phases 1-2) on device.

"Distributing the elements of the input datasets into many additional
temporary sub-arrays according to a number of characters in each word" used
to be a host-side Python dict loop (``core/bucketing.bucketize_words``).
This kernel is that loop as one sequential-grid VMEM sweep over the packed
word tensor: for every word it emits

  * its byte **length** (= destination bucket id, since buckets are dense
    per-length: bucket ``l`` holds exactly the words of length ``l``),
  * its **stable rank** within that bucket (arrival order preserved), and
  * the running per-length **histogram** (the paper's phase-1 count pass),

so the caller can place every word with a single device scatter
(``ops.bucketize``) — no gather inside the kernel, no host loop outside it.

Layout: words live along the 128-lane axis — the input is the *transposed*
packed matrix ``(lanes, n)`` so one ``(lanes, C)`` block holds C complete
words. Byte lengths come from the big-endian packing contract of
``core/packing.py``: length = position of the last non-zero byte (interior
NUL bytes therefore count toward the length, matching ``unpack_words``;
*trailing* NUL bytes are unrecoverable after packing — by design).

Stable ranks need a prefix over all earlier words, which is exactly what the
TPU grid's sequential execution provides: the histogram output block is
revisited by every grid step (its index_map is constant), so it carries the
running counts from block to block — each step reads the pre-update counts
(= ranks of its first element per bucket), adds its block histogram, and
writes back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .lex import lane_roll

__all__ = ["distribute_rows_kernel", "distribute_rows_pallas"]


def distribute_rows_kernel(keys_ref, dest_ref, rank_ref, cnt_ref, *,
                           n_valid, num_buckets, col_block):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    x = keys_ref[...]                         # (lanes, C) uint32, big-endian
    # byte length = last non-zero byte position + 1 (0 for the empty word)
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    last = jnp.zeros(x.shape, jnp.int32)
    for k, shift in enumerate((24, 16, 8, 0)):
        byte = (x >> shift) & jnp.uint32(0xFF)
        last = jnp.maximum(last, jnp.where(byte != 0, 4 * lane + k + 1, 0))
    length = jnp.max(last, axis=0, keepdims=True)        # (1, C)

    col = j * col_block + lax.broadcasted_iota(jnp.int32, length.shape, 1)
    valid = col < n_valid
    dest = jnp.where(valid, length, num_buckets)         # invalid -> discard id
    dest_ref[...] = dest

    # Stable rank: within-block exclusive prefix count of same-destination
    # words, offset by the running (pre-block) histogram carried in cnt_ref.
    running = cnt_ref[...]                               # (1, B_pad)
    bucket = lax.broadcasted_iota(jnp.int32, running.shape, 1)
    rank = jnp.zeros_like(dest)
    block_hist = jnp.zeros_like(running)
    for p in range(num_buckets):                         # static, <= 4*lanes+1
        m = (dest == p).astype(jnp.int32)
        incl = _prefix_sum_lanes(m)
        base = running[:, p:p + 1]                       # (1, 1)
        rank = jnp.where(m == 1, incl - 1 + base, rank)
        block_hist = jnp.where(bucket == p, incl[:, col_block - 1:], block_hist)
    cnt_ref[...] = running + block_hist
    rank_ref[...] = rank


def _prefix_sum_lanes(m):
    """Inclusive prefix sum along the lanes of a (1, C) int32 row:
    ceil(log2 C) rotate-and-add steps (Hillis-Steele). ``cumsum`` has no
    Pallas TPU lowering."""
    col = lax.broadcasted_iota(jnp.int32, m.shape, 1)
    step = 1
    while step < m.shape[1]:
        m = m + jnp.where(col >= step, lane_roll(m, step), 0)
        step *= 2
    return m


@functools.partial(jax.jit, static_argnames=("n_valid", "num_buckets",
                                             "interpret", "col_block"))
def distribute_rows_pallas(keys_t, *, n_valid: int, num_buckets: int,
                           interpret: bool = False, col_block: int = 128):
    """keys_t: (lanes, n_pad) uint32, words along lanes, n_pad % col_block == 0.
    Returns (dest (1, n_pad) int32, rank (1, n_pad) int32,
    counts (1, B_pad) int32) — ``dest`` is the word's byte length (bucket
    id; ``num_buckets`` marks padding columns >= ``n_valid``), ``rank`` its
    stable slot inside the bucket, ``counts[:, :num_buckets]`` the final
    length histogram."""
    lanes, n_pad = keys_t.shape
    b_pad = max(128, -(-num_buckets // 128) * 128)
    kern = functools.partial(distribute_rows_kernel, n_valid=n_valid,
                             num_buckets=num_buckets, col_block=col_block)
    return pl.pallas_call(
        kern,
        out_shape=(
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, b_pad), jnp.int32),
        ),
        grid=(n_pad // col_block,),
        in_specs=[pl.BlockSpec((lanes, col_block), lambda j: (0, j))],
        out_specs=(
            pl.BlockSpec((1, col_block), lambda j: (0, j)),
            pl.BlockSpec((1, col_block), lambda j: (0, j)),
            # constant index_map: the same block is revisited every step and
            # carries the running histogram (sequential TPU grid)
            pl.BlockSpec((1, b_pad), lambda j: (0, 0)),
        ),
        interpret=interpret,
    )(keys_t)
