"""Hierarchical multi-block sort — the paper's decomposition at tile scale.

The single-block kernels (``kernels/oets_kernel.py``, ``bitonic_kernel.py``)
pad every row to one VMEM block, so a row wider than a tile either fails or
pays O(n) OETS phases over the whole width. This module is the scale-out:

  1. split each row into ``nb`` blocks of ``block_size`` lanes (the paper's
     "distribute the elements into sub-arrays"),
  2. sort every block locally with the existing OETS/bitonic row kernels —
     one pallas grid over all blocks of all rows at once,
  3. run ``nb`` alternating even/odd rounds of the cross-block merge kernel
     (``kernels/merge_kernel.py``) — odd-even transposition sort lifted from
     lanes to blocks, with compare-exchange generalised to merge-split.

Round r with parity p merges block pairs (2i+p, 2i+p+1); after ``nb`` rounds
the row is globally sorted (the 0-1 principle applied block-wise). Handles
1-D arrays of arbitrary length and (rows, cols) batches whose cols span many
VMEM blocks.

Every entry point is a view over one tuple-based core (``block_sort_lex``):
the kernels compare full lexicographic tuples (``kernels/lex.py``), so
key-only is the 1-tuple, key-value the 2-tuple, and multi-lane word keys any
wider tuple. ``repro.kernels.ops.sort``/``sort_lex`` pick this path
automatically beyond one block; ``block_size`` is the override knob.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..kernels.bitonic_kernel import bitonic_rows_lex_pallas
from ..kernels.merge_kernel import merge_adjacent_lex_pallas
from ..kernels.oets_kernel import oets_rows_lex_pallas
from ..kernels.ops import (_SUBLANES, _as_rows, _auto_interpret, _next_pow2,
                           _pad_cols)

__all__ = ["block_sort", "block_sort_kv", "block_sort_lex",
           "default_block_size"]

_MIN_BLOCK = 128          # one lane tile — smallest block the kernels accept
_DEFAULT_MIN_BLOCK = 512
# VMEM cap counts every ref the merge kernel holds: each is (8, 2B) x 4B.
# Key-only merge has 2 refs (in+out) -> 4 MiB at B=32Ki; every further array
# in the tuple (payload or extra key lane) adds 2 refs, halving the cap at
# each doubling: kv (4 refs) -> 4 MiB at B=16Ki. With double buffering and
# one network stage's temporaries the TPU v5e compiler counts 16.6-17 MiB of
# scoped VMEM at the cap, over its 16 MiB default scope; the merge kernel
# therefore asks for ``merge_kernel.VMEM_LIMIT`` (32 MiB of the core's
# 128 MiB).
_MAX_BLOCK = 1 << 15
_TARGET_BLOCKS = 16       # merge rounds = num_blocks; keep that small


def default_block_size(n: int, kv: bool = False, n_arrays: int | None = None) -> int:
    """Cost-model block pick for an n-lane row.

    Per-element phase count is ~log^2(B) (local bitonic) + nb * log(2B)
    (merge rounds, nb = ceil(n/B)), so growing B trades a quadratic-log local
    term against linearly fewer rounds; the VMEM cap bounds B above — each
    array in the sorted tuple carries in+out refs, so the cap halves per
    pow2 tuple width (``kv=True`` is shorthand for ``n_arrays=2``). Aim for
    ~_TARGET_BLOCKS blocks, clamped to [512, 32Ki / pow2(n_arrays)] lanes."""
    t = n_arrays if n_arrays is not None else (2 if kv else 1)
    cap = max(_MIN_BLOCK, _MAX_BLOCK // _next_pow2(t))
    b = _next_pow2(max(1, -(-n // _TARGET_BLOCKS)))
    return max(_DEFAULT_MIN_BLOCK, min(cap, b))


def _validate_block(block_size, n, n_arrays):
    b = block_size or default_block_size(n, n_arrays=n_arrays)
    if b < _MIN_BLOCK or b & (b - 1):
        raise ValueError(
            f"block_size must be a power of two >= {_MIN_BLOCK}, got {b}")
    return b


def _pad_grid_rows(x):
    """Pad rows so the kernels' row grid tiles exactly; returns (padded, real).

    rows <= 8 runs as a single (rows,)-high block; beyond that the kernels
    tile 8 sublanes at a time, so rows must be a multiple of 8."""
    rows = x.shape[0]
    if rows <= _SUBLANES or rows % _SUBLANES == 0:
        return x, rows
    pad = (-rows) % _SUBLANES
    fill = jnp.zeros((pad, x.shape[1]), x.dtype)
    return jnp.concatenate([x, fill], axis=0), rows


def _merge_round(xs, nb, block, parity, interpret):
    """One block-pair merge round of the given parity over (rows, nb*block).

    ``xs`` is a tuple of lane/payload arrays; untouched edge blocks (the
    first block on odd rounds, the last on rounds with a dangling block) are
    carried through by concatenation around the merged span."""
    npairs = (nb - parity) // 2
    if npairs == 0:
        return xs
    lo = parity * block
    hi = lo + npairs * 2 * block
    merged = merge_adjacent_lex_pallas(
        *(a[:, lo:hi] for a in xs), block=block, interpret=interpret)
    if lo == 0 and hi == nb * block:
        return merged
    return tuple(jnp.concatenate([a[:, :lo], m, a[:, hi:]], axis=1)
                 for a, m in zip(xs, merged))


def _merge_rounds(xs, nb, block, interpret):
    """nb alternating even/odd block-pair merge rounds over (rows, nb*block),
    as a loop over (even, odd) round pairs: the program holds two merge
    kernels whatever nb is, where an unrolled sequence held nb of them and
    its compile time grew with the row width."""
    def pair(_, xs):
        xs = _merge_round(xs, nb, block, 0, interpret)
        return _merge_round(xs, nb, block, 1, interpret)

    xs = jax.lax.fori_loop(0, nb // 2, pair, tuple(xs))
    if nb % 2:
        xs = _merge_round(xs, nb, block, 0, interpret)
    return xs


@functools.partial(jax.jit, static_argnames=("block_size", "local_algorithm", "interpret"))
def _block_sort_tuple_2d(arrs, *, block_size, local_algorithm, interpret):
    """Tuple core: sort each row of same-shape 2-D ``arrs`` by lex compare."""
    rows, n = arrs[0].shape
    nb = -(-n // block_size)
    npad = nb * block_size
    # every array pads with its own dtype sentinel so the padding tuple is
    # the lex maximum under the kernels' full-tuple compare — it can never
    # displace a real payload even when real keys equal the key sentinel.
    arrs = [_pad_cols(a, npad) for a in arrs]

    # local phase: every block of every row is one kernel row
    loc = [a.reshape(rows * nb, block_size) for a in arrs]
    real = loc[0].shape[0]
    loc = [_pad_grid_rows(a)[0] for a in loc]
    fn = (bitonic_rows_lex_pallas if local_algorithm == "bitonic"
          else oets_rows_lex_pallas)
    arrs = [s[:real].reshape(rows, npad)
            for s in fn(*loc, interpret=interpret)]

    if nb > 1:
        padded = [_pad_grid_rows(a)[0] for a in arrs]
        real_rows = rows
        merged = _merge_rounds(tuple(padded), nb, block_size, interpret)
        arrs = [m[:real_rows] for m in merged]
    return tuple(a[:, :n] for a in arrs)


def block_sort_lex(arrs, *, block_size: int | None = None,
                   local_algorithm: str = "bitonic",
                   interpret: bool | None = None):
    """Sort a tuple of same-shape 1-D arrays or (rows, cols) batches as
    lexicographic tuples (lane 0 most significant; trailing arrays are
    payload/tie-break lanes). Returns the sorted tuple.

    ``block_size``: lanes per block (power of two >= 128); None = cost model
    (cap halves per pow2 tuple width — VMEM holds in+out refs per array).
    ``local_algorithm``: 'bitonic' (default) or 'oets' for the in-block sort.
    """
    if local_algorithm not in ("bitonic", "oets"):
        raise ValueError(f"unknown local algorithm {local_algorithm!r}")
    arrs = list(arrs)
    if not arrs:
        raise ValueError("need at least one array to sort")
    if any(a.shape != arrs[0].shape for a in arrs[1:]):
        raise ValueError("all lex arrays must have identical shapes")
    interpret = _auto_interpret(interpret)
    views = [_as_rows(a) for a in arrs]
    vec = views[0][1]
    arrs2 = [v[0] for v in views]
    if 0 in arrs2[0].shape:
        return tuple(arrs)
    b = _validate_block(block_size, arrs2[0].shape[1], len(arrs2))
    out = _block_sort_tuple_2d(tuple(arrs2), block_size=b,
                               local_algorithm=local_algorithm,
                               interpret=interpret)
    return tuple(o[0] for o in out) if vec else out


def block_sort(x, *, block_size: int | None = None,
               local_algorithm: str = "bitonic",
               interpret: bool | None = None):
    """Sort a 1-D array or each row of a (rows, cols) array ascending.

    ``block_size``: lanes per block (power of two >= 128); None = cost model.
    ``local_algorithm``: 'bitonic' (default) or 'oets' for the in-block sort.
    """
    (out,) = block_sort_lex((x,), block_size=block_size,
                            local_algorithm=local_algorithm,
                            interpret=interpret)
    return out


def block_sort_kv(keys, vals, *, block_size: int | None = None,
                  local_algorithm: str = "bitonic",
                  interpret: bool | None = None):
    """Key-value variant of :func:`block_sort`; ``vals`` rides the same
    permutation as the 2nd (tie-break) lex lane."""
    if keys.shape != vals.shape:
        raise ValueError("keys and vals must have identical shapes")
    return block_sort_lex((keys, vals), block_size=block_size,
                          local_algorithm=local_algorithm,
                          interpret=interpret)
