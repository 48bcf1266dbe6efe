"""Public jit'd wrappers around the Pallas sorting kernels.

Entry points:
  * ``sort(x)`` / ``sort_kv(keys, vals)`` — the unified front-end. Accepts
    1-D arrays or (rows, cols) batches of any width and picks the engine from
    a small cost model (``choose_plan``): single-tile rows run the OETS
    kernel, single-block pow2-padded rows the bitonic kernel, and anything
    wider the hierarchical block sort (``core/blocksort.py`` — block-local
    sort + cross-block odd-even merge rounds). ``algorithm``/``block_size``
    override the model.
  * ``sort_lex(keys_lanes, vals=None)`` — the variadic lexicographic
    front-end: sorts tuples of same-shape arrays lane-by-lane (lane 0 most
    significant), the multi-character word keys of the paper's pipeline
    (``core/packing.py``). Same engine tiers as ``sort``, plus an
    ``engine='auto'|'lanes'|'packed'`` routing knob: 'packed' collapses the
    tuple into 1-2 uint32 rank-key lanes (``kernels/keypack.py``), sorts
    those, and unpacks (integer tuples) or gathers the original lanes
    through the sorted permutation (float tuples, conserving every bit) —
    chosen automatically when the tuple fits the 2-lane budget with fewer
    packed than original lanes.
  * ``merge_sorted(a, b)`` / ``merge_sorted_lex(a_lanes, b_lanes)`` — the
    run-merge front-end shared by every granularity (pipeline run
    tournament, distributed 'take' merge and final combine): 'packed'
    (rank-key searchsorted + one scatter), 'kernel' (the block-parallel
    Pallas merge-path kernel, ``kernels/runmerge_kernel.py``), or 'lanes'
    (the ``lex_merge_take`` broadcast oracle).
  * ``segmented_sort(keys, counts)`` — the fused bucket pipeline: one
    batched lex kernel launch over a whole (num_buckets, capacity, lanes)
    bucket tensor with per-bucket count masking (``core/bucketing``'s
    'pallas' path).
  * ``distribute(keys)`` / ``bucketize(keys, capacity)`` — the paper's
    phases 1-2 on device: the Pallas length-histogram + stable-rank pass
    (``kernels/distribute_kernel.py``) plus one scatter places every packed
    word into its per-length bucket — the ingest counterpart of
    ``segmented_sort``, replacing the host dict loop of
    ``core/bucketing.bucketize_words``.
  * ``sort_rows`` / ``sort_rows_kv`` / ``sort_rows_lex`` — the single-block
    row kernels (every row padded to one VMEM block; width bounded by the
    tile).
  * ``partition_rows`` — splitter bucketing (the paper's distribute step).

Beyond one device, ``core/distributed.py`` lifts these same tiers to the
mesh: ``distributed_sort``/``distributed_sort_lex`` pick between odd-even
block sort and splitter sample sort with a ``choose_engine`` cost model
mirroring ``choose_plan``, and run this module's ``sort_lex`` as the
device-local sort on TPU.

These wrappers handle everything the raw kernels require of their caller:
lane padding (cols -> multiple of 128 for OETS, next pow2 >= 128 for
bitonic) with per-dtype lex-maximal sentinels so padding sinks to the row
tail, sublane padding (rows -> multiple of the 8-row block), and automatic
``interpret=True`` on any backend but TPU (the CPU the tests run on),
compiled on TPU.

Sentinel / dtype contract: padding uses the dtype's lex-maximal value under
the canonical total order of ``kernels/lex.py`` (``iinfo.max`` for ints —
including signed, where it is the positive max, never -1 — and for floats
the all-ones-bits NaN, which the order places strictly above every other
value). Real elements *equal* to the sentinel still sort correctly:
key-only outputs are sliced back to the real width, and kv/lex payload
lanes participate in the compare as final tie-breaks, keeping the
all-sentinel padding tuple strictly maximal.

float32 NaN contract (``jnp.sort``-equivalent): every engine at every tier
compares the canonical order bits of ``kernels/lex.to_order_bits``, so NaNs
— all bit patterns, either sign — sort strictly above ``+inf`` and sink to
the tail, ``-0.0`` and ``+0.0`` compare equal (either may precede the
other), and the output is always a bit-level permutation of the input:
engines compare order bits but swap the raw values, so NaN payload bits and
``-0.0`` signs are conserved, never canonicalised. Distinct NaN bit
patterns compare equal, so their relative order is unspecified — exactly
``jnp.sort``'s observable contract. ``tests/test_ops_dtypes.py`` and the
``nan`` generator of the conformance matrix (``tests/test_conformance.py``)
pin this on every (op, engine, mode) cell.
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..runtime.trace import span
from .bitonic_kernel import bitonic_rows_lex_pallas
from .distribute_kernel import distribute_rows_pallas
from .keypack import (merge_take_packed, pack_rank_keys, plan_pack,
                      unpack_rank_keys)
from .lex import concat_lanes, lex_merge_take, sentinel_for
from .oets_kernel import oets_rows_lex_pallas
from .partition_kernel import partition_rows_pallas
from .kway_kernel import merge_runs_kway_pallas, merge_runs_kway_take
from .runmerge_kernel import DEFAULT_MERGE_BLOCK, merge_runs_lex_pallas

__all__ = ["sort", "sort_kv", "sort_lex", "segmented_sort", "distribute",
           "bucketize", "BucketizeResult", "scatter_to_buckets",
           "choose_plan", "choose_lex_engine",
           "merge_sorted", "merge_sorted_lex", "choose_merge_engine",
           "merge_runs_lex", "choose_kway_engine",
           "pallas_lowering", "execution_provenance",
           "sort_rows", "sort_rows_kv", "sort_rows_lex", "partition_rows"]

log = logging.getLogger("repro.kernels")

_LANES = 128
_SUBLANES = 8
# widest row the single-block kernels handle before the hierarchical path
# wins: one pow2 VMEM block of 1024 lanes (bitonic: 55 phases; beyond this
# blocksort's local-sort + merge-round phase count is strictly lower).
_MAX_SINGLE_BLOCK = 1024


def _auto_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def pallas_lowering(interpret: bool | None = None) -> str:
    """How the Pallas kernel bodies of this module execute for a given
    ``interpret`` request: ``'interpret'`` (the Pallas interpreter, unrolled
    into the surrounding XLA program — the only option on CPU) or
    ``'compiled'`` (native Mosaic/Triton lowering on TPU/GPU). ``None``
    resolves the same auto rule every op front-end uses."""
    return "interpret" if _auto_interpret(interpret) else "compiled"


def execution_provenance(interpret: bool | None = None,
                         mode: str | None = None) -> dict:
    """Provenance of a run through these ops on this host: the fields every
    benchmark record and conformance result is stamped with so numbers are
    only ever compared like-with-like (``benchmarks/gate.py``,
    ``repro.testing``). ``mode`` is the caller's execution-mode label (e.g.
    ``'interpret-cpu'``); when omitted it is derived from the backend and
    the resolved Pallas lowering."""
    backend = jax.default_backend()
    lowering = pallas_lowering(interpret)
    dev = jax.devices()[0]
    return {
        "backend": backend,
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "pallas": lowering,
        "mode": mode or ("interpret-" + backend if lowering == "interpret"
                         else "compiled-" + backend),
        "jax": jax.__version__,
    }


# shared with the kernel modules (kernels/lex.py holds the definition so the
# per-kernel modules never import this front-end back — no cycle)
_sentinel = sentinel_for


def _pad_cols(x, target):
    pad = target - x.shape[1]
    if pad == 0:
        return x
    fill = jnp.full((x.shape[0], pad), _sentinel(x.dtype), x.dtype)
    return concat_lanes([x, fill], axis=1)


def _pad_rows(x, multiple):
    pad = (-x.shape[0]) % multiple
    if pad == 0:
        return x
    fill = jnp.zeros((pad, x.shape[1]), x.dtype)
    return concat_lanes([x, fill], axis=0)


def _next_pow2(n):
    return 1 << max(0, (n - 1).bit_length())


def _as_rows(x):
    """Promote a 1-D array to a single kernel row; returns (2-D view, was_1d)."""
    if x.ndim == 1:
        return x[None, :], True
    if x.ndim == 2:
        return x, False
    raise ValueError("expected a 1-D or 2-D array")


def choose_plan(cols: int, algorithm: str = "auto",
                block_size: int | None = None):
    """Pick (algorithm, block_size) for ``cols``-wide rows.

    The cost model orders the engines by total comparator phases per row:
    ``oets`` (cols phases) only pays off within one lane tile where its
    padding is tightest; ``bitonic`` (log^2 phases, pow2 padding) up to one
    VMEM block; ``blocksort`` beyond, where padding to a single giant block
    would explode phase count and VMEM. The model is width-driven only —
    lex lane count scales every engine's compare cost by the same factor,
    so the tier boundaries do not move. Explicit ``algorithm`` overrides."""
    if algorithm != "auto":
        return algorithm, block_size
    if cols <= _LANES:
        return "oets", None
    if _next_pow2(cols) <= _MAX_SINGLE_BLOCK:
        return "bitonic", None
    return "blocksort", block_size


def sort(x, algorithm: str = "auto", block_size: int | None = None,
         interpret: bool | None = None):
    """Sort a 1-D array or each row of a (rows, cols) array ascending.

    ``algorithm``: 'auto' (cost model), 'oets', 'bitonic', or 'blocksort'.
    ``block_size``: blocksort block override (power of two >= 128).
    """
    (out,) = sort_lex((x,), algorithm=algorithm, block_size=block_size,
                      interpret=interpret)
    return out


def sort_kv(keys, vals, algorithm: str = "auto",
            block_size: int | None = None, interpret: bool | None = None):
    """Key-value counterpart of :func:`sort`; ``vals`` rides the keys'
    permutation as the final lex tie-break (equal (key, val) pairs are
    interchangeable)."""
    if keys.shape != vals.shape:
        raise ValueError("keys and vals must have identical shapes")
    lanes, ov = sort_lex((keys,), vals=vals, algorithm=algorithm,
                         block_size=block_size, interpret=interpret)
    return lanes[0], ov


def choose_lex_engine(dtypes, max_values=None, engine: str = "auto") -> str:
    """Pick the lane engine for :func:`sort_lex` — ``choose_plan``'s cost
    model at tuple granularity. 'packed' wins exactly when the rank-key
    packing is lossless *and* shrinks the comparator's lane count: every
    swap network phase moves and compares each lane, so fewer lanes is
    strictly less work, while a lossy packing would have to carry the
    original lanes as tie-breaks and lose. Float32 lanes route like any
    other: their order bits are the canonical comparator representation
    (``kernels/lex.to_order_bits``), and :func:`sort_lex` conserves their
    bits by gathering the originals through the packed permutation instead
    of unpacking. Explicit ``engine`` overrides, but never unsoundly: a
    'packed' request that the plan cannot honour exactly falls back to
    'lanes'."""
    if engine not in ("auto", "lanes", "packed"):
        raise ValueError(f"unknown engine {engine!r}")
    if engine == "lanes":
        return "lanes"
    dtypes = tuple(jnp.dtype(d) for d in dtypes)
    try:
        plan = plan_pack(dtypes, max_values)
    except TypeError:
        return "lanes"
    if not plan.exact:
        return "lanes"
    if engine == "packed":
        return "packed"
    return "packed" if plan.n_packed < len(dtypes) else "lanes"


def sort_lex(keys_lanes, vals=None, algorithm: str = "auto",
             block_size: int | None = None, interpret: bool | None = None,
             engine: str = "auto", max_values=None):
    """Lexicographic sort: ``keys_lanes`` is a sequence of same-shape 1-D or
    (rows, cols) arrays, compared element-wise lane-by-lane (lane 0 most
    significant — the lane-packing contract of ``core/packing.py``). All
    lanes and the optional ``vals`` payload travel through one permutation;
    ``vals`` doubles as the final tie-break lane.

    Returns a tuple of sorted lanes, or ``(lanes_tuple, sorted_vals)`` when
    ``vals`` is given. Engine tiers are the same as :func:`sort`
    (``choose_plan`` on the row width); every tier — including the
    multi-block blocksort — runs the full tuple through one Pallas engine.

    ``engine``: 'lanes' (every key lane is its own comparator lane),
    'packed' (collapse the tuple into 1-2 uint32 rank-key lanes via
    ``kernels/keypack.py``, sort those, and unpack — or, when a float lane
    is present, sort ``(rank keys, iota)`` and gather the original lanes
    through the permutation, conserving every float bit; honoured only when
    the packing is lossless, else falls back to 'lanes'), or 'auto'
    (:func:`choose_lex_engine`). ``max_values``: optional per-lane upper
    bounds (hashable tuple) that tighten the packed widths.
    """
    lanes = list(keys_lanes)
    if not lanes:
        raise ValueError("need at least one key lane")
    arrs = lanes + ([vals] if vals is not None else [])
    if any(a.shape != arrs[0].shape for a in arrs[1:]):
        raise ValueError("all lanes (and vals) must have identical shapes")
    eng = choose_lex_engine([a.dtype for a in lanes], max_values, engine)
    if eng == "packed":
        packed = pack_rank_keys(lanes, max_values)
        if any(jnp.issubdtype(a.dtype, jnp.floating) for a in lanes):
            # The float order-bit transform is compare-only (NaN patterns
            # collapse, -0.0 normalises), so unpacking cannot restore the
            # input bits. Sort (rank keys, iota) instead and gather every
            # original lane — and vals — through the permutation: stable,
            # bit-conserving, and the iota tie-break keeps real rows that
            # equal the packed padding prefix left of the padding tail.
            x0 = lanes[0]
            iota = jax.lax.broadcasted_iota(jnp.int32, x0.shape, x0.ndim - 1)
            sorted_packed = sort_lex(tuple(packed.lanes) + (iota,),
                                     algorithm=algorithm,
                                     block_size=block_size,
                                     interpret=interpret, engine="lanes")
            perm = sorted_packed[-1]
            if x0.ndim == 1:
                gather = lambda a: a[perm]
            else:
                gather = lambda a: jnp.take_along_axis(a, perm, axis=-1)
            out = tuple(gather(a) for a in lanes)
            return out if vals is None else (out, gather(vals))
        out_packed = sort_lex(packed.lanes, vals=vals, algorithm=algorithm,
                              block_size=block_size, interpret=interpret,
                              engine="lanes")
        if vals is not None:
            out_packed, out_vals = out_packed
        out = tuple(unpack_rank_keys(out_packed,
                                     [a.dtype for a in lanes], max_values))
        return out if vals is None else (out, out_vals)
    views = [_as_rows(a) for a in arrs]
    vec = views[0][1]
    a2 = [v[0] for v in views]
    if 0 in a2[0].shape:
        out = tuple(arrs)
    else:
        algo, block = choose_plan(a2[0].shape[1], algorithm, block_size)
        if algo == "blocksort":
            from ..core.blocksort import block_sort_lex  # lazy: core imports kernels
            out = block_sort_lex(tuple(a2), block_size=block,
                                 interpret=interpret)
        else:
            out = tuple(sort_rows_lex(a2, algorithm=algo, interpret=interpret))
        if vec:
            out = tuple(o[0] for o in out)
    if vals is None:
        return out
    return out[:-1], out[-1]


def segmented_sort(keys, counts=None, algorithm: str = "auto",
                   block_size: int | None = None,
                   interpret: bool | None = None):
    """Fused on-device segmented sort over the paper's bucket tensor.

    ``keys``: (num_buckets, capacity, lanes) — the 3-D array of the paper's
    distribute step (``core/bucketing.Buckets.keys``), lane-major
    significance. ``counts``: (num_buckets,) real slots per bucket; slots at
    index >= count are masked to the dtype sentinel so they sink to every
    bucket's tail (pass ``None`` when the tensor is already sentinel-padded).

    One batched lex kernel launch sorts *all* buckets: rows = buckets,
    cols = capacity, one comparator lane per packed key lane — any lane
    count and any capacity (the blocksort tier included). Returns the sorted
    (num_buckets, capacity, lanes) tensor.
    """
    if keys.ndim != 3:
        raise ValueError("keys must be (num_buckets, capacity, lanes)")
    if 0 in keys.shape:
        return keys
    n_lanes = keys.shape[2]
    if counts is not None:
        slot = jnp.arange(keys.shape[1], dtype=jnp.int32)
        mask = slot[None, :] >= jnp.asarray(counts, jnp.int32)[:, None]
        keys = jnp.where(mask[..., None], _sentinel(keys.dtype), keys)
    sorted_lanes = sort_lex([keys[..., l] for l in range(n_lanes)],
                            algorithm=algorithm, block_size=block_size,
                            interpret=interpret)
    return jnp.stack(sorted_lanes, axis=-1)


def choose_merge_engine(total: int, engine: str = "auto") -> str:
    """Pick the run-merge engine for a ``total``-element combine —
    ``choose_plan``'s cost model at merge granularity. 'packed' (rank-key
    searchsorted + one scatter) is the jnp fast path on every backend:
    O(n log n) gathers against the broadcast's O(|a|·|b|·L). The Pallas
    merge-path 'kernel' additionally replaces the HBM-wide scatter with
    block-local VMEM merges, which only pays off compiled on TPU and past
    one output tile (below that the packed scatter is a single cheap
    launch). Lane count does not move the boundary — it scales both sides'
    compare cost equally, so the model is size- and backend-driven only.
    'lanes' — the broadcast ``lex_merge_take`` oracle — is never chosen
    automatically. Explicit ``engine`` overrides."""
    if engine != "auto":
        if engine not in ("lanes", "packed", "kernel"):
            raise ValueError(f"unknown engine {engine!r}")
        return engine
    if jax.default_backend() == "tpu" and total > 2 * DEFAULT_MERGE_BLOCK:
        return "kernel"
    return "packed"


@functools.partial(jax.jit, static_argnames=("n_arr", "n_cmp", "max_values"))
def _merge_packed_jit(*arrs, n_arr, n_cmp, max_values):
    return tuple(merge_take_packed(list(arrs[:n_arr]), list(arrs[n_arr:]),
                                   n_cmp=n_cmp, max_values=max_values))


@functools.partial(jax.jit, static_argnames=("n_arr",))
def _merge_lanes_jit(*arrs, n_arr):
    return tuple(lex_merge_take(list(arrs[:n_arr]), list(arrs[n_arr:])))


def merge_sorted_lex(a_lanes, b_lanes, engine: str = "auto",
                     n_cmp: int | None = None, max_values=None,
                     block_size: int | None = None,
                     interpret: bool | None = None):
    """Merge two *sorted* lex-tuple runs (tuples of parallel 1-D arrays, may
    differ in length) into one sorted run — the shared run-merge primitive
    of the pipeline tournament, the distributed 'take' merge, and the
    sample-sort combine.

    Every lane participates in the compare in tuple order (trailing lanes
    are payload tie-breaks, ``kernels/lex.py`` conventions); output is
    bit-identical to ``lex_merge_take`` across engines. ``engine``: 'packed'
    (rank-key searchsorted ranks + one scatter), 'kernel' (the block-parallel
    Pallas merge-path kernel), 'lanes' (the broadcast oracle), or 'auto'
    (:func:`choose_merge_engine`). 'kway' routes the pair through the k-way
    front-end :func:`merge_runs_lex` (its 2-run case — one key-sort +
    gather pass or the streaming kernel per :func:`choose_kway_engine`).
    ``n_cmp``: the leading ``n_cmp`` lanes are pre-packed compare lanes to
    rank on as-is (see ``keypack.merge_take_packed``); ``max_values``:
    per-lane packing bounds (hashable tuple).
    """
    if engine == "kway":
        return merge_runs_lex([a_lanes, b_lanes], n_cmp=n_cmp,
                              max_values=max_values, block_size=block_size,
                              interpret=interpret)
    a_lanes, b_lanes = tuple(a_lanes), tuple(b_lanes)
    if max_values is not None:
        max_values = tuple(max_values)  # static under jit: must be hashable
    if len(a_lanes) != len(b_lanes) or not a_lanes:
        raise ValueError("runs must share a non-zero lane arity")
    if any(x.ndim != 1 for x in a_lanes + b_lanes):
        raise ValueError("runs must be tuples of 1-D arrays")
    if a_lanes[0].shape[0] == 0:
        return b_lanes
    if b_lanes[0].shape[0] == 0:
        return a_lanes
    eng = choose_merge_engine(a_lanes[0].shape[0] + b_lanes[0].shape[0],
                              engine)
    if eng == "lanes":
        return _merge_lanes_jit(*a_lanes, *b_lanes, n_arr=len(a_lanes))
    if eng == "packed":
        return _merge_packed_jit(*a_lanes, *b_lanes, n_arr=len(a_lanes),
                                 n_cmp=n_cmp, max_values=max_values)
    return merge_runs_lex_pallas(a_lanes, b_lanes, n_cmp=n_cmp,
                                 max_values=max_values, block=block_size,
                                 interpret=_auto_interpret(interpret))


def choose_kway_engine(total: int, engine: str = "auto") -> str:
    """Pick the k-way combine tier — :func:`choose_merge_engine`'s model at
    k-run granularity. 'take' (one fused key sort + ONE gather per lane,
    :func:`repro.kernels.kway_kernel.merge_runs_kway_take`) is the jnp
    fast path everywhere: one data pass, one fused dispatch. The Pallas
    streaming 'kernel' additionally keeps the combine in VMEM tiles behind
    double-buffered DMA, which pays off compiled on TPU past one output
    tile, exactly like the 2-way boundary. Explicit ``engine`` overrides
    (e.g. conformance forcing 'kernel' under the interpreter)."""
    if engine != "auto":
        if engine not in ("take", "kernel"):
            raise ValueError(f"unknown k-way engine {engine!r}")
        return engine
    if jax.default_backend() == "tpu" and total > 2 * DEFAULT_MERGE_BLOCK:
        return "kernel"
    return "take"


@functools.partial(jax.jit, static_argnames=("n_arr", "n_runs", "n_cmp",
                                             "max_values"))
def _kway_take_jit(*arrs, counts=None, n_arr, n_runs, n_cmp, max_values):
    runs = [list(arrs[r * n_arr:(r + 1) * n_arr]) for r in range(n_runs)]
    return merge_runs_kway_take(runs, n_cmp=n_cmp, max_values=max_values,
                                counts=counts)


def merge_runs_lex(runs, engine: str = "auto", n_cmp: int | None = None,
                   max_values=None, block_size: int | None = None,
                   interpret: bool | None = None, counts=None):
    """Merge k *sorted* lex-tuple runs into one sorted run in a SINGLE pass
    — the streaming replacement for the pipeline tournament's ceil(log2 k)
    pairwise rounds (each of which re-reads and re-writes all the data).

    ``runs``: sequence of equal-arity tuples of parallel 1-D arrays, any
    lengths (empty runs drop statically). ``engine``: 'take' (global
    merge-path ranks + one scatter per lane), 'kernel' (the one-launch
    streaming Pallas kernel, ``kernels/kway_kernel.py``), or 'auto'
    (:func:`choose_kway_engine`). ``n_cmp``/``max_values`` follow
    :func:`merge_sorted_lex`. Output is bit-identical to the tournament and
    the NumPy lexsort oracle across engines.

    ``counts``: for runs of a fixed shape whose tails are padding, each
    run's real rows (k,) int32; the merge then holds their ``sum(counts)``
    rows first, and no run is dropped for its shape."""
    runs = [tuple(r) for r in runs]
    if max_values is not None:
        max_values = tuple(max_values)  # static under jit: must be hashable
    if not runs or not runs[0] or any(len(r) != len(runs[0]) for r in runs):
        raise ValueError("runs must share a non-zero lane arity")
    if any(x.ndim != 1 for r in runs for x in r):
        raise ValueError("runs must be tuples of 1-D arrays")
    nonempty = runs if counts is not None else [r for r in runs
                                                if r[0].shape[0]]
    if not nonempty:
        return runs[0]
    if len(nonempty) == 1:
        return nonempty[0]
    total = sum(r[0].shape[0] for r in nonempty)
    eng = choose_kway_engine(total, engine)
    if eng == "kernel":
        return merge_runs_kway_pallas(nonempty, n_cmp=n_cmp,
                                      max_values=max_values,
                                      block=block_size,
                                      interpret=_auto_interpret(interpret),
                                      counts=counts)
    with span("dispatch", program="_kway_take_jit"):
        return _kway_take_jit(*[x for r in nonempty for x in r],
                              counts=counts,
                              n_arr=len(runs[0]), n_runs=len(nonempty),
                              n_cmp=n_cmp, max_values=max_values)


def merge_sorted(a, b, engine: str = "auto", block_size: int | None = None,
                 interpret: bool | None = None):
    """Key-only special case of :func:`merge_sorted_lex`: merge two sorted
    1-D arrays into one."""
    (out,) = merge_sorted_lex((a,), (b,), engine=engine,
                              block_size=block_size, interpret=interpret)
    return out


def distribute(keys, interpret: bool | None = None):
    """Run the on-device distribute pass over packed words (the paper's
    phases 1-2: count, then assign every element its sub-array slot).

    ``keys``: (n, lanes) uint32 packed words (``core/packing.pack_words``).
    Returns ``(dest, rank, counts)``: ``dest`` (n,) int32 — each word's byte
    length, which *is* its bucket id (buckets are dense per-length, id 0 =
    the empty word); ``rank`` (n,) int32 — the word's stable slot within
    its bucket (arrival order); ``counts`` (num_buckets,) int32 — the
    length histogram, ``num_buckets = 4 * lanes + 1``. All on device; the
    kernel carries running counts across grid steps, so ranks are globally
    stable without a host prefix pass.
    """
    interpret = _auto_interpret(interpret)
    n, lanes = keys.shape
    num_buckets = 4 * lanes + 1
    if n == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
                jnp.zeros((num_buckets,), jnp.int32))
    n_pad = max(_LANES, -(-n // _LANES) * _LANES)
    keys_t = jnp.zeros((lanes, n_pad), jnp.uint32).at[:, :n].set(
        jnp.asarray(keys, jnp.uint32).T)
    dest, rank, counts = distribute_rows_pallas(
        keys_t, n_valid=n, num_buckets=num_buckets, interpret=interpret)
    return dest[0, :n], rank[0, :n], counts[0, :num_buckets]


def _optimistic_capacity(n: int, num_buckets: int) -> int:
    """First-shot capacity for the two-tier autotune: a uniform length
    spread with 4x headroom, rounded to a power of two so repeated sizes
    share jit cache entries. Clamped at ~n/2 so a small bucket count (1-lane
    words have only 5) never degenerates the optimistic tensor to the
    worst case — a distribution skewed past half the input is exactly the
    case the exact-count retry tier exists for."""
    return max(1, min(n, _next_pow2(-(-4 * n // num_buckets)),
                      _next_pow2(-(-n // 2))))


class BucketizeResult(NamedTuple):
    """Result of :func:`bucketize`. ``buckets``
    (num_buckets, capacity, lanes) uint32 — bucket ``l`` holds the words of
    byte length ``l`` in arrival order, unused slots at the sentinel;
    ``counts`` (num_buckets,) int32 *true* per-bucket counts (never inferred
    from sentinel compares); ``dropped`` — host int, the number of elements
    clipped out of the tensor because their bucket exceeded an explicit
    ``capacity`` under ``on_overflow='clip'`` (0 on every other path).
    Indexes like the historical ``(buckets, counts)`` pair."""

    buckets: jax.Array
    counts: jax.Array
    dropped: int


def bucketize(keys, capacity: int | None = None,
              interpret: bool | None = None,
              on_overflow: str = "clip") -> BucketizeResult:
    """Scatter packed words into the paper's dense per-length bucket tensor
    — ``bucketize_words``'s host dict loop as one kernel pass + one device
    scatter.

    ``keys``: (n, lanes) uint32 packed words. ``capacity``: slots per bucket
    (static under jit). ``None`` runs the two-tier autotune: the scatter is
    dispatched immediately at an optimistic capacity (uniform spread + 4x
    headroom) *without* reading the histogram back, then the exact counts —
    already computed by the distribute kernel, never inferred from sentinel
    compares — decide whether a single retry at the true max is needed. On
    the happy path the histogram sync overlaps the in-flight scatter instead
    of blocking its launch; only a skewed length distribution pays the
    second scatter. The autotune path can never overflow.

    ``on_overflow`` is the degrade policy when an *explicit* capacity is
    exceeded — the overflow is never silent:
      * ``'clip'``  — keep the statically sized tensor, drop the excess
                      elements from it (true counts still report them), log
                      a structured warning, and report the loss in
                      ``BucketizeResult.dropped``;
      * ``'raise'`` — raise :class:`repro.runtime.CapacityOverflow` carrying
                      the required capacity, so a supervisor can escalate;
      * ``'retry'`` — re-scatter once at the exact required capacity (the
                      true counts are already on hand) and return with
                      ``dropped == 0``.
    """
    from ..runtime.failure import CapacityOverflow
    if on_overflow not in ("clip", "raise", "retry"):
        raise ValueError(f"unknown on_overflow policy {on_overflow!r}")
    n, lanes = keys.shape
    num_buckets = 4 * lanes + 1
    dest, rank, counts = distribute(keys, interpret=interpret)
    keys = jnp.asarray(keys, jnp.uint32)
    if capacity is None:
        if n == 0:
            capacity = 0
        else:
            capacity = _optimistic_capacity(n, num_buckets)
            buckets = _scatter_to_buckets(keys, dest, rank,
                                          num_buckets=num_buckets,
                                          capacity=capacity)
            true_max = int(jnp.max(counts))  # syncs after the dispatch above
            if true_max <= capacity:
                return BucketizeResult(buckets, counts, 0)
            capacity = true_max
        return BucketizeResult(
            _scatter_to_buckets(keys, dest, rank, num_buckets=num_buckets,
                                capacity=capacity), counts, 0)
    dropped = int(jnp.sum(jnp.maximum(counts - capacity, 0))) if n else 0
    if dropped:
        true_max = int(jnp.max(counts))
        if on_overflow == "raise":
            raise CapacityOverflow(
                f"bucketize overflow: largest bucket holds {true_max} and "
                f"exceeds capacity {capacity} ({dropped} element(s) would "
                f"drop)", capacity, required=true_max, dropped=dropped)
        if on_overflow == "retry":
            log.warning("bucketize overflow: capacity %d -> %d (exact-count "
                        "retry, %d element(s) would have dropped)",
                        capacity, true_max, dropped)
            capacity, dropped = true_max, 0
        else:
            log.warning("bucketize overflow: dropping %d element(s) past "
                        "capacity %d (max bucket holds %d) — pass "
                        "on_overflow='raise'|'retry' for a lossless policy",
                        dropped, capacity, true_max)
    return BucketizeResult(
        _scatter_to_buckets(keys, dest, rank, num_buckets=num_buckets,
                            capacity=capacity), counts, dropped)


@functools.partial(jax.jit, static_argnames=("num_buckets", "capacity"))
def scatter_to_buckets(keys, dest, rank, *, num_buckets, capacity):
    """The traceable core of :func:`bucketize`: one scatter placing word
    ``i`` at ``buckets[dest[i], rank[i]]``, unused slots at the uint32
    sentinel, ranks past ``capacity`` dropped into a discard slot. Pure and
    static-shaped, so it composes under an outer ``jax.jit`` — the
    compiled-mode path of the conformance kit (``repro.testing``) runs
    ``distribute`` + this in one program; :func:`bucketize` itself adds the
    host-synced capacity autotune / overflow policies around it and is
    therefore *not* traceable."""
    n, lanes = keys.shape
    flat = jnp.full((num_buckets * capacity + 1, lanes),
                    jnp.uint32(0xFFFFFFFF), jnp.uint32)
    keep = rank < capacity
    slot = jnp.where(keep, dest * capacity + rank, num_buckets * capacity)
    return flat.at[slot].set(keys)[: num_buckets * capacity].reshape(
        num_buckets, capacity, lanes)


_scatter_to_buckets = scatter_to_buckets


def sort_rows(x, algorithm: str = "oets", interpret: bool | None = None):
    """Sort each row of a (rows, cols) array ascending with a single-block
    Pallas kernel (every row padded to one VMEM block).

    ``algorithm``: 'oets' (paper-faithful) or 'bitonic' (beyond-paper).
    """
    (out,) = sort_rows_lex([x], algorithm=algorithm, interpret=interpret)
    return out


def sort_rows_kv(keys, vals, algorithm: str = "oets", interpret: bool | None = None):
    """Row-wise key-value sort; ``vals`` must share ``keys``' shape/rows."""
    if keys.shape != vals.shape:
        raise ValueError("keys and vals must have identical shapes")
    ok, ov = sort_rows_lex([keys, vals], algorithm=algorithm,
                           interpret=interpret)
    return ok, ov


def sort_rows_lex(arrs, algorithm: str = "oets", interpret: bool | None = None):
    """Row-wise lexicographic sort of a list of same-shape (rows, cols)
    arrays through a single-block kernel; returns the sorted list.

    Every array pads with its *own* dtype sentinel on purpose: the kernels
    compare full tuples lexicographically, so the all-sentinel padding tuple
    stays strictly maximal and can never displace a real element even when
    real leading lanes equal the sentinel. Do not "simplify" to zero padding.
    """
    interpret = _auto_interpret(interpret)
    rows, cols = arrs[0].shape
    if algorithm == "oets":
        target = max(_LANES, -(-cols // _LANES) * _LANES)
        fn = oets_rows_lex_pallas
    elif algorithm == "bitonic":
        target = max(_LANES, _next_pow2(cols))
        fn = bitonic_rows_lex_pallas
    else:
        raise ValueError(f"unknown algorithm {algorithm!r}")
    padded = [_pad_rows(_pad_cols(a, target), _SUBLANES) for a in arrs]
    out = fn(*padded, interpret=interpret)
    return [o[:rows, :cols] for o in out]


def partition_rows(keys, splitters, interpret: bool | None = None):
    """Bucket each element of (rows, cols) int32 ``keys`` by sorted
    ``splitters`` (the paper's distribute-into-sub-arrays step).

    Returns (bucket_ids (rows, cols), counts (rows, n_buckets)) with
    n_buckets = len(splitters) + 1. bucket id = #splitters <= key."""
    interpret = _auto_interpret(interpret)
    rows, cols = keys.shape
    n_spl = int(splitters.shape[0])
    n_buckets = n_spl + 1
    spl_pad = jnp.full((1, max(_LANES, -(-n_spl // _LANES) * _LANES)),
                       jnp.iinfo(jnp.int32).max, jnp.int32)
    spl_pad = spl_pad.at[0, :n_spl].set(splitters.astype(jnp.int32))
    cols_p = max(_LANES, -(-cols // _LANES) * _LANES)
    xp = _pad_rows(_pad_cols(keys.astype(jnp.int32), cols_p), _SUBLANES)
    bid, cnt = partition_rows_pallas(
        xp, spl_pad, n_splitters=n_spl, n_buckets=n_buckets, interpret=interpret)
    # Padded *cols* of real rows are sentinels (int32 max) and land in the top
    # bucket — subtract them there. Padded *rows* are zero-filled (their
    # elements land in bucket 0, not the top bucket), so the correction must
    # only touch the real rows or it drives their top-bucket count negative.
    pad_cols = cols_p - cols
    if pad_cols:
        cnt = cnt.at[:rows, n_buckets - 1].add(-pad_cols)
    return bid[:rows, :cols], cnt[:rows]
