"""The one-launch streaming k-way merge tier (``kernels/kway_kernel.py``)
and its plumbing: the merge-path rank tournament, the fused key-sort 'take'
tier, the Pallas streaming kernel (interpret mode here), and the
``merge_runs`` / ``merge_sorted_lex`` engine knobs — every path held
bit-identical to the NumPy lexsort oracle and to the legacy pairwise
tournament.

Sizes stay small: the kernel cases compile interpret-mode Pallas programs
on this CPU container (block 128, a few hundred elements — still genuinely
multi-block, so the double-buffered segment DMA is on the tested path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.keypack import packed_cmp_lanes
from repro.kernels.kway_kernel import (kway_ranks, merge_runs_kway_pallas,
                                       merge_runs_kway_take)
from repro.kernels.lex import to_order_bits
from repro.kernels.ops import choose_kway_engine, merge_runs_lex, merge_sorted_lex
from repro.pipeline import merge_runs


def _sorted_run(rng, n, n_lanes=3, hi=2**32):
    lanes = [rng.integers(0, hi, n).astype(np.uint32) for _ in range(n_lanes)]
    order = np.lexsort(tuple(reversed(lanes)))
    return [jnp.asarray(a[order]) for a in lanes]


def _oracle(runs):
    """NumPy lexsort of the concatenation — all lanes compare, so the merged
    lanes are unique per tuple multiset and bit-identical across engines."""
    n_lanes = len(runs[0])
    flat = [np.concatenate([np.asarray(r[i]) for r in runs])
            for i in range(n_lanes)]
    order = np.lexsort(tuple(reversed(flat)))
    return [lane[order] for lane in flat]


def _assert_lanes_equal(got, expect):
    assert len(got) == len(expect)
    for g, e in zip(got, expect):
        g, e = np.asarray(g), np.asarray(e)
        if g.dtype.kind == "f":
            g, e = g.view(np.uint32), e.view(np.uint32)
        np.testing.assert_array_equal(g, e)


# ---------------------------------------------------------------------------
# kway_ranks: the merge-path split
# ---------------------------------------------------------------------------

def test_kway_ranks_breaks_ties_by_run_index():
    """Hand-checkable ties: compare-equal elements must rank lower-run-first
    (then in-run order), the a-before-b protocol along the whole tree."""
    r0 = (jnp.asarray(np.array([0, 5, 5], np.uint32)),)
    r1 = (jnp.asarray(np.array([5, 5, 7], np.uint32)),)
    r2 = (jnp.asarray(np.array([5, 9], np.uint32)),)
    ranks = kway_ranks([r0, r1, r2])
    assert [r.tolist() for r in ranks] == [[0, 1, 2], [3, 4, 6], [5, 7]]


@pytest.mark.parametrize("sizes", [(17,), (9, 13), (32, 0, 21, 5, 40)])
def test_kway_ranks_is_a_permutation(sizes):
    rng = np.random.default_rng(sum(sizes))
    cmp_runs = [tuple(_sorted_run(rng, n, 2, hi=50)) for n in sizes]
    ranks = kway_ranks(cmp_runs)
    assert [r.shape[0] for r in ranks] == list(sizes)
    flat = np.concatenate([np.asarray(r) for r in ranks])
    assert sorted(flat.tolist()) == list(range(sum(sizes)))
    # within a run, ranks must ascend (runs are sorted)
    for r in ranks:
        assert np.all(np.diff(np.asarray(r)) > 0) or r.shape[0] <= 1


def _ranks_oracle(cmp_runs):
    """NumPy ranks: lexsort by compare lanes, then run index, then in-run
    index, inverted."""
    nc = len(cmp_runs[0])
    lanes = [np.concatenate([np.asarray(c[i]) for c in cmp_runs])
             for i in range(nc)]
    run = np.concatenate([np.full(c[0].shape[0], r)
                          for r, c in enumerate(cmp_runs)])
    inrun = np.concatenate([np.arange(c[0].shape[0]) for c in cmp_runs])
    order = np.lexsort((inrun, run) + tuple(reversed(lanes)))
    ranks = np.empty_like(order)
    ranks[order] = np.arange(order.size)
    return ranks


@pytest.mark.parametrize("nc", [1, 5])
@pytest.mark.parametrize("k", [2, 3, 8, 16])
def test_kway_ranks_match_numpy_oracle(k, nc):
    """Heavy ties (lane values 0..3) and empty runs mixed in: the ranks are
    exactly the NumPy lexsort's, ties broken by run then in-run index."""
    rng = np.random.default_rng(100 * k + nc)
    sizes = rng.integers(0, 40, k)
    sizes[::3] = 0
    sizes[1] = max(sizes[1], 1)
    cmp_runs = [tuple(_sorted_run(rng, int(n), nc, hi=4)) for n in sizes]
    got = np.concatenate([np.asarray(r) for r in kway_ranks(cmp_runs)])
    np.testing.assert_array_equal(got, _ranks_oracle(cmp_runs))


def test_kway_ranks_lower_to_no_gather_and_no_sort():
    """At ds2-kway's shapes (7 x 32768 + 624 rows, 5 uint32 compare lanes)
    the ranks lower to neither a gather nor a sort: a chain of binary-search
    gathers was the combine's cost on the chip, and a sort of these lanes
    takes the TPU compiler minutes."""
    cmp_runs = [tuple(jax.ShapeDtypeStruct((n,), jnp.uint32)
                      for _ in range(5)) for n in [32768] * 7 + [624]]
    text = jax.jit(kway_ranks).lower(cmp_runs).as_text()
    assert text.count("stablehlo.gather") == 0
    assert text.count("stablehlo.sort") == 0


# ---------------------------------------------------------------------------
# the jnp 'take' tier: fused key sort + one gather per lane
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes", [(5, 7), (5, 0, 9, 3), (64, 48, 33, 16, 9),
                                   (20,) * 8])
def test_take_matches_oracle(sizes):
    rng = np.random.default_rng(len(sizes))
    runs = [_sorted_run(rng, n) for n in sizes]
    _assert_lanes_equal(merge_runs_kway_take(runs), _oracle(runs))


def test_take_dup_heavy_ties_match_oracle():
    """Tiny alphabet: nearly everything ties on the leading lanes, so the
    run-index tie protocol carries the whole output order."""
    rng = np.random.default_rng(99)
    runs = [_sorted_run(rng, n, 3, hi=3) for n in (40, 40, 40, 40)]
    _assert_lanes_equal(merge_runs_kway_take(runs), _oracle(runs))


def test_take_float32_nan_and_neg_zero():
    """float32 lane with NaNs and -0.0: the take tier's key sort runs on
    canonical order bits, so NaNs land above +inf and -0.0 collapses onto
    +0.0 — exactly the repo comparator, bit-preserving through the gather."""
    rng = np.random.default_rng(7)
    runs = []
    for n in (33, 21, 17):
        v = rng.uniform(-5, 5, n).astype(np.float32)
        v[rng.random(n) < 0.25] = np.nan
        v[rng.random(n) < 0.1] = -0.0
        p = rng.integers(0, 2**31, n).astype(np.int32)
        ob = np.asarray(to_order_bits(jnp.asarray(v)))
        order = np.lexsort((p, ob))
        runs.append([jnp.asarray(v[order]), jnp.asarray(p[order])])
    got = merge_runs_kway_take(runs)
    # oracle in order-bit space (payload rides in the packed compare list)
    va = np.concatenate([np.asarray(r[0]) for r in runs])
    pa = np.concatenate([np.asarray(r[1]) for r in runs])
    order = np.lexsort((pa, np.asarray(to_order_bits(jnp.asarray(va)))))
    _assert_lanes_equal(got, [va[order], pa[order]])


# ---------------------------------------------------------------------------
# the Pallas streaming kernel (interpret mode, multi-block)
# ---------------------------------------------------------------------------

def test_kernel_matches_oracle_multiblock():
    """258 elements at block 128 -> 3 output blocks: the scalar-prefetched
    starts matrix, the 2-slot double-buffered segment DMA, and the loser
    tree all sit on the differential path."""
    rng = np.random.default_rng(42)
    runs = [_sorted_run(rng, n) for n in (130, 77, 50, 1)]
    got = merge_runs_kway_pallas(runs, block=128, interpret=True)
    _assert_lanes_equal(got, _oracle(runs))


def test_kernel_prepacked_cmp_prefix():
    """The ``n_cmp`` contract: rank on pre-packed leading compare lanes
    only (the pipeline hands the fused program's rank keys over); the data
    lanes ride untouched and come back merged bit-identically."""
    rng = np.random.default_rng(8)
    ext_runs = []
    for n in (70, 66, 40):
        lanes = _sorted_run(rng, n, 2, hi=2**16)
        cmp = packed_cmp_lanes(lanes, (2**16 - 1,) * 2)
        assert len(cmp) == 1  # 2x16 bits packs into one uint32 rank key
        ext_runs.append(tuple(cmp) + tuple(lanes))
    got = merge_runs_kway_pallas(ext_runs, n_cmp=1, block=128,
                                 interpret=True)
    expect = _oracle([r[1:] for r in ext_runs])
    _assert_lanes_equal(got[1:], expect)


def test_kernel_rejects_bad_block_and_arity():
    rng = np.random.default_rng(3)
    runs = [_sorted_run(rng, 8), _sorted_run(rng, 8)]
    with pytest.raises(ValueError, match="power of two"):
        merge_runs_kway_pallas(runs, block=96)
    with pytest.raises(ValueError, match="arity"):
        merge_runs_kway_pallas([runs[0], runs[1][:2]])


# ---------------------------------------------------------------------------
# ops / pipeline engine knobs
# ---------------------------------------------------------------------------

def test_pipeline_engines_bit_identical():
    """merge_runs: 'kway' (default route), 'kway_kernel' (forced Pallas
    tier), and 'tournament' (the legacy oracle) agree bit-for-bit."""
    rng = np.random.default_rng(11)
    runs = [_sorted_run(rng, n) for n in (64, 48, 33, 16, 9)]
    expect = _oracle(runs)
    for engine in ("auto", "kway", "kway_kernel", "tournament"):
        got = merge_runs(runs, engine=engine, block_size=128)
        _assert_lanes_equal(got, expect)
    with pytest.raises(ValueError, match="engine"):
        merge_runs(runs, engine="bogus")


def test_merge_sorted_lex_kway_engine():
    """The 2-run special case routes through the k-way front-end and still
    matches the pairwise packed engine bit-for-bit."""
    rng = np.random.default_rng(21)
    a, b = _sorted_run(rng, 60), _sorted_run(rng, 45)
    got = merge_sorted_lex(a, b, engine="kway")
    expect = merge_sorted_lex(a, b, engine="packed")
    _assert_lanes_equal(got, expect)


def test_merge_runs_lex_degenerate_and_empty():
    rng = np.random.default_rng(31)
    empty = tuple(jnp.zeros((0,), jnp.uint32) for _ in range(3))
    one = tuple(_sorted_run(rng, 12))
    with pytest.raises(ValueError, match="arity"):
        merge_runs_lex([])  # the pipeline tier, not ops, owns the [] case
    assert merge_runs([]) == ()
    _assert_lanes_equal(merge_runs_lex([empty, empty]), list(empty))
    _assert_lanes_equal(merge_runs_lex([empty, one, empty]), list(one))
    mixed = merge_runs_lex([one, empty, tuple(_sorted_run(rng, 5))])
    assert mixed[0].shape[0] == 17


def test_choose_kway_engine_contract():
    assert choose_kway_engine(10**6) in ("take", "kernel")
    assert choose_kway_engine(4, engine="kernel") == "kernel"
    assert choose_kway_engine(4, engine="take") == "take"
    with pytest.raises(ValueError):
        choose_kway_engine(4, engine="bogus")
