"""Pipeline tier: the on-device distribute (ops.distribute / ops.bucketize)
against the host reference bucketizer, the zero-host-loop guard on
``bucketed_sort_words``, and the chunked sorted-run ingest
(``repro.pipeline``) against the shortlex oracle.

Sizes stay small: every case compiles interpret-mode Pallas programs on this
CPU container. Words cap at 11 bytes (3 uint32 lanes, 13 buckets) so the
fused program stays in the oets/bitonic tiers.
"""

from unittest import mock

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

import repro.core.bucketing as core_bucketing
from repro.core import bucketize_packed, bucketize_words, sorted_packed
from repro.core.packing import SENTINEL_U32, pack_words, unpack_words
from repro.kernels import bucketize, distribute
from repro.pipeline import (SortedRun, chunked_sort_packed,
                            chunked_sort_words, merge_runs, merge_two)


def _shortlex(words):
    return sorted(words, key=lambda w: (len(w.encode()), w.encode()))


def _word_set(kind, n, rng, max_len=11):
    """Three length distributions the differential sweep covers."""
    alpha = "abcdefgh"
    if kind == "random":
        lens = rng.integers(0, max_len + 1, n)
    elif kind == "dup":  # few distinct words, many repeats
        pool = ["".join(rng.choice(list(alpha), rng.integers(1, max_len + 1)))
                for _ in range(max(2, n // 10))]
        return [pool[i] for i in rng.integers(0, len(pool), n)]
    elif kind == "skew":  # nearly everything one length, a thin tail
        lens = np.where(rng.random(n) < 0.9, 5,
                        rng.integers(0, max_len + 1, n))
    else:
        raise ValueError(kind)
    return ["".join(rng.choice(list(alpha), l)) for l in lens]


# ---------------------------------------------------------------------------
# device distribute / bucketize vs the host reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["random", "dup", "skew"])
def test_device_bucketize_matches_host(kind):
    """300 words > 2 x the 128-word kernel block, so the sequential-grid
    running-count carry (stable ranks across block boundaries) is on the
    differential path, not just the single-block case."""
    rng = np.random.default_rng({"random": 0, "dup": 1, "skew": 2}[kind])
    words = _word_set(kind, 300, rng)
    keys = jnp.asarray(pack_words(words))
    host = bucketize_words(words)
    dev_keys, dev_counts, _ = bucketize(keys)
    dev_counts = np.asarray(dev_counts)
    # dense per-length device buckets vs sparse host buckets: same counts,
    # same contents in arrival order, everything else empty
    host_by_len = dict(zip(host.lengths.tolist(),
                           range(len(host.lengths))))
    for l in range(dev_keys.shape[0]):
        if l in host_by_len:
            hi = host_by_len[l]
            cnt = int(host.counts[hi])
            assert dev_counts[l] == cnt
            np.testing.assert_array_equal(
                np.asarray(dev_keys)[l, :cnt],
                host.keys[hi, :cnt])
        else:
            assert dev_counts[l] == 0
    # all unused device slots hold the sentinel
    slot = np.arange(dev_keys.shape[1])
    mask = slot[None, :] >= dev_counts[:, None]
    assert (np.asarray(dev_keys)[mask] == SENTINEL_U32).all()


def test_distribute_stable_ranks_and_histogram():
    words = ["aa", "bb", "aa", "c", "dd", "c", "aa", ""]
    dest, rank, counts = distribute(jnp.asarray(pack_words(words)))
    assert np.asarray(dest).tolist() == [2, 2, 2, 1, 2, 1, 2, 0]
    # arrival order within each length bucket
    assert np.asarray(rank).tolist() == [0, 1, 2, 0, 3, 1, 4, 0]
    assert np.asarray(counts).tolist()[:3] == [1, 2, 5]


def test_bucketize_explicit_capacity_counts_overflow():
    """Clipped words drop from the tensor but stay in the true counts (the
    exact-count contract); bucketize_packed raises like the host version."""
    keys = jnp.asarray(pack_words(["aa", "bb", "cc", "d"]))
    bk, counts, dropped = bucketize(keys, capacity=2)
    assert int(counts[2]) == 3 and bk.shape[1] == 2
    assert dropped == 1  # the clipped word is *reported*, never silent
    with pytest.raises(ValueError, match="exceeds capacity"):
        bucketize_packed(keys, capacity=2)


def test_bucketize_skew_overflow_policies():
    """A skewed dataset (90% of words one length) against a capacity sized
    for the uniform case: 'clip' must report exactly how many words fell
    past capacity, 'retry' must converge losslessly at the true max, and
    'raise' must carry capacity/required/dropped on the exception."""
    from repro.runtime import CapacityOverflow

    rng = np.random.default_rng(33)
    words = _word_set("skew", 120, rng, max_len=7)
    keys = jnp.asarray(pack_words(words))
    per_len = np.bincount([len(w.encode()) for w in words], minlength=9)
    cap = 16
    want_drop = int(np.maximum(per_len - cap, 0).sum())
    assert want_drop > 0  # the skew really overflows this capacity

    bk, counts, dropped = bucketize(keys, capacity=cap, on_overflow="clip")
    assert dropped == want_drop
    np.testing.assert_array_equal(np.asarray(counts), per_len[: counts.shape[0]])

    bk, counts, dropped = bucketize(keys, capacity=cap, on_overflow="retry")
    assert dropped == 0 and bk.shape[1] == int(per_len.max())

    with pytest.raises(CapacityOverflow) as ei:
        bucketize(keys, capacity=cap, on_overflow="raise")
    assert ei.value.capacity == cap
    assert ei.value.required == int(per_len.max())
    assert ei.value.dropped == want_drop


@pytest.mark.parametrize("kind", ["random", "skew"])
def test_bucketize_capacity_autotune_exact(kind):
    """The two-tier autotune (capacity=None): the optimistic first shot
    must hold every word on near-uniform inputs, and the skewed case —
    one length holding most of the words, far past the optimistic cap —
    must retry at the true max. Either way zero words drop and the tensor
    equals the host reference's buckets."""
    rng = np.random.default_rng({"random": 21, "skew": 22}[kind])
    words = _word_set(kind, 260, rng, max_len=7)
    keys = jnp.asarray(pack_words(words))
    bk, counts, dropped = bucketize(keys)
    assert bk.shape[1] >= int(jnp.max(counts))  # no overflow ever
    assert dropped == 0
    host = bucketize_words(words)
    host_by_len = dict(zip(host.lengths.tolist(), range(len(host.lengths))))
    for l in range(bk.shape[0]):
        if l in host_by_len:
            cnt = int(host.counts[host_by_len[l]])
            assert int(counts[l]) == cnt
            np.testing.assert_array_equal(
                np.asarray(bk)[l, :cnt], host.keys[host_by_len[l], :cnt])
        else:
            assert int(counts[l]) == 0
    if kind == "skew":
        # the dominant length must exceed the optimistic first-shot cap,
        # otherwise this case stopped exercising the retry tier
        from repro.kernels.ops import _optimistic_capacity
        assert int(jnp.max(counts)) > _optimistic_capacity(len(words),
                                                           bk.shape[0])


def test_host_reference_buckets_by_byte_length():
    """Host and device agree on non-ASCII: both bucket by *encoded byte*
    length (the unit the packed lanes sort by), so 'é' (2 bytes) shares a
    bucket with 'ab', not with 'a'."""
    host = bucketize_words(["é", "ab", "a"])
    assert host.lengths.tolist() == [1, 2]
    assert host.counts.tolist() == [1, 2]
    _, _, counts = distribute(jnp.asarray(pack_words(["é", "ab", "a"])))
    assert np.asarray(counts).tolist()[:3] == [0, 1, 2]
    words = ["é", "ab", "a", "日本", "zz"]
    got = core_bucketing.bucketed_sort_words(words, algorithm="pallas")
    assert got == _shortlex(words)


def test_assign_buckets_rejects_unsorted_bounds():
    from repro.pipeline import assign_buckets
    with pytest.raises(ValueError, match="ascending"):
        assign_buckets([5], [16, 4])


def test_bucketize_packed_empty_input():
    b = bucketize_packed(jnp.zeros((0, 1), jnp.uint32))
    assert b.keys.shape[1] == 0 and int(b.counts.sum()) == 0


def test_bucketed_sort_words_never_calls_host_bucketizer():
    """The acceptance pin: after packing, the end-to-end path has no
    host-side per-word Python loop — the host dict-loop bucketizer must be
    dead code on the production path."""
    words = ["serpent", "sorbet", "sierra", "samba", "sonata", "sunset",
             "s", "", "sorbet"]
    with mock.patch.object(core_bucketing, "bucketize_words",
                           side_effect=AssertionError("host bucketizer ran")):
        got = core_bucketing.bucketed_sort_words(words, algorithm="pallas")
    assert got == _shortlex(words)


def test_sorted_packed_shortlex_and_lengths():
    words = ["zz", "a", "zzz", "b", "aaa", ""]
    lens, keys = sorted_packed(jnp.asarray(pack_words(words)))
    assert unpack_words(np.asarray(keys)) == _shortlex(words)
    assert np.asarray(lens).tolist() == sorted(len(w) for w in words)


# ---------------------------------------------------------------------------
# the fused program's compaction against a NumPy shortlex reference
# ---------------------------------------------------------------------------

def _words_of_lengths(lengths, seed):
    """Words of the given byte lengths over a 3-letter alphabet (so equal
    words repeat), in a shuffled arrival order."""
    rng = np.random.default_rng(seed)
    lengths = rng.permutation(np.asarray(lengths, np.int64))
    return ["".join(rng.choice(list("abc"), int(l))) for l in lengths]


def _shortlex_reference(words, capacity, lanes=4):
    """NumPy shortlex reference of the fused program's n output rows: each
    length keeps its first ``capacity`` words in arrival order, those sort
    by (length, packed lanes), and the rows past them hold length 0 and
    the sentinel. Returns ``(lengths, keys, (hi, lo), kept)`` with the
    packed rank keys the top 64 bits of (length, lane0, ..., laneL-1)."""
    keys = np.asarray(pack_words(words, width=4 * lanes)).astype(np.uint64)
    lens = np.asarray([len(w.encode()) for w in words], np.int64)
    seen = {}
    keep = np.zeros(len(words), bool)
    for i, l in enumerate(lens):
        keep[i] = seen.get(l, 0) < capacity
        seen[l] = seen.get(l, 0) + 1
    order = np.lexsort([keys[:, l] for l in reversed(range(lanes))]
                       + [lens])
    order = order[keep[order]]
    kept = len(order)
    out_lens = np.zeros(len(words), np.int32)
    out_keys = np.full((len(words), lanes), SENTINEL_U32, np.uint32)
    out_lens[:kept] = lens[order]
    out_keys[:kept] = keys[order]
    len_bits = (4 * lanes).bit_length()
    total = len_bits + 32 * lanes
    hi = np.zeros(len(words), np.uint32)
    lo = np.zeros(len(words), np.uint32)
    for r in range(len(words)):
        v = int(out_lens[r])
        for l in range(lanes):
            v = (v << 32) | int(out_keys[r, l])
        v >>= max(total - 64, 0)
        hi[r], lo[r] = v >> 32, v & 0xFFFFFFFF
    return out_lens, out_keys, (hi, lo), kept


# (name, byte length of every word, capacity): the bucket layouts the
# compaction has to place
_LAYOUTS = [
    ("one_length", [5] * 48, 48),
    ("empty_buckets_between", [0] * 7 + [2] * 20 + [9] * 20 + [13] * 3, 20),
    ("all_lengths_last_full", [l for l in range(1, 16) for _ in range(3)]
     + [16] * 24, 24),
    ("n_is_1", [3], 1),
    ("capacity_above_n", list(np.arange(60) % 17), 96),
    ("capacity_below_largest", [4] * 40 + [7] * 12 + [1] * 5, 9),
]


@pytest.mark.parametrize("name,lengths,capacity", _LAYOUTS,
                         ids=[c[0] for c in _LAYOUTS])
def test_fused_program_compacts_bucket_layouts(name, lengths, capacity):
    """The fused program's outputs have n rows; the first
    ``sum(min(counts, capacity))`` are the kept words in exact shortlex
    order, the rest length 0 and the sentinel, and lengths, keys and packed
    rank keys all equal the NumPy reference bit for bit."""
    words = _words_of_lengths(lengths, seed=len(name))
    n = len(words)
    lens, keys, counts, packed = core_bucketing._fused_sort_packed(
        jnp.asarray(pack_words(words, width=16)), capacity=capacity,
        algorithm="pallas")
    want_lens, want_keys, want_packed, kept = _shortlex_reference(
        words, capacity)
    assert kept == int(np.minimum(np.asarray(counts), capacity).sum())
    assert lens.shape == (n,) and keys.shape == (n, 4)
    assert [p.shape for p in packed] == [(n,), (n,)]
    np.testing.assert_array_equal(np.asarray(lens), want_lens)
    np.testing.assert_array_equal(np.asarray(keys), want_keys)
    for got, want in zip(packed, want_packed):
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("on_overflow", ["clip", "retry"])
def test_sorted_packed_below_the_largest_bucket(on_overflow):
    """A capacity below the largest bucket through ``sorted_packed``:
    'clip' returns the kept words, each length's first ``capacity`` in
    arrival order, and 'retry' every word, both bit for bit against the
    NumPy reference."""
    words = _words_of_lengths([4] * 40 + [7] * 12 + [1] * 5 + [16] * 10,
                              seed=7)
    capacity = 9
    lens, keys, packed = sorted_packed(
        jnp.asarray(pack_words(words, width=16)), capacity=capacity,
        return_packed=True, on_overflow=on_overflow)
    want_lens, want_keys, want_packed, kept = _shortlex_reference(
        words, capacity if on_overflow == "clip" else len(words))
    assert kept == (9 + 9 + 5 + 9 if on_overflow == "clip" else len(words))
    np.testing.assert_array_equal(np.asarray(lens), want_lens[:kept])
    np.testing.assert_array_equal(np.asarray(keys), want_keys[:kept])
    for got, want in zip(packed, want_packed):
        np.testing.assert_array_equal(np.asarray(got), want[:kept])


# ---------------------------------------------------------------------------
# run merge
# ---------------------------------------------------------------------------

def _run_of(words):
    ws = _shortlex(words)
    keys = jnp.asarray(pack_words(ws, width=11))
    lens = jnp.asarray([len(w.encode()) for w in ws], jnp.int32)
    return SortedRun(lengths=lens, keys=keys)


def test_merge_two_unequal_lengths_and_duplicates():
    a = _run_of(["aa", "b", "zz", "aa"])
    b = _run_of(["ab", "c", "c", "yy", "aaa", "q"])
    merged = SortedRun.from_lanes(merge_two(a.lanes(), b.lanes()))
    want = _shortlex(["aa", "b", "zz", "aa", "ab", "c", "c", "yy", "aaa", "q"])
    assert unpack_words(np.asarray(merged.keys)) == want


def test_merge_runs_tournament_odd_count():
    groups = [["dd", "a"], ["bb", "e"], ["cc"], ["aa", "zzz"], ["b"]]
    merged = SortedRun.from_lanes(merge_runs([_run_of(g).lanes()
                                              for g in groups]))
    want = _shortlex([w for g in groups for w in g])
    assert unpack_words(np.asarray(merged.keys)) == want


def test_merge_is_shortlex_not_bytelex():
    """'z' must come before 'aa' — the length lane decides, not the bytes."""
    merged = SortedRun.from_lanes(
        merge_two(_run_of(["z"]).lanes(), _run_of(["aa"]).lanes()))
    assert unpack_words(np.asarray(merged.keys)) == ["z", "aa"]


# ---------------------------------------------------------------------------
# chunked ingest end-to-end
# ---------------------------------------------------------------------------

def test_chunked_sort_multiple_chunks_matches_oracle():
    """> 1 chunk (the acceptance pin): 130 words through 48-word chunks —
    3 runs, 2 merge rounds — exactly equals the shortlex oracle."""
    rng = np.random.default_rng(11)
    words = _word_set("random", 130, rng, max_len=9)
    got = chunked_sort_words(words, chunk_size=48)
    assert got == _shortlex(words)


def test_chunked_equals_single_launch():
    rng = np.random.default_rng(12)
    words = _word_set("dup", 90, rng, max_len=7)
    chunked = chunked_sort_words(words, chunk_size=32)
    single = core_bucketing.bucketed_sort_words(words, algorithm="pallas")
    assert chunked == single == _shortlex(words)


def test_chunked_sort_packed_run_is_exact():
    rng = np.random.default_rng(13)
    words = _word_set("skew", 100, rng, max_len=7)
    keys = jnp.asarray(pack_words(words))
    run = chunked_sort_packed(keys, chunk_size=40)
    assert run.keys.shape == keys.shape
    assert unpack_words(np.asarray(run.keys)) == _shortlex(words)
    byte_lens = [len(w.encode()) for w in _shortlex(words)]
    assert np.asarray(run.lengths).tolist() == byte_lens


def test_chunked_edge_cases():
    assert chunked_sort_words([]) == []
    assert chunked_sort_words(["b", "a"], chunk_size=1) == ["a", "b"]
    with pytest.raises(ValueError):
        chunked_sort_words(["a"], chunk_size=0)


def test_prefetch_map_orders_and_overlaps():
    """The packing double-buffer: results come back in order, one per item,
    and item i+1 runs on the worker thread while the consumer still holds
    item i (i.e. before the generator is advanced again)."""
    import time

    from repro.pipeline.ingest import _prefetch_map
    calls = []

    def fn(x):
        calls.append(x)
        return x * 10

    gen = _prefetch_map(fn, [1, 2, 3])
    first = next(gen)
    # without advancing the generator, the worker must already be packing
    # item 2 — that is the whole point of the prefetch
    deadline = time.monotonic() + 5
    while len(calls) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert calls == [1, 2]
    assert [first] + list(gen) == [10, 20, 30]
    assert calls == [1, 2, 3]
    assert list(_prefetch_map(fn, [])) == []


def test_packed_ingest_stages_h2d_on_worker_thread():
    """The device half of the ingest double buffer: every chunk's
    host->device staging (``_stage_chunk``) must run through the prefetch
    worker — never the consumer thread — once per chunk, in order, and the
    staged result must still sort exactly. Pins the H2D overlap the same way
    ``test_prefetch_map_orders_and_overlaps`` pins the packing half."""
    import threading

    import repro.pipeline.ingest as ingest_mod
    rng = np.random.default_rng(24)
    words = _word_set("random", 100, rng, max_len=7)
    keys = np.asarray(pack_words(words))
    staged = []
    main = threading.current_thread()
    real = ingest_mod._stage_chunk

    def spy(chunk):
        staged.append((int(chunk.shape[0]),
                       threading.current_thread() is not main))
        return real(chunk)

    with mock.patch.object(ingest_mod, "_stage_chunk", spy):
        run = chunked_sort_packed(keys, chunk_size=40)
    assert [s[0] for s in staged] == [40, 40, 20]  # once per chunk, in order
    assert all(off_main for _, off_main in staged)
    assert unpack_words(np.asarray(run.keys)) == _shortlex(words)


def test_merge_engine_knob_reaches_run_combine():
    """The ``merge_engine`` knob threads from the ingest front-ends to
    ``merge_runs``: every engine yields the identical shortlex result, and
    an unknown engine fails loudly."""
    rng = np.random.default_rng(25)
    words = _word_set("dup", 120, rng, max_len=7)
    outs = {eng: chunked_sort_words(words, chunk_size=48, merge_engine=eng)
            for eng in ("auto", "kway", "tournament")}
    assert outs["auto"] == outs["kway"] == outs["tournament"] \
        == _shortlex(words)
    with pytest.raises(ValueError, match="engine"):
        chunked_sort_words(words, chunk_size=48, merge_engine="bogus")


def test_chunked_words_runs_carry_packed_rank_keys():
    """Every per-chunk run ships the fused program's packed shortlex rank
    keys to the merge tier (no re-pack), and the packed lanes order exactly
    as the shortlex tuples."""
    from repro.pipeline import sorted_run
    rng = np.random.default_rng(23)
    words = _word_set("random", 60, rng, max_len=7)
    run = sorted_run(jnp.asarray(pack_words(words)))
    assert run.packed is not None and len(run.packed) == 2
    cmp = run.cmp_lanes()
    assert len(cmp) <= 1 + run.keys.shape[1]
    # packed lex order must be non-decreasing down the sorted run
    flat = np.stack([np.asarray(c) for c in cmp])
    prev, cur = flat[:, :-1], flat[:, 1:]
    gt = np.zeros(prev.shape[1], bool)
    eq = np.ones(prev.shape[1], bool)
    for i in range(flat.shape[0]):
        gt = gt | (eq & (prev[i] > cur[i]))
        eq = eq & (prev[i] == cur[i])
    assert not gt.any()


words_strategy = st.lists(
    st.text(alphabet=st.characters(min_codepoint=33, max_codepoint=126),
            min_size=0, max_size=11),
    min_size=0, max_size=60)


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(words_strategy, st.integers(min_value=1, max_value=25))
def test_chunked_pipeline_property(ws, chunk):
    """Random word lists x random chunk sizes: the chunked pipeline equals
    the shortlex oracle, and the device bucketize histogram equals the host
    length histogram."""
    got = chunked_sort_words(ws, chunk_size=chunk)
    assert got == _shortlex(ws)
    if ws:
        keys = jnp.asarray(pack_words(ws))
        _, _, counts = distribute(keys)
        hist = np.bincount([len(w.encode()) for w in ws],
                           minlength=counts.shape[0])
        np.testing.assert_array_equal(np.asarray(counts), hist)
