"""The sort pipeline's host spans (``runtime/trace.py``) and the named
scopes of its two device programs, read back from a profiler trace recorded
on the CPU: which spans the chunked path emits, how they nest, on which
thread, with which attributes; that tracing leaves the output alone; and
that the lowered programs carry every scope name."""

import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.bucketing import _fused_sort_packed
from repro.core.packing import pack_words
from repro.kernels.kway_kernel import _kway_merge_jit
from repro.pipeline import chunked_sort_packed, chunked_sort_words
from repro.pipeline.merge import merge_runs
from repro.runtime.trace import SPANS, span

CHUNK = 128
N_WORDS = 300            # three chunks: 128, 128 and a tail of 44
N_CHUNKS = 3


def _words(n, seed=0):
    rng = np.random.default_rng(seed)
    return ["".join(rng.choice(list("abcdefg"), int(k)))
            for k in rng.integers(1, 8, n)]


def read_spans(trace_dir):
    """``(name, thread, start, end, attrs)`` of every ``sort.<span>`` event
    of the one trace under ``trace_dir``, in time order (on the CPU the XLA
    operations share the host plane, and a ``sort.21`` there is an HLO
    instruction)."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            out += [(ev.name, f"{plane.name}#{i}", int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns), dict(ev.stats))
                    for ev in line.events
                    if ev.name.startswith("sort.") and ev.name[5:] in SPANS]
    return sorted(out, key=lambda s: (s[2], -s[3]))


def _named(spans, name):
    return [s for s in spans if s[0] == "sort." + name]


def _inside(inner, outer):
    return (inner[1] == outer[1] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    keys = np.asarray(pack_words(_words(N_WORDS)))
    plain = chunked_sort_packed(keys, chunk_size=CHUNK)  # compiles here
    plain = (np.asarray(plain.lengths), np.asarray(plain.keys))
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(trace_dir):
        run = chunked_sort_packed(keys, chunk_size=CHUNK)
        traced_out = (np.asarray(run.lengths), np.asarray(run.keys))
    return read_spans(trace_dir), plain, traced_out, keys


def test_the_chunked_path_emits_its_spans(traced):
    names = {s[0] for s in traced[0]}
    assert names == {"sort." + n for n in (
        "job", "stage", "ingest_chunk", "dispatch", "sync",
        "streaming_combine")}


def test_spans_nest_job_chunk_then_dispatch_and_sync(traced):
    spans = traced[0]
    (job,) = _named(spans, "job")
    chunks = _named(spans, "ingest_chunk")
    assert len(chunks) == N_CHUNKS
    assert all(_inside(c, job) for c in chunks)
    for s in _named(spans, "dispatch") + _named(spans, "sync"):
        assert _inside(s, job)
    # each chunk's fused launch and its count read sit inside that chunk
    for c in chunks:
        inner = [s for s in spans if _inside(s, c) and s is not c]
        assert [(s[0], s[4].get("program", s[4].get("what")))
                for s in inner] == [
            ("sort.dispatch", "_fused_sort_packed"),
            ("sort.sync", "max_count"),
            ("sort.dispatch", "slice_outputs")]
    (combine,) = _named(spans, "streaming_combine")
    assert _inside(combine, job)
    assert [s[4]["program"] for s in _named(spans, "dispatch")
            if _inside(s, combine)] == ["_kway_take_jit"]


def test_one_host_sync_per_chunk(traced):
    syncs = _named(traced[0], "sync")
    assert len(syncs) == N_CHUNKS
    assert {s[4]["what"] for s in syncs} == {"max_count"}


def test_staging_runs_on_the_prefetch_thread(traced):
    spans = traced[0]
    (job,) = _named(spans, "job")
    stages = _named(spans, "stage")
    assert [s[4]["chunk"] for s in stages] == list(range(N_CHUNKS))
    assert all(s[1] != job[1] for s in stages)
    assert len({s[1] for s in stages}) == 1


def test_span_attributes(traced):
    spans, keys = traced[0], traced[3]
    (job,) = _named(spans, "job")
    assert job[4] == {"rows": N_WORDS, "chunks": N_CHUNKS}
    rows = [CHUNK, CHUNK, N_WORDS - 2 * CHUNK]
    assert [s[4] for s in _named(spans, "ingest_chunk")] == [
        {"chunk": i, "rows": r, "capacity": r} for i, r in enumerate(rows)]
    assert [s[4]["bytes"] for s in _named(spans, "stage")] == [
        r * keys.shape[1] * 4 for r in rows]
    assert {s[4]["program"] for s in _named(spans, "dispatch")} == {
        "_fused_sort_packed", "slice_outputs", "run_lanes", "_kway_take_jit",
        "stack_lanes"}
    (combine,) = _named(spans, "streaming_combine")
    assert combine[4] == {"runs": N_CHUNKS, "rows": N_WORDS}


def test_output_is_bit_identical_with_the_profiler_on(traced):
    _, plain, traced_out, _ = traced
    for a, b in zip(plain, traced_out):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("entry", ["words", "tournament"])
def test_other_entries_emit_their_spans(entry, tmp_path):
    words = _words(200, seed=1)
    keys = np.asarray(pack_words(words))
    runs = [chunked_sort_packed(keys[i:j], chunk_size=64).lanes()
            for i, j in ((0, 64), (64, 128), (128, 200))]
    if entry == "words":
        def call():
            return chunked_sort_words(words, chunk_size=64)
    else:
        def call():
            return merge_runs(runs, engine="tournament")
    plain = call()
    with jax.profiler.trace(str(tmp_path)):
        out = call()
    spans = read_spans(str(tmp_path))
    if entry == "words":
        assert out == plain
        (job,) = _named(spans, "job")
        assert job[4] == {"rows": 200, "chunks": 4}
        (unpack,) = [s for s in _named(spans, "sync")
                     if s[4]["what"] == "unpack_keys"]
        assert _inside(unpack, job)
    else:
        for a, b in zip(out, plain):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert [s[4] for s in _named(spans, "merge_round")] == [
            {"runs": 3, "rows": 200}, {"runs": 2, "rows": 200}]


def test_span_rejects_a_name_it_does_not_know():
    with pytest.raises(ValueError, match="unknown span"):
        span("exchange")


def _scopes(text, program):
    """The first scope under ``program`` of every op location in a lowered
    module's text."""
    return set(re.findall(r'"jit\(' + program + r'\)/(\w+)/', text))


def test_fused_program_carries_its_scopes():
    text = _fused_sort_packed.lower(
        jax.ShapeDtypeStruct((256, 2), jnp.uint32), capacity=256,
        algorithm="pallas").as_text(debug_info=True)
    assert {"distribute", "bucket_scatter", "bucket_sort", "compact",
            "rank_keys"} <= _scopes(text, "_fused_sort_packed")


def test_fused_program_compacts_without_a_scatter():
    """The compaction copies each bucket's block to its offset: the lowered
    program keeps its ``compact`` and ``rank_keys`` scopes, and no scatter
    sits under ``compact``."""
    text = _fused_sort_packed.lower(
        jax.ShapeDtypeStruct((256, 4), jnp.uint32), capacity=256,
        algorithm="pallas").as_text(debug_info=True)
    assert {"compact", "rank_keys"} <= _scopes(text, "_fused_sort_packed")
    compact_ops = set(re.findall(
        r'"jit\(_fused_sort_packed\)/compact/(?:[^"]*/)?(\w+)"', text))
    assert "dynamic_update_slice" in compact_ops
    assert not {op for op in compact_ops if "scatter" in op}


def test_kway_program_carries_its_scopes():
    args = [jax.ShapeDtypeStruct((n,), d) for n in (300, 200, 100)
            for d in (jnp.uint32, jnp.int32, jnp.uint32)]
    text = _kway_merge_jit.lower(
        *args, n_arr=3, n_runs=3, n_cmp=1, max_values=None, block=128,
        interpret=True).as_text(debug_info=True)
    assert _scopes(text, "_kway_merge_jit") == {
        "kway_ranks", "kway_starts", "kway_pad", "kway_kernel"}


_MESH = """
import glob, json, os, sys, tempfile
import jax, numpy as np
sys.path.insert(0, {tests!r})
from test_trace_spans import _words, read_spans
from repro.core.distributed import distributed_chunked_sort_lex
from repro.core.packing import pack_words

assert len(jax.devices()) == 4
keys = np.asarray(pack_words(_words(400, seed=2)))
distributed_chunked_sort_lex(keys)
d = tempfile.mkdtemp()
with jax.profiler.trace(d):
    distributed_chunked_sort_lex(keys)
print(json.dumps([[s[0], s[4]] for s in read_spans(d)]))
"""


@pytest.fixture(scope="module")
def mesh_spans():
    """``(name, attrs)`` of every span of one traced call of the mesh path
    on 4 fake devices, 400 words, after an untraced call that compiles."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run(
        [sys.executable, "-c",
         _MESH.format(tests=os.path.dirname(os.path.abspath(__file__)))],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_mesh_path_emits_exchange_and_boundary_sync(mesh_spans):
    spans = mesh_spans
    (exchange,) = [a for n, a in spans if n == "sort.run_exchange"]
    # 4 runs cut at 3 splitters: at least one slice per destination, at
    # most 4 x 4; each row carries its length and key lanes and its
    # compare lanes
    assert 4 <= exchange["slices"] <= 16
    assert exchange["bytes"] >= 400 * 4 * 3
    assert sorted(a["what"] for n, a in spans if n == "sort.sync"
                  and a["what"] != "max_count") == [
        "run_boundaries", "splitter_samples"]
    assert sum(n == "sort.ingest_chunk" for n, _ in spans) == 4
    assert sum(n == "sort.streaming_combine" for n, _ in spans) == 4


def test_mesh_path_emits_one_job_and_one_gather(mesh_spans):
    """The mesh call is one ``sort.job`` of its rows and chunks, ends in
    one ``sort.gather`` of every row, and its exchange hands the combines
    every row in slots of fixed rows: 4 runs x 4 destinations x 128."""
    spans = mesh_spans
    named = {}
    for n, a in spans:
        named.setdefault(n, []).append(a)
    assert named["sort.job"] == [{"rows": 400, "chunks": 4}]
    assert named["sort.gather"] == [{"rows": 400}]
    (exchange,) = named["sort.run_exchange"]
    assert exchange["rows"] == 400
    assert exchange["slots"] == 4 * 4 * 128 >= exchange["rows"]


def test_the_readme_lists_every_span_and_its_attributes():
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "README.md")) as f:
        rows = {m.group(1): m.group(2) for m in re.finditer(
            r"^\| `sort\.(\w+)` \|.*\| ([^|]*) \|$", f.read(), re.M)}
    assert set(rows) == set(SPANS)
    for name, attrs in (("job", "rows chunks"), ("gather", "rows"),
                        ("run_exchange", "slices bytes rows slots")):
        assert set(re.findall(r"`(\w+)`", rows[name])) == set(
            attrs.split()), name
