"""The mesh cell ``ds2-mesh4``: its configuration is DS2 over four workers,
the harness finds it and its entry by name, the entry refuses a chunk size that is not one chunk per chip and a
program whose mesh path has no fixed shapes, a tiny four-device cell runs
whole on fake CPU devices with nothing compiled in its window, and the
cell's readers read known values off hand-built traces."""

import json
import os
import subprocess
import sys

import pytest

import harness
import spans as sp
import tracereduce as tr
from test_perfbench_harness import REPO, _copy
from test_perfbench_trace import _run

E = tr.Event
CELLS = ("ds2-kway", "ds1-kway", "ds2-onechunk", "ds2-mesh4")
MESH_READERS = ("exchange_ms", "exchange_pad_share", "mesh_syncs_per_job",
                "mesh_combine_ms", "mesh_combine_roofline",
                "exchange_roofline", "gather_roofline")
JOB = "/host:CPU#1"


def _read(name, run):
    return harness.load_module("metrics", name).read(run)


def test_benchmark_json_lists_every_cell_and_piece():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert [w["name"] for w in spec["workloads"]] == list(CELLS)
    for metric in spec["per_layer"]:
        assert os.path.isfile(os.path.join(
            REPO, "bench", "metrics", metric["name"] + ".py"))
    mesh = [m for m in spec["per_layer"] if m["name"] in MESH_READERS]
    assert [m["workloads"] for m in mesh] == [["ds2-mesh4"]] * 7
    assert {m["moves"] for m in mesh} == {"words_per_s"}


def test_the_harness_finds_the_mesh_cell_and_its_entry():
    cell = harness.load_cell("ds2-mesh4")
    assert cell.spec["chips"] == 4
    assert cell.config["name"] == "paper-ds2-mesh4"
    assert cell.spec["chunk_size"] == -(-cell.config["words_per_job"] // 4)
    assert {m["name"] for m in cell.end_to_end} == {"words_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(MESH_READERS)
    entry = harness.load_module("entries", cell.spec["entry"])
    assert callable(entry.make(cell.spec, [None] * 4))


def test_the_mesh_configuration_is_ds2_over_four_workers():
    """The deployment's corpus is DS2's, key for key, so the cell's traffic
    is the paper's dataset 2; it adds the workers, cut from the paper's 8
    threads to the cell's 4 chips, and where the result goes."""
    def config(name):
        with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
            return json.load(f)
    ds2, mesh = config("paper-ds2"), config("paper-ds2-mesh4")
    added = {"workers", "result"}
    assert set(mesh) - set(ds2) == added
    for key in set(ds2) - {"name", "source", "sources"}:
        assert mesh[key] == ds2[key], key
    assert {k: v for k, v in mesh["sources"].items() if k != "workers"} \
        == ds2["sources"]
    assert mesh["source"] != ds2["source"]
    assert mesh["workers"] == harness.load_cell("ds2-mesh4").spec["chips"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "paper-ds2-mesh4")
    assert entry["reduced"] == ["workers"]
    assert entry["source"] == mesh["source"]


def test_the_entry_refuses_a_chunk_that_is_not_one_per_chip(monkeypatch):
    entry = harness.load_module("entries", "distributed_chunked_sort_lex")
    spec = dict(harness.load_cell("ds2-mesh4").spec)
    for chunk in (57499, 57501, 32768):
        with pytest.raises(harness.Refused, match="one chunk per chip"):
            entry.make(dict(spec, chunk_size=chunk), [None] * 4)
    with pytest.raises(harness.Refused, match="one chunk per chip"):
        entry.make(spec, [None] * 2)
    # a program from before the fixed-shape exchange: no sort.gather
    import repro.runtime.trace as trace
    monkeypatch.setattr(trace, "SPANS", tuple(
        s for s in trace.SPANS if s != "gather"))
    with pytest.raises(harness.Refused, match="fixed-shape"):
        entry.make(spec, [None] * 4)


_TINY_MESH = """
import json, sys
sys.path.insert(0, {bench!r})
import jax
import harness

harness.require_accelerator = lambda chips: jax.devices()[:chips]
harness.peaks_for = lambda kind: {{"hbm_bytes_per_s": 819e9}}
for trace in ("0", "1"):
    assert harness.main(["--workload", "tiny-mesh4", "--seed",
                         str(2**31 + 9), "--seconds", "1.5",
                         "--trace", trace]) == 0
"""


def test_a_tiny_four_device_cell_runs_whole(tmp_path):
    """600 words over 4 fake devices, a pool of 2: both runs (untraced and
    traced) are correct, every job of the window ran on programs compiled
    in the warm-up, and on the CPU no mesh reader finds a chip to read."""
    root = _copy(tmp_path)
    cell = {"config": "tiny", "chips": 4,
            "entry": "distributed_chunked_sort_lex", "chunk_size": 150,
            "pool": 2, "why": "a tiny four-device test cell"}
    (root / "bench" / "cells" / "tiny-mesh4.json").write_text(
        json.dumps(cell))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny-mesh4", "config": "tiny",
                              "traffic": "tiny-mesh4", "chips": 4,
                              "why": cell["why"]})
    for metric in spec["per_layer"]:
        if metric["name"] in MESH_READERS:
            metric["workloads"].append("tiny-mesh4")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "-c",
         _TINY_MESH.format(bench=str(root / "bench"))],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    results = [json.loads(x) for x in lines if x.startswith("{")]
    windows = [x for x in lines if x.startswith("window: ")]
    assert len(results) == len(windows) == 2
    for result, window in zip(results, windows):
        assert result["correct"] is True, result
        assert result["failed"] == 0 and result["attempted"] >= 2
        assert result["device"]["count"] == 4
        assert "compiles inside the window: 0 " in window, window
    assert set(results[0]["metrics"]) == {"words_per_s", "setup_s"}
    assert results[1]["metrics"] == {}


def _span(name, start, end, thread=JOB, **attrs):
    return sp.Span("sort." + name, thread, start, end - start, attrs)


def _mesh_trace(spans, window=(0, 100)):
    """Two chips: on each, a k-way combine of 10 ns and a gather of 4 ns;
    the split on chip 0 alone, 6 ns."""
    chips = [tr.ChipTrace(ops=[], modules=[
        E("jit__kway_merge_jit(3)", 10, 10), E("jit__gather_blocks(5)", 40, 4),
        E("jit__fused_sort_packed(1)", 0, 10)]),
        tr.ChipTrace(ops=[], modules=[
            E("jit__kway_take_jit(4)", 20, 10), E("jit__split_slots(2)", 50, 6),
            E("jit__gather_blocks(5)", 60, 4)])]
    trace = tr.Trace(chips=chips, host=[], window=window)
    trace.spans, trace.scoped_ops = spans, [[], []]
    return _run(trace, jobs=2)


def _job_spans():
    # two jobs on the job thread, each with its exchange and syncs; a third
    # exchange starts after the window; the worker's spans hold no job
    return [_span("job", 0, 48, rows=9, chunks=4),
            _span("sync", 5, 6, what="max_count"),
            _span("sync", 7, 9, what="run_boundaries"),
            _span("run_exchange", 10, 25, slices=4, bytes=1, rows=9,
                  slots=12),
            _span("job", 50, 99, rows=9, chunks=4),
            _span("sync", 51, 52, what="splitter_samples"),
            _span("run_exchange", 60, 70, slices=4, bytes=1, rows=9,
                  slots=16),
            _span("run_exchange", 120, 130, slices=4, bytes=1, rows=9,
                  slots=99),
            _span("run_exchange", 0, 90, thread="/host:CPU#0", rows=1,
                  slots=1000)]


def test_the_mesh_readers_read_known_values():
    run = _mesh_trace(_job_spans())
    ns_per_job_ms = 1e-6 / 2
    assert _read("exchange_ms", run) == pytest.approx((15 + 10) * ns_per_job_ms)
    assert _read("exchange_pad_share", run) == pytest.approx(
        ((12 - 9) + (16 - 9)) / (12 + 16))
    assert _read("mesh_syncs_per_job", run) == 1.5
    assert _read("mesh_combine_ms", run) == pytest.approx(20 * ns_per_job_ms)
    # 40 B x 1000 words at 36e6 B/s take 1/900 s, over the device time a job
    least = 40 * 1000 / 36e6
    for name, ns in (("mesh_combine_roofline", 20), ("exchange_roofline", 6),
                     ("gather_roofline", 8)):
        assert _read(name, run) == pytest.approx(
            100 * least / (ns / 1e9 / 2)), name


@pytest.mark.parametrize("trace", ["parent", "no_device", "untraced",
                                   "one_chip"])
def test_every_mesh_reader_is_none_without_what_it_reads(trace):
    """The parent's spans (an exchange with no ``slots``, no gather
    program), a trace with no device (the CPU's), no trace at all, and a
    one-chip run with no exchange: each reader leaves its metric out."""
    if trace == "parent":
        spans = [s if s.name != "sort.run_exchange" else sp.Span(
            s.name, s.thread, s.start_ns, s.dur_ns,
            {"slices": 4, "bytes": 1}) for s in _job_spans()]
        run = _mesh_trace(spans)
        assert _read("exchange_pad_share", run) is None
        assert _read("exchange_ms", run) is not None
        run.trace.chips = [tr.ChipTrace(ops=[], modules=[
            E("jit__kway_merge_jit(3)", 10, 10)])]
        for name in ("exchange_roofline", "gather_roofline"):
            assert _read(name, run) is None, name
        return
    if trace == "no_device":
        run = _mesh_trace(_job_spans())
        run.trace.chips = []
    elif trace == "untraced":
        run = _run(None)
    else:
        run = _mesh_trace([_span("job", 0, 48, rows=9, chunks=1)])
        run.trace.chips = [tr.ChipTrace(ops=[], modules=[
            E("jit__fused_sort_packed(1)", 0, 10)])]
    for name in MESH_READERS:
        assert _read(name, run) is None, name
