"""The harness on the CPU: it finds its pieces by file name, refuses to run
without a TPU, and, steered here onto CPU devices at a tiny size, runs a
whole cell and decides ``correct``, also against a broken timed path."""

import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

import corpus
import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ("ds2-kway", "ds1-kway", "ds2-onechunk")
TINY = {"words_per_job": 600}
TINY_CELLS = {
    "tiny-kway": {"config": "tiny", "chips": 1,
                  "entry": "chunked_sort_packed", "chunk_size": 256,
                  "pool": 2, "why": "a tiny test cell, three runs merged"},
    "tiny-onechunk": {"config": "tiny", "chips": 1,
                      "entry": "chunked_sort_packed", "chunk_size": 1024,
                      "pool": 2, "why": "a tiny test cell in one chunk"},
}

def _spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _copy(tmp_path):
    """A checkout of the benchmark in ``tmp_path`` (``src`` linked in), with
    a tiny configuration and cells added as files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), root / "src")
    with open(os.path.join(REPO, "bench", "configs", "paper-ds1.json")) as f:
        config = json.load(f)
    config.update(TINY, name="tiny")
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    spec = _spec()
    for name, cell in TINY_CELLS.items():
        (root / "bench" / "cells" / f"{name}.json").write_text(
            json.dumps(cell))
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": name, "chips": cell["chips"],
                                  "why": cell["why"]})
        for metric in spec["end_to_end"] + spec["per_layer"]:
            if "workloads" in metric:
                metric["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """The copy, with the harness pointed at it and steered onto the CPU
    device."""
    import jax
    root = _copy(tmp_path)
    # the harness turns on the persistent cache for every program; in a
    # test worker, shared with other test files, that must not outlive it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    names = ("jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in names}
    monkeypatch.setattr(harness, "ROOT", str(root))
    monkeypatch.setattr(harness, "require_accelerator",
                        lambda chips: [jax.devices("cpu")[0]] * chips)
    monkeypatch.setattr(harness, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    yield root
    for name, value in before.items():
        jax.config.update(name, value)


def _result(capsys, argv):
    assert harness.main(argv) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def _break_entry(monkeypatch, fault):
    """Wrap the cell's entry point so that its output is broken by
    ``fault(keys, lengths, sorted_keys) -> (lengths, keys)``."""
    load = harness.load_module

    def load_broken(kind, name):
        module = load(kind, name)
        if kind != "entries":
            return module

        def make(cell, devices):
            job = module.make(cell, devices)

            def broken(keys):
                lengths, out = job(keys)
                return fault(keys, np.asarray(lengths), np.asarray(out))
            return broken
        return types.SimpleNamespace(make=make)

    monkeypatch.setattr(harness, "load_module", load_broken)


def _lengths_of(keys):
    """Byte length of each packed word: its nonzero bytes."""
    return np.count_nonzero(
        np.asarray(keys).astype(">u4").view(np.uint8).reshape(
            len(keys), -1), axis=1).astype(np.int32)


def _altered(keys, lengths, out):
    out = out.copy()
    out[len(out) // 2, 0] ^= 1
    return lengths, out


FAULTS = {
    "unchanged": lambda keys, lengths, out: (_lengths_of(keys), keys),
    "half_left_out": lambda keys, lengths, out: (
        lengths[:len(lengths) // 2], out[:len(out) // 2]),
    "answer_altered": _altered,
}


def control_bytes_only(keys, lengths, out):
    """The control: the reference with one guarantee broken, the length
    left out of the compare, so words order by bytes alone ("aa" < "z")."""
    order = np.lexsort(tuple(keys[:, i] for i in reversed(
        range(keys.shape[1]))))
    return _lengths_of(keys)[order], keys[order]


def test_benchmark_json_names_every_piece():
    spec = _spec()
    assert [w["name"] for w in spec["workloads"]] == list(CELLS)
    assert {m["name"] for m in spec["end_to_end"]} == {
        "words_per_s", "job_p90_ms", "setup_s"}
    for metric in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.isfile(os.path.join(
            REPO, "bench", "metrics", metric["name"] + ".py"))
    for config in spec["configs"]:
        with open(os.path.join(REPO, config["file"])) as f:
            assert json.load(f)["source"] == config["source"]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_is_found_by_name(name):
    cell = harness.load_cell(name)
    entry = next(w for w in _spec()["workloads"] if w["name"] == name)
    assert cell.spec["why"] == entry["why"]
    assert cell.config["name"] == entry["config"]
    assert {m["name"] for m in cell.end_to_end} >= {"words_per_s", "setup_s"}
    assert cell.per_layer
    assert os.path.isfile(os.path.join(
        REPO, "bench", "entries", cell.spec["entry"] + ".py"))


def test_a_new_cell_is_files_only(checkout):
    cell = harness.load_cell("tiny-kway")
    assert cell.config["words_per_job"] == 600
    assert cell.spec["chunk_size"] == 256
    with pytest.raises(harness.Refused):
        harness.load_cell("no-such-cell")


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.Refused):
        harness.peaks_for("TPU v99")


def _run_py(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ds1-kway", "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run_py(REPO, env)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_run_refuses_without_the_program(tmp_path):
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    proc = _run_py(tmp_path, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_a_whole_run_is_correct(checkout, capsys, cell):
    result = _result(capsys, ["--workload", cell, "--seed", str(2**31 + 7),
                              "--seconds", "0.5"])
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"words_per_s", "job_p90_ms",
                                      "setup_s"}
    assert result["device"]["count"] == TINY_CELLS[cell]["chips"]
    assert list(result)[-1] == "checks"
    assert result["checks"]["mismatched_rows"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(checkout, capsys, monkeypatch,
                                            fault):
    _break_entry(monkeypatch, FAULTS[fault])
    result = _result(capsys, ["--workload", "tiny-kway", "--seed", "11",
                              "--seconds", "0.2"])
    assert result["correct"] is False
    assert result["checks"]["mismatched_rows"]["value"] > 0


def test_the_control_is_not_correct(checkout, capsys, monkeypatch):
    _break_entry(monkeypatch, control_bytes_only)
    result = _result(capsys, ["--workload", "tiny-kway", "--seed", "12",
                              "--seconds", "0.2"])
    assert result["correct"] is False
    assert result["checks"]["mismatched_rows"]["value"] > 0


def test_a_traced_run_on_the_cpu_reports_no_device_metric(checkout, capsys):
    """No TPU plane in a CPU trace: every device reader finds nothing and
    leaves its metric out, rather than reading 0."""
    result = _result(capsys, ["--workload", "tiny-kway", "--seed", "13",
                              "--seconds", "0.2", "--trace", "1"])
    assert result["correct"] is True
    assert result["metrics"] == {}
    assert result["device"]["busy_s"] == 0.0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
