"""The benchmark harness: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name, each piece in a file of its own:
the cell among ``BENCHMARK.json``'s workloads and in the file its
``traffic`` names, ``bench/cells/<traffic>.json`` (configuration, chips,
entry point, chunk size, pool size); its configuration in
``bench/configs/<config>.json``; its entry point in
``bench/entries/<entry>.py``; each metric's reader in
``bench/metrics/<metric>.py``; and the chip's peaks in ``bench/peaks.json``
under its device kind. A new cell, configuration or metric is new files and
new ``BENCHMARK.json`` entries; this file does not change.

A run: find the chips; draw a pool of distinct corpora from ``--seed``; warm
up on the cell's own shapes (one job of the pool, fetch included: a job's
shapes follow from its word count and chunk size alone); then for ``--seconds`` run sort jobs back to back with one caller,
cycling through the pool, each job timed from the call until its sorted
lengths and keys are NumPy arrays on the host. After the window: read the
devices' memory peak, compare every job's output with the NumPy shortlex
reference of its corpus, and print the metrics. With ``--trace 1`` the window
runs under the profiler and the per-layer metrics are printed instead of the
end-to-end ones.

The last line of standard output is the result (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and last ``checks``: each number compared with its limit). The same checks
are the last lines of standard error. Without a TPU that runs the Pallas
kernels natively, or with fewer chips than the cell asks for, the run exits
non-zero before any result.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass

import numpy as np

import corpus
import tracereduce
from compile_log import CompileLog

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Refused(Exception):
    """The run cannot be made here; it exits non-zero with no result."""


@dataclass
class Cell:
    name: str
    spec: dict        # the cell's file
    config: dict      # its configuration's file
    end_to_end: list  # BENCHMARK.json's metrics that this cell reports
    per_layer: list


@dataclass
class Run:
    """What a metric's reader reads: one run's window and its trace."""
    cell: Cell
    peaks: dict
    words_per_job: int
    latencies_s: list
    window_s: float
    setup_s: float
    trace: tracereduce.Trace | None


def _bench(*parts) -> str:
    return os.path.join(ROOT, "bench", *parts)


def _read_json(path: str):
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric["workloads"] if "workloads" in metric else True


def load_cell(name: str) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` and its files describe it."""
    try:
        spec_json = _read_json(os.path.join(ROOT, "BENCHMARK.json"))
    except FileNotFoundError as e:
        raise Refused(f"no BENCHMARK.json under {ROOT}") from e
    entries = [w for w in spec_json["workloads"] if w["name"] == name]
    if not entries:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    try:
        spec = _read_json(_bench("cells", entries[0]["traffic"] + ".json"))
        config = _read_json(_bench("configs", spec["config"] + ".json"))
    except FileNotFoundError as e:
        raise Refused(f"cell {name!r}: {e}") from e
    for key in ("config", "chips"):
        if spec[key] != entries[0][key]:
            raise Refused(f"cell {name!r}: {key} {spec[key]!r} in its file, "
                          f"{entries[0][key]!r} in BENCHMARK.json")
    return Cell(
        name=name, spec=spec, config=config,
        end_to_end=[m for m in spec_json["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec_json["per_layer"] if _applies(m, name)])


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = _bench(kind, name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no {kind} module {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def peaks_for(device_kind: str) -> dict:
    """The chip's peaks; a kind that the table lacks is an error."""
    table = _read_json(_bench("peaks.json"))
    if device_kind not in table:
        raise Refused(f"device kind {device_kind!r} is not in "
                      f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def require_accelerator(chips: int) -> list:
    """The first ``chips`` TPU devices, with the Pallas kernels compiled
    natively; anything else refuses the run."""
    import jax
    from repro.kernels import ops
    devices = jax.devices()
    if devices[0].platform != "tpu" or ops.pallas_lowering() != "compiled":
        raise Refused(f"needs a TPU with compiled Pallas kernels; JAX found "
                      f"{devices[0].platform} (pallas "
                      f"{ops.pallas_lowering()})")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chip(s); JAX found "
                      f"{len(devices)}")
    return devices[:chips]


def say(msg: str) -> None:
    print(msg, flush=True)


def run_job(entry, keys, annotate):
    """One job: the entry's call, then its sorted lengths and keys fetched
    to the host."""
    with annotate("call"):
        out = entry(keys)
    with annotate("fetch"):
        return np.asarray(out[0]), np.asarray(out[1])


def run_window(entry, pool, seconds: float, annotate):
    """Jobs back to back, cycling through the pool, until ``seconds`` have
    passed; the last job started runs to its end. Returns the latencies,
    the outputs as ``(pool index, lengths, keys)``, the jobs that raised,
    and the window's length, from its start to the last job's end."""
    latencies, outputs, failed = [], [], 0
    with annotate(tracereduce.WINDOW):
        start = end = time.perf_counter()
        while end - start < seconds:
            i = len(latencies) % len(pool)
            t = time.perf_counter()
            try:
                lengths, keys = run_job(entry, pool[i][0], annotate)
            except Exception:
                traceback.print_exc()
                failed += 1
                end = time.perf_counter()
                break
            end = time.perf_counter()
            latencies.append(end - t)
            outputs.append((i, lengths, keys))
    return latencies, outputs, failed, end - start


class GcLog:
    """Collections of Python's cyclic garbage collector while the context
    is open: how many, and their seconds, by generation."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._start = None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.count[info["generation"]] += 1
            self.seconds[info["generation"]] += (time.perf_counter()
                                                 - self._start)

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)

    def __str__(self):
        return ", ".join(f"gen{g} x{c} {s:.3f} s" for g, (c, s) in
                         enumerate(zip(self.count, self.seconds)))


def latency_summary(latencies) -> str:
    if not latencies:
        return "no job"
    med = float(np.median(latencies))
    slow = sum(1 for x in latencies if x > 2 * med)
    return (f"latency median {med} s, max {max(latencies)} s at job "
            f"{int(np.argmax(latencies))}, {slow} job(s) over twice the "
            f"median")


def compare(pool, outputs):
    """Every job's output against the NumPy reference of its corpus:
    ``(rows that differ, jobs with any, seconds the reference took)``."""
    t = time.perf_counter()
    refs = {i: corpus.reference(*pool[i]) for i in {o[0] for o in outputs}}
    ref_s = time.perf_counter() - t
    bad = [corpus.mismatched_rows(lengths, keys, *refs[i])
           for i, lengths, keys in outputs]
    return sum(bad), sum(1 for b in bad if b), ref_s


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, started: float | None = None) -> int:
    started = time.perf_counter() if started is None else started
    args = parse_args(argv)
    try:
        return _run(args, started)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1


def _run(args, started: float) -> int:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"no repro package under {src}")
    cell = load_cell(args.workload)
    if src not in sys.path:
        sys.path.insert(0, src)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.kernels import ops
    from repro.launch.cache import use_compile_cache

    cache_dir = use_compile_cache()
    # cache every program, also those that compile in under a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    chips = cell.spec["chips"]
    devices = require_accelerator(chips)
    kind = devices[0].device_kind
    peaks = peaks_for(kind)
    entry = load_module("entries", cell.spec["entry"]).make(cell.spec,
                                                             devices)
    readers = {m["name"]: load_module("metrics", m["name"])
               for m in (cell.per_layer if args.trace else cell.end_to_end)}
    init_s = time.perf_counter() - started
    say(f"cell {cell.name}: config {cell.spec['config']}, {chips} chip(s), "
        f"entry {cell.spec['entry']}, chunk_size {cell.spec['chunk_size']}, "
        f"pool {cell.spec['pool']}, seed {args.seed}")
    say(f"devices: {devices}; provenance {ops.execution_provenance()}; "
        f"compile cache {cache_dir}")
    say(f"setup import_and_backend_s {init_s}")

    t = time.perf_counter()
    pool = corpus.make_pool(cell.config, args.seed, cell.spec["pool"])
    say(f"setup pool_s {time.perf_counter() - t} ({len(pool)} corpora of "
        f"{cell.config['words_per_job']} words)")

    annotate = jax.profiler.TraceAnnotation
    t = time.perf_counter()
    with CompileLog() as warm:
        run_job(entry, pool[0][0], annotate)
    say(f"setup warmup_s {time.perf_counter() - t} (1 job); {warm}")
    setup_s = time.perf_counter() - started
    say(f"setup_s {setup_s}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        cpu = time.process_time()
        with CompileLog() as in_window, GcLog() as gcs:
            latencies, outputs, failed, window_s = run_window(
                entry, pool, args.seconds, annotate)
        cpu = time.process_time() - cpu
        trace = None
        if trace_dir:
            jax.profiler.stop_trace()
            t = time.perf_counter()
            trace = tracereduce.load(trace_dir)
            say(f"trace read in {time.perf_counter() - t} s")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    say(f"window: {len(latencies)} job(s) in {window_s} s; compiles inside "
        f"the window: {in_window.compiles()} ({in_window})")
    say(f"window host: {latency_summary(latencies)}; process CPU {cpu} s; "
        f"garbage collections {gcs}; load average {os.getloadavg()}")
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    bad_rows, bad_jobs, ref_s = compare(pool, outputs)
    say(f"reference_s {ref_s} (after the window; not in setup_s)")
    attempted = len(outputs) + failed
    checks = {
        "failed_jobs": {"value": failed + bad_jobs, "limit": 0},
        "mismatched_rows": {"value": bad_rows, "limit": 0},
    }
    correct = (failed == 0 and bad_rows == 0 and bad_jobs == 0
               and len(outputs) > 0)

    run = Run(cell=cell, peaks=peaks,
              words_per_job=cell.config["words_per_job"],
              latencies_s=latencies, window_s=window_s, setup_s=setup_s,
              trace=trace)
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    for name, reader in readers.items():
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted,
              "failed": failed + bad_jobs, "metrics": metrics,
              "device": device}
    if trace is not None:
        lo, hi = trace.window
        busy = [tracereduce.busy_ns(c, lo, hi) for c in trace.chips]
        for i, b in enumerate(busy):
            say(f"chip {i}: busy_s {b / 1e9}, window_s {(hi - lo) / 1e9}, "
                f"idle_share {1 - b / (hi - lo) if hi > lo else None}")
        device["busy_s"] = sum(busy) / len(busy) / 1e9 if busy else 0.0
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = tracereduce.breakdown(trace)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
