"""Benchmark harness: one module per paper table + framework benches.
Prints ``name,us_per_call,derived`` CSV rows and appends every run's rows
(with execution provenance) to ``BENCH_kernels.json`` — a trajectory file,
one entry per invocation, so PRs can be compared for regressions
(``benchmarks/gate.py`` is the comparator).

    PYTHONPATH=src python -m benchmarks.run [--only tableX]
        [--trajectory PATH]

Exits nonzero when any module (or the roofline report) fails — a bench
sweep that prints tracebacks but reports success is how regressions ship;
``tests/test_bench_run_exit.py`` pins this via the ``BENCH_INJECT_FAILURE``
environment knob (set it to a module name to fault that module without
running it).
"""

import argparse
import json
import os
import sys
import time
import traceback

_TRAJECTORY = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "BENCH_kernels.json")

MODULES = [
    "table1_preprocessing",
    "table2_3_datastructure",
    "table4_scaling",
    "bench_kernels",
    "bench_merge",
    "bench_pipeline",
    "bench_distributed",
    "bench_moe_dispatch",
    "bench_serving",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--trajectory", default=_TRAJECTORY,
                    help="trajectory JSON to append to (tests point this "
                         "at a scratch file so real history stays clean)")
    args = ap.parse_args()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    failures = []
    ran = []
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        ran.append(name)
        print(f"# --- {name} ---", flush=True)
        try:
            if os.environ.get("BENCH_INJECT_FAILURE") == name:
                raise RuntimeError(
                    f"injected failure in {name} (BENCH_INJECT_FAILURE)")
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
        except Exception:
            failures.append(name)
            traceback.print_exc()
    # roofline table from dry-run artifacts, when present; absence is fine
    # (it prints a hint) but an exception is a failure like any module's
    try:
        from benchmarks import roofline
        print("# --- roofline (from dry-run artifacts) ---", flush=True)
        sys.argv = ["roofline", "--csv"]
        roofline.main()
    except Exception:
        failures.append("roofline")
        traceback.print_exc()
    _write_trajectory(args.trajectory, ran, failures)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


def _write_trajectory(path, modules, failures) -> None:
    """Append this run's emit() records to the trajectory file."""
    from benchmarks.common import RECORDS, provenance
    if not RECORDS:
        return
    history = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f)
        except (json.JSONDecodeError, OSError):
            history = []
    # record which modules ran so partial (--only / failed) runs are
    # distinguishable from full sweeps when comparing entries across PRs
    history.append({
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "modules": list(modules),
        "failures": list(failures),
        "provenance": provenance(),
        "records": list(RECORDS),
    })
    with open(path, "w") as f:
        json.dump(history, f, indent=1)
    print(f"# wrote {len(RECORDS)} records to {path}", flush=True)


if __name__ == '__main__':
    main()
