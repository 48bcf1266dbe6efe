#!/usr/bin/env python3
"""One run of one benchmark cell; see ``harness.py``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""

import time

STARTED = time.perf_counter()

import sys  # noqa: E402

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(started=STARTED))
