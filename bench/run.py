#!/usr/bin/env python3
"""Run one benchmark cell: see ``bench/harness/cli.py``.

  python3 bench/run.py --workload ycsb-a.open --seed 7 --seconds 20 --trace 0
"""

import time

_STARTED = time.perf_counter()  # set-up runs from here to the window

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT / "src"))
sys.path.insert(0, str(_ROOT))

from bench.harness import cli  # noqa: E402
from bench.harness.common import Stopwatch  # noqa: E402

if __name__ == "__main__":
    clock = Stopwatch()
    clock.t0 = _STARTED
    sys.exit(cli.main(setup_clock=clock))
