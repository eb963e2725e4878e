#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and its control's.

For each seed, runs the cell as the benchmark does (a short window by
default) and prints one JSON line with every compared number.  The
control is a run with one guarantee of the configuration broken: with
``--fault drop_gossip`` no replica but the coordinator ever receives a
write.  The benchmark's own runs never call this.

  python3 bench/control.py --workload ycsb-a.open --seconds 6 \\
      --seeds 11 12 13 --fault drop_gossip
"""

import argparse
import json
import os
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT / "src"))
sys.path.insert(0, str(_ROOT))

FAULTS = ("none", "drop_gossip")


def _drop_gossip(self, *args, **kwargs):
    self.inbox.drain()  # gossip arrives and is thrown away
    return 0


def fault_patch(fault: str):
    """(object, attribute, replacement) that breaks the program
    underneath, or None."""
    if fault == "drop_gossip":
        from repro.core.kvs import StorageNode

        return StorageNode, "drain_inbox", _drop_gossip
    return None


def plant(fault: str) -> None:
    """Break the program for the rest of this process."""
    patch = fault_patch(fault)
    if patch is not None:
        setattr(*patch)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--fault", choices=FAULTS, default="none")
    args = ap.parse_args()
    os.environ["REPRO_DEVICE_TIER"] = "1"
    import jax

    from bench.harness import cli, common
    from bench.harness.spec import load_cell
    from repro.launch.compile_cache import enable_compile_cache

    cell = load_cell(args.workload)
    device = common.require_tpu(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    plant(args.fault)
    for seed in args.seeds:
        line = cli.run_cell(cell, seed, args.seconds, False,
                            common.Stopwatch(), device=device)
        print(json.dumps({
            "seed": seed, "fault": args.fault, "correct": line["correct"],
            "program": {n: v for n, v, _lim in line["checks"]},
            "metrics": {k: v["value"] for k, v in line["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
