"""Cells shrunk to sizes a CPU test can hold, and a CPU run of one."""

import numpy as np

from bench.harness import cli, common, spec

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**31 + 2**20 + 7  # past 32 signed bits: --seed takes such seeds too


def small_cell(name: str, bench_dir=spec.BENCH_DIR):
    cell = spec.load_cell(name, bench_dir)
    cell.config.update(records=4096, cache_prefill_keys=257)
    cell.params.update(shape_waves=4, backlog_waves=8)
    cell.traffic["warmup_s"] = 1.0
    cell.traffic["arrivals"]["rate_per_s"] = 40.0
    return cell


def run_small(cell, seconds=1.5, traced=False, seed=SEED):
    line = cli.run_cell(cell, seed, seconds, traced, common.Stopwatch(),
                        device=dict(CPU))
    line["checks"] = {n: (v, lim) for n, v, lim in line["checks"]}
    return line


def assert_sound(line):
    assert line["correct"], line["checks"]
    assert all(v <= lim for v, lim in line["checks"].values())
    assert np.isfinite([m["value"] for m in line["metrics"].values()]).all()
