"""A cell, a traffic mix and a per-layer metric added as new files alone
are found by name and run; the harness's own files stay as they are."""

import json
import shutil

import pytest

from bench.harness import spec
from bench.tests._small import run_small, small_cell


@pytest.fixture
def device_tier(monkeypatch):
    import repro.core.arena as arena

    monkeypatch.setattr(arena, "_DEVICE_TIER_CACHE", True)


def test_new_files_make_a_new_cell(tmp_path, device_tier):
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(spec.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(spec.BENCH_DIR.parent / "BENCHMARK.json", root)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    # the new files: a mix, a cell and a reader
    (bench / "traffic" / "ycsb-b-poisson.json").write_text(json.dumps({
        "ops": {"read": 0.95, "update": 0.05},
        "keys": {"distribution": "zipfian", "theta": 0.99},
        "arrivals": {"process": "poisson", "rate_per_s": 40},
        "warmup_s": 1}))
    (bench / "cells" / "ycsb-b.open.json").write_text(json.dumps({
        "config": "ycsb-2m-r2", "traffic": "ycsb-b-poisson", "chips": 1,
        "driver": "store", "why": "read-mostly YCSB-B", "params": {}}))
    (bench / "layers" / "cache.misses_per_call.b.py").write_text(
        "def read(window):\n"
        "    calls = window.work['calls_completed']\n"
        "    misses = window.counter_sum('cache.', '.misses')\n"
        "    return misses / calls if calls else None\n")
    # ... and their entries in BENCHMARK.json
    top = json.loads((root / "BENCHMARK.json").read_text())
    top["workloads"].append({"name": "ycsb-b.open", "config": "ycsb-2m-r2",
                             "traffic": "ycsb-b-poisson", "chips": 1,
                             "why": "read-mostly YCSB-B"})
    for m in top["end_to_end"]:
        if m["name"] == "dag_p99_ms":
            m["workloads"].append("ycsb-b.open")
    top["per_layer"].append({
        "name": "cache.misses_per_call.b", "unit": "misses/call",
        "better": "lower", "source": "program_counter",
        "layer": "executor cache (core/cache.py)", "moves": "dag_p99_ms",
        "workloads": ["ycsb-b.open"]})
    (root / "BENCHMARK.json").write_text(json.dumps(top))

    cell = small_cell("ycsb-b.open", bench_dir=bench)
    assert cell.traffic["ops"] == {"read": 0.95, "update": 0.05}
    assert [m["name"] for m in cell.per_layer] == ["cache.misses_per_call.b"]
    line = run_small(cell, traced=True)
    assert line["correct"], line["checks"]
    assert "cache.misses_per_call.b" in line["metrics"]
    line = run_small(cell)
    assert set(line["metrics"]) == {"dag_p99_ms", "setup_s"}
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there was edited


def test_unknown_or_mismatched_cells_are_refused(tmp_path):
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(spec.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(spec.BENCH_DIR.parent / "BENCHMARK.json", root)
    path = bench / "cells" / "ycsb-a.open.json"
    cell = json.loads(path.read_text())
    cell["chips"] = 4
    path.write_text(json.dumps(cell))
    with pytest.raises(ValueError, match="chips"):
        spec.load_cell("ycsb-a.open", bench)


def test_every_cell_names_files_that_exist():
    top = json.loads((spec.BENCH_DIR.parent / "BENCHMARK.json").read_text())
    for w in top["workloads"]:
        cell = spec.load_cell(w["name"], spec.BENCH_DIR, top)
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.load_reader(m["name"]))
