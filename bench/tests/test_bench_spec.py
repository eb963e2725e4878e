"""BENCHMARK.json keeps the shape the benchmark's contract gives it."""

import json
import re

from bench.harness import spec

TOP = json.loads((spec.BENCH_DIR.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths():
    assert set(TOP) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert TOP["paths"] == ["bench"]
    assert TOP["command"] == ["python3", "bench/run.py"]
    assert 1 <= TOP["run_seconds"] <= 51


def test_entries_names_and_lines():
    for c in TOP["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
        data = json.loads((spec.BENCH_DIR.parent / c["file"]).read_text())
        assert all(k in data for k in c["reduced"])
    for w in TOP["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in TOP["end_to_end"] + TOP["per_layer"]:
        assert set(m) - {"workloads"} <= {
            "name", "unit", "better", "bound", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in TOP["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    names = [m["name"] for m in TOP["end_to_end"] + TOP["per_layer"]]
    assert len(names) == len(set(names))


def test_every_cell_reports_setup_another_metric_and_a_layer():
    e2e = {m["name"] for m in TOP["end_to_end"]}
    assert "setup_s" in e2e
    for w in TOP["workloads"]:
        cell = spec.load_cell(w["name"])
        mine = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in mine and len(mine) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in mine  # a layer metric moves one it reports
    layers = {}
    for m in TOP["per_layer"]:
        assert _line(m["layer"]) and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["unit"] == "%" and ("roofline" in m["name"]
                                 or "mfu" in m["name"]):
            assert m["better"] == "higher"
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_a_full_check_fits_its_budget_at_twenty_four_cells():
    runs = 2 + 14 * 24
    seconds = runs * (TOP["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert seconds <= 43200
