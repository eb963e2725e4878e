"""The program's spans in a traced run: ``bench/idle_split.py`` and the
per-layer metrics that read the program's phase counters."""

import json
import time
from pathlib import Path

import pytest

from bench import idle_split
from bench.harness import common, trace
from bench.tests._small import CPU, SEED, assert_sound, small_cell

DATA = Path(__file__).parent / "data"

PROGRAM_LAYERS = {"engine.tick_ms.p99", "engine.queue_ms.p99",
                  "cache.flush_ms.p99", "sched.keyset_ms.p99",
                  "kvs.sync_wait_share.p99", "host.gc_pause_share.p99"}


@pytest.fixture
def device_tier(monkeypatch):
    """The device slab tier, as every benchmark run has it."""
    import repro.core.arena as arena

    monkeypatch.setattr(arena, "_DEVICE_TIER_CACHE", True)


def test_a_gap_inside_a_program_span_takes_its_name(tmp_path):
    import gc

    import jax
    import jax.numpy as jnp

    from repro.obs import Tracer
    from repro.obs import host as obs_host

    obs_host.install()
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    tr = Tracer()
    with trace.capture(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.tick"):
                with tr.phase("engine.tick"):
                    with tr.phase("sched.keyset"):
                        time.sleep(0.05)
                    f(x).block_until_ready()  # ends the first gap
                    gc.collect(2)
            f(x).block_until_ready()
    path = trace.find_xplane(str(tmp_path))
    record = trace.reduce_xplane(path, 1, "cpu")
    assert not any(h[0].startswith("cb.") for h in record["host"])
    bench_only = trace.summarize(record, chips=1)
    spans = idle_split.program_spans(path)
    assert {"cb.engine.tick", "cb.sched.keyset",
            "cb.host.gc.gen2"} <= {s[0] for s in spans}
    record["host"] += spans
    split = idle_split.idle_split(record, chips=1)
    assert split["cb.sched.keyset"] >= 0.045
    assert split["cb.host.gc.gen2"] > 0  # the collection, inside the tick
    assert "bench.tick" not in split or split["bench.tick"] < 0.045
    assert sum(split.values()) == pytest.approx(
        bench_only.window_s - bench_only.busy_s, rel=1e-9)
    # the phase counted what the trace shows
    assert tr.metrics.snapshot()["sched.keyset.n"] == 1


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("trace_*.json")))
def test_with_harness_spans_alone_the_split_is_the_harness_breakdown(name):
    rec = json.loads((DATA / name).read_text())
    s = trace.summarize(rec, chips=1)
    split = idle_split.idle_split(rec, chips=1)
    assert split == pytest.approx(s.idle_by_span, rel=1e-9, abs=1e-12)
    assert sum(split.values()) == pytest.approx(s.window_s - s.busy_s,
                                                rel=1e-9)


def test_ycsb_a_traced_run_reads_the_program_layers(device_tier):
    line = idle_split.run_split(small_cell("ycsb-a.open"), SEED, 1.5,
                                common.Stopwatch(), device=dict(CPU))
    line["checks"] = {n: (v, lim) for n, v, lim in line.pop("checks")}
    assert_sound(line)
    assert PROGRAM_LAYERS <= set(line["metrics"])
    # every accepted metric is still read beside them
    assert {"engine.step_ms.p99", "cache.hit_share.p99",
            "kvs.syncs_per_call.p99", "device.idle_share.p99",
            "dag.p95_ms"} <= set(line["metrics"])
    assert line["metrics"]["engine.tick_ms.p99"]["value"] > 0
    assert line["metrics"]["sched.keyset_ms.p99"]["value"] > 0
    assert 0 <= line["metrics"]["host.gc_pause_share.p99"]["value"] < 100
    # the harness's own breakdown names its spans alone; the split names
    # the program's
    assert all(not k.startswith("cb.")
               for k, _v in line["breakdown"]["idle_gaps"])
    names = [k for k, _v in line["idle_split"]]
    assert any(k.startswith("cb.") for k in names)
    idle = sum(v for _k, v in line["idle_split"])
    dev = line["device"]
    assert idle == pytest.approx(dev["window_s"] - dev["busy_s"], rel=1e-6)
