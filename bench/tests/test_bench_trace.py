"""The trace -> metrics reduction."""

import json
from pathlib import Path

import pytest

from bench.harness import trace

DATA = Path(__file__).parent / "data"


def _synthetic():
    ns = 1.0
    return {
        "host": [["bench.window", 1000 * ns, 10000 * ns],
                 ["bench.step", 1400 * ns, 3100 * ns],
                 ["bench.tick", 4500 * ns, 1500 * ns],
                 ["bench.wait", 6000 * ns, 5000 * ns]],
        "devices": {"0": [
            ["early", "jit_other", 500.0, 1000.0],  # clipped to [1000, 1500]
            ["fusion.1", "jit_slab_ingest_rows", 2000.0, 1000.0],
            ["fusion.2", "jit_slab_ingest_rows", 2500.0, 1000.0],  # overlaps
            ["flash_fwd", "jit__predict_fn", 5000.0, 2000.0],
            ["late", "jit_other", 12000.0, 10.0],  # after the window
        ]},
    }


def test_busy_idle_and_gaps_by_hand():
    s = trace.summarize(_synthetic(), chips=1)
    assert s.window_s == pytest.approx(10000e-9)
    # union: [1000,1500] + [2000,3500] + [5000,7000] = 4000 ns
    assert s.busy_s == pytest.approx(4000e-9)
    assert s.idle_share == pytest.approx(0.6)
    # gaps [1500,2000] and [3500,5000] fall in the step span, [7000,11000]
    # in the wait span
    assert s.idle_by_span == pytest.approx(
        {"bench.step": 2000e-9, "bench.wait": 4000e-9})
    # per-op time sums durations (not their union)
    assert s.seconds_matching(["slab_"]) == pytest.approx(2000e-9)
    assert s.seconds_matching(["flash"]) == pytest.approx(2000e-9)
    assert s.seconds_matching(["nothing"]) == 0.0
    br = s.breakdown(top=2)
    assert [name for name, _ in br["device_ops"]] == [
        "jit__predict_fn/flash_fwd", "jit_slab_ingest_rows/fusion.1"]
    assert br["idle_gaps"][0] == ["bench.wait", pytest.approx(4000e-9)]


def test_two_chips_average():
    rec = _synthetic()
    rec["devices"]["1"] = [["fusion.9", "jit_x", 1000.0, 10000.0]]
    s = trace.summarize(rec, chips=2)
    assert s.busy_s == pytest.approx((4000e-9 + 10000e-9) / 2)


def test_a_trace_without_window_or_device_is_refused():
    rec = _synthetic()
    rec["host"] = rec["host"][1:]
    with pytest.raises(ValueError, match="bench.window"):
        trace.summarize(rec, chips=1)
    rec = _synthetic()
    rec["devices"] = {}
    with pytest.raises(ValueError, match="device"):
        trace.summarize(rec, chips=1)


def test_reduce_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    with trace.capture(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    rec = trace.reduce_xplane(trace.find_xplane(str(tmp_path)), 1, "cpu")
    names = [h[0] for h in rec["host"]]
    assert "bench.window" in names and "bench.step" in names
    s = trace.summarize(rec, chips=1)
    assert 0 < s.busy_s <= s.window_s


def _brute_busy(ops, lo, hi, step=100.0):
    """Busy ns inside [lo, hi) on a grid of ``step`` ns: independent of
    the interval union that ``summarize`` computes."""
    import numpy as np

    n = int((hi - lo) // step)
    grid = np.zeros(n, bool)
    for _name, _module, start, dur in ops:
        a = int(max(0, (start - lo) // step))
        b = int(min(n, -(-(start + dur - lo) // step)))
        if b > a:
            grid[a:b] = True
    return grid.sum() * step


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("trace_*.json")))
def test_recorded_chip_trace(name):
    """A slice of a real ``--trace 1`` run on a TPU v5e."""
    rec = json.loads((DATA / name).read_text())
    s = trace.summarize(rec, chips=1)
    w = [h for h in rec["host"] if h[0] == trace.WINDOW_SPAN][0]
    brute = _brute_busy(rec["devices"]["0"], w[1], w[1] + w[2])
    n_ops = len(rec["devices"]["0"])
    assert abs(s.busy_s * 1e9 - brute) <= 200.0 * n_ops + 200.0
    assert 0.0 < s.busy_s < s.window_s
    idle = sum(s.idle_by_span.values())
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
    assert set(s.idle_by_span) <= {h[0] for h in rec["host"]} | {
        trace.OUTSIDE}
    assert sum(v for _k, v in s.breakdown(10)["device_ops"]) <= sum(
        s.op_s.values()) + 1e-12
