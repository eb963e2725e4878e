"""Benchmark suite runner: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Figure mapping:
  fig1   function composition          (square(increment(x)))
  fig4   data locality                 (10-array sum, hot/cold/storage)
  fig5   distributed aggregation       (gossip vs gather)
  fig6   autoscaling trace             (load spike, plateaus, drain)
  fig7   consistency-level latency     (lww/dsrr/sk/mk/dsc)
  table2 anomaly counts under LWW
  fig8   prediction-serving pipeline   (3 stages, real smoke-scale model)
  fig9   Retwis                        (lww vs causal vs redis model)
  kernels  storage-layer Pallas merge micro
  merge_plane  batched arena data plane vs per-key merges
  gossip_plane  packed-plane replication wire vs per-key-object inbox
  read_plane  batched R-replica read-repair vs per-key get_merged
  checkpoint_plane  plane-native bulk checkpoint restore vs per-key
                get_tree (+ chaos-schedule save/restore invariants)
  pipeline_throughput  open-loop fig8 serving at in-flight {1,4,16}
  serve_models  continuous-batched REAL forward passes vs per-request
                dispatch + KVS-resident-params DAG serving
  chaos_soak  fig8-shaped open-loop serving under ChaosMonkey channel
              faults / node kills; durability + no-zombie + bounded-p99
              gates asserted in-bench

``--smoke`` runs the kernel micro-benches (kernels + merge_plane +
gossip_plane + read_plane + checkpoint_plane) plus tiny
pipeline_throughput and serve_models passes — the fast perf-regression
gate used by scripts/verify.sh (the merge/read/checkpoint benches
cross-check winners against the Python oracle and assert on mismatch;
pipeline_throughput asserts its cross-request batching telemetry;
serve_models asserts the >= 3x continuous-batching speedup, token
bit-identity and the zero second-request weight-fetch invariant).

``--check`` is the trajectory regression gate: it runs the read_plane,
checkpoint_plane, pipeline_throughput, serve_models and chaos_soak
smoke benches fresh and compares their new records against the LAST
matching entries already in ``BENCH_read_plane.json`` /
``BENCH_checkpoint_plane.json`` / ``BENCH_pipeline_throughput.json`` /
``BENCH_serve_models.json`` / ``BENCH_chaos_soak.json``,
failing on a >20% keys/s, req/s or tokens/s drop on the batched/plane
paths (the jitter-prone per-key Python baselines are recorded but not
gated) or a >20% chaos-p99 latency regression (latency gates in the
OPPOSITE direction: bigger is worse).  The chaos bench's hard gates —
zero acked-write loss after heal, no zombie runs, chaos p99 within 5x
healthy — are asserted inside the bench itself on every run.  CI
consumes the trajectory files through this gate instead of only
appending to them.
"""

from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path

# a fresh record must keep >= this fraction of the last recorded rate
CHECK_KEEP = 0.8
# gated rate fields: the optimized paths; per-key python baselines are
# informational (they swing with host load and would flake the gate)
CHECK_FIELDS = ("batched_keys_per_s", "bulk_keys_per_s",
                "device_keys_per_s", "plane_keys_per_s",
                "host_plane_keys_per_s", "req_per_s", "tokens_per_s")
# gated latency fields (direction inverted: fresh must stay BELOW
# 1/CHECK_KEEP of the recorded value — a >20% p99 growth fails)
CHECK_LATENCY_FIELDS = ("latency_p99_virtual_ms",)

_ROOT = Path(__file__).resolve().parent.parent


def _load_runs(path: Path) -> list:
    if not path.exists():
        return []
    try:
        runs = json.loads(path.read_text())
    except (ValueError, OSError):
        return []
    return runs if isinstance(runs, list) else []


def _last_smoke(runs: list) -> dict:
    for run in reversed(runs):
        if isinstance(run, dict) and run.get("smoke"):
            return run
    return {}


def _gate_rates(label: str, base: dict, fresh: dict) -> list:
    """Compare every gated rate field present in both records."""
    failures = []
    for field in CHECK_FIELDS:
        b, f = base.get(field), fresh.get(field)
        if not b or f is None:
            continue
        if f < CHECK_KEEP * b:
            failures.append(
                f"{label}: {field} {f:.0f} < {CHECK_KEEP:.0%} of "
                f"recorded {b:.0f}")
    return failures


def _gate_latencies(label: str, base: dict, fresh: dict) -> list:
    """Latency fields gate in the opposite sense: growth is regression."""
    failures = []
    for field in CHECK_LATENCY_FIELDS:
        b, f = base.get(field), fresh.get(field)
        if not b or f is None:
            continue
        if f > b / CHECK_KEEP:
            failures.append(
                f"{label}: {field} {f:.2f} > {1 / CHECK_KEEP:.0%} of "
                f"recorded {b:.2f}")
    return failures


def check() -> None:
    """Run the recorded smoke benches fresh and fail on regression vs
    the last entries in the trajectory files."""
    from . import (
        chaos_soak,
        checkpoint_plane,
        pipeline_throughput,
        read_plane,
        serve_models,
    )

    rp_path = _ROOT / "BENCH_read_plane.json"
    cp_path = _ROOT / "BENCH_checkpoint_plane.json"
    pt_path = _ROOT / "BENCH_pipeline_throughput.json"
    sm_path = _ROOT / "BENCH_serve_models.json"
    cs_path = _ROOT / "BENCH_chaos_soak.json"
    base_rp = _last_smoke(_load_runs(rp_path))
    base_cp = _last_smoke(_load_runs(cp_path))
    base_pt = _last_smoke(_load_runs(pt_path))
    base_sm = _last_smoke(_load_runs(sm_path))
    base_cs = _last_smoke(_load_runs(cs_path))

    print("name,us_per_call,derived")
    read_plane.main(smoke=True)
    checkpoint_plane.main(smoke=True)  # chaos invariants assert inside
    pipeline_throughput.main(smoke=True)
    serve_models.main(smoke=True)
    chaos_soak.main(smoke=True)  # durability/zombie/5x gates assert inside

    fresh_rp = _load_runs(rp_path)[-1]
    fresh_cp = _load_runs(cp_path)[-1]
    fresh_pt = _load_runs(pt_path)[-1]
    fresh_sm = _load_runs(sm_path)[-1]
    fresh_cs = _load_runs(cs_path)[-1]
    failures: list = []

    base_cells = {
        (c.get("K"), c.get("D"), c.get("R"), c.get("tier", "host")): c
        for c in base_rp.get("cells", [])
    }
    for cell in fresh_rp.get("cells", []):
        ident = (cell.get("K"), cell.get("D"), cell.get("R"),
                 cell.get("tier", "host"))
        base = base_cells.get(ident)
        if base is None:
            continue  # new cell shape: nothing recorded to gate against
        failures += _gate_rates(
            f"read_plane K={ident[0]} D={ident[1]} R={ident[2]} "
            f"tier={ident[3]}", base, cell)

    base_cp_cells = {
        (c.get("K"), c.get("D"), c.get("tier", "host")): c
        for c in base_cp.get("cells", [])
    }
    for cell in fresh_cp.get("cells", []):
        ident = (cell.get("K"), cell.get("D"), cell.get("tier", "host"))
        base = base_cp_cells.get(ident)
        if base is None:
            continue
        failures += _gate_rates(
            f"checkpoint_plane K={ident[0]} D={ident[1]} tier={ident[2]}",
            base, cell)

    base_rows = {r.get("in_flight"): r for r in base_pt.get("rows", [])}
    for row in fresh_pt.get("rows", []):
        base = base_rows.get(row.get("in_flight"))
        if base is None:
            continue
        failures += _gate_rates(
            f"pipeline_throughput in_flight={row.get('in_flight')}",
            base, row)

    base_sm_rows = {r.get("mode"): r for r in base_sm.get("rows", [])}
    for row in fresh_sm.get("rows", []):
        base = base_sm_rows.get(row.get("mode"))
        if base is None:
            continue
        failures += _gate_rates(
            f"serve_models mode={row.get('mode')}", base, row)

    if base_cs.get("chaos"):
        failures += _gate_latencies(
            "chaos_soak chaos-pass", base_cs["chaos"],
            fresh_cs.get("chaos", {}))

    checked = bool(base_cells or base_cp_cells or base_rows or base_sm_rows
                   or base_cs.get("chaos"))
    if failures:
        print("# PERF REGRESSION (>20% below recorded trajectory):",
              file=sys.stderr)
        for f in failures:
            print(f"#   {f}", file=sys.stderr)
        raise SystemExit(1)
    print(f"# --check ok: no >20% regression vs recorded trajectory"
          f" (baselines: {'present' if checked else 'none yet'})",
          file=sys.stderr)


def main(argv=None) -> None:
    from . import (
        chaos_soak,
        checkpoint_plane,
        fig1_composition,
        fig4_locality,
        fig5_gossip,
        fig6_autoscaling,
        fig7_consistency,
        fig8_prediction,
        fig9_retwis,
        gossip_plane,
        kernels_micro,
        merge_plane,
        pipeline_throughput,
        read_plane,
        serve_models,
        table2_anomalies,
    )

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = sys.argv[1:] if argv is None else argv
    if "--check" in args:
        check()
        return
    smoke = "--smoke" in args
    print("name,us_per_call,derived")
    if smoke:
        suites = [
            ("kernels", lambda: kernels_micro.main(K=64, D=256, R=2, iters=3)),
            ("merge_plane", lambda: merge_plane.main(smoke=True)),
            ("gossip_plane", lambda: gossip_plane.main(smoke=True)),
            ("read_plane", lambda: read_plane.main(smoke=True)),
            ("checkpoint_plane", lambda: checkpoint_plane.main(smoke=True)),
            ("pipeline_throughput",
             lambda: pipeline_throughput.main(smoke=True)),
            ("serve_models", lambda: serve_models.main(smoke=True)),
            ("chaos_soak", lambda: chaos_soak.main(smoke=True)),
        ]
    else:
        suites = [
            ("fig1", fig1_composition.main),
            ("fig4", fig4_locality.main),
            ("fig5", fig5_gossip.main),
            ("fig6", fig6_autoscaling.main),
            ("fig7", fig7_consistency.main),
            ("table2", table2_anomalies.main),
            ("fig8", fig8_prediction.main),
            ("fig9", fig9_retwis.main),
            ("kernels", kernels_micro.main),
            ("merge_plane", merge_plane.main),
            ("gossip_plane", gossip_plane.main),
            ("read_plane", read_plane.main),
            ("checkpoint_plane", checkpoint_plane.main),
            ("pipeline_throughput", pipeline_throughput.main),
            ("serve_models", serve_models.main),
            ("chaos_soak", chaos_soak.main),
        ]
    failed = []
    for name, fn in suites:
        t0 = time.time()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
