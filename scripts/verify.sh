#!/usr/bin/env bash
# Tier-1 verification gate: full pytest suite + kernel micro-bench smoke.
#
# The smoke pass runs the storage-layer plane benches (kernels +
# merge_plane + gossip_plane + read_plane + checkpoint_plane) at tiny
# sizes so perf regressions in the batched merge/replication/read/
# checkpoint planes fail fast (the benches cross-check kernel winners
# against the Python oracle and assert on mismatch; read_plane and
# checkpoint_plane also append their keys/s cells to BENCH_*.json for
# the cross-PR perf trajectory).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
export JAX_PLATFORMS=cpu  # tests run on the CPU; chip_smoke.py is the chip check

echo "== tier-1 pytest =="
python -m pytest -x -q

echo "== tier-1 pytest (device tier, 4 host devices) =="
# same suite with the device-resident slab tier on everywhere and the
# CPU backend split into 4 devices, so every merge/gossip/read path also
# exercises donated device slabs + the "kvs" mesh sharding
JAX_PLATFORMS=cpu \
XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
REPRO_DEVICE_TIER=1 \
python -m pytest -x -q

echo "== tier-1 pytest (REPRO_TRACE=1, span tracing on everywhere) =="
# same suite with env-enabled span tracing: proves the observability
# plane is a pure observer — every test must pass bit-identically with
# every DAG run traced
REPRO_TRACE=1 python -m pytest -x -q

echo "== kernel micro-bench smoke =="
python -m benchmarks.run --smoke

echo "== perf regression gate (vs recorded trajectory) =="
# re-runs the smoke benches and fails if keys/s or req/s fell more than
# 20% below the last recorded BENCH_*.json entries
python -m benchmarks.run --check

echo "== examples/quickstart.py =="
if ! qs_out=$(python examples/quickstart.py); then
    echo "verify: FAILED — examples/quickstart.py errored (the Figure-2" >&2
    echo "client script is the public API contract; a broken quickstart" >&2
    echo "means the release is broken no matter what the tests say)" >&2
    exit 1
fi
# surface the cluster's final registry snapshot (engine/cache/kvs
# telemetry) so each verify run leaves a readable observability record
printf '%s\n' "$qs_out" | sed -n '/^telemetry snapshot:/,/^DSC mode/p' | sed '$d'

echo "== examples/fault_tolerant_training.py =="
if ! ft_out=$(python examples/fault_tolerant_training.py); then
    echo "verify: FAILED — examples/fault_tolerant_training.py errored" >&2
    echo "(the failure-plane contract: checkpoints ack under partition," >&2
    echo "heartbeats detect losses without an oracle, restore resumes" >&2
    echo "from the checkpoint written under the fault)" >&2
    exit 1
fi
# the detector/faultnet lines prove the failure plane actually engaged;
# the planecp lines prove checkpoint state moved through the bulk plane
printf '%s\n' "$ft_out" | grep -E \
    '^(\[detector\]|\[faultnet\]|\[planecp\]|resumed and finished|  (detector|faultnet|planecp)\.)'

echo "== examples/prediction_serving.py =="
if ! ps_out=$(python examples/prediction_serving.py); then
    echo "verify: FAILED — examples/prediction_serving.py errored (the" >&2
    echo "serving example is the continuous-batching API contract:" >&2
    echo "KVS-resident params + batched DAG waves + slot-churn decode)" >&2
    exit 1
fi
# the serving counters prove the batched paths actually ran
printf '%s\n' "$ps_out" | grep -E \
    '^(pipeline over Cloudburst|continuous batching|  (engine\.batched|serve\.))'

echo "verify: OK"
