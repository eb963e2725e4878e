#!/usr/bin/env python3
"""Cloudburst's main path, end to end, on a TPU.

One process runs every phase, seeded by ``--seed``:

* store: ``Cluster(3 VMs x 3 executors, 4 Anna nodes, replication 2)``
  with the device slab tier on.  It loads YCSB-sized records (1 KiB,
  carried as 256 float32) through ``put_many``, drives a few hundred DAG
  calls whose functions ``get_many``/``put_many`` Zipf(0.99)-chosen keys
  (a few more under distributed-session causal consistency), runs gossip
  ticks, then reads every acknowledged write back -- merged through
  ``get_merged_many`` and from each replica -- against a per-key
  ``oracle_lww_fold`` of the writes this script made.
* serve: llama3.2-3b at its published widths, random params from the
  seed.  Continuous batching through ``ServingEngine`` and the section
  6.3.1 pipeline as a Cloudburst DAG, both checked against the same model
  under the pure-jnp reference kernels (``ops.set_backend("reference")``).

``--chips 4`` runs only the store phase, with the slabs K-sharded over
the host's chips, and compares it with the single-device run and with the
oracle.

Only a run on a TPU that passes every check prints the result line: the
last line of stdout, one JSON object.  Anywhere else the script exits
non-zero.

  python chip_smoke.py [--seed 0] [--chips 4]
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import re
import sys
import time
from pathlib import Path

os.environ["REPRO_DEVICE_TIER"] = "1"  # arenas keep their slabs on the device
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    Cluster, LWWLattice, RandomPolicy, oracle_lww_fold)
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import Model, get_config  # noqa: E402
from repro.obs import host as obs_host  # noqa: E402
from repro.serve import Request, ServingEngine, make_pipeline_stages  # noqa: E402

RECORD_FLOATS = 256  # YCSB's default record: 10 fields x 100 B, ~1 KiB
ZIPF_THETA = 0.99  # YCSB's request-distribution constant
N_KEYS = 1 << 20  # YCSB recordcount: ~1 GiB per replica copy
# Served vs reference logits: both run the same bf16 weights and
# activations; only the attention kernels differ (Pallas flash/decode vs
# materialized jnp softmax).  A bf16 value carries 8 significant bits, so
# 1/8 is a few bf16 steps at the logits' scale (|logit| of a few units).
LOGIT_TOL = 0.125


def key_name(i: int) -> str:
    return f"user{i:08d}"


def key_index(key: str) -> int:
    return int(key[4:])


# ---------------------------------------------------------------------------
# compile and memory accounting: the program's own ``host.jit.*``
# counters (``repro.obs.host``), read as deltas around each phase
# ---------------------------------------------------------------------------


def peak_bytes() -> list:
    """Peak device bytes in use so far, per device."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in jax.devices()]


def report(tag: str, mark, log=print) -> None:
    """Compiles since ``mark`` (an ``obs_host.snapshot()``) and the
    devices' peak bytes."""
    now = obs_host.snapshot()
    log(f"{tag}: {now['host.jit.compile_s'] - mark['host.jit.compile_s']:.1f}"
        f" s compiling ({now['host.jit.compiles'] - mark['host.jit.compiles']}"
        f" programs), peak device bytes {peak_bytes()}")


# ---------------------------------------------------------------------------
# store phase
# ---------------------------------------------------------------------------


def zipf_sampler(rng: np.random.Generator, n_keys: int):
    """YCSB's scrambled Zipfian: rank r drawn with p ~ 1/r^theta, ranks
    scattered over the key space by a fixed permutation."""
    cdf = np.cumsum(np.arange(1, n_keys + 1, dtype=np.float64) ** -ZIPF_THETA)
    cdf /= cdf[-1]
    perm = rng.permutation(n_keys)

    def draw(size: int) -> np.ndarray:
        return perm[np.minimum(np.searchsorted(cdf, rng.random(size)),
                               n_keys - 1)]

    return draw


def read_records(cloudburst, keys):
    """YCSB read: one batched multi-get; returns a checksum of the rows."""
    rows = cloudburst.get_many(list(keys))
    return float(sum(float(np.asarray(r).sum()) for r in rows if r is not None))


def update_records(cloudburst, _checksum, keys, records):
    """YCSB update: one batched multi-put; returns each write's version."""
    written = cloudburst.put_many(list(zip(keys, records)))
    return [(k, lat.timestamp) for k, lat in zip(keys, written)]


def causal_update(cloudburst, read_keys, key, value):
    """Read a few causal keys, then write one that depends on them: the
    cache checks the dependencies it holds in one vector-clock launch."""
    cloudburst.get_many(list(read_keys))
    return cloudburst.put(key, value).joined_clock()


def _drive(cluster, futures) -> list:
    while not all(f.done() for f in futures):
        if cluster.step() == 0:
            cluster.tick()
    return [f.get() for f in futures]


def store_phase(seed: int, n_keys: int, *, n_dag_calls: int = 320,
                n_causal: int = 24, in_flight: int = 32,
                chunk: int = 1 << 16, scheduler_policy=None,
                log=print) -> dict:
    """Load, DAG traffic, gossip, read-back; raises on any mismatch.

    Under the default locality policy placement reads executor
    utilization in wall-clock seconds, so two runs may place functions
    (and so stamp writes) differently; a seeded ``RandomPolicy`` makes
    the writes a function of the seed alone."""
    rng = np.random.default_rng(seed)
    cluster = Cluster(n_vms=3, executors_per_vm=3, n_kvs_nodes=4,
                      replication=2, seed=seed,
                      scheduler_policy=scheduler_policy)
    kvs = cluster.kvs
    if not kvs.device_tier:
        raise RuntimeError("the device slab tier is off")
    out: dict = {"keys": n_keys}

    # -- load: the whole key space through the batched put path ----------
    t0 = time.perf_counter()
    records = rng.standard_normal((n_keys, RECORD_FLOATS), dtype=np.float32)
    load_clock = np.zeros(n_keys, np.int64)
    for lo in range(0, n_keys, chunk):
        items = []
        for i in range(lo, min(lo + chunk, n_keys)):
            ts = cluster.client_clock.tick()
            load_clock[i] = ts[0]
            items.append((key_name(i), LWWLattice(ts, records[i])))
        kvs.put_many(items, sync=True)
    out["load_s"] = time.perf_counter() - t0
    log(f"store: loaded {n_keys} keys x {RECORD_FLOATS * 4} B "
        f"in {out['load_s']:.1f} s")

    # -- DAG traffic: YCSB read + update over Zipf keys -------------------
    t0 = time.perf_counter()
    cluster.register(read_records, "read_records")
    cluster.register(update_records, "update_records")
    cluster.register_dag("ycsb", ["read_records", "update_records"])
    cluster.register(causal_update, "causal_update")
    cluster.register_dag("causal", ["causal_update"])
    draw = zipf_sampler(rng, n_keys)
    writes = collections.defaultdict(list)  # key -> [LWWLattice]
    pending_vals = {}
    for lo in range(0, n_dag_calls, in_flight):
        futures = []
        for call in range(lo, min(lo + in_flight, n_dag_calls)):
            read_keys = [key_name(i) for i in draw(4)]
            upd = list(dict.fromkeys(key_name(i) for i in draw(2)))
            vals = [rng.standard_normal(RECORD_FLOATS, dtype=np.float32)
                    for _ in upd]
            pending_vals[call] = dict(zip(upd, vals))
            futures.append((call, cluster.call_dag_async(
                "ycsb", {"read_records": (read_keys,),
                         "update_records": (upd, vals)})))
        for (call, _), result in zip(futures, _drive(cluster, [f for _, f in futures])):
            for key, ts in result:
                writes[key].append(LWWLattice(ts, pending_vals[call][key]))
        cluster.tick()
    causal_writes = []
    causal_keys = [f"causal/{i}" for i in range(8)]
    for i in range(n_causal):
        key = causal_keys[i % len(causal_keys)]
        reads = [causal_keys[(i + d) % len(causal_keys)] for d in (1, 2, 3)]
        vc = cluster.call_dag_async(
            "causal", {"causal_update": (reads, key, i)}, mode="dsc").get()
        causal_writes.append((key, vc))
        cluster.tick()
    for _ in range(3):  # flush the caches, then let gossip reach every replica
        cluster.tick()
    # a restarted run's first attempt also wrote, and the oracle only
    # sees the final attempt's versions
    restarts = cluster.telemetry().get("engine.run_restarts", 0)
    if restarts:
        raise AssertionError(f"{restarts} DAG runs restarted")
    out["dag_calls"] = n_dag_calls
    out["causal_calls"] = n_causal
    out["dag_writes"] = sum(len(v) for v in writes.values())
    out["dag_s"] = time.perf_counter() - t0
    log(f"store: {n_dag_calls} ycsb DAG calls ({out['dag_writes']} writes to "
        f"{len(writes)} keys) + {n_causal} causal calls "
        f"in {out['dag_s']:.1f} s")

    # -- oracle: per-key LWW fold over every write this script made -------
    exp_vals = records  # updated in place: the load's rows are no longer needed
    exp_clock = load_clock.copy()
    exp_node = np.full(n_keys, "client", dtype=object)
    for key, lats in writes.items():
        i = key_index(key)
        load = LWWLattice((int(load_clock[i]), "client"), records[i])
        win = oracle_lww_fold([load] + lats)
        exp_vals[i] = win.value
        exp_clock[i] = win.timestamp[0]
        exp_node[i] = win.timestamp[1]

    def check(tag, keys, vals, clocks, node_ids):
        idx = np.fromiter((key_index(k) for k in keys), np.int64, len(keys))
        bad = ~(np.all(vals.view(np.uint32) == exp_vals[idx].view(np.uint32),
                       axis=1)
                & (clocks[:, 0] == exp_clock[idx])
                & (node_ids == exp_node[idx]))
        if bad.any():
            raise AssertionError(
                f"{tag}: {int(bad.sum())} keys differ from the oracle, "
                f"first {keys[int(np.argmax(bad))]}")
        return idx

    # -- read-back 1: merged across replicas (the read plane) -------------
    t0 = time.perf_counter()
    all_keys = [key_name(i) for i in range(n_keys)]
    merged_vals = np.empty_like(records)
    merged_clock = np.empty(n_keys, np.int64)
    merged_node = np.empty(n_keys, dtype=object)
    seen = 0
    for lo in range(0, n_keys, chunk):
        batch = kvs.get_merged_many(all_keys[lo:lo + chunk]).to_host()
        node_ids = np.asarray(batch.node_ids, dtype=object)
        for pg in batch.groups.values():
            nodes = node_ids[pg.node_idx[:, 0]]
            idx = check("get_merged_many", pg.keys, pg.vals, pg.clocks, nodes)
            merged_vals[idx] = pg.vals
            merged_clock[idx] = pg.clocks[:, 0]
            merged_node[idx] = nodes
            seen += len(idx)
    if seen != n_keys:
        raise AssertionError(f"get_merged_many returned {seen}/{n_keys} keys")
    # a sample through the per-key read-repair fold (``try_reduce_lww``)
    for i in draw(64):
        lat = kvs.get_merged(key_name(int(i)))
        check("get_merged", [key_name(int(i))],
              np.asarray(lat.value, np.float32).reshape(1, -1),
              np.asarray([[lat.timestamp[0]]]),
              np.asarray([lat.timestamp[1]], dtype=object))

    # -- read-back 2: every replica holds every acknowledged write --------
    copies = np.zeros(n_keys, np.int64)
    for node in kvs.nodes.values():
        batch = node.engine.export_planes(all_keys).to_host()
        node_ids = np.asarray(batch.node_ids, dtype=object)
        for pg in batch.groups.values():
            idx = check(f"replica {node.node_id}", pg.keys, pg.vals, pg.clocks,
                        node_ids[pg.node_idx[:, 0]])
            copies[idx] += 1
        del batch
    if not (copies == kvs.replication).all():
        raise AssertionError(
            f"replica copies per key: min {copies.min()} max {copies.max()}, "
            f"want {kvs.replication}")
    for key, vc in causal_writes:
        for node in kvs.nodes.values():
            held = node.store.get(key)
            if held is not None and not held.joined_clock().dominates(vc):
                raise AssertionError(f"causal write to {key} lost at "
                                     f"{node.node_id}")
        held_by = sum(node.store.get(key) is not None
                      for node in kvs.nodes.values())
        if held_by != kvs.replication:
            raise AssertionError(f"causal key {key} on {held_by} replicas")
    out["verify_s"] = time.perf_counter() - t0
    out["verified_merged"] = n_keys
    out["verified_replica_copies"] = int(copies.sum())
    out["verified_causal_writes"] = len(causal_writes)
    log(f"store: read-back matches the oracle: {n_keys} merged keys, "
        f"{int(copies.sum())} replica copies, {len(causal_writes)} causal "
        f"writes on {kvs.replication} replicas (in {out['verify_s']:.1f} s)")
    xfer = kvs.transfer_stats()
    xfer.pop("per_engine")
    log(f"store: kvs.transfer_stats() = {xfer}")
    out["transfer"] = xfer
    node0 = next(iter(kvs.nodes.values()))
    out["slab_shardings"] = {
        f"{shape}/{dtype}": slab.vals.sharding
        for (shape, dtype), slab in node0.engine.arena._slabs.items()}
    out["merged"] = (merged_vals, merged_clock, merged_node)
    return out


def sharded_store_phase(seed: int, n_keys: int, *, log=print,
                        **kw) -> None:
    """``--chips 4``: the store phase with its slabs K-sharded over the
    host's chips, then again on one device (``ops.set_merge_mesh(None)``).
    Each run checks its own oracle; the two read-backs must agree bit
    for bit.  Both use a seeded ``RandomPolicy``, so both make the same
    writes (see ``store_phase``)."""
    chips = ops.merge_mesh_size()
    if chips < 2:
        raise RuntimeError("no multi-device merge mesh")
    obs_host.install()
    mark = obs_host.snapshot()
    sharded = store_phase(seed, n_keys, scheduler_policy=RandomPolicy(),
                          log=log, **kw)
    report(f"store[K-sharded over {chips} chips]", mark, log)
    for group, sharding in sharded["slab_shardings"].items():
        log(f"store: slab {group} planes: {sharding}")
        spec = getattr(sharding, "spec", ())
        if not spec or spec[0] != "kvs":
            raise AssertionError(f"slab {group} is not K-sharded")
    gc.collect()  # the sharded cluster's slabs leave the chips
    ops.set_merge_mesh(None)
    mark = obs_host.snapshot()
    single = store_phase(seed, n_keys, scheduler_policy=RandomPolicy(),
                         log=log, **kw)
    report("store[single device]", mark, log)
    for a, b, name in zip(sharded["merged"], single["merged"],
                          ("values", "clocks", "nodes")):
        same = (np.array_equal(a.view(np.uint32), b.view(np.uint32))
                if a.dtype == np.float32 else np.array_equal(a, b))
        if not same:
            raise AssertionError(f"sharded and single-device {name} differ")
    log(f"store: sharded read-back equals the single-device run bit for "
        f"bit over {n_keys} keys")


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------


def _first_logits(model, params, prompts, buckets):
    """Last-prompt-position logits per prompt, each prefilled alone at
    B=1 in its bucket as ``ServingEngine`` does.  A fresh jit each call:
    the kernel backend is read when the function is traced."""
    fn = jax.jit(lambda p, t, n: model.prefill_batch(p, t, n)[0][0, -1])
    out = []
    for p in prompts:
        toks = np.zeros((1, next(b for b in buckets if len(p) <= b)), np.int32)
        toks[0, :len(p)] = p
        out.append(np.asarray(fn(params, toks, np.asarray([len(p)], np.int32)),
                              np.float32))
    return out


def _teacher_forced_logits(model, params, seqs, positions, width):
    """Reference logits at ``positions`` of each right-padded sequence."""
    fn = jax.jit(lambda p, t, pos: jnp.take_along_axis(
        model.forward(p, {"tokens": t}), pos[:, :, None], axis=1))
    toks = np.zeros((len(seqs), width), np.int32)
    for j, s in enumerate(seqs):
        toks[j, :len(s)] = s
    return np.asarray(fn(params, toks, np.asarray(positions, np.int32)))


def _top2_margin(logits):
    top2 = np.partition(logits, -2, axis=-1)[..., -2:]
    return top2[..., 1] - top2[..., 0]


def serve_phase(seed: int, cfg, *, n_requests: int = 8, new_tokens: int = 32,
                bucket: int = 128, log=print) -> dict:
    """Continuous batching + the pipeline DAG, checked against the
    reference backend; raises on any mismatch."""
    model = Model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(seed)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"serve: {cfg.name}: {n_params} params "
        f"({sum(x.nbytes for x in jax.tree.leaves(params))} B) "
        f"initialised in {time.perf_counter() - t0:.1f} s")
    rng = np.random.default_rng(seed + 1)
    small = bucket // 2
    buckets = (small, bucket)
    # mixed lengths over two prompt buckets (bounds the cold compiles)
    lengths = [int(rng.integers(small // 2 + 1, small + 1)) if i % 2 == 0
               else int(rng.integers(small + 1, bucket + 1))
               for i in range(n_requests)]
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in lengths]
    out: dict = {"requests": n_requests, "new_tokens": new_tokens,
                 "prompt_lengths": lengths}

    # -- continuous batching ---------------------------------------------
    t0 = time.perf_counter()
    engine = ServingEngine(model, params, max_slots=8,
                           max_len=bucket + new_tokens, prompt_buckets=buckets)
    reqs = [Request(req_id=i, prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    engine.generate(reqs)
    out["engine_s"] = time.perf_counter() - t0
    out["engine_stats"] = engine.stats
    log(f"serve: ServingEngine {n_requests} requests x {new_tokens} tokens "
        f"in {out['engine_s']:.1f} s (compiles included), "
        f"stats={engine.stats}")
    # the logits the engine served from: each request's own prefill
    served_logits = [np.asarray(r.first_logits, np.float32) for r in reqs]

    # -- the section 6.3.1 pipeline as a Cloudburst DAG -------------------
    t0 = time.perf_counter()
    # one VM: the whole wave lands on one cache, so it batches into one
    # padded forward pass per prompt bucket
    cluster = Cluster(n_vms=1, executors_per_vm=3, seed=seed)
    preprocess, stage, combine = make_pipeline_stages(
        model, params=params, max_len=bucket, metrics=cluster.metrics)
    cluster.register(preprocess, "preprocess")
    cluster.register(stage, "model")
    cluster.register(combine, "combine")
    cluster.register_dag("pipeline", ["preprocess", "model", "combine"])
    futures = [cluster.call_dag_async("pipeline", {"preprocess": (p,)})
               for p in prompts]
    rendered = _drive(cluster, futures)
    out["pipeline_s"] = time.perf_counter() - t0
    out["batched_invokes"] = int(cluster.batched_invokes)
    log(f"serve: pipeline DAG, one wave of {len(prompts)} requests in "
        f"{out['pipeline_s']:.1f} s, engine.batched_invokes="
        f"{out['batched_invokes']}")
    if out["batched_invokes"] < 1:
        raise AssertionError("the pipeline wave was not batched")

    # -- the reference: same model, pure-jnp kernels ----------------------
    ops.set_backend("reference")
    try:
        ref_logits = _first_logits(model, params, prompts, buckets)
        seqs = [np.concatenate([p, np.asarray(r.out_tokens[:-1], np.int32)])
                for p, r in zip(prompts, reqs)]
        positions = [len(p) - 1 + np.arange(new_tokens) for p in prompts]
        ref_tf = _teacher_forced_logits(model, params, seqs, positions,
                                        bucket + new_tokens)
    finally:
        ops.set_backend("kernel")

    diff = max(float(np.abs(s - r).max())
               for s, r in zip(served_logits, ref_logits))
    out["first_logits_max_abs_diff"] = diff
    log(f"serve: first-step logits, max |served - reference| = {diff:.6f} "
        f"(tolerance {LOGIT_TOL})")
    if not diff <= LOGIT_TOL:
        raise AssertionError(f"first-step logits differ by {diff}")
    margin = _top2_margin(ref_tf)  # (requests, new_tokens)
    served = np.asarray([r.out_tokens for r in reqs])
    decided = margin > LOGIT_TOL
    wrong = decided & (served != ref_tf.argmax(-1))
    out["tokens_checked"] = int(decided.sum())
    out["tokens_total"] = int(served.size)
    log(f"serve: greedy tokens equal to the reference at "
        f"{int(decided.sum()) - int(wrong.sum())}/{int(decided.sum())} "
        f"positions whose reference top-2 margin exceeds {LOGIT_TOL} "
        f"({served.size} generated)")
    if wrong.any():
        raise AssertionError(f"{int(wrong.sum())} greedy tokens differ")
    checked = 0
    for text, ref in zip(rendered, ref_logits):
        m = re.fullmatch(r"label=(\d+) score=(-?[\d.]+)", text)
        ref_score = float(jax.nn.log_softmax(ref).max())
        if abs(float(m.group(2)) - ref_score) > LOGIT_TOL + 5e-4:
            raise AssertionError(f"pipeline score {text} vs {ref_score}")
        if _top2_margin(ref) > LOGIT_TOL:
            checked += 1
            if int(m.group(1)) != int(ref.argmax()):
                raise AssertionError(f"pipeline label {text} vs "
                                     f"{int(ref.argmax())}")
    out["pipeline_labels_checked"] = checked
    log(f"serve: pipeline scores within tolerance for {len(rendered)} "
        f"requests, labels equal at {checked} with a clear top-2 margin")
    return out


def kvs_resident_note(cfg) -> str:
    """Why the KVS-resident param path is not run at full width: a device
    slab holds at least 8 rows (one of them scratch) per distinct (leaf
    shape, dtype), and the tensorstore keeps one key per leaf."""
    groups = collections.Counter(
        (leaf.shape, str(leaf.dtype))
        for leaf in jax.tree.leaves(Model(cfg).abstract_params()))
    slab = sum(max(8, 1 << n.bit_length()) * int(np.prod(shape))
               * np.dtype(dtype).itemsize
               for (shape, dtype), n in groups.items())
    return (f"kvs-resident params: not run at full width. {cfg.name}'s "
            f"{len(groups)} distinct leaf shapes need {slab / 1e9:.1f} GB of "
            f"device slab per arena that holds the tree (8-row minimum slab "
            f"per shape, one key per leaf), more than one chip's HBM; the "
            f"pipeline serves params bound locally instead")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the store phase, K-sharded over 4 chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    obs_host.install()
    print(f"device: {dev.device_kind} ({dev.platform}), {len(devices)} "
          f"visible, jax {jax.__version__}, compile cache {cache_dir}")

    if args.chips == 4:
        sharded_store_phase(args.seed, N_KEYS)
    else:
        mark = obs_host.snapshot()
        store = store_phase(args.seed, N_KEYS)
        report("store", mark)
        del store
        gc.collect()
        cfg = get_config("llama3.2-3b")
        mark = obs_host.snapshot()
        serve_phase(args.seed, cfg)
        report("serve", mark)
        print(kvs_resident_note(cfg))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
