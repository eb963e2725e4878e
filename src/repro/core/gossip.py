"""Gossip-based distributed aggregation (paper §6.1.3).

Kempe et al.'s push-sum protocol [48]: every participant holds (value,
weight); each round it halves both and pushes one half to a random peer;
``value/weight`` converges to the population mean under dynamic membership.
The paper implements it in 60 lines of Python over Cloudburst's send/recv —
we do the same, plus:

* ``gather_*``: the centralized workaround the paper compares against
  (publish metric to KVS, a fixed leader reads them all) — requires a fixed
  population, unlike push-sum;
* ``device_push_sum``: the TPU-native adaptation — the same protocol as a
  ``shard_map`` program over the device mesh using ``ppermute``, which is
  what fine-grained messaging lowers to on ICI.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .lattices import LamportClock, LWWLattice
from .netsim import NetworkProfile, VirtualClock, DEFAULT_PROFILE


# ---------------------------------------------------------------------------
# Executor-level push-sum over Cloudburst messaging
# ---------------------------------------------------------------------------


def push_sum_round(
    values: Dict[str, Tuple[float, float]],
    rng: random.Random,
    clock: Optional[VirtualClock] = None,
    profile: NetworkProfile = DEFAULT_PROFILE,
    members: Optional[Sequence[str]] = None,
) -> Dict[str, Tuple[float, float]]:
    """One synchronous round of push-sum over the current membership."""
    ids = list(members) if members is not None else list(values)
    inbox: Dict[str, List[Tuple[float, float]]] = {i: [] for i in ids}
    for node in ids:
        x, w = values[node]
        peer = rng.choice(ids)
        inbox[node].append((x / 2.0, w / 2.0))
        inbox[peer].append((x / 2.0, w / 2.0))
    if clock is not None:
        # rounds proceed in parallel: one message hop per round
        clock.advance(profile.sample(profile.tcp, 64))
    return {
        node: (sum(x for x, _ in msgs), sum(w for _, w in msgs))
        for node, msgs in inbox.items()
    }


def push_sum(
    metrics: Dict[str, float],
    tolerance: float = 0.05,
    max_rounds: int = 1000,
    seed: int = 0,
    clock: Optional[VirtualClock] = None,
    profile: NetworkProfile = DEFAULT_PROFILE,
    membership_schedule: Optional[Dict[int, Sequence[str]]] = None,
) -> Tuple[float, int]:
    """Run push-sum until every estimate is within ``tolerance`` of the mean.

    ``membership_schedule`` optionally maps round -> member list, exercising
    the protocol's tolerance to membership churn (the autoscaling setting).
    """
    rng = random.Random(seed)
    true_mean = sum(metrics.values()) / len(metrics)
    state = {k: (v, 1.0) for k, v in metrics.items()}
    members = list(metrics)
    for rnd in range(1, max_rounds + 1):
        if membership_schedule and rnd in membership_schedule:
            members = list(membership_schedule[rnd])
        state = push_sum_round(state, rng, clock=clock, profile=profile, members=members)
        estimates = [x / w for x, w in (state[m] for m in members) if w > 1e-12]
        if estimates and all(
            abs(e - true_mean) <= tolerance * max(abs(true_mean), 1e-12)
            for e in estimates
        ):
            return float(np.mean(estimates)), rnd
    return float(np.mean([x / w for x, w in state.values()])), max_rounds


# ---------------------------------------------------------------------------
# The "gather" workaround (paper §6.1.3): fixed leader reads a KVS
# ---------------------------------------------------------------------------


def gather_via_kvs(
    kvs,
    metrics: Dict[str, float],
    clock: Optional[VirtualClock] = None,
    op_model=None,
    profile: NetworkProfile = DEFAULT_PROFILE,
) -> float:
    """Each member publishes its metric; a predetermined leader gathers."""
    clk = LamportClock("gather")
    model = op_model or profile.kvs_op
    for node, value in metrics.items():
        kvs.put(f"__metric_{node}", LWWLattice(clk.tick(), value))
    if clock is not None:
        # publishes happen in parallel across members: account one
        # message hop for the whole publish wave (approximate the
        # slowest with a single sample)
        clock.advance(profile.sample(model, 64))
    total = 0.0
    for node in metrics:
        lat = kvs.get_merged(f"__metric_{node}")
        total += lat.reveal()
        if clock is not None:
            clock.advance(profile.sample(model, 64))  # leader reads serially
    return total / len(metrics)


# ---------------------------------------------------------------------------
# TPU-native push-sum: shard_map + ppermute over the device mesh
# ---------------------------------------------------------------------------


def device_push_sum(values: jax.Array, rounds: int, seed: int = 0) -> jax.Array:
    """Push-sum across devices along axis "i" using collective_permute.

    The random peer choice of Kempe et al. becomes a per-round random
    permutation (fixed at trace time, as ICI schedules must be static); the
    (x, w) halving and merge are exactly the paper's algorithm.  Returns the
    per-device estimates, which converge to the global mean.
    """
    n = values.shape[0]
    rng = np.random.default_rng(seed)
    perms = [rng.permutation(n) for _ in range(rounds)]

    from jax.sharding import AxisType

    mesh = jax.make_mesh((n,), ("i",), axis_types=(AxisType.Auto,))

    def body(x):
        v = x.reshape(())
        w = jnp.ones(())
        for perm in perms:
            links = [(int(s), int(d)) for s, d in enumerate(perm)]
            v_half, w_half = v * 0.5, w * 0.5
            v_in = jax.lax.ppermute(v_half, "i", links)
            w_in = jax.lax.ppermute(w_half, "i", links)
            v = v_half + v_in
            w = w_half + w_in
        return (v / w).reshape((1,))

    from jax.sharding import PartitionSpec as P

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("i"), out_specs=P("i"),
                       check_vma=False)
    return fn(values)
