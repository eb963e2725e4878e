"""The one traffic generator: every mix is a data file it reads.

A mix (``bench/traffic/<mix>.json``) gives its parameters by key:

* ``arrivals``: ``{"process": "poisson", "rate_per_s": r}`` (open loop at
  a constant rate) or ``{"process": "poisson", "phases": [[seconds,
  rate], ...]}`` (open loop whose rate steps through the phases, repeated
  over the window: on/off bursts are phases at rate 0);
* ``ops``: shares of ``read`` and ``update``;
* ``keys``: ``{"distribution": "zipfian", "theta": 0.99}`` (YCSB's
  scrambled Zipfian) or ``{"distribution": "uniform"}``;
* ``warmup_s``: seconds of the same traffic run before the window.

Every seed does the same work in another order.  The sizes of a
stretch of traffic (the gaps between arrivals, the keys, the count of
reads against updates) are drawn once by ``fixed_rng``, which no seed
changes; the run's ``--seed`` draws their order and the records that
updates write.  So two seeds send the same calls at the same gaps, and
differ only in which comes when.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, stream); any seed that fits
    64 bits works, negative ones too."""
    words = [ord(c) for c in stream]
    return np.random.default_rng([seed & (2**64 - 1), *words])


def fixed_rng(stream: str) -> np.random.Generator:
    """The generator of a stretch's sizes: the same for every seed."""
    return rng_for(0, "sizes/" + stream)


def seeded_order(rng: np.random.Generator, values: np.ndarray) -> np.ndarray:
    """``values`` in an order drawn by ``rng``."""
    return values[rng.permutation(len(values))]


# YCSB's ScrambledZipfianGenerator (core/.../generator/): ranks come from
# a Zipfian over ITEM_COUNT items whose zeta is precomputed, and a rank is
# mapped to a key by FNV-1a-64 modulo the key space.
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_ZETAN = 26.46902820178302  # zeta(ITEM_COUNT + 1, 0.99)
YCSB_THETA = 0.99
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def fnvhash64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV over the eight low bytes, then the
    absolute value of the signed 64-bit result."""
    val = np.asarray(values, np.int64).astype(np.uint64)
    h = np.full(val.shape, FNV_OFFSET_BASIS_64, np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (val & np.uint64(0xFF))) * prime
            val = val >> np.uint64(8)
    return np.abs(h.view(np.int64))


def ycsb_zipf_ranks(u: np.ndarray, theta: float = YCSB_THETA,
                    items: int = YCSB_ITEM_COUNT + 1,
                    zetan: float = YCSB_ZETAN) -> np.ndarray:
    """``ZipfianGenerator.nextLong`` (Gray et al.'s method) for uniform
    draws ``u``: rank 0 and 1 exactly, the rest by its closed form."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    tail = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    return np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, tail))


def zipf_sampler(rng: np.random.Generator, n_keys: int, theta: float
                 ) -> Callable[[int], np.ndarray]:
    """YCSB's scrambled Zipfian over ``n_keys`` records, as ``CoreWorkload``
    builds it for a run with no inserts: key space [0, n_keys], rank r
    goes to key ``fnvhash64(r) % (n_keys + 1)``, and a key not loaded (the
    last) is drawn again.  YCSB precomputes zeta only for theta 0.99."""
    if theta != YCSB_THETA:
        raise ValueError(f"YCSB's scrambled Zipfian is defined for theta "
                         f"{YCSB_THETA}, not {theta}")
    space = n_keys + 1

    def draw(size: int) -> np.ndarray:
        out = np.empty(0, np.int64)
        while len(out) < size:
            keys = np.fmod(fnvhash64(ycsb_zipf_ranks(rng.random(size))), space)
            out = np.concatenate([out, keys[(keys >= 0) & (keys < n_keys)]])
        return out[:size]

    return draw


def key_sampler(rng: np.random.Generator, spec: Dict[str, Any],
                n_keys: int) -> Callable[[int], np.ndarray]:
    dist = spec["distribution"]
    if dist == "zipfian":
        return zipf_sampler(rng, n_keys, float(spec["theta"]))
    if dist == "uniform":
        return lambda size: rng.integers(0, n_keys, size)
    raise ValueError(f"unknown key distribution {dist!r}")


def open_arrivals(rng: np.random.Generator, arrivals: Dict[str, Any],
                  seconds: float, sizes: np.random.Generator) -> np.ndarray:
    """Due times over ``[0, seconds)`` of a Poisson process at the mix's
    ``rate_per_s``, or at the rate of each of its ``phases`` in turn.
    Each stretch of constant rate holds ``round(rate * length)`` arrivals
    at sorted uniform times, a Poisson process conditioned on its count,
    drawn by ``sizes``; ``rng`` draws the order of their gaps."""
    phases = arrivals.get("phases") or [[seconds, arrivals["rate_per_s"]]]
    if any(float(length) <= 0 or float(rate) < 0 for length, rate in phases):
        raise ValueError(f"phases need lengths > 0 and rates >= 0: {phases}")
    stretches = [np.zeros(0)]
    t = 0.0
    while t < seconds:
        for length, rate in phases:
            end = min(t + float(length), seconds)
            count = int(round(float(rate) * (end - t)))
            times = np.sort(sizes.uniform(t, end, count))
            # count + 1 gaps from t to end, the last one closing the stretch
            gaps = seeded_order(rng, np.diff(np.concatenate([[t], times,
                                                             [end]])))
            stretches.append(np.minimum(t + np.cumsum(gaps[:count]),
                                        np.nextafter(end, t)))
            t = end
            if t >= seconds:
                break
    return np.sort(np.concatenate(stretches))


def op_sequence(rng: np.random.Generator, shares: Dict[str, float], n: int
                ) -> np.ndarray:
    """``n`` op names in the mix's exact shares, in a seeded order."""
    names = sorted(shares)
    counts = [int(round(shares[k] * n)) for k in names]
    counts[-1] = n - sum(counts[:-1])
    ops = np.repeat(np.asarray(names, dtype=object), counts)
    return ops[rng.permutation(n)]
