"""The traffic generator: repeatable per seed, fixed counts, Zipf shape."""

import numpy as np
import pytest

from bench.harness import traffic

BIG_SEED = 2**33 + 12345  # more than 32 bits: --seed takes such seeds too


def test_same_seed_same_traffic_and_streams_differ():
    a = traffic.rng_for(BIG_SEED, "window")
    b = traffic.rng_for(BIG_SEED, "window")
    c = traffic.rng_for(BIG_SEED, "warmup")
    assert np.array_equal(a.random(8), b.random(8))
    assert not np.array_equal(traffic.rng_for(BIG_SEED, "window").random(8),
                              c.random(8))
    assert traffic.rng_for(-5, "x").random() == traffic.rng_for(-5, "x").random()


def _arrivals(seed, mix, seconds):
    return traffic.open_arrivals(traffic.rng_for(seed, "w"), mix, seconds,
                                 traffic.fixed_rng("w"))


def test_open_arrivals_fix_their_count_and_stay_in_the_window():
    gap_sets = []
    for seed in (1, 2, BIG_SEED):
        due = _arrivals(seed, {"rate_per_s": 237.0}, 20.0)
        assert len(due) == 4740
        assert np.all(np.diff(due) >= 0)
        assert 0.0 <= due[0] and due[-1] < 20.0
        # Poisson: gaps are about exponential with mean 1/rate
        assert abs(np.diff(due).mean() - 1 / 237.0) < 1e-4
        gap_sets.append(np.diff(np.concatenate([[0.0], due, [20.0]])))
    # every seed has the same gaps, each in its own order
    for gaps in gap_sets[1:]:
        assert np.allclose(np.sort(gaps), np.sort(gap_sets[0]), atol=1e-9)
        assert not np.allclose(gaps, gap_sets[0])


def test_op_shares_are_exact_and_the_order_is_seeded():
    ops = [traffic.op_sequence(traffic.rng_for(s, "ops"),
                               {"read": 0.5, "update": 0.5}, 1001)
           for s in (3, 4)]
    for o in ops:
        assert (o == "read").sum() + (o == "update").sum() == 1001
        assert abs(int((o == "read").sum()) - 500) <= 1
    assert not np.array_equal(ops[0], ops[1])


def _fnv_plain(value: int) -> int:
    """FNV as YCSB's Utils.fnvhash64 writes it, in Python integers."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= value & 0xFF
        value >>= 8
        h = (h * 1099511628211) % 2**64
    signed = h - 2**64 if h >= 2**63 else h
    return abs(signed)


def _zeta_099(n: float) -> float:
    """sum_{i<=n} i^-0.99 by Euler-Maclaurin, with zeta(0.99) from its
    Laurent series (Euler's and the first Stieltjes constant)."""
    s = 0.99
    zeta_s = 1 / (s - 1) + 0.5772156649015329 + 0.0728158454836767 * (s - 1)
    return n ** (1 - s) / (1 - s) + zeta_s + n ** -s / 2


def test_zipf_keeps_its_shape_and_scrambles_ranks():
    # YCSB's constants: its precomputed zeta over 10^10 + 1 items, and
    # its FNV hash
    assert abs(_zeta_099(traffic.YCSB_ITEM_COUNT + 1)
               - traffic.YCSB_ZETAN) < 1e-4
    vals = np.array([0, 1, 2, 255, 65536, 10**9 + 7, 2**40 + 3])
    assert list(traffic.fnvhash64(vals)) == [_fnv_plain(int(v)) for v in vals]
    # ranks follow zeta(r) / zetan: the hottest two exactly, the rest by
    # Gray et al.'s closed form, which YCSB keeps although it runs up to
    # 0.007 above the exact sum
    u = traffic.rng_for(8, "u").random(400_000)
    ranks = traffic.ycsb_zipf_ranks(u)
    for r in (1, 2, 100, 1 << 16, 1 << 21):
        exact = np.sum(np.arange(1, r + 1, dtype=np.float64) ** -0.99)
        assert abs((ranks < r).mean() - exact / traffic.YCSB_ZETAN) < 0.01, r
    # over a key space: the hottest key is fnvhash64(0) mod (n + 1) with
    # 1/zetan of the draws, the next fnvhash64(1)'s; about half of all
    # draws land past rank 2^16, spread over the whole space
    n = 1 << 16
    keys = traffic.zipf_sampler(traffic.rng_for(9, "k"), n, 0.99)(200_000)
    assert keys.min() >= 0 and keys.max() < n
    share = np.bincount(keys, minlength=n) / len(keys)
    first, second = (_fnv_plain(r) % (n + 1) for r in (0, 1))
    assert abs(share[first] - 1 / traffic.YCSB_ZETAN) < 0.002
    assert abs(share[second] - 0.5 ** 0.99 / traffic.YCSB_ZETAN) < 0.0015
    assert np.argmax(share) == first
    assert (share > 0).mean() > 0.75  # most keys drawn at least once
    # the same seed draws the same keys
    again = traffic.zipf_sampler(traffic.rng_for(9, "k"), n, 0.99)(200_000)
    assert np.array_equal(keys, again)
    with pytest.raises(ValueError):
        traffic.zipf_sampler(traffic.rng_for(9, "k"), n, 0.9)


def test_phased_arrivals_step_their_rate_and_fix_each_count():
    # on/off bursts: 2 s at 300 calls/s, 3 s off, repeated over 12 s
    mix = {"process": "poisson", "phases": [[2.0, 300.0], [3.0, 0.0]]}
    for seed in (1, BIG_SEED):
        due = _arrivals(seed, mix, 12.0)
        counts = np.histogram(due, bins=[0, 2, 5, 7, 10, 12])[0]
        assert list(counts) == [600, 0, 600, 0, 600]
        assert np.all(np.diff(due) >= 0)
    assert np.array_equal(_arrivals(1, mix, 12.0), _arrivals(1, mix, 12.0))
    assert len(_arrivals(1, mix, 0.0)) == 0
    with pytest.raises(ValueError):
        _arrivals(1, {"phases": [[0.0, 10.0]]}, 5.0)


def test_two_seeds_send_the_same_calls_in_another_order():
    from bench.harness import store

    mix = {"ops": {"read": 0.5, "update": 0.5},
           "keys": {"distribution": "zipfian", "theta": 0.99}}
    draws = [store.Ops.draw(traffic.rng_for(seed, "w"),
                            traffic.fixed_rng("w"), mix, 3001, 1 << 16, 4)
             for seed in (1, BIG_SEED)]
    a, b = draws
    assert np.array_equal(np.sort(a.key), np.sort(b.key))
    assert (a.op == "read").sum() == (b.op == "read").sum()
    assert not np.array_equal(a.key, b.key)
    assert not np.array_equal(a.rows, b.rows)
