"""The key-set and request generators of the benchmark."""
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.spec import load_module  # noqa: E402

ops = load_module("generators", "ops")


def test_amzn_copy_matches_the_repository_generator():
    from benchmarks.datasets import amzn

    mine = load_module("datasets", "amzn")
    for n, seed in ((1000, 7), (4096, 2**31 + 5)):
        np.testing.assert_array_equal(mine.amzn(n, seed), amzn(n, seed))
    np.testing.assert_array_equal(mine.draw({"n": 1000}, 7), amzn(1000, 7))
    k = mine.draw({"n": 1000, "integer": True}, 7)      # SOSD's whole keys
    np.testing.assert_array_equal(k, np.floor(amzn(1000, 7)))
    for params in ({}, {"integer": True}):
        a = mine.absent(k, params, np.random.default_rng(1), 50)
        assert np.unique(a).size == 50 and not np.isin(a, k).any()
    assert np.array_equal(a, np.floor(a))


def test_zipfian_exponent_and_head():
    rng = np.random.default_rng(0)
    n, theta = 10_000, 0.99
    z = ops.zipfian(n, theta, 400_000, rng)
    assert z.min() >= 0 and z.max() < n
    f = np.bincount(z, minlength=n).astype(float)
    r = np.arange(1, 51)
    slope = np.polyfit(np.log(r), np.log(f[:50]), 1)[0]
    assert abs(slope + theta) < 0.05, slope
    zeta = np.sum(1.0 / np.arange(1, n + 1) ** theta)
    assert abs(f[0] / z.size - 1 / zeta) < 0.01


def test_scan_lengths_and_shares():
    rng = np.random.default_rng(1)
    keys = np.arange(0.0, 5000.0)
    mix = {"ops": [
        {"kind": "range", "share": 0.95, "keys": 1, "start": "zipfian",
         "theta": 0.99, "length": [1, 100]},
        {"kind": "insert", "share": 0.05, "keys": 1,
         "choose": "fresh_uniform"}]}
    fresh = iter(np.arange(1e6, 2e6))
    plan = ops.plan(mix, keys, rng, 20_000,
                    lambda m: np.asarray([next(fresh) for _ in range(m)]))
    kinds = [k for k, _ in plan]
    assert kinds.count("insert") == 1000 == ops.insert_keys(mix, 20_000)
    span = np.asarray([p[1, 0] - p[0, 0] + 1 for k, p in plan
                       if k == "range" and p[1, 0] < keys[-1]])
    assert span.min() == 1 and span.max() == 100
    assert abs(span.mean() - 50.5) < 1.0


def test_arrivals_hold_the_count():
    rng = np.random.default_rng(2)
    t = ops.arrivals(250.0, 4.0, rng)
    assert t.size == 1000 and np.all(np.diff(t) >= 0)
    assert 0.0 <= t[0] and t[-1] < 4.0
