"""The general request generator: every traffic mix names it and gives it
data only.

A mix's ``ops`` list holds one entry per request kind:

  {"kind": "find",   "share": 1.0, "keys": 64, "choose": "existing_uniform"}
  {"kind": "range",  "share": 0.95, "keys": 1, "start": "zipfian",
   "theta": 0.99, "length": [1, 100]}
  {"kind": "insert", "share": 0.05, "keys": 1, "choose": "fresh_uniform"}

``share`` is the kind's share of requests and ``keys`` the operations in one
request.  Key choice:

* ``existing_uniform``: keys of the set drawn uniformly over entries (the
  SOSD lookup set);
* ``zipfian``: a range starts at a record drawn from YCSB's zipfian
  distribution (Gray et al.; exponent ``theta``) over record numbers, which
  map to keys in a random order (YCSB's ``insertorder=hashed``), and spans
  ``length`` records, uniform over the inclusive bounds; ``uniform`` starts
  at a record drawn uniformly;
* ``fresh_uniform``: keys not in the set, drawn uniformly from the key space
  (new records), each used once.

Every seed gets the same number of requests of each kind; the seed sets
their order, their keys and the arrival times.
"""
from __future__ import annotations

import numpy as np

KINDS = ("find", "range", "insert")


def arrivals(rate: float, seconds: float, rng) -> np.ndarray:
    """Open-loop arrival times in [0, seconds): a Poisson process held to
    its mean count, round(rate * seconds), whose times are then sorted
    uniform draws."""
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


def zipfian(n_items: int, theta: float, size: int, rng) -> np.ndarray:
    """``size`` draws from YCSB's ZipfianGenerator over [0, n_items): item i
    has weight 1 / (i + 1)^theta (Gray et al., SIGMOD 1994)."""
    zetan = float(np.sum(1.0 / np.arange(1, n_items + 1,
                                         dtype=np.float64) ** theta))
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n_items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    z = (n_items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    z = np.where(uz < 1.0 + 0.5 ** theta, 1, z)
    z = np.where(uz < 1.0, 0, z)
    return np.minimum(z, n_items - 1)


def counts(ops: list, n: int) -> list:
    """Requests of each op entry out of ``n`` (largest remainder)."""
    want = np.asarray([float(o["share"]) for o in ops]) * n
    got = np.floor(want).astype(int)
    for i in np.argsort(-(want - got))[:n - got.sum()]:
        got[i] += 1
    return [int(c) for c in got]


def insert_keys(mix: dict, n: int) -> int:
    """Fresh keys that ``n`` requests of ``mix`` insert."""
    return sum(c * int(o["keys"]) for o, c in zip(mix["ops"],
                                                  counts(mix["ops"], n),
                                                  strict=True)
               if o["kind"] == "insert")


def plan(mix: dict, keys: np.ndarray, rng, n: int, fresh) -> list:
    """``n`` requests of ``mix`` over the sorted key set ``keys`` as
    ``(kind, payload)`` pairs, in random order.  Payloads are what
    ``repro.serve.frontend.Request`` takes: a key vector, or the (2, k)
    [lo; hi] stack of a range.  ``fresh(m)`` hands out m unused keys."""
    N = keys.size
    order = None
    reqs = []
    for op, c in zip(mix["ops"], counts(mix["ops"], n), strict=True):
        kind, k = op["kind"], int(op["keys"])
        if kind not in KINDS:
            raise ValueError(f"unknown op kind {kind!r}")
        if c == 0:
            continue
        if kind == "range":
            if op["start"] == "zipfian":
                if order is None:
                    order = rng.permutation(N)
                start = order[zipfian(N, float(op["theta"]), c * k, rng)]
            elif op["start"] == "uniform":
                start = rng.integers(0, N, c * k)
            else:
                raise ValueError(f"unknown range start {op['start']!r}")
            lmin, lmax = op["length"]
            span = rng.integers(int(lmin), int(lmax) + 1, c * k)
            lo = keys[start]
            hi = keys[np.minimum(start + span - 1, N - 1)]
            pay = np.stack([lo, hi]).reshape(2, c, k).transpose(1, 0, 2)
        elif op["choose"] == "existing_uniform":
            pay = keys[rng.integers(0, N, c * k)].reshape(c, k)
        elif op["choose"] == "fresh_uniform":
            pay = fresh(c * k).reshape(c, k)
        else:
            raise ValueError(f"unknown key choice {op['choose']!r}")
        reqs += [(kind, p) for p in pay]
    return [reqs[i] for i in rng.permutation(len(reqs))]
