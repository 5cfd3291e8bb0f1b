"""Key set of the SOSD amzn (books) shape, the paper's Fig. 5 dataset.

``amzn`` is a copy of ``benchmarks/datasets.amzn``: book popularity as
heavy-tailed lognormal counts, keys the cumulative popularity (a heavy head).
Its one change is a stable sort in place, which gives the same array faster
on the nearly sorted input.  It is a surrogate of the SOSD file's shape,
in f64, the program's key type.  ``draw`` with ``integer`` set rounds the
keys down to whole numbers, as SOSD's keys are unsigned integers (under
2^44 here, duplicates kept).
"""
from __future__ import annotations

import numpy as np


def amzn(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    pop = rng.lognormal(3.0, 1.5, n)
    keys = np.cumsum(pop) + rng.random(n)
    keys *= 1e3
    keys.sort(kind="stable")
    return keys


def draw(params: dict, seed: int) -> np.ndarray:
    keys = amzn(int(params["n"]), seed)
    if params.get("integer"):
        np.floor(keys, out=keys)
    return keys


def _member(sorted_keys: np.ndarray, q: np.ndarray) -> np.ndarray:
    i = np.searchsorted(sorted_keys, q)
    return (i < sorted_keys.size) & \
        (sorted_keys[np.minimum(i, sorted_keys.size - 1)] == q)


def absent(keys: np.ndarray, params: dict, rng, m: int) -> np.ndarray:
    """m distinct keys outside the set, in random order: the successors of
    random members (the next integer, or the next f64) where those are not
    members."""
    if m == 0:
        return np.zeros(0)
    cand = rng.choice(keys, 8 * m)
    cand = cand + 1.0 if params.get("integer") else np.nextafter(cand, np.inf)
    cand = np.unique(cand[~_member(keys, cand)])
    if cand.size < m:
        raise RuntimeError(f"only {cand.size} absent keys found for {m}")
    return rng.permutation(rng.choice(cand, m, replace=False))
