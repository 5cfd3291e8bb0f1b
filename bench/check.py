"""What decides ``correct``: every answer of a run against a plain
sorted-array reference, under the configuration's guarantee.

The reference state is a sorted key array; a find answers (a copy of q
exists, keys < q), a range [lo, hi] answers (keys < lo, max(keys <= hi,
keys < lo)), and an insert adds a copy of each key.  These are the
semantics of ``repro.api.Index`` with no tombstones.

Inserts are applied in the order they were submitted, so the states a read
may see are the prefixes of that order.  The guarantee: a read sees every
insert acknowledged before it was submitted, and none submitted after it
was answered.  :func:`verify` accepts a read when its whole answer equals
the reference at one prefix length k in that interval; with no inserts the
interval is the single initial state, and the check is exact equality.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Rec:
    """One request as the run saw it.  ``payload`` is a key vector, or the
    (2, k) [lo; hi] stack of a range; ``answer`` is (found, rank),
    (rank_lo, rank_hi), or None for an insert; ``submitted`` and ``done``
    are on the front-end's clock (``done`` None: never answered)."""
    kind: str
    payload: np.ndarray
    submitted: float
    done: float | None = None
    answer: tuple | None = None
    error: bool = False


def _searchsorted(a: np.ndarray, v: np.ndarray, side: str) -> np.ndarray:
    """``np.searchsorted(a, v, side)`` with the needles searched in sorted
    order, which walks a large ``a`` once instead of at random."""
    order = np.argsort(v, kind="stable")
    out = np.empty(v.shape, np.int64)
    out[order] = np.searchsorted(a, v[order], side=side)
    return out


def reference_answers(live: np.ndarray, kind: str, payload: np.ndarray):
    """The reference's answer for one read over the sorted ``live`` keys."""
    if kind == "find":
        lt = np.searchsorted(live, payload, side="left")
        le = np.searchsorted(live, payload, side="right")
        return le > lt, lt
    lo = np.searchsorted(live, payload[0], side="left")
    hi = np.searchsorted(live, payload[1], side="right")
    return lo, np.maximum(hi, lo)


def verify(base: np.ndarray, recs: list) -> dict:
    """Check every answered read of ``recs`` (all requests of a run, in
    submission order) against ``base`` plus the inserts among them.
    Returns counts: ``wrong`` reads whose answer matches no admissible
    state, ``unanswered`` requests (never answered, or failed), ``reads``
    and ``inserts`` checked."""
    ins = [r for r in recs if r.kind == "insert"]
    unanswered = sum(1 for r in recs if r.done is None or r.error)
    ins_keys = [np.asarray(r.payload, np.float64).ravel() for r in ins]
    P = np.concatenate([[0], np.cumsum([k.size for k in ins_keys])]
                       ).astype(np.int64)
    flat = np.concatenate(ins_keys) if ins_keys else np.zeros(0)
    ack = np.asarray([r.done if (r.done is not None and not r.error)
                      else np.inf for r in ins], np.float64)
    sub = np.asarray([r.submitted for r in ins], np.float64)
    # A read must see the longest prefix of inserts that were all
    # acknowledged before it was submitted.
    ack_prefix = np.maximum.accumulate(ack) if ins else ack

    reads = [r for r in recs if r.kind in ("find", "range")
             and r.done is not None and not r.error]
    if not reads:
        return {"wrong": 0, "unanswered": unanswered, "reads": 0,
                "inserts": len(ins)}
    kA = np.searchsorted(ack_prefix, [r.submitted for r in reads],
                         side="right")
    kB = np.searchsorted(sub, [r.done for r in reads], side="right")
    kB = np.maximum(kA, kB)
    ok = np.zeros(len(reads), bool)
    for kind in ("find", "range"):
        idx = [i for i, r in enumerate(reads) if r.kind == kind]
        if idx:
            ok[idx] = _check_kind(base, flat, P, kind,
                                  [reads[i] for i in idx], kA[idx], kB[idx])
    return {"wrong": int((~ok).sum()), "unanswered": unanswered,
            "reads": len(reads), "inserts": len(ins)}


def _check_kind(base, flat, P, kind, reads, kA, kB) -> np.ndarray:
    """Per read: does some prefix length k in [kA, kB] reproduce its whole
    answer?"""
    if kind == "find":
        lo_v = np.concatenate([r.payload for r in reads])
        hi_v = lo_v
        a0 = np.concatenate([r.answer[0] for r in reads]).astype(bool)
    else:
        lo_v = np.concatenate([r.payload[0] for r in reads])
        hi_v = np.concatenate([r.payload[1] for r in reads])
        a0 = np.concatenate([r.answer[0] for r in reads]).astype(np.int64)
    a1 = np.concatenate([r.answer[1] for r in reads]).astype(np.int64)
    sizes = np.asarray([r.payload.shape[-1] for r in reads])
    owner = np.repeat(np.arange(len(reads)), sizes)
    ka, kb = kA[owner], kB[owner]
    # Counts at prefix kA: base plus the inserts of the first kA requests.
    lt = _searchsorted(base, lo_v, "left")
    le = _searchsorted(base, hi_v, "right")
    for k in np.unique(ka):
        m = ka == k
        pref = np.sort(flat[:P[k]])
        lt[m] += np.searchsorted(pref, lo_v[m], side="left")
        le[m] += np.searchsorted(pref, hi_v[m], side="right")
    match = np.zeros(len(reads), bool)
    width = kb - ka
    per = np.diff(P)                    # keys of each insert request
    for d in range(int(width.max()) + 1):
        if d:
            # add the insert request taken at step d, one key at a time
            live = d <= width
            j = np.where(live, ka + d - 1, 0)
            for o in range(int(per[j[live]].max()) if live.any() else 0):
                use = live & (o < per[j])
                v = flat[np.where(use, P[j] + o, 0)]
                lt += use & (v < lo_v)
                le += use & (v <= hi_v)
        if kind == "find":
            good = ((le > lt) == a0) & (lt == a1)
        else:
            good = (lt == a0) & (np.maximum(le, lt) == a1)
        good &= d <= width
        bad = np.bincount(owner, weights=~good, minlength=len(reads))
        match |= bad == 0
        if match.all():
            break
    return match
