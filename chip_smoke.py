"""Smoke run of the learned-index service on TPU: the served path, end to
end, at full size.

It drives the entry points a user calls: ``repro.api.Index.build`` over a
mesh (the sharded backend the front-end serves) and
``repro.serve.frontend.BatchingFrontend``, in one process that holds the
chips::

    python chip_smoke.py              # one chip: 10^8 keys
    python chip_smoke.py --chips 4    # four chips: 2x10^8 keys range-
                                      # partitioned, 5x10^7 per chip

Keys have the amzn SOSD shape (``benchmarks/datasets.py``), drawn from
``--seed`` and rounded to f32, the key space of the kernel path.  One
tenant takes this traffic through ``submit(Request(...))``: point finds
(half present keys, half absent) with range requests, one insert batch,
one delete batch, then the finds and ranges again; then one insert batch
packed into a narrow key range, which uses up the Lemma 4.1 budget of the
leaves there and so rebuilds them, and the finds and ranges once more.
Every answer is
compared with ``np.searchsorted`` on a host sorted array that carries the
same mutations, and any mismatch exits non-zero.  The run also asserts the
path, so that no fallback can hide the chip: a TPU backend, the kernel path
chosen by ``path="auto"``, compiled (not interpreted) kernels, and
``tpu_custom_call`` in both compiled dispatch programs.

The last line of stdout is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.  Without a TPU the run exits 2 and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

N_FIND = 384        # point finds per traffic phase (half present)
N_RANGE = 256       # range requests per traffic phase
N_UPDATE = 256      # keys in the insert batch and in the delete batch
N_HOT = 8192        # keys in the batch that rebuilds leaves (a leaf's
                    # budget is about its ~1.5k keys)
REQ_KEYS = 32       # keys per find / range request
N_LEAVES = 1 << 16  # leaves per shard: ~1.5k keys each at 10^8 keys
MIN_N = 1 << 26     # smallest allowed size cut


def log(msg: str) -> None:
    print(msg, flush=True)


class Mismatch(Exception):
    pass


def member(sorted_keys: np.ndarray, q: np.ndarray) -> np.ndarray:
    i = np.searchsorted(sorted_keys, q)
    return (i < sorted_keys.size) & \
        (sorted_keys[np.minimum(i, sorted_keys.size - 1)] == q)


class Reference:
    """The plain sorted-array semantics the index must match: ``find`` is
    (a live copy exists, live keys < q); a range [lo, hi] is (live keys
    < lo, live keys <= hi, clamped to the first); an insert adds copies; a
    delete removes one copy of each distinct key."""

    def __init__(self, keys: np.ndarray):
        self.live = keys

    def find(self, q):
        lo = np.searchsorted(self.live, q, side="left")
        hi = np.searchsorted(self.live, q, side="right")
        return hi > lo, lo

    def range(self, lo, hi):
        a = np.searchsorted(self.live, lo, side="left")
        b = np.searchsorted(self.live, hi, side="right")
        return a, np.maximum(a, b)

    def insert(self, keys):
        k = np.sort(keys)
        self.live = np.insert(self.live,
                              np.searchsorted(self.live, k, side="right"), k)

    def delete(self, keys):
        u = np.unique(keys)
        u = u[member(self.live, u)]
        self.live = np.delete(self.live,
                              np.searchsorted(self.live, u, side="left"))


def expect(what: str, got, want) -> int:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = np.flatnonzero(got != want)[:5] if got.shape == want.shape \
            else []
        raise Mismatch(f"{what}: shapes {got.shape}/{want.shape}, first "
                       f"mismatches at {list(bad)}: got {got[bad]} want "
                       f"{want[bad]}")
    return got.size


def make_keys(n: int, seed: int) -> np.ndarray:
    """n keys of the amzn shape, rounded to f32 (sorted, duplicates kept)."""
    from benchmarks.datasets import amzn
    return amzn(n, seed).astype(np.float32).astype(np.float64)


def absent_keys(rng, distinct: np.ndarray, m: int) -> np.ndarray:
    """m f32 keys outside the key set: f32 successors of random members
    where those are not members, plus one below and one above the set."""
    cand = np.nextafter(rng.choice(distinct, 8 * m).astype(np.float32),
                        np.float32(np.inf)).astype(np.float64)
    cand = np.unique(cand[~member(distinct, cand)])
    if cand.size < m - 2:
        raise RuntimeError(f"only {cand.size} absent keys found")
    ends = np.asarray([np.float32(distinct[0] / 2),
                       np.float32(distinct[-1] * 2)], np.float64)
    return rng.permutation(np.concatenate([rng.choice(cand, m - 2,
                                                      replace=False), ends]))


def hot_keys(rng, distinct: np.ndarray, m: int) -> np.ndarray:
    """m f32 keys, duplicates allowed, from one narrow key range: 64
    consecutive members and their f32 successors."""
    i = int(rng.integers(distinct.size // 4, 3 * distinct.size // 4))
    run = distinct[i:i + 64]
    succ = np.nextafter(run.astype(np.float32),
                        np.float32(np.inf)).astype(np.float64)
    return rng.choice(np.concatenate([run, succ]), m)


def traffic(fe, ref: Reference, rng, present, absent, label: str) -> dict:
    """One phase of finds and ranges, submitted together, every answer
    checked against ``ref``."""
    from repro.serve.frontend import Request
    q = rng.permutation(np.concatenate([
        rng.choice(present, N_FIND // 2), rng.choice(absent, N_FIND // 2)]))
    pick = lambda: np.where(rng.random(N_RANGE) < 0.5,
                            rng.choice(present, N_RANGE),
                            rng.choice(absent, N_RANGE))
    a, b = pick(), pick()
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    narrow = rng.random(N_RANGE) < 0.5      # short scans from lo
    pos = np.minimum(np.searchsorted(ref.live, lo) +
                     rng.integers(0, 100, N_RANGE), ref.live.size - 1)
    hi = np.where(narrow, np.maximum(ref.live[pos], lo), hi)
    swap = rng.random(N_RANGE) < 0.1        # degenerate: lo > hi
    lo, hi = np.where(swap, hi, lo), np.where(swap, lo, hi)
    finds = [fe.submit(Request(0, "find", c))
             for c in np.array_split(q, N_FIND // REQ_KEYS)]
    ranges = [fe.submit(Request(0, "range", np.stack(c)))
              for c in zip(np.array_split(lo, N_RANGE // REQ_KEYS),
                           np.array_split(hi, N_RANGE // REQ_KEYS),
                           strict=True)]
    n = {"finds": 0, "ranges": 0}
    for r in finds:
        found, rank = r.result(timeout=1200)
        want_found, want_rank = ref.find(r.keys)
        expect(f"{label} find found", found, want_found)
        n["finds"] += expect(f"{label} find rank", rank, want_rank)
    for r in ranges:
        rlo, rhi = r.result(timeout=1200)
        want_lo, want_hi = ref.range(r.keys[0], r.keys[1])
        expect(f"{label} range rank_lo", rlo, want_lo)
        n["ranges"] += expect(f"{label} range rank_hi", rhi, want_hi)
    return n


def assert_chip_path(fe) -> None:
    """The served path is the compiled kernel path on the TPU."""
    from repro.kernels import ops
    pack = fe.pack
    if not pack.use_kernel:
        raise AssertionError("path='auto' fell back to jnp")
    if pack.interpret or ops._default_interpret():
        raise AssertionError("kernels would run interpreted")
    w = fe.config.batch_floor
    for kind, width in (("find", w), ("range", 2 * w)):
        fn, args = pack.dispatch(kind, np.zeros((pack.n_tenants, width)))
        if "tpu_custom_call" not in fn.lower(*args).compile().as_text():
            raise AssertionError(f"no tpu_custom_call in the {kind} program")


def run(chips: int, n: int, seed: int) -> dict:
    import jax

    from repro.api import Index
    from repro.compile_cache import use_compile_cache
    from repro.serve.frontend import BatchingFrontend, Request

    cache = use_compile_cache()
    devs = jax.devices()
    log(f"devices: {len(devs)} x {devs[0].device_kind}; using {chips}; "
        f"compile cache {cache}")
    rng = np.random.default_rng(seed + 1)

    t0 = time.perf_counter()
    keys = make_keys(n, seed)
    distinct = keys[np.concatenate([[True], keys[1:] != keys[:-1]])]
    log(f"keys: {n} drawn (amzn shape, seed {seed}), rounded to f32: "
        f"{distinct.size} distinct ({time.perf_counter() - t0:.1f} s)")

    mesh = jax.make_mesh((chips,), ("data",), devices=devs[:chips])
    t0 = time.perf_counter()
    index = Index.build(keys, mesh=mesh, n_leaves=N_LEAVES)
    jax.block_until_ready(index.backend.shards[-1].index.keys)
    log(f"build: {time.perf_counter() - t0:.1f} s "
        f"(Index.build, {chips} shard(s), {N_LEAVES} leaves per shard)")

    ref = Reference(keys)
    present = distinct
    absent = absent_keys(rng, distinct, 4 * N_FIND)
    ins = np.concatenate([absent[:N_UPDATE - 32],
                          rng.choice(distinct, 32, replace=False)])
    base_dels = rng.choice(distinct, N_UPDATE, replace=False)
    dels = np.concatenate([
        ins[:32], base_dels[~np.isin(base_dels, ins)][:N_UPDATE - 32]])
    absent = absent[N_UPDATE:]
    checked = {"finds": 0, "ranges": 0}

    with BatchingFrontend([index.backend]) as fe:
        t0 = time.perf_counter()
        fe.warmup(batch_sizes=(N_FIND, N_RANGE))
        log(f"compile: {time.perf_counter() - t0:.1f} s (warmup: find and "
            "range programs per capacity class, first runs included)")
        assert_chip_path(fe)
        log("path: kernel (path='auto'), compiled; find and range programs "
            "contain tpu_custom_call")

        for k, v in traffic(fe, ref, rng, present, absent, "before").items():
            checked[k] += v
        fe.submit(Request(0, "insert", ins)).result(timeout=1200)
        ref.insert(ins)
        fe.submit(Request(0, "delete", dels)).result(timeout=1200)
        ref.delete(dels)
        t0 = time.perf_counter()
        for k, v in traffic(fe, ref, rng, present, absent, "after").items():
            checked[k] += v
        log(f"after updates: {time.perf_counter() - t0:.1f} s for the "
            "second phase (includes retraces for the grown delta tier)")

        hot = hot_keys(rng, distinct, N_HOT)
        absent = absent[~np.isin(absent, hot)]
        rebuilt = sum(d.rebuilds for d in index.backend.shards)
        t0 = time.perf_counter()
        fe.submit(Request(0, "insert", hot)).result(timeout=1200)
        ref.insert(hot)
        t_ins = time.perf_counter() - t0
        rebuilt = sum(d.rebuilds for d in index.backend.shards) - rebuilt
        if rebuilt == 0:
            raise AssertionError("the packed insert batch rebuilt no leaf")
        t0 = time.perf_counter()
        for k, v in traffic(fe, ref, rng, present, absent,
                            "after rebuild").items():
            checked[k] += v
        log(f"leaf rebuild: {hot.size} inserts into one key range rebuilt "
            f"{rebuilt} leaves ({t_ins:.1f} s, refit on the host CPU "
            f"backend); third phase {time.perf_counter() - t0:.1f} s")
    if index.live_count != ref.live.size:
        raise Mismatch(f"live count {index.live_count} != {ref.live.size}")
    log(f"checked: {checked['finds']} finds, {checked['ranges']} ranges, "
        f"{ins.size + hot.size} inserts, {dels.size} deletes; live count "
        f"{ref.live.size}: every answer equals the reference")
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        log(f"peak device bytes {d.id}: {stats.get('peak_bytes_in_use')} "
            f"of {stats.get('bytes_limit')}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=None,
                    help="keys (default 10^8 on one chip, 2x10^8 on four)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    full = 100_000_000 * (2 if args.chips == 4 else 1)
    n = full if args.n is None else args.n
    if n < MIN_N:
        ap.error(f"--n below {MIN_N} is not a full-size run")
    plat = os.environ.get("JAX_PLATFORMS")
    if plat and "cpu" not in plat.split(","):
        # the index build runs on the host CPU backend (DynamicRMI.build)
        os.environ["JAX_PLATFORMS"] = plat + ",cpu"

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX found {devs[0].platform}", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"{args.chips} chips asked, {len(devs)} found",
              file=sys.stderr)
        return 2
    if n < full:
        log(f"size cut: {n} keys instead of {full}")
    try:
        device = run(args.chips, n, args.seed)
    except (Mismatch, AssertionError) as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
