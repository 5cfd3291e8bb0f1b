"""Shared benchmark machinery: the paper's index roster, timed builds and
lookups, CSV rows for run.py, and the per-PR trajectory appender for the
committed BENCH_*.json baselines."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.core import btree, pgm, radix_spline, reuse, rmi, rmrt, synth

_POOLS: dict = {}


def git_sha() -> str:
    """Short HEAD sha of the repo the benchmarks live in ("unknown" outside
    a checkout) — the trajectory key, together with the suite name."""
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=Path(__file__).resolve().parent, text=True,
            stderr=subprocess.DEVNULL).strip()
    except Exception:
        return "unknown"


def append_bench(path, suite: str, rows: list, mode: str = "interpret/CPU",
                 note: str = "") -> dict:
    """Append a per-PR trajectory entry to a committed BENCH json.

    The file's top-level ``meta``/``rows`` (the original baseline) are left
    untouched; entries accumulate under ``trajectory`` keyed by
    (git sha, suite) — re-running the same suite at the same sha replaces
    its entry instead of duplicating it, so the trajectory stays one row
    per PR per suite.  Returns the written document."""
    p = Path(path)
    data = json.loads(p.read_text()) if p.exists() else \
        {"meta": {}, "rows": []}
    sha = git_sha()
    traj = data.setdefault("trajectory", [])
    traj[:] = [e for e in traj
               if (e.get("sha"), e.get("suite")) != (sha, suite)]
    entry = {"sha": sha, "suite": suite, "mode": mode,
             "date": time.strftime("%Y-%m-%d"), "rows": rows}
    if note:
        entry["note"] = note
    traj.append(entry)
    p.write_text(json.dumps(data, indent=1) + "\n")
    print(f"appended {len(rows)} rows to {p.name} "
          f"(suite={suite}, sha={sha})")
    return data


def worker_rows(module: str, flag: str, n_devices: int, argv: list,
                timeout: int = 3600) -> list:
    """Collect benchmark rows from a forced-host-device-count subprocess.

    XLA's host device count locks at first jax init, so any bench needing a
    >1-device CPU mesh re-execs itself: ``python -m <module> <flag>
    <n_devices> <argv...>`` with XLA_FLAGS forcing the count; the worker
    prints its rows as JSON on the last stdout line.  Shared by the
    distributed rows of bench_lookup and the sharded rows of
    bench_updates.  Raises ``RuntimeError`` when the worker fails, times
    out or prints no parseable rows, so a broken phase fails the run.
    Refuses to re-exec from a parent whose backend is the TPU: the parent
    holds the chip, and a child that needs it would fail or hang."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{module} {flag}: forced-device-count workers run on the CPU "
            "backend only; this process holds the TPU")
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count"
                         f"={n_devices}")
    try:
        proc = subprocess.run(
            [sys.executable, "-m", module, flag, str(n_devices),
             *map(str, argv)],
            env=env, capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired as e:
        raise RuntimeError(
            f"{module} worker timed out after {timeout}s") from e
    if proc.returncode != 0:
        raise RuntimeError(f"{module} worker failed (exit "
                           f"{proc.returncode}):\n{proc.stderr[-2000:]}")
    try:
        return json.loads(proc.stdout.splitlines()[-1])
    except (json.JSONDecodeError, IndexError) as e:
        raise RuntimeError(f"{module} worker emitted no parseable rows:\n"
                           f"{proc.stdout[-2000:]}") from e


def worker_suite(module: str, flag: str, n_devices: int, n: int,
                 timeout: int = 3600) -> list:
    """The one-knob ``--n``-sized worker call shared by the bench suites
    (sharded/restack/recover rows of bench_updates, the serve suite): one
    place owns the forced-device-count re-exec convention instead of a
    per-suite wrapper each."""
    return worker_rows(module, flag, n_devices, ["--n", n], timeout=timeout)


def poisson_arrivals(rate_qps: float, duration_s: float,
                     seed: int = 0) -> np.ndarray:
    """Open-loop Poisson arrival times in [0, duration_s): exponential
    inter-arrival gaps at ``rate_qps``, cumulatively summed.  Open-loop
    means the offered load never backs off when the server lags — queueing
    delay shows up in the measured latency instead of silently throttling
    the generator — which is what an SLO benchmark must measure."""
    rng = np.random.default_rng(seed)
    out = []
    t = 0.0
    chunk = max(int(rate_qps * duration_s * 1.25) + 16, 16)
    while t < duration_s:
        gaps = rng.exponential(1.0 / rate_qps, size=chunk)
        ts = t + np.cumsum(gaps)
        out.append(ts)
        t = float(ts[-1])
    ts = np.concatenate(out)
    return ts[ts < duration_s]


def pools(eps: float = 0.9):
    """Cached (linear, mlp) pools; pre-train time reported separately."""
    if eps not in _POOLS:
        sp = synth.generate_pool(eps)
        t0 = time.time()
        lin = reuse.build_pool(sp, kind="linear")
        jax.block_until_ready(lin.err_hi)
        t_lin = time.time() - t0
        t0 = time.time()
        mlp = reuse.build_pool(sp, kind="mlp", train_steps=400)
        jax.block_until_ready(mlp.err_hi)
        t_mlp = time.time() - t0
        _POOLS[eps] = (lin, mlp, t_lin, t_mlp, sp.size)
    return _POOLS[eps]


@dataclass
class IndexSpec:
    name: str
    build: callable
    lookup: callable


def roster(eps: float = 0.9, n_leaves: int = 1024, warm: bool = True):
    """The paper's §5 roster: BTree, RMI, RMI-NN, RMI-MR, RMI-NN-MR, PGM,
    RS, RMRT."""
    lin_pool, mlp_pool, *_ = pools(eps)
    return [
        IndexSpec("BTree", lambda k: btree.build_btree(k, fanout=16),
                  btree.lookup),
        IndexSpec("RMI", lambda k: rmi.build_rmi(k, n_leaves, kind="linear"),
                  rmi.lookup),
        IndexSpec("RMI-MR", lambda k: rmi.build_rmi(k, n_leaves,
                                                    kind="linear",
                                                    pool=lin_pool),
                  rmi.lookup),
        IndexSpec("RMI-NN", lambda k: rmi.build_rmi(k, n_leaves, kind="mlp",
                                                    train_steps=150),
                  rmi.lookup),
        IndexSpec("RMI-NN-MR", lambda k: rmi.build_rmi(k, n_leaves,
                                                       kind="mlp",
                                                       pool=mlp_pool,
                                                       train_steps=150),
                  rmi.lookup),
        IndexSpec("PGM", lambda k: pgm.build_pgm(k, eps=64), pgm.lookup),
        IndexSpec("RS", lambda k: radix_spline.build_rs(k, eps=32),
                  radix_spline.lookup),
        IndexSpec("RMRT", lambda k: rmrt.build_rmrt(k, leaf_cap=4096,
                                                    fanout=64, kind="linear",
                                                    pool=lin_pool),
                  rmrt.lookup),
    ]


def timed_build(spec: IndexSpec, keys, repeats: int = 2):
    """Median warm build time (first build pays jit compile; excluded)."""
    times = []
    idx = None
    for r in range(repeats + 1):
        t0 = time.time()
        idx = spec.build(keys)
        _block(idx)
        if r:
            times.append(time.time() - t0)
    return idx, float(np.median(times))


def timed_lookup(spec: IndexSpec, idx, queries, repeats: int = 3):
    res = spec.lookup(idx, queries)
    jax.block_until_ready(res)
    times = []
    for _ in range(repeats):
        t0 = time.time()
        jax.block_until_ready(spec.lookup(idx, queries))
        times.append(time.time() - t0)
    ns_per_q = float(np.median(times)) / queries.shape[0] * 1e9
    return res, ns_per_q


def _block(idx):
    for leaf in jax.tree.leaves(idx.__dict__ if hasattr(idx, "__dict__")
                                else idx):
        if isinstance(leaf, jax.Array):
            leaf.block_until_ready()


def verify(keys, queries, result) -> bool:
    truth = jnp.searchsorted(jnp.asarray(keys), queries, side="left")
    return bool(jnp.all(jnp.asarray(result) == truth))
