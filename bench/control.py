"""The control of the check: the plain reference put in the program's place
and computed one precision below what the configuration states (keys in
f64, so f32, the step a kernel on f32 keys would take), which the check has
to find not correct::

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, in one process: the cell's set-up and a short window at its
own load, driven by the program; then the check twice, on the program's
answers and on the control's, which answers every read of the window (and
every read-back) as the reference would over keys and queries rounded to
f32.  Prints one JSON line per seed with both checks.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT / "src"), str(ROOT), *(
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench")]


def _f32(x):
    return np.asarray(x, np.float64).astype(np.float32).astype(np.float64)


def f32_answers(base: np.ndarray, recs: list) -> list:
    """Each read answered by the reference over the state its submission
    must see (base plus every insert acknowledged before it), with keys
    and queries rounded to f32."""
    ins = [r for r in recs if r.kind == "insert" and r.done is not None
           and not r.error]
    acks = np.asarray([r.done for r in ins])
    flat = [_f32(np.asarray(r.payload).ravel()) for r in ins]
    live0 = np.sort(_f32(base))
    out = list(recs)
    reads = [i for i, r in enumerate(recs) if r.kind in ("find", "range")
             and r.done is not None and not r.error]
    seen = np.searchsorted(np.sort(acks), [recs[i].submitted for i in reads],
                           side="right") if reads else []
    for k in np.unique(seen):
        extra = np.sort(np.concatenate(flat[:k])) if k else np.zeros(0)
        cnt = lambda v, side, extra=extra: (
            np.searchsorted(live0, v, side=side)
            + np.searchsorted(extra, v, side=side))
        for i in (reads[j] for j in np.flatnonzero(seen == k)):
            r = recs[i]
            q = _f32(r.payload)
            if r.kind == "find":
                lt, le = cnt(q, "left"), cnt(q, "right")
                ans = (le > lt, lt)
            else:
                lo, hi = cnt(q[0], "left"), cnt(q[1], "right")
                ans = (lo, np.maximum(hi, lo))
            out[i] = replace(r, answer=ans)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.spec import resolve

    cell = resolve(args.workload)
    devices, _ = harness.prepare(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False, devices,
                               time.monotonic(), lambda m: None,
                               control=f32_answers)
        print(json.dumps({"seed": seed, "attempted": out["attempted"],
                          "program": {"correct": out["correct"],
                                      "check": out["check"]},
                          "control": out["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
