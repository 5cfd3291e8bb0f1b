"""Run one benchmark cell once on the chips of this machine::

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` (its parts: ``bench/spec.py``).
The run draws its data and traffic from ``--seed``, sets up the served index,
measures ``--seconds`` of its traffic, checks every answer against the plain
reference, and prints one JSON object as the last line of standard output:
the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics from
a profiler trace of the window with ``--trace 1``.  The numbers compared for
``correct`` come last in that line, under ``check``, and as the last lines
of standard error.  Without a TPU, or with fewer chips than the cell asks
for, it exits 2 and prints no result; where a program compiles inside the
window, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# modules here are imported as ``bench.*``; the script's own directory
# would shadow top-level modules with their names
sys.path[:] = [str(ROOT / "src"), str(ROOT), *(
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench")]


def log(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.spec import resolve
    cell = resolve(args.workload)
    plat = os.environ.get("JAX_PLATFORMS")
    if plat and "cpu" not in plat.split(","):
        # the index build runs on the host's CPU backend
        os.environ["JAX_PLATFORMS"] = plat + ",cpu"

    from bench.harness import CompiledInWindow, NoChip, prepare, run_cell
    try:
        devices, cache = prepare(cell.chips)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    log(f"cell {cell.name}: {cell.chips} x {devices[0].device_kind}, seed "
        f"{args.seed}, {args.seconds} s, trace {args.trace}; compile cache "
        f"{cache}")
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       devices, T_START, log)
    except CompiledInWindow as e:
        print(e, file=sys.stderr)
        return 3
    for k, v in out["check"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
