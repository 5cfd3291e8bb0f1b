"""Save what a cell's trace shows, for reading by hand::

    python bench/look.py --workload <cell> --seed <n> --seconds 2 --out f.json

One traced run of the cell (``harness.run_cell``); writes the window's
trace reduced to plain events (``bench/reduce.py``, loadable with
``reduce.load``) to ``f.json`` and, to ``f.json.names.json``, every plane
and line with its longest-running event names and one example of each
name's stats.  Used to key the per-layer readers on names the trace really
shows (``bench/programs.py``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT / "src"), str(ROOT), *(
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench")]


def describe(xplane, out: str) -> None:
    """The planes, lines and the longest-running event names of a trace,
    with every stat of one example event each."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    planes = []
    for plane in pd.planes:
        lines = []
        for ln in plane.lines:
            tot, ex = {}, {}
            for e in ln.events:
                tot[e.name] = tot.get(e.name, 0.0) + e.duration_ns
                if e.name not in ex:
                    ex[e.name] = {str(s[0]): str(s[1])[:300]
                                  for s in e.stats}
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:40]
            lines.append({"line": ln.name, "events": len(tot),
                          "top": [[k, v, ex[k]] for k, v in top]})
        planes.append({"plane": plane.name, "lines": lines})
    Path(out).write_text(json.dumps(planes, indent=1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from bench import harness, reduce
    from bench.spec import resolve

    def save(xplane):
        describe(xplane, args.out + ".names.json")
        reduce.save(reduce.read_xplane(xplane), args.out)

    cell = resolve(args.workload)
    devices, _ = harness.prepare(cell.chips)
    out = harness.run_cell(cell, args.seed, args.seconds, True, devices,
                           time.monotonic(), print, on_trace=save)
    print(f"trace of {out['attempted']} requests saved to {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
