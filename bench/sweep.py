"""Find an open-loop cell's knee on the chip, once, and fix its rate::

    python bench/sweep.py --workload <cell> --seed <n> --rates 500,1000,...
        [--seconds 4] [--write]

One set-up, then one window per offered rate (requests/s), lowest first.
For each rate it prints p50/p99 latency from the scheduled arrival, the
backlog trend (the growth of latency over the window, from a straight-line
fit of latency on arrival time), and how late the generator ran.  The knee
is the highest rate below the first whose backlog grows: latency grows over
the window by more than half the window's p50 and more than 5 ms, a request
fails, or a program compiles in the window (a batch outgrew the warmed
capacity classes).  With ``--write`` the mix file gets ``rate_rps`` = 0.8 x
the knee.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT / "src"), str(ROOT), *(
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench")]


def log(msg: str) -> None:
    print(msg, flush=True)


def growth_s(due, done, seconds) -> float:
    """Latency growth over the window: slope of latency on arrival time,
    times the window's length."""
    import numpy as np

    lat = done - due
    if lat.size < 3:
        return 0.0
    slope = np.polyfit(due - due.min(), lat, 1)[0]
    return float(slope * seconds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    import numpy as np

    from bench import drive, harness
    from bench.spec import load_module, resolve

    cell = resolve(args.workload)
    devices, _ = harness.prepare(cell.chips)
    gen = load_module("generators", cell.mix["generator"])
    spare = sum(gen.insert_keys(cell.mix, int(r * args.seconds) + 1)
                for r in rates) + 4096
    counters = harness.Counters()
    st = harness.set_up(cell, args.seed, args.seconds, devices, log,
                        spare_fresh=spare)
    log("setup: " + ", ".join(f"{k} {v:.3f}" for k, v in st.phases.items()))
    rng = np.random.default_rng([args.seed, 3])
    fe = st.fe
    rows = []
    for rate in rates:
        times = gen.arrivals(rate, args.seconds, rng)
        plan = gen.plan(cell.mix, st.keys, rng, times.size, st.fresh)
        c0 = counters.snap()
        sent, t0, t_end = drive.run_window(fe, drive.requests(plan), times,
                                           args.seconds)
        recs = harness._records(sent, t_end + 60, fe.clock)
        c1 = counters.snap()
        due = np.asarray([s.due for s in sent])
        ok = np.asarray([r.done is not None and not r.error for r in recs])
        done = np.asarray([r.done if r.done is not None else np.nan
                           for r in recs])
        lat = np.where(ok, done - due, t_end + 60 - due)
        late = np.asarray([s.late for s in sent])
        row = {"rate_rps": rate, "requests": len(sent),
               "failed": int((~ok).sum()),
               "p50_ms": 1e3 * float(np.percentile(lat, 50)),
               "p99_ms": 1e3 * float(np.percentile(lat, 99)),
               "growth_ms": 1e3 * growth_s(due[ok], done[ok], args.seconds),
               "late_p99_ms": 1e3 * float(np.percentile(late, 99)),
               "late_max_ms": 1e3 * float(late.max()),
               "compiles": c1["compiles"] - c0["compiles"],
               "classes": sorted(fe.stats.qcaps)}
        row["grows"] = bool((row["growth_ms"] > 5.0 and
                             row["growth_ms"] > 0.5 * row["p50_ms"]) or
                            row["failed"] or row["compiles"])
        rows.append(row)
        log("sweep " + json.dumps(row))
        if row["grows"]:
            break
        fe.stats.qcaps.clear()
    fe.stop()
    log("memory: peak bytes per chip " + ", ".join(
        str((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices))
    if not rows[-1]["grows"]:
        knee = rows[-1]["rate_rps"]         # no growth seen: at least this
    else:
        knee = rows[-2]["rate_rps"] if len(rows) > 1 else None
    log(f"knee: {knee} requests/s")
    if args.write and knee:
        path = ROOT / "bench" / "traffic" / f"{cell.traffic}.json"
        mix = json.loads(path.read_text())
        mix["rate_rps"] = round(0.8 * knee, 1)
        path.write_text(json.dumps(mix, indent=2) + "\n")
        log(f"wrote rate_rps {mix['rate_rps']} to {path.name}")
    print(json.dumps({"knee_rps": knee, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
