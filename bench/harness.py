"""One run of one cell: set-up, the measured window, the check, the numbers.

Set-up draws the key set and the traffic from the seed, builds the index
through ``repro.api.Index.build(keys, mesh=...)``, fills the delta tier if
the configuration asks, starts ``repro.serve.frontend.BatchingFrontend`` and
warms, through ``submit``, the capacity classes the mix lists.  The window
then drives the mix for ``seconds``; afterwards every answer is checked
against the plain reference (``bench/check.py``), and each acknowledged
insert is read back.
"""
from __future__ import annotations

import shutil
import tempfile
import time
from dataclasses import dataclass, field, replace

import numpy as np

from bench import check, drive, reduce, work
from bench.peaks import peaks
from bench.spec import Cell, load_module

WAIT_AFTER_S = 60.0          # how long answers may come after the window
READBACK_KEYS = 64           # keys per read-back request


class NoChip(RuntimeError):
    pass


class CompiledInWindow(RuntimeError):
    """The window's batches reached a capacity class that the mix does not
    warm: its programs compiled, or were loaded from the compile cache,
    inside the measured window, and the run's numbers measure that."""


def chips_or_fail(n: int) -> list:
    """The first ``n`` TPU devices; :class:`NoChip` when JAX finds no TPU or
    fewer chips.  Never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"{n} chips asked, {len(devs)} found")
    return devs[:n]


def prepare(n: int) -> tuple:
    """``(devices, cache_dir)``: the first ``n`` chips (:func:`chips_or_fail`)
    and JAX's persistent compilation cache turned on, keeping every program
    however quick to compile, so that only a cell's first run compiles."""
    import jax

    from repro.compile_cache import use_compile_cache

    devices = chips_or_fail(n)
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devices, cache


class Counters:
    """JAX's compile events, counted from the moment this is made, with the
    names of the functions traced and compiled."""

    def __init__(self):
        import jax

        self.n = {"traces": 0, "compiles": 0, "cache_hits": 0,
                  "cache_misses": 0}
        self.funs: list = []        # (kind, fun_name) of traces, compiles
        names = {"/jax/core/compile/jaxpr_trace_duration": "traces",
                 "/jax/core/compile/backend_compile_duration": "compiles",
                 "/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}

        def on(event, *a, **k):
            if event in names:
                self.n[names[event]] += 1
                if "fun_name" in k:
                    self.funs.append((names[event], str(k["fun_name"])))

        jax.monitoring.register_event_listener(on)
        jax.monitoring.register_event_duration_secs_listener(on)

    def snap(self) -> dict:
        return dict(self.n, funs=len(self.funs))

    def watch_gc(self) -> None:
        """Record the Python collector's pauses from now on (``gc_s``)."""
        import gc

        self.gc_s, t = [], {}

        def cb(phase, info):
            if phase == "start":
                t[0] = time.perf_counter()
            elif 0 in t:
                self.gc_s.append((info["generation"],
                                  time.perf_counter() - t.pop(0)))

        gc.callbacks.append(cb)
        self._gc_cb = cb

    def unwatch_gc(self) -> None:
        import gc

        gc.callbacks.remove(self._gc_cb)

    def names(self, since: dict) -> str:
        """``kind name x count`` of each function traced or compiled since
        the snapshot ``since``, most frequent first."""
        from collections import Counter
        c = Counter(self.funs[since["funs"]:])
        return ", ".join(f"{k} {n} x{v}" for (k, n), v in c.most_common(12))


@dataclass
class Setup:
    keys: np.ndarray            # the drawn key set (sorted)
    base: np.ndarray            # the live set when the window opens
    index: object
    fe: object
    reqs: list                  # the window's requests, made at set-up
    times: np.ndarray           # scheduled arrivals, seconds from the start
    fresh: object               # fresh(m): m unused keys absent from base
    phases: dict = field(default_factory=dict)


def _serve(fe, kind: str, payload) -> tuple:
    from repro.serve.frontend import Request

    r = fe.submit(Request(0, kind, payload))
    return r, r.result(timeout=1200)


def set_up(cell: Cell, seed: int, seconds: float, devices, log,
           spare_fresh: int = 0) -> Setup:
    import jax

    from repro.api import Index
    from repro.serve.frontend import BatchingFrontend

    cfg, mix = cell.config, cell.mix
    ds = load_module("datasets", cfg["dataset"]["generator"])
    gen = load_module("generators", mix["generator"])
    phases = {}
    t = time.perf_counter()
    keys = ds.draw(cfg["dataset"], seed)
    rng = np.random.default_rng([seed % (1 << 64), 1])
    times = gen.arrivals(float(mix["rate_rps"]), seconds, rng)
    n = times.size
    fill = int(cfg.get("delta_fill", 0))
    warm = mix.get("warm", {})
    ins_op = [o for o in mix["ops"] if o["kind"] == "insert"]
    warm_ins = int(warm.get("insert", 0)) * (int(ins_op[0]["keys"])
                                             if ins_op else 0)
    m = fill + warm_ins + gen.insert_keys(mix, n) + spare_fresh
    fresh_all = ds.absent(keys, cfg["dataset"], rng, m)
    pos = fill + warm_ins

    def fresh(k):
        nonlocal pos
        if pos + k > fresh_all.size:
            raise RuntimeError("fresh key pool exhausted")
        pos += k
        return fresh_all[pos - k:pos]

    reqs = drive.requests(gen.plan(mix, keys, rng, n, fresh))
    phases["draw_s"] = time.perf_counter() - t

    t = time.perf_counter()
    mesh = jax.make_mesh((len(devices),), ("data",), devices=devices)
    index = Index.build(keys, mesh=mesh,
                        n_leaves=int(cfg["index"]["n_leaves"]))
    jax.block_until_ready(index.backend.shards[-1].index.keys)
    phases["build_s"] = time.perf_counter() - t

    t = time.perf_counter()
    if fill:
        index.insert(fresh_all[:fill])
        jax.block_until_ready(index.backend.shards[-1].delta_keys)
    phases["fill_s"] = time.perf_counter() - t

    t = time.perf_counter()
    fe = BatchingFrontend([index.backend]).start()
    if devices[0].platform == "tpu":
        from repro.kernels import ops
        want = cfg["path"] == "kernel"
        if fe.pack.use_kernel != want or (want and (
                fe.pack.interpret or ops._default_interpret())):
            raise RuntimeError(f"the served path is not the configuration's "
                               f"{cfg['path']!r} path")
    wrng = np.random.default_rng([seed % (1 << 64), 2])
    sample = lambda c: keys[wrng.integers(0, keys.size, c)]
    reads = [k for k in ("find", "range") if warm.get(k)]
    for _ in range(2):              # the second pass runs what the first
        for c in warm.get("find", []):      # compiled, as the window will
            _serve(fe, "find", sample(int(c)))
        for c in warm.get("range", []):
            _serve(fe, "range", np.stack([sample(int(c))] * 2))
    for i in range(int(warm.get("insert", 0))):
        k = warm_ins // int(warm["insert"])
        _serve(fe, "insert", fresh_all[fill + i * k:fill + (i + 1) * k])
        for kind in reads:
            c = int(warm[kind][0])
            _serve(fe, kind, sample(c) if kind == "find"
                   else np.stack([sample(c)] * 2))
    phases["warmup_s"] = time.perf_counter() - t
    fe.stats.qcaps.clear()          # from here on: the classes traffic uses
    base = np.sort(np.concatenate([keys, fresh_all[:fill + warm_ins]])) \
        if fill + warm_ins else keys
    return Setup(keys=keys, base=base, index=index, fe=fe, reqs=reqs,
                 times=times, fresh=fresh, phases=phases)


def _annotate(fe) -> None:
    """Host spans around the front-end's steps, so that the trace names what
    the dispatcher thread was doing in each idle gap."""
    import jax

    for name in ("_collect", "_apply_updates", "_dispatch", "_resolve",
                 "_maintain"):
        fn = getattr(fe, name)

        def wrapped(*a, _fn=fn, _n=f"frontend.{name.lstrip('_')}", **k):
            with jax.profiler.TraceAnnotation(_n):
                return _fn(*a, **k)

        setattr(fe, name, wrapped)


def _records(sent: list, deadline: float, clock) -> list:
    """Wait (until ``deadline``) for every request; their check records."""
    recs = []
    for s in sent:
        r = s.req
        err = False
        try:
            r.result(timeout=max(deadline - clock(), 0.0))
        except TimeoutError:
            pass
        except Exception:       # broad: a failed request is counted
            err = True
        ans = None
        if r.done() and not err:
            ans = (r.found, r.rank) if r.kind == "find" else \
                (r.rank_lo, r.rank_hi) if r.kind == "range" else None
        recs.append(check.Rec(r.kind, r.keys, r.arrival,
                              r.done_at if r.done() else None, ans, err))
    return recs


def _limits(base, recs: list, back: list) -> dict:
    """The numbers compared, each with its limit: reads of the window that
    match no admissible state, requests never answered, and read-backs of
    acknowledged inserts that do not find them."""
    v = check.verify(base, recs)
    vb = check.verify(base, recs + back)
    out = {"wrong_answers": (v["wrong"], 0),
           "unanswered": (vb["unanswered"], 0)}
    if back:
        out["lost_inserts"] = (vb["wrong"] - v["wrong"], 0)
    return out


def _pct(x, q) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


E2E = {
    "p50_ms": lambda w: 1e3 * _pct(w["lat"], 50),
    "setup_s": lambda w: w["setup_s"],
}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, devices,
             t_start: float, log, fault=None, control=None,
             on_trace=None) -> dict:
    """Everything after the chip check: returns the result line's object.
    ``fault(setup)``, for the tests, breaks the timed path under the
    harness before the window opens; ``control(base, recs)``, for
    ``bench/control.py``, answers the same requests in the program's place,
    and the line gets that answer's check too, under ``control``;
    ``on_trace(xplane)``, for ``bench/look.py``, sees the traced window's
    profile before it is deleted.  Raises :class:`CompiledInWindow` when the
    window reached a capacity class the mix does not warm; other traces and
    compiles inside the window, which the program makes on its own, are
    counted and named on the window's line."""
    import jax

    counters = Counters()
    st = set_up(cell, seed, seconds, devices, log)
    fe, index = st.fe, st.index
    cs0 = counters.snap()
    log("setup: " + ", ".join(f"{k} {v:.3f}" for k, v in st.phases.items())
        + f"; compile cache hits {cs0['cache_hits']}, misses "
        f"{cs0['cache_misses']}, backend compiles {cs0['compiles']}")
    dcap = max(d.delta_keys.shape[0] for d in index.backend.shards)
    dlive = max(d.delta_live for d in index.backend.shards)
    n_ins = sum(r.keys.size for r in st.reqs if r.kind == "insert")
    if dlive + n_ins > dcap:
        log(f"warning: {n_ins} window inserts cross the delta tier's "
            f"capacity class {dcap} (live {dlive}): raise the fill")
    if fault is not None:
        fault(st)
    from repro.core import distributed as dist_mod

    shards = work.shard_leaves(index.backend) if traced else None
    stats0 = replace(fe.stats, qcaps=set(fe.stats.qcaps))
    rebuilds0 = sum(d.rebuilds for d in index.backend.shards)
    tc0 = dict(dist_mod.TRACE_COUNTS)
    c0 = counters.snap()
    counters.watch_gc()
    tdir = None
    if traced:
        _annotate(fe)
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
    setup_s = time.monotonic() - t_start
    with jax.profiler.TraceAnnotation(reduce.WINDOW_SPAN):
        sent, t0, t_end = drive.run_window(fe, st.reqs, st.times, seconds)
    stats1 = replace(fe.stats, qcaps=set(fe.stats.qcaps))
    tc1 = dict(dist_mod.TRACE_COUNTS)
    if traced:
        jax.profiler.stop_trace()
    recs = _records(sent, t_end + WAIT_AFTER_S, fe.clock)
    c1 = counters.snap()            # every request of the window served
    counters.unwatch_gc()
    gcs = counters.gc_s
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices]
    rebuilds = sum(d.rebuilds for d in index.backend.shards) - rebuilds0

    # read back every acknowledged insert, after the window
    acked = [r.payload for r in recs if r.kind == "insert"
             and r.done is not None and not r.error]
    back = []
    if acked:
        kind = "range" if cell.mix.get("warm", {}).get("range") else "find"
        ks = np.concatenate(acked)
        for i in range(0, ks.size, READBACK_KEYS):
            c = ks[i:i + READBACK_KEYS]
            back.append(drive.Sent(_serve(fe, kind, c if kind == "find"
                                          else np.stack([c, c]))[0], 0.0))
    back = _records(back, fe.clock() + WAIT_AFTER_S, fe.clock)
    fe.stop()
    late = np.asarray([s.late for s in sent]) if sent else np.zeros(1)
    log(f"window: {len(sent)} requests, generator late p50 "
        f"{1e3 * _pct(late, 50):.3f} ms p99 {1e3 * _pct(late, 99):.3f} ms "
        f"max {1e3 * late.max():.3f} ms; in the window: jaxpr traces "
        f"{c1['traces'] - c0['traces']}, backend compiles "
        f"{c1['compiles'] - c0['compiles']}, stacked-program traces "
        f"{sum(tc1.values()) - sum(tc0.values())}; leaf rebuilds "
        f"{rebuilds}; batches {stats1.batches - stats0.batches}; capacity "
        f"classes {sorted(stats1.qcaps)}"
        + f"; Python collections {len(gcs)}, of generation 2 "
        f"{sum(g == 2 for g, _ in gcs)}, longest "
        f"{1e3 * max((d for _, d in gcs), default=0.0):.1f} ms"
        + (f"; traced or compiled: {counters.names(c0)}"
           if c1["funs"] > c0["funs"] else ""))
    log("memory: peak bytes per chip " + ", ".join(map(str, peak)))
    cold = sorted(stats1.qcaps - {int(c) for k in ("find", "range")
                                  for c in cell.mix.get("warm", {}).get(k, [])})
    if cold:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
        raise CompiledInWindow(
            f"the window's batches reached the capacity classes {cold}, "
            f"which the mix does not warm ({c1['compiles'] - c0['compiles']} "
            f"backend compiles in the window)")

    # the check: the window's reads, then the read-back of its inserts
    t = time.perf_counter()
    limits = _limits(st.base, recs, back)
    log(f"check: {len(recs)} requests, {len(back)} read-back requests "
        f"({time.perf_counter() - t:.2f} s)")
    correct = all(val <= lim for val, lim in limits.values())

    failed_mask = np.asarray([r.done is None or r.error for r in recs])
    due = np.asarray([s.due for s in sent])
    done = np.asarray([r.done if r.done is not None else np.nan
                       for r in recs])
    lat = np.where(failed_mask, t_end + WAIT_AFTER_S - due, done - due)
    w = {"lat": lat, "setup_s": setup_s}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(max(peak))}
    out = {"correct": bool(correct), "attempted": len(recs),
           "failed": int(failed_mask.sum())}
    if not traced:
        out["metrics"] = {m["name"]: {"value": float(E2E[m["name"]](w)),
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    else:
        xplane = reduce.find_xplane(tdir)
        if on_trace is not None:
            on_trace(xplane)
        tr = reduce.read_xplane(xplane)
        shutil.rmtree(tdir, ignore_errors=True)
        if len(tr.ops) != len(devices):
            raise RuntimeError(f"the trace holds {len(tr.ops)} device "
                               f"planes for {len(devices)} chips")
        in_tr = [r for r in recs if r.done is not None and not r.error
                 and t0 <= r.done <= t_end and r.kind in ("find", "range")]
        q = np.concatenate([r.payload.ravel() for r in in_tr]) \
            if in_tr else np.zeros(0)
        ctx = {"trace": tr, "window_s": (tr.window[1] - tr.window[0]) * 1e-9,
               "chips": len(devices), "peaks": peaks(d0.device_kind),
               "stats": {k: getattr(stats1, k) - getattr(stats0, k)
                         for k in ("batches", "queries", "ranges", "updates",
                                   "padded_slots")},
               "needed_bytes": work.needed_bytes(
                   q, *shards, key_bytes=4 if fe.pack.use_kernel else 8)}
        metrics = {}
        for m in cell.per_layer:
            val = load_module("layer_metrics", m["name"]).read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
        out["metrics"] = metrics
        busy = [reduce.busy_ns(ops, tr.window) for ops in tr.ops]
        device["busy_s"] = float(np.mean(busy)) * 1e-9
        device["window_s"] = ctx["window_s"]
        out["breakdown"] = reduce.breakdown(tr, prefix="frontend.")
    out["device"] = device
    if control is not None:
        out["control"] = {k: {"value": int(val), "limit": lim}
                          for k, (val, lim) in _limits(
                              st.base, control(st.base, recs),
                              control(st.base, back)).items()}
    out["check"] = {k: {"value": int(val), "limit": lim}
                    for k, (val, lim) in limits.items()}
    return out
