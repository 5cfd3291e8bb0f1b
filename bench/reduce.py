"""From a profiler trace to numbers: the busy union of each chip, device time
by name, and idle gaps named by what the host was doing.

:func:`read_xplane` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain lists (:class:`Trace`); everything after that works on those
lists, so the tests feed it a small recorded trace.  Times are in
nanoseconds on the trace's own clock.
"""
from __future__ import annotations

import bisect
import json
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.window"     # the host span around the measured window
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STAT_CHARS = 160                 # each device event stat kept, truncated


@dataclass
class Event:
    name: str
    start: float
    dur: float
    stats: dict = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    """``ops[c]`` / ``modules[c]``: chip c's device op and program events;
    ``host``: (thread, event) pairs of the host's spans."""
    ops: list
    modules: list
    host: list
    window: tuple = (0.0, 0.0)

    def to_json(self) -> dict:
        ev = lambda e: [e.name, e.start, e.dur, e.stats]
        return {"window": list(self.window),
                "ops": [[ev(e) for e in c] for c in self.ops],
                "modules": [[ev(e) for e in c] for c in self.modules],
                "host": [[t, ev(e)] for t, e in self.host]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        ev = lambda x: Event(x[0], x[1], x[2], x[3])
        return cls(ops=[[ev(x) for x in c] for c in d["ops"]],
                   modules=[[ev(x) for x in c] for c in d["modules"]],
                   host=[(t, ev(x)) for t, x in d["host"]],
                   window=tuple(d["window"]))


def find_xplane(log_dir: str | Path) -> Path:
    found = sorted(Path(log_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_xplane(path: str | Path, device_prefix: str = "/device:TPU:"
                ) -> Trace:
    """Device planes named ``<device_prefix><n>`` (in n order) and the host
    plane's thread lines.  The window is the host span ``WINDOW_SPAN``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devs, host = [], []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(device_prefix) and name[len(device_prefix):
                                                   ].isdigit():
            lines = {ln.name: ln for ln in plane.lines}
            pick = lambda ln: [Event(e.name, e.start_ns, e.duration_ns,
                                     _stats(e)) for e in ln.events] \
                if ln is not None else []
            devs.append((int(name[len(device_prefix):]),
                         pick(lines.get(OPS_LINE)),
                         pick(lines.get(MODULES_LINE))))
        elif name == "/host:CPU":
            for ln in plane.lines:
                host += [(ln.name, Event(e.name, e.start_ns, e.duration_ns))
                         for e in ln.events]
    devs.sort(key=lambda d: d[0])
    win = [e for _, e in host if e.name == WINDOW_SPAN]
    window = (win[0].start, win[0].end) if win else (0.0, 0.0)
    return Trace(ops=[d[1] for d in devs], modules=[d[2] for d in devs],
                 host=host, window=window)


def _stats(e) -> dict:
    return {str(k): v if isinstance(v, (int, float)) else str(v)[:STAT_CHARS]
            for k, v in e.stats}


def clip(events: list, window: tuple) -> list:
    """(start, end) of each event, cut to the window; empty ones dropped."""
    lo, hi = window
    out = [(max(e.start, lo), min(e.end, hi)) for e in events]
    return [(a, b) for a, b in out if b > a]


def union(intervals: list) -> list:
    """Merged, sorted intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def busy_ns(events: list, window: tuple) -> float:
    """Length of the union of the events' intervals inside the window."""
    return float(sum(b - a for a, b in union(clip(events, window))))


def time_by_name(events: list, window: tuple, key=lambda e: e.name) -> dict:
    """Device time inside the window per ``key(event)``."""
    out: dict = {}
    for e in events:
        a, b = max(e.start, window[0]), min(e.end, window[1])
        if b > a:
            k = key(e)
            out[k] = out.get(k, 0.0) + (b - a)
    return out


def gaps(events: list, window: tuple) -> list:
    """(start, end) of each stretch of the window with no event running."""
    out, t = [], window[0]
    for a, b in union(clip(events, window)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def name_gap(gap: tuple, host: list, prefix: str = "") -> str:
    """What the host was doing in a gap: the host span (of those whose name
    starts with ``prefix``) that overlaps it most; "host idle" if none."""
    best, name = 0.0, "host idle"
    for _, e in host:
        if prefix and not e.name.startswith(prefix):
            continue
        ov = min(e.end, gap[1]) - max(e.start, gap[0])
        if ov > best:
            best, name = ov, e.name
    return name


def op_label(e: Event, modules: list, starts: list) -> str:
    """``<program>/<instruction>`` of a device op: the TPU trace names an op
    by its whole HLO instruction text, ``%fusion.3 = f32[...] fusion(...)``,
    and the program is the module event that holds the op in time."""
    instr = e.name.split(" = ", 1)[0].lstrip("%")
    i = bisect.bisect_right(starts, e.start) - 1
    if i >= 0 and modules[i].end >= e.start:
        return f"{modules[i].name.split('(', 1)[0]}/{instr}"
    return instr


def breakdown(trace: Trace, top: int = 10, prefix: str = "") -> dict:
    """The ``breakdown`` of a result line: device ops by time (summed over
    chips) and the longest idle gaps of chip 0, named by host activity."""
    tot: dict = {}
    for ops, mods in zip(trace.ops, trace.modules, strict=True):
        mods = sorted(mods, key=lambda m: m.start)
        starts = [m.start for m in mods]
        label = lambda e, mods=mods, starts=starts: op_label(e, mods, starts)
        for k, v in time_by_name(ops, trace.window, key=label).items():
            tot[k] = tot.get(k, 0.0) + v
    dev = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    g = sorted(gaps(trace.ops[0], trace.window),
               key=lambda ab: ab[0] - ab[1])[:top] if trace.ops else []
    return {"device_ops": [[k, v * 1e-9] for k, v in dev],
            "idle_gaps": [[name_gap(ab, trace.host, prefix),
                           (ab[1] - ab[0]) * 1e-9] for ab in g]}


def save(trace: Trace, path: str | Path) -> None:
    Path(path).write_text(json.dumps(trace.to_json()))


def load(path: str | Path) -> Trace:
    return Trace.from_json(json.loads(Path(path).read_text()))
