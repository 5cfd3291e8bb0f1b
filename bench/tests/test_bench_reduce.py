"""The reduction from a profiler trace to numbers, on a small recorded trace:
60 ms of a traced window of a YCSB E mix (zipfian scans and inserts over
10^7 keys) on one TPU v5 lite (op names cut short, stats dropped)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import reduce  # noqa: E402

TRACE = Path(__file__).parent / "data" / "trace_small.json"


@pytest.fixture(scope="module")
def tr():
    return reduce.load(TRACE)


def covered(events, window):
    """Brute force: split the window at every event boundary and keep the
    pieces some event covers."""
    lo, hi = window
    cuts = sorted({lo, hi} | {min(max(t, lo), hi) for e in events
                              for t in (e.start, e.end)})
    return [(a, b) for a, b in zip(cuts, cuts[1:], strict=False)
            if any(e.start <= a and b <= e.end for e in events)]


def test_busy_union_matches_brute_force(tr):
    assert tr.ops and tr.ops[0]
    for ops in tr.ops:
        want = sum(b - a for a, b in covered(ops, tr.window))
        assert reduce.busy_ns(ops, tr.window) == pytest.approx(want)
        assert 0 < want <= tr.window[1] - tr.window[0]


def test_gaps_are_the_complement(tr):
    ops = tr.ops[0]
    span = tr.window[1] - tr.window[0]
    g = reduce.gaps(ops, tr.window)
    assert sum(b - a for a, b in g) + reduce.busy_ns(ops, tr.window) == \
        pytest.approx(span)
    for a, b in g:
        assert not any(e.dur > 0 and e.start < b and a < e.end for e in ops)


def test_time_by_name_sums_clipped_durations(tr):
    ops = tr.ops[0]
    by = reduce.time_by_name(ops, tr.window)
    lo, hi = tr.window
    assert sum(by.values()) == pytest.approx(
        sum(max(min(e.end, hi) - max(e.start, lo), 0) for e in ops))
    assert sum(by.values()) >= reduce.busy_ns(ops, tr.window)


def test_breakdown_names_gaps_by_host_spans(tr):
    b = reduce.breakdown(tr, prefix="frontend.")
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    assert all(n.startswith("frontend.") or n == "host idle"
               for n, _ in b["idle_gaps"])


def test_ops_are_labelled_by_their_program(tr):
    mods = sorted(tr.modules[0], key=lambda m: m.start)
    starts = [m.start for m in mods]
    labels = {reduce.op_label(e, mods, starts) for e in tr.ops[0]}
    assert any(lb.startswith("jit_shard_fn/") for lb in labels)
    assert all(" = " not in lb and not lb.startswith("%") for lb in labels)
