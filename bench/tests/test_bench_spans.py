"""The readers of the serving loop's spans (``queue_wait_ms``, ``stage_ms``,
``inflight_ms``): which spans each counts against the window, their median,
and nothing where a trace holds none of them."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import reduce, spec  # noqa: E402

TRACE = Path(__file__).parent / "data" / "trace_small.json"
MS = 1e6                            # ns per ms
WINDOW = (100 * MS, 200 * MS)

# (reader, its span, which end of a span must lie inside the window)
READERS = [("queue_wait_ms", "serve.queued", "end"),
           ("stage_ms", "serve.stage", "start"),
           ("inflight_ms", "serve.inflight", "start")]


def read(name: str, host: list, window=WINDOW):
    tr = reduce.Trace(ops=[], modules=[], host=host, window=window)
    return spec.load_module("layer_metrics", name).read({"trace": tr})


def span(name: str, start_ms: float, dur_ms: float, thread="python"):
    return (thread, reduce.Event(name, start_ms * MS, dur_ms * MS))


@pytest.mark.parametrize("name,span_name,edge", READERS)
def test_median_of_the_spans_in_the_window(name, span_name, edge):
    host = [span(span_name, 110, 4), span(span_name, 130, 10),
            span(span_name, 150, 2),
            span("serve.other", 120, 50), span("frontend.dispatch", 120, 70)]
    assert read(name, host) == pytest.approx(4.0)
    host.append(span(span_name, 160, 30))
    assert read(name, host) == pytest.approx(7.0)     # even count: mean


@pytest.mark.parametrize("name,span_name,edge", READERS)
def test_window_clipping(name, span_name, edge):
    # starts before the window and ends inside it
    early = span(span_name, 90, 20)
    # starts inside the window and ends after it
    late = span(span_name, 190, 40)
    inside = span(span_name, 150, 1)
    counted = early if edge == "end" else late
    host = [early, late, inside]
    assert read(name, host) == pytest.approx(
        (counted[1].dur * 1e-6 + 1.0) / 2)
    # wholly outside the window on either side
    assert read(name, [span(span_name, 10, 5), span(span_name, 300, 5)]) \
        is None


@pytest.mark.parametrize("name,span_name,edge", READERS)
def test_none_without_spans(name, span_name, edge):
    assert read(name, []) is None
    assert read(name, [span(span_name, 120, 5)], window=(0.0, 0.0)) is None
    tr = reduce.load(TRACE)
    assert not any(e.name.startswith("serve.") for _, e in tr.host)
    assert spec.load_module("layer_metrics", name).read({"trace": tr}) is None
