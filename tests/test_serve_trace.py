"""The serving loop's host spans (``serve.*`` in repro.serve.frontend).

A ``BatchingFrontend`` serves finds, a range and an insert on the CPU under
``jax.profiler``; the trace is read back with ``bench.reduce.read_xplane``,
the reader the benchmark uses, and the spans are checked against the loop's
structure: one ``serve.queued`` per request, one ``serve.stage`` and one
``serve.inflight`` per dispatched batch, the staging steps inside their
stage, the host sync inside its resolve, and each request's queue wait over
before its batch is staged.  Without a trace the spans record nothing.
"""
import gc
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import reduce  # noqa: E402
from repro.core import distributed as dist_mod  # noqa: E402
from repro.serve import frontend  # noqa: E402
from repro.serve.frontend import (  # noqa: E402
    BatchingFrontend, Request, ServeConfig)

# One batch per group: every request of a group is submitted well inside
# the latency budget of the group's first.
BUDGET_S = 0.5


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _frontend(budget_s: float = BUDGET_S, max_batch: int = 4096):
    rng = np.random.default_rng(31)
    pool = np.arange(1.0, 40_000.0)
    base = np.sort(rng.choice(pool, 3000, replace=False))
    mesh = jax.make_mesh((1,), ("data",))
    tenant = dist_mod.ShardedDynamicIndex.build(jnp.asarray(base), mesh,
                                                n_leaves=32, eps=0.7)
    fe = BatchingFrontend([tenant], path="jnp",
                          config=ServeConfig(latency_budget_s=budget_s,
                                             max_batch=max_batch))
    return fe, base, np.setdiff1d(pool, base), rng


def _serve(fe, group: list) -> list:
    reqs = [fe.submit(r) for r in group]
    return [r.result(timeout=120.0) for r in reqs]


def _spans(tr, name: str) -> list:
    return sorted((e for _, e in tr.host if e.name == name),
                  key=lambda e: e.start)


def _inside(inner, outer) -> bool:
    return outer.start <= inner.start and inner.end <= outer.end


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    fe, base, fresh, rng = _frontend()
    fe.start()
    fe.warmup((1, 64))
    ins = fresh[:5]
    groups = [
        [Request(0, "find", rng.choice(base, n)) for n in (16, 8, 1)],
        [Request(0, "range", np.stack([np.sort(rng.choice(base, 4))] * 2)),
         Request(0, "find", rng.choice(base, 8))],
        [Request(0, "insert", ins),
         Request(0, "find", np.concatenate([ins, rng.choice(base, 3)]))],
    ]
    tdir = tmp_path_factory.mktemp("serve-trace")
    batches0 = fe.stats.batches
    jax.profiler.start_trace(str(tdir), profiler_options=_options())
    try:
        answers = [_serve(fe, g) for g in groups]
        gc.collect()
        fe.stop()           # the last batch's spans close before the trace
    finally:
        jax.profiler.stop_trace()
        fe.stop()
    tr = reduce.read_xplane(reduce.find_xplane(tdir))
    live = np.sort(np.concatenate([base, ins]))
    return dict(tr=tr, groups=groups, answers=answers, base=base, live=live,
                batches=fe.stats.batches - batches0)


def test_traced_serving_answers_exactly(served):
    # the last group's insert applies before its find dispatches
    keys = [served["base"], served["base"], served["live"]]
    for g, answers, live in zip(served["groups"], served["answers"], keys,
                                strict=True):
        for req, ans in zip(g, answers, strict=True):
            if req.kind == "insert":
                assert ans is None
                continue
            q = req.keys if req.kind == "find" else req.keys[0]
            np.testing.assert_array_equal(
                ans[1 if req.kind == "find" else 0],
                np.searchsorted(live, q, side="left"))
    assert served["batches"] == len(served["groups"])


def test_one_queued_span_per_request(served):
    n = sum(len(g) for g in served["groups"])
    assert len(_spans(served["tr"], "serve.queued")) == n


def test_one_stage_and_inflight_span_per_batch(served):
    tr = served["tr"]
    n = served["batches"]
    assert len(_spans(tr, "serve.stage")) == n
    assert len(_spans(tr, "serve.inflight")) == n
    assert len(_spans(tr, "serve.resolve")) == n
    assert len(_spans(tr, "serve.apply_updates")) == n
    # a batch's time in flight starts where its staging ends, before the
    # next batch is staged
    stages = _spans(tr, "serve.stage")
    nexts = [s.start for s in stages[1:]] + [float("inf")]
    for st, inf, nxt in zip(stages, _spans(tr, "serve.inflight"), nexts,
                            strict=True):
        assert st.end <= inf.start < nxt


def test_staging_steps_lie_inside_their_stage(served):
    tr = served["tr"]
    stages = _spans(tr, "serve.stage")
    kids = {k: _spans(tr, k) for k in
            ("serve.refresh", "serve.put", "serve.enqueue")}
    # finds alone, a range with finds (two programs), an insert with finds
    assert len(kids["serve.put"]) == len(kids["serve.enqueue"]) == 4
    assert len(kids["serve.refresh"]) == 4
    for name, spans in kids.items():
        for e in spans:
            assert sum(_inside(e, s) for s in stages) == 1, name


def test_device_wait_lies_inside_its_resolve(served):
    tr = served["tr"]
    resolves = _spans(tr, "serve.resolve")
    waits = _spans(tr, "serve.device_wait")
    assert len(waits) == 4              # the range batch syncs twice
    for w in waits:
        assert sum(_inside(w, r) for r in resolves) == 1
    for inf, r in zip(_spans(tr, "serve.inflight"), resolves, strict=True):
        assert r.start < inf.end <= r.end


def test_queue_wait_ends_before_its_batch_is_staged(served):
    tr = served["tr"]
    queued = _spans(tr, "serve.queued")          # by start: group order
    stages = _spans(tr, "serve.stage")
    i, prev_end = 0, 0.0
    for g, stage in zip(served["groups"], stages, strict=True):
        mine = queued[i:i + len(g)]
        i += len(g)
        # each wait ends at the cut of its own batch, after the batch
        # ahead of it was staged
        assert all(prev_end <= q.start <= q.end <= stage.start
                   for q in mine)
        prev_end = stage.end


def test_collector_pauses_are_spans(served):
    assert _spans(served["tr"], "serve.gc")


def test_maintenance_runs_between_batches(served):
    tr = served["tr"]
    maint = _spans(tr, "serve.maintain")
    assert maint                # the queue drains after each group
    busy = _spans(tr, "serve.stage") + _spans(tr, "serve.resolve")
    assert not any(m.start < b.end and b.start < m.end
                   for m in maint for b in busy)


def test_untraced_serving_records_nothing(tmp_path):
    """Serving with no trace running works, and a span opened then is not
    recorded even where it closes under a later trace."""
    # the size cap, not the clock, cuts a batch: the budget never runs out
    fe, base, _, rng = _frontend(budget_s=600.0, max_batch=12)
    with fe:
        fe.warmup((1,))
        q = rng.choice(base, 12)
        found, rank = fe.lookup(0, q, timeout=120.0)
        np.testing.assert_array_equal(
            rank, np.searchsorted(base, q, side="left"))
        assert found.all()
        pending = fe.submit_find(0, q[:6])      # its wait opens untraced
        gc.disable()                        # no collector pause in the trace
        try:
            jax.profiler.start_trace(str(tmp_path),
                                     profiler_options=_options())
            try:
                # fills the batch to the cap: the cut falls in the trace
                fe.submit_find(0, q[6:]).result(timeout=120.0)
                pending.result(timeout=120.0)
                fe.stop()
            finally:
                jax.profiler.stop_trace()
        finally:
            gc.enable()
    tr = reduce.read_xplane(reduce.find_xplane(tmp_path))
    names = [e.name for _, e in tr.host if e.name.startswith("serve.")]
    # the batch is staged under the trace; of its two waits only the one
    # opened under the trace is recorded, and the loop's idle collect
    # began before it
    assert names.count("serve.stage") == 1
    assert names.count("serve.queued") == 1
    assert names.count("serve.collect") <= 1


def test_the_collector_hook_lives_while_a_frontend_runs():
    fe, _, _, _ = _frontend()
    other = BatchingFrontend(fe.pack.tenants, path="jnp")
    hooks = lambda: sum(cb is frontend._gc_span for cb in gc.callbacks)
    users = frontend._gc_users      # frontends other tests left running
    assert hooks() == min(users, 1)
    with fe:
        with other:
            assert (hooks(), frontend._gc_users) == (1, users + 2)
        assert (hooks(), frontend._gc_users) == (1, users + 1)
        fe.stop()                   # a second stop changes nothing
    assert (hooks(), frontend._gc_users) == (min(users, 1), users)


def test_a_span_closed_on_another_thread_keeps_its_start(tmp_path):
    """The cross-thread spans (``serve.queued``, ``serve.inflight``) rest on
    this: an annotation entered on one thread and exited on another is
    recorded on the exiting thread's line with the entering call's start."""
    jax.profiler.start_trace(str(tmp_path), profiler_options=_options())
    try:
        span = jax.profiler.TraceAnnotation("serve.test_cross")
        span.__enter__()
        with jax.profiler.TraceAnnotation("serve.test_opener"):
            pass

        def close():
            with jax.profiler.TraceAnnotation("serve.test_closer"):
                time.sleep(0.005)
            span.__exit__(None, None, None)

        th = threading.Thread(target=close)
        th.start()
        th.join()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(reduce.find_xplane(tmp_path)))
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    lines = [{e.name: e for e in ln.events} for ln in host.lines]
    closer = next(ln for ln in lines if "serve.test_closer" in ln)
    opener = next(ln for ln in lines if "serve.test_opener" in ln)
    assert closer is not opener
    assert "serve.test_cross" in closer and "serve.test_cross" not in opener
    cross = closer["serve.test_cross"]
    assert cross.start_ns <= opener["serve.test_opener"].start_ns
    assert cross.start_ns + cross.duration_ns >= \
        closer["serve.test_closer"].start_ns + \
        closer["serve.test_closer"].duration_ns
