"""The load: open-loop arrivals on a schedule, through
``BatchingFrontend.submit(Request(...))``.

Each request is timed from its scheduled time, so a stall is charged to
every request behind it.  The front-end stamps its own ``arrival`` at
submission, and its batcher's deadline runs from that stamp.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass

from repro.serve.frontend import Request


@dataclass
class Sent:
    req: Request
    due: float              # scheduled time (front-end clock)
    late: float = 0.0       # submission minus due, seconds


def open_loop(fe, reqs: list, times, t0: float) -> list:
    """Submit ``reqs[i]`` at ``t0 + times[i]`` from one thread; returns the
    :class:`Sent` records once the last one is submitted."""
    sent = []
    clock = fe.clock
    for r, t in zip(reqs, times, strict=True):
        due = t0 + float(t)
        wait = due - clock()
        if wait > 0:
            time.sleep(wait)
        now = clock()
        fe.submit(r)
        sent.append(Sent(r, due, now - due))
    return sent


def requests(plan: list) -> list:
    """The ``Request`` of each ``(kind, payload)`` of a plan, made before
    the window, so that making them delays no arrival."""
    return [Request(0, kind, payload) for kind, payload in plan]


def run_window(fe, reqs: list, times, seconds: float) -> tuple:
    """Drive the window with the :func:`requests` ``reqs``; returns
    ``(sent, t0, t_end)``.  The schedule runs on a thread of its own; the
    caller's thread waits.  It starts a moment ahead, so that its first
    arrivals are not late."""
    t0 = fe.clock() + 0.01
    t_end = t0 + seconds
    box: dict = {}

    def body():
        try:
            box["sent"] = open_loop(fe, reqs, times, t0)
        except BaseException as e:      # re-raised on the caller's thread
            box["error"] = e

    th = threading.Thread(target=body, name="bench-load", daemon=True)
    th.start()
    th.join()
    if "error" in box:
        raise box["error"]
    wait = t_end - fe.clock()
    if wait > 0:
        time.sleep(wait)
    return box["sent"], t0, t_end
