"""Median time a request waits in the batcher's queue, in ms: the program's
``serve.queued`` spans (from ``submit`` to the cut that takes the request,
``repro.serve.frontend``) that end inside the traced window."""
import numpy as np

SPAN = "serve.queued"


def read(ctx):
    tr = ctx["trace"]
    lo, hi = tr.window
    d = [e.dur for _, e in tr.host if e.name == SPAN and lo <= e.end <= hi]
    return float(np.median(d)) * 1e-6 if d else None
