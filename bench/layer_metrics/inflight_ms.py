"""Median time a batch is in flight, in ms: the program's ``serve.inflight``
spans (from its stacked programs' calls returning to the last caller woken,
``repro.serve.frontend``) that start inside the traced window."""
import numpy as np

SPAN = "serve.inflight"


def read(ctx):
    tr = ctx["trace"]
    lo, hi = tr.window
    d = [e.dur for _, e in tr.host if e.name == SPAN and lo <= e.start <= hi]
    return float(np.median(d)) * 1e-6 if d else None
