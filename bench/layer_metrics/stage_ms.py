"""Median host time to stage a batch, in ms: the program's ``serve.stage``
spans (from the cut batch to its stacked programs' calls returning:
refresh of the tenant stack, ``device_put``, the calls,
``repro.serve.frontend``) that start inside the traced window."""
import numpy as np

SPAN = "serve.stage"


def read(ctx):
    tr = ctx["trace"]
    lo, hi = tr.window
    d = [e.dur for _, e in tr.host if e.name == SPAN and lo <= e.start <= hi]
    return float(np.median(d)) * 1e-6 if d else None
