"""Padding share of the batcher: pad lanes over all query slots the stacked
dispatches carried in the window (``FrontendStats.padded_slots`` against
live find keys and both endpoints of each live range)."""


def read(ctx):
    s = ctx["stats"]
    slots = s["queries"] + 2 * s["ranges"] + s["padded_slots"]
    return 100.0 * s["padded_slots"] / slots if slots else None
