"""Idle share of the chip in the traced window: 1 minus the union of the
device's op intervals over the window's length, averaged over the chips
used (open-loop cells)."""
from bench import reduce


def read(ctx):
    tr = ctx["trace"]
    span = tr.window[1] - tr.window[0]
    busy = [reduce.busy_ns(ops, tr.window) for ops in tr.ops]
    if span <= 0 or not any(busy):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / span)
