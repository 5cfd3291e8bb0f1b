"""Device time of the stacked lookup programs per dispatched batch, in ms,
averaged over the chips used (a batch runs on all of them at once)."""
from bench import programs, reduce


def read(ctx):
    n = ctx["stats"]["batches"]
    tr = ctx["trace"]
    if not n or not tr.modules:
        return None
    ns = [reduce.busy_ns([e for e in mods if programs.is_lookup_program(e)],
                         tr.window) for mods in tr.modules]
    t = sum(ns) / len(ns)
    return t * 1e-6 / n if t > 0 else None
