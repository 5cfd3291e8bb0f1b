"""Share of the lookup dispatch's roofline: the least time the chip needs
for the work the lookups need (``bench/work.py``: bytes over the peak HBM
bandwidth) over the device time of the stacked lookup programs in the
trace, summed over the chips used.  The whole dispatch is counted, routing,
search and rank algebra, so a change that moves work between them leaves
the share honest."""
from bench import programs, reduce


def read(ctx):
    tr = ctx["trace"]
    ns = sum(reduce.busy_ns([e for e in mods
                             if programs.is_lookup_program(e)], tr.window)
             for mods in tr.modules)
    need = ctx["needed_bytes"]
    if ns <= 0 or not need:
        return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (ns * 1e-9)
