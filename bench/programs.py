"""How the program's work is named in a device trace.

The stacked find and range dispatches are both ``jax.jit`` of a
``shard_map`` body called ``shard_fn``, so their program names in the trace
say neither which one ran nor that it is the lookup dispatch; both are keyed
here on that shared name.
"""
from __future__ import annotations

LOOKUP_MODULE = "shard_fn"          # both stacked dispatch programs


def is_lookup_program(e) -> bool:
    """A program event (modules line) of a stacked lookup dispatch."""
    return LOOKUP_MODULE in e.name
