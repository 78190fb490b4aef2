"""Share of the traced query's device busy time spent stealing work: the
self time of ops under the `engine.steal` scope (the boundary steal and the
in-trip multi-way steal) (`bench/phases.py`) (layer: engine loop)."""
from bench import phases


def read(ctx):
    p = phases.of(ctx)
    return p.share("engine.steal") if p is not None and p.scoped else None
