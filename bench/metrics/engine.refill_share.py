"""Share of the traced query's device busy time spent refilling lanes: the
self time of ops under the `engine.refill` scope (the refill cond's branch,
the staged refill's batched entry calls and their swap-in)
(`bench/phases.py`) (layer: engine loop)."""
from bench import phases


def read(ctx):
    p = phases.of(ctx)
    return p.share("engine.refill") if p is not None and p.scoped else None
