"""Share of the traced query's device busy time that no engine phase scope
holds: the self time of the persistent loop's while/cond bookkeeping, carry
copies and counter folds (`bench/phases.py`) (layer: engine loop)."""
from bench import phases


def read(ctx):
    p = phases.of(ctx)
    return p.share(phases.CONTROL) if p is not None and p.scoped else None
