"""Share of the traced query's device busy time spent in the lock-step
per-root chunk programs: the self time of every program whose HLO module
is `jit__lockstep_counts` (`core/driver.py`), over busy time
(`bench/phases.py`) (layer: engine loop). A trace in which no program
carries that name gives nothing."""
from bench import phases

MODULE = "jit__lockstep_counts"


def read(ctx):
    p = phases.of(ctx)
    if p is None or not p.busy_ns:
        return None
    own = [t for prog, t in p.program_ns.items() if MODULE in prog]
    return 100.0 * sum(own) / p.busy_ns if own else None
