"""Share of the traced query's device busy time spent in the bitset layer:
the self time of ops under the `kernels.bitset_ops` scope, which every
entry point of `kernels/bitset_ops/ops.py` opens, in whatever engine phase
(`bench/phases.py`) (layer: kernels)."""
from bench import phases


def read(ctx):
    p = phases.of(ctx)
    return p.kernel_share() if p is not None and p.has_kernels else None
