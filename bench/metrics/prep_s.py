"""Host prep seconds of the warm-up query: PrepStream's reduce, order, stage
and pack stages, which run once per graph (layer: host prep)."""


def read(ctx):
    return sum(ctx.prep_timings.values()) if ctx.prep_timings else None
