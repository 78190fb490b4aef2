"""Host seconds per window query in the driver: gathering, padding and
uploading each chunk and dispatching its step, as `DistributedMCE.stats`
times it (`host_pack_s`) (layer: driver)."""


def read(ctx):
    if not ctx.queries:
        return None
    return sum(q["driver"]["host_pack_s"] for q in ctx.queries) / len(ctx.queries)
