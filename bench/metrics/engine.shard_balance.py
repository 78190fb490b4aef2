"""How evenly the dealing spread the chunk programs' lane-trips over the
shards: the sum over the window queries' buckets of the mean per-shard
lane-trips over the sum of the largest, from the driver's per-shard
counters (`shard_lane_iters` in `stats["buckets"]`, one entry per shard).
A chunk ends when its slowest shard does, so 100% means no shard waits on
another. A program that counts no per-shard lane-trips gives nothing
(layer: driver)."""


def read(ctx):
    mean = top = 0
    for q in ctx.queries:
        for b in q["driver"].get("buckets", {}).values():
            shards = b.get("shard_lane_iters")
            if shards:
                mean += sum(shards) / len(shards)
                top += max(shards)
    return 100.0 * mean / top if top else None
