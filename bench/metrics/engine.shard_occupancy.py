"""Share of the lane-trips of every shard that did useful work, counting
the wait for each bucket's slowest shard as idle lane-trips: the sum of
`live_iters` over the sum, per bucket, of the number of shards times the
largest `shard_lane_iters`, over the window queries' buckets of the
driver's `stats["buckets"]`. On one chip it equals `engine.occupancy`. A
program that counts no per-shard lane-trips gives nothing (layer: engine
loop)."""


def read(ctx):
    live = cap = 0
    for q in ctx.queries:
        for b in q["driver"].get("buckets", {}).values():
            shards = b.get("shard_lane_iters")
            if not shards:
                return None
            live += b["live_iters"]
            cap += len(shards) * max(shards)
    return 100.0 * live / cap if cap else None
