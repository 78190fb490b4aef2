"""Share of the lock-step per-root engine's lane-trips that did useful
work over the window's queries: sum of `live_iters` over sum of
`lane_iters` of the driver's per-bucket counters (`stats["buckets"]`,
keyed by (u_pad, x_pad, engine)) whose engine is `perroot`. Every lane of
a lock-step chunk spins until its slowest root is done; the rest of the
lane-trips are that wait (layer: engine loop)."""


def read(ctx):
    live = lane = 0
    for q in ctx.queries:
        for (_, _, engine), b in q["driver"].get("buckets", {}).items():
            if engine == "perroot":
                live += b["live_iters"]
                lane += b["lane_iters"]
    return 100.0 * live / lane if lane else None
