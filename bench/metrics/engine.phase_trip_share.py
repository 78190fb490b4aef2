"""Share of the persistent lane queue's loop trips that ran its refill and
steal conds over the window's queries: sum of `phase_trips` over sum of
`trips` of the driver's per-bucket counters (`stats["buckets"]`, keyed by
(u_pad, x_pad, engine)) whose engine is `persistent`. The other trips are
bare lane steps in the segment's inner loop. A program that does not count
`phase_trips` gives nothing (layer: engine loop)."""


def read(ctx):
    phase = trips = 0
    for q in ctx.queries:
        for (_, _, engine), b in q["driver"].get("buckets", {}).items():
            if engine == "persistent" and "phase_trips" in b:
                phase += b["phase_trips"]
                trips += b["trips"]
    return 100.0 * phase / trips if trips else None
