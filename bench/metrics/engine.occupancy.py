"""Share of lane-trips that did useful work over the window's queries:
sum of `live_iters` over sum of `lane_iters`, the engine's own exact device
counts (layer: engine loop)."""


def read(ctx):
    lane = sum(q["stats"]["lane_iters"] for q in ctx.queries)
    if not lane:
        return None
    return 100.0 * sum(q["stats"]["live_iters"] for q in ctx.queries) / lane
