"""Share of the traced window in which no operation ran on a chip: one minus
the union of op intervals over the window, averaged over the cell's chips
(layer: device)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.window_ns or not sum(t.busy_ns):
        return None
    return 100.0 * (1.0 - sum(t.busy_ns) / (len(t.busy_ns) * t.window_ns))
