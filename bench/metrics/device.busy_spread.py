"""How far the chips' busy times part over the traced query: the largest
per-chip busy union less the smallest, over the largest
(`trace.Summary.busy_ns`). A chip that finishes its shard early and waits
on the slowest is idle, unless a collective holds it busy (layer:
device)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.busy_ns or not max(t.busy_ns):
        return None
    return 100.0 * (max(t.busy_ns) - min(t.busy_ns)) / max(t.busy_ns)
