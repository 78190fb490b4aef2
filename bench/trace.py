"""Reduction of a profiler trace to device busy time, top ops and idle gaps.

The JAX profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`.
Each chip is a plane `/device:TPU:<id>` whose `XLA Ops` line holds one event
per HLO operation run (start and duration in ns). The benchmark wraps each
window query in a `jax.profiler.TraceAnnotation("query")`, which lands on the
host plane `/host:CPU` on the same clock. The traced window runs from the
start of the first traced query to the end of the last.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
QUERY_SPAN = "query"
TOP = 10


@dataclasses.dataclass
class Summary:
    window_ns: float
    busy_ns: list                 # per chip: union of op intervals
    top_ops: list                 # (op, ns) summed over chips, largest first
    gaps: list                    # (host activity, ns), longest first
    n_ops: int                    # op events inside the window, all chips

    @property
    def window_s(self) -> float:
        return self.window_ns * 1e-9

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns) / len(self.busy_ns) * 1e-9

    def breakdown(self) -> dict:
        return {"device_ops": [[n, t * 1e-9] for n, t in self.top_ops],
                "idle_gaps": [[n, t * 1e-9] for n, t in self.gaps]}

    def describe(self) -> str:
        return (f"window {self.window_s:.6f}s, busy per chip "
                f"{[b * 1e-9 for b in self.busy_ns]}s, {self.n_ops} ops")


def latest(trace_dir: str) -> Optional[str]:
    """The newest xplane file under `trace_dir`, or None."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def merge(intervals: list) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(merged: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def gaps_between(merged: list, lo: float, hi: float) -> list:
    """(start, end) of the idle stretches of [lo, hi] around `merged`."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _host_label(spans: list, start: float, end: float) -> str:
    """The innermost host span that covers the middle of [start, end]."""
    mid = (start + end) / 2
    best = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no host span"


def reduce(ops: dict, host: list) -> Optional[Summary]:
    """Summary of a trace given as plain data.

    ops: {chip id: [(name, start_ns, duration_ns), ...]} from the chips'
    op lines; host: [(name, start_ns, duration_ns), ...] from the host's
    threads."""
    queries = [(s, s + d) for n, s, d in host if n == QUERY_SPAN]
    if not queries or not ops:
        return None
    lo, hi = min(s for s, _ in queries), max(e for _, e in queries)
    spans = [(n, s, s + d) for n, s, d in host]
    busy = []
    totals: collections.Counter = collections.Counter()
    gaps: list = []
    n_ops = 0
    for chip in sorted(ops):
        inside = [(n, s, s + d) for n, s, d in ops[chip] if s + d > lo
                  and s < hi]
        n_ops += len(inside)
        merged = merge([(s, e) for _, s, e in inside])
        busy.append(sum(e - s for s, e in clip(merged, lo, hi)))
        for n, s, e in inside:
            totals[n] += min(e, hi) - max(s, lo)
        if chip == min(ops):
            gaps = [(_host_label(spans, s, e), e - s)
                    for s, e in gaps_between(clip(merged, lo, hi), lo, hi)]
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_ns=hi - lo, busy_ns=busy,
                   top_ops=totals.most_common(TOP),
                   gaps=gaps[:TOP], n_ops=n_ops)


def read_xplane(path: str, device_ids: list) -> tuple[dict, list]:
    """(ops by chip, host spans) from an xplane file, as plain data."""
    from jax.profiler import ProfileData

    ops: dict = {}
    host: list = []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = [(e.name, e.start_ns,
                                             e.duration_ns)
                                            for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events]
    return ops, host


def summarize(path: Optional[str], device_ids: list) -> Optional[Summary]:
    if path is None:
        return None
    return reduce(*read_xplane(path, device_ids))
