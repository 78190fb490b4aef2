"""Self-time reduction of a traced query: device time by engine phase.

`trace.py` sums each op's inclusive duration, so an op that holds others (a
while loop, a cond) is counted again for its children. Here every op event
on a chip's `XLA Ops` line gets its exclusive (self) time: a sweep over the
nested intervals gives each instant of the busy union to the innermost op
running then, so the self times add up to the busy union exactly.

Each op is attributed by its own path of scopes, the HLO `op_name` of the
program that ran it (never by instruction name across programs, whose names
collide): its self time goes to the first engine phase scope in the path
(`engine.refill`, `engine.steal`, `engine.step`), or to `control` if the
path has none (while/cond bookkeeping, carry copies, counter folds). The
self time of ops under `kernels.bitset_ops` is summed apart, whatever their
phase. Idle stretches are named by the innermost program span over them
(`prep.*`, `driver.*`), where `trace.py` names them by any host event.

A program with no phase scope at all (one built before the scopes existed)
reads as unscoped, and the metrics that need the scopes are then left out.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import re
from typing import Optional

from bench import trace

PHASES = ("engine.refill", "engine.steal", "engine.step")
CONTROL = "control"
KERNELS = "kernels.bitset_ops"
PROGRAM_SPANS = ("prep.", "driver.")
NO_SPAN = "no program span"
MODULES_LINE = "XLA Modules"
# the event stat that carries an op's scope path on the chip, as
# `<op_name>:<op type>` (TPU v5 lite, recorded in tests/bench/data)
PATH_KEY = "tf_op"
STATS = (PATH_KEY, "program_id", "hlo_module")


@dataclasses.dataclass
class Phases:
    busy_ns: float                # busy union over the window, all chips
    phase_ns: dict                # engine phase or CONTROL -> self ns
    kernel_ns: float              # self ns of ops under KERNELS
    program_ns: dict              # program -> self ns
    stage_idle: dict              # innermost program span -> idle ns
    n_ops: int
    scoped: bool                  # some op ran under an engine phase scope
    has_kernels: bool             # some op ran under KERNELS

    def share(self, phase: str) -> Optional[float]:
        """Percent of busy time whose self time went to `phase`."""
        if not self.busy_ns:
            return None
        return 100.0 * self.phase_ns.get(phase, 0.0) / self.busy_ns

    def kernel_share(self) -> Optional[float]:
        return (100.0 * self.kernel_ns / self.busy_ns
                if self.busy_ns else None)

    def describe(self) -> list:
        ms = lambda ns: round(ns * 1e-6, 3)  # noqa: E731
        shares = {p: round(self.share(p) or 0.0, 3)
                  for p in (CONTROL,) + PHASES}
        return [
            f"phases (self time, % of busy {ms(self.busy_ns)} ms, "
            f"{self.n_ops} ops): {shares}; kernels.bitset_ops "
            f"{round(self.kernel_share() or 0.0, 3)}%",
            "self busy per program (ms): "
            f"{ {p: ms(t) for p, t in sorted(self.program_ns.items())} }",
            "stage idle (ms): "
            f"{ {s: ms(t) for s, t in sorted(self.stage_idle.items())} }"]


def scopes(path: Optional[str]) -> list:
    """The scope names of an op path, each taken out of the transforms
    that wrap it: `.../vmap(engine.step)/add` holds `engine.step`."""
    out = []
    for part in (path or "").split("/"):
        m = re.fullmatch(r"\w+\((.*)\)", part)
        while m:
            part = m.group(1)
            m = re.fullmatch(r"\w+\((.*)\)", part)
        out.append(part)
    return out


@functools.lru_cache(maxsize=None)
def phase_of(path: Optional[str]) -> str:
    """The first engine phase scope in an op's path, else CONTROL."""
    for part in scopes(path):
        if part in PHASES:
            return part
    return CONTROL


@functools.lru_cache(maxsize=None)
def under_kernels(path: Optional[str]) -> bool:
    return KERNELS in scopes(path)


def self_times(intervals: list) -> list:
    """Exclusive time of each (start, end): every instant of the union goes
    to the innermost interval then, the one that started last."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    own = [0.0] * len(intervals)
    stack: list = []
    cursor = 0.0

    def advance(until: float) -> None:
        nonlocal cursor
        while stack and intervals[stack[-1]][1] <= until:
            top = stack.pop()
            end = intervals[top][1]
            if end > cursor:
                own[top] += end - cursor
                cursor = end
        if stack and until > cursor:
            own[stack[-1]] += until - cursor
        cursor = max(cursor, until)

    for i in order:
        if not stack:
            cursor = intervals[i][0]
        advance(intervals[i][0])
        stack.append(i)
    advance(float("inf"))
    return own


def _stage(spans: list, start: float, end: float) -> str:
    """The innermost program span over the middle of [start, end]."""
    mid = (start + end) / 2
    best = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else NO_SPAN


def reduce(ops: dict, host: list) -> Optional[Phases]:
    """Phases of a trace given as plain data.

    ops: {chip: [(program, name, start_ns, duration_ns, path), ...]}, where
    path is the op's `op_name` in its own program (None if unknown);
    host: [(name, start_ns, duration_ns), ...] from the host's threads."""
    queries = [(s, s + d) for n, s, d, *_ in host if n == trace.QUERY_SPAN]
    if not queries or not ops:
        return None
    lo, hi = min(s for s, _ in queries), max(e for _, e in queries)
    spans = [(n, s, s + d) for n, s, d, *_ in host
             if n.startswith(PROGRAM_SPANS)]
    phase_ns: collections.Counter = collections.Counter()
    program_ns: collections.Counter = collections.Counter()
    stage_idle: collections.Counter = collections.Counter()
    busy = kernel_ns = 0.0
    n_ops = 0
    scoped = has_kernels = False
    for chip in sorted(ops):
        inside = [(prog, max(s, lo), min(s + d, hi), path)
                  for prog, _, s, d, path in ops[chip]
                  if s + d > lo and s < hi]
        n_ops += len(inside)
        own = self_times([(s, e) for _, s, e, _ in inside])
        for (prog, _, _, path), t in zip(inside, own):
            phase = phase_of(path)
            scoped |= phase != CONTROL
            phase_ns[phase] += t
            program_ns[prog] += t
            if under_kernels(path):
                has_kernels = True
                kernel_ns += t
        busy += sum(own)
        if chip == min(ops):
            merged = trace.merge([(s, e) for _, s, e, _ in inside])
            for s, e in trace.gaps_between(merged, lo, hi):
                stage_idle[_stage(spans, s, e)] += e - s
    return Phases(busy_ns=busy, phase_ns=dict(phase_ns), kernel_ns=kernel_ns,
                  program_ns=dict(program_ns), stage_idle=dict(stage_idle),
                  n_ops=n_ops, scoped=scoped, has_kernels=has_kernels)


def op_path(stats: dict) -> Optional[str]:
    """The op's scope path from its `tf_op` stat, without the op type."""
    path = stats.get(PATH_KEY)
    return str(path).rsplit(":", 1)[0] if path else None


def instruction(event_name: str) -> str:
    """The HLO instruction of an op event named `fusion.3` or
    `%fusion.3 = pred[4096]{0} fusion(...)`."""
    return event_name.split(" ", 1)[0].lstrip("%")


def _program(stats: dict, modules: list, start: float, dur: float) -> tuple:
    """(id, name) of the program an op event ran in: its own stats, else
    the `XLA Modules` event that spans it, named `<module>(<id>)`."""
    pid, name = stats.get("program_id"), stats.get("hlo_module")
    if pid is None or name is None:
        for s, e, mname, mstats in modules:
            if s <= start and start + dur <= e:
                m = re.fullmatch(r"(.*)\((\d+)\)", mname)
                name = name or (m.group(1) if m else mname)
                if pid is None:
                    pid = mstats.get("program_id",
                                     int(m.group(2)) if m else None)
                break
    return pid, f"{name or 'no program'}({pid})"


def extract(planes: list, device_ids: list,
            op_names: Optional[dict] = None) -> tuple:
    """(ops, host) as `reduce` takes them, from planes given as plain data:
    [{"name", "lines": [{"name", "events": [[name, start, dur, stats]]}]}].
    An op event's program is named by its stats, or else by the
    `XLA Modules` event that spans it; its path is its `tf_op` stat, or
    else its instruction's `op_name` in that program's HLO (`op_names`:
    {program id: {instruction: op_name}})."""
    op_names = op_names or {}
    ops: dict = {}
    host: list = []
    for plane in planes:
        m = trace.DEVICE_PLANE.match(plane["name"])
        if m and int(m.group(1)) in device_ids:
            lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
            modules = sorted((s, s + d, n, st) for n, s, d, st in
                             lines.get(MODULES_LINE, []))
            rows = []
            known: dict = {}        # program id -> (id, name), once found
            for name, s, d, stats in lines.get(trace.OPS_LINE, []):
                pid = stats.get("program_id")
                if pid in known:
                    pid, prog = known[pid]
                else:
                    pid, prog = _program(stats, modules, s, d)
                    if stats.get("program_id") is not None:
                        known[pid] = (pid, prog)
                path = (op_path(stats)
                        or op_names.get(pid, {}).get(instruction(name)))
                rows.append((prog, name, s, d, path))
            ops[int(m.group(1))] = rows
        elif plane["name"] == trace.HOST_PLANE:
            for ln in plane["lines"]:
                host += [(n, s, d) for n, s, d, _ in ln["events"]]
    return ops, host


def _keep(plane: str, line: str, name: str) -> bool:
    """Of each chip its op and module lines, of the host the query and
    program spans."""
    if trace.DEVICE_PLANE.match(plane):
        return line in (trace.OPS_LINE, MODULES_LINE)
    return plane == trace.HOST_PLANE and (name == trace.QUERY_SPAN
                                          or name.startswith(PROGRAM_SPANS))


def load(path: str, device_ids: list) -> Optional[Phases]:
    """The phases of the xplane file at `path`."""
    from bench import xplane

    return reduce(*extract(xplane.read(path, _keep, STATS), device_ids,
                           xplane.op_names(path)))


def of(ctx) -> Optional[Phases]:
    """The phases of the run's traced query, reduced once per run and kept
    on `ctx`; None when the run has no device trace. The first reduction
    logs the phases, the self busy time per program, the idle time per
    program span and the traced query's per-bucket counters."""
    if ctx.trace is None:
        return None
    if not hasattr(ctx, "phases"):
        import jax

        from bench import harness

        path = trace.latest(harness.TRACE_DIR)
        ids = [d.id for d in jax.devices()[:ctx.chips]]
        ctx.phases = load(path, ids) if path else None
        if ctx.phases is not None:
            for line in ctx.phases.describe():
                print(line, flush=True)
        buckets = (ctx.queries[0]["driver"].get("buckets")
                   if ctx.queries else None)
        if buckets:
            print(f"traced query per bucket (u_pad, x_pad, engine): "
                  f"{buckets}", flush=True)
    return ctx.phases
