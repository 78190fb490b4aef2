"""One run of one cell: build the service, warm up, measure, trace, check.

`run_cell` is everything a run does after `run.py` has found the chips. It
takes the devices it is given, so a test can drive it on the CPU.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import os
import shutil
import time
from typing import Iterator, Optional

import numpy as np

from bench import graphs, reference, spec, trace

CACHE_HITS = "/jax/compilation_cache/cache_hits"
CACHE_MISSES = "/jax/compilation_cache/cache_misses"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")
TRACE_QUERIES = 1           # window queries the --trace 1 run traces


class _Events:
    """Counts of JAX's compile and cache events, and compile seconds."""

    def __init__(self):
        self.counts: collections.Counter = collections.Counter()
        self.compile_s = 0.0
        self._installed = False

    def install(self) -> None:
        if self._installed:
            return
        import jax

        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        self._installed = True

    def _event(self, name, **_):
        self.counts[name] += 1

    def _duration(self, name, secs, **_):
        self.counts[name] += 1
        if name == BACKEND_COMPILE:
            self.compile_s += secs

    def snapshot(self) -> dict:
        return {"hits": self.counts[CACHE_HITS],
                "misses": self.counts[CACHE_MISSES],
                "builds": self.counts[BACKEND_COMPILE],
                "compile_s": self.compile_s}


# jax.monitoring listeners are process-wide and cannot be taken back, so
# one counter serves every run in a process (tests run several)
EVENTS = _Events()


def diff(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def enable_compile_cache() -> str:
    """JAX's persistent cache in the program's fixed directory, keeping every
    program, with source paths cut to file names so that the cache key does
    not depend on where the checkout lies (Pallas kernels carry their source
    locations inside the custom call's payload)."""
    import jax
    from repro.launch import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", r".*/")
    return path


def query_stream(traffic: dict, seed: int) -> Iterator[tuple]:
    """The traffic's queries, cycled from an offset drawn from `seed`, as
    (EngineConfig, engine) pairs."""
    from repro.core.engine import EngineConfig

    qs = [(EngineConfig(**q["cfg"]), q["engine"]) for q in traffic["queries"]]
    i = int(np.random.default_rng(seed).integers(len(qs)))
    while True:
        yield qs[i % len(qs)]
        i += 1


@dataclasses.dataclass
class RunContext:
    """What a per-layer metric's `read(ctx)` can read."""

    chips: int
    prep_timings: dict            # PrepStream.timings after the warm-up
    queries: list                 # one dict per window query
    trace: Optional[trace.Summary] = None
    peaks: Optional[dict] = None  # the chip's row of bench/peaks.json


def _query(svc, cfg, engine) -> dict:
    t0 = time.perf_counter()
    res = svc.query(cfg, engine=engine)
    return {"seconds": time.perf_counter() - t0, "cliques": int(res.cliques),
            "exhausted": bool(res.iters_exhausted), "stats": dict(res.stats),
            "driver": dict(svc.last_driver.stats)}


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             devices: list, t_start: float, log=print) -> dict:
    """One run of `cell` on `devices`; returns the result line's object."""
    import jax
    from jax.sharding import Mesh
    from repro.graph.csr import CSRGraph
    from repro.launch.mce_service import MCEService

    EVENTS.install()
    log(f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")

    t0 = time.perf_counter()
    n, indptr, indices = graphs.build(cell.config["graph"])
    log(f"graph: n={n} m={len(indices) // 2} in "
        f"{time.perf_counter() - t0:.3f}s")
    svc = MCEService(CSRGraph(indptr, indices),
                     mesh=Mesh(np.array(devices), ("data",)))
    stream = query_stream(cell.traffic, seed)
    ev0 = EVENTS.snapshot()
    warm = []
    for _ in cell.traffic["queries"]:
        cfg, engine = next(stream)
        warm.append(_query(svc, cfg, engine))
        log(f"warm-up query: {warm[-1]['seconds']:.3f}s "
            f"cliques={warm[-1]['cliques']} stats={warm[-1]['stats']}")
    prep = dict(svc.stream.timings)
    ev_setup = diff(EVENTS.snapshot(), ev0)
    log(f"prep: {prep}")
    log("buckets (U, real roots, x_pad): "
        f"{[(b.u_pad, b.num_roots - b.n_pad, b.x_pad) for b in svc.stream]}")

    n_traced = TRACE_QUERIES if traced else 0
    if n_traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    ev1 = EVENTS.snapshot()
    window = []
    w0 = time.perf_counter()
    setup_s = w0 - t_start
    if n_traced:
        jax.profiler.start_trace(TRACE_DIR)
    while not window or time.perf_counter() - w0 < seconds:
        cfg, engine = next(stream)
        with jax.profiler.TraceAnnotation("query"):
            window.append(_query(svc, cfg, engine))
        if len(window) == n_traced:
            jax.profiler.stop_trace()
    if 0 < len(window) < n_traced:
        jax.profiler.stop_trace()
    w1 = time.perf_counter()
    ev_window = diff(EVENTS.snapshot(), ev1)
    query_s = (w1 - w0) / len(window)
    log(f"window: {len(window)} queries in {w1 - w0:.3f}s; per query "
        f"{[round(q['seconds'], 4) for q in window]}")
    log(f"compile events: set-up hits={ev_setup['hits']} "
        f"misses={ev_setup['misses']} builds={ev_setup['builds']} "
        f"compile_s={ev_setup['compile_s']:.3f}; window "
        f"hits={ev_window['hits']} misses={ev_window['misses']} "
        f"builds={ev_window['builds']}")

    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    metrics = {}
    breakdown = None
    if traced:
        summary = trace.summarize(trace.latest(TRACE_DIR),
                                  [d.id for d in devices])
        ctx = RunContext(chips=len(devices), prep_timings=prep,
                         queries=window, trace=summary,
                         peaks=(spec.load_peaks(dev.device_kind, cell.root)
                                if dev.platform == "tpu" else None))
        for m in cell.per_layer:
            value = spec.load_metric(m["name"], cell.root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            breakdown = summary.breakdown()
            log(f"trace: {summary.describe()}")
    else:
        e2e = {"setup_s": setup_s, "query_s": query_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    del svc
    gc.collect()
    t_ref = time.perf_counter()
    want = reference.count_maximal_cliques(n, indptr, indices)
    log(f"reference: {want} maximal cliques in "
        f"{time.perf_counter() - t_ref:.3f}s")
    answers = warm + window
    gap = max(abs(q["cliques"] - want) for q in answers)
    truncated = sum(q["exhausted"] for q in answers)
    failed = sum(q["cliques"] != want or q["exhausted"] for q in window)
    checks = {"count_gap": {"value": gap, "limit": 0},
              "truncated": {"value": truncated, "limit": 0}}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": len(window), "failed": failed, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
