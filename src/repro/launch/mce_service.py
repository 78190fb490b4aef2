"""Long-lived MCE service: pack once, answer many queries (DESIGN.md §6).

`serve.py`-style deployment posture for clique workloads: a resident
`PrepStream` with `cache=True` owns the packed `RootBucket`s. The first
query streams them (host packing overlapped with device execution via
the double-buffered driver); every later query — a different pivot
backend, dynamic-reduction ablation, or re-count after an elastic mesh
resize — replays the cached buckets with zero host prep.

Usage:
  PYTHONPATH=src python -m repro.launch.mce_service --graph ba:n=3000,m=6
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

from jax.sharding import Mesh

from repro.core.driver import DistributedMCE
from repro.core.engine import EngineConfig, MCEResult, PrepStream
from repro.graph.csr import CSRGraph


class MCEService:
    """Resident prepared-stream handle + per-query distributed drivers.

    `stats` accumulates occupancy/health counters ACROSS queries (cached
    replays included): `live_iters` / `lane_iters` are the useful vs
    capacity lane-trips of every engine dispatch (occupancy() = ratio),
    `truncated` counts chunks that hit cfg.max_iters with work left,
    `steals` / `entry_terms` are the lane queue's adopted branch-set
    halves and roots finished inside their entry call, and
    `engine_choices` tallies the per-bucket auto-policy picks. The
    per-query deltas ride on each returned result as `res.stats`.
    """

    def __init__(self, g: CSRGraph, *, mesh: Optional[Mesh] = None,
                 axis: str = "data", chunk: int = 1024,
                 bucket_sizes: Sequence[int] = (32, 64, 128, 256, 512, 1024),
                 max_x_rows: int = 8192,
                 split_threshold: Optional[int] = None,
                 stream_roots: int = 1024,
                 engine: str = "perroot", lanes: int = 64):
        self.stream = PrepStream(g, bucket_sizes=bucket_sizes,
                                 max_x_rows=max_x_rows,
                                 split_threshold=split_threshold,
                                 stream_roots=stream_roots, cache=True)
        self.mesh = mesh
        self.axis = axis
        self.chunk = chunk
        self.engine = engine
        self.lanes = lanes
        self.queries = 0
        self.last_driver: Optional[DistributedMCE] = None
        self.stats = {"live_iters": 0, "lane_iters": 0, "truncated": 0,
                      "steals": 0, "entry_terms": 0,
                      "engine_choices": {"perroot": 0, "persistent": 0}}

    def occupancy(self) -> float:
        """Useful lane-trips / lane-trip capacity over all queries so far."""
        cap = self.stats["lane_iters"]
        return self.stats["live_iters"] / cap if cap else 0.0

    def query(self, cfg: EngineConfig = EngineConfig(),
              ckpt_path: Optional[str] = None,
              resume: bool = False,
              engine: Optional[str] = None,
              lanes: Optional[int] = None) -> MCEResult:
        """Run one counting query over the shared packed buckets.

        `engine`/`lanes` override the service defaults for this query
        only (e.g. A/B the persistent queue against lock-step vmap on
        identical packed buckets). Only `None` means "use the service
        default" — a falsy-but-explicit override (empty string, 0) is a
        caller error and raises instead of silently falling back."""
        if engine is None:
            engine = self.engine
        elif engine not in ("perroot", "persistent", "auto"):
            raise ValueError(f"unknown engine override {engine!r} "
                             "(expected 'perroot'|'persistent'|'auto')")
        if lanes is None:
            lanes = self.lanes
        elif not isinstance(lanes, int) or isinstance(lanes, bool) \
                or lanes < 1:
            raise ValueError(f"lanes override must be a positive int, "
                             f"got {lanes!r}")
        kwargs = {} if self.mesh is None else {"mesh": self.mesh,
                                               "axis": self.axis}
        drv = DistributedMCE(prep=self.stream, chunk=self.chunk,
                             ckpt_path=ckpt_path, cfg=cfg,
                             engine=engine, lanes=lanes, **kwargs)
        res = drv.run(resume=resume)
        self.last_driver = drv
        self.queries += 1
        keys = ("live_iters", "lane_iters", "truncated", "steals",
                "entry_terms")
        delta = {k: int(drv.last_counters.get(k, 0)) for k in keys}
        delta["engine_choices"] = dict(drv.stats["engine_choices"])
        for k in keys:
            self.stats[k] += delta[k]
        for k, v in delta["engine_choices"].items():
            self.stats["engine_choices"][k] += v
        res.stats = delta  # per-query slice of the accumulated service stats
        return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="ba:n=3000,m=6")
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--engine", default="perroot",
                    choices=["perroot", "persistent", "auto"])
    ap.add_argument("--lanes", type=int, default=64)
    args = ap.parse_args()
    from repro.launch import compile_cache
    from repro.launch.mce_run import parse_graph

    compile_cache.enable()
    g = parse_graph(args.graph)
    svc = MCEService(g, chunk=args.chunk, engine=args.engine,
                     lanes=args.lanes)
    for label, cfg in [("pivot", EngineConfig(backend="pivot")),
                       ("pivot-nodyn", EngineConfig(backend="pivot",
                                                    dynamic_red=False))]:
        t0 = time.time()
        res = svc.query(cfg)
        occ = (res.stats["live_iters"] / res.stats["lane_iters"]
               if res.stats["lane_iters"] else 0.0)
        print(f"{label:12s} cliques={res.cliques} calls={res.calls} "
              f"occ={occ:.2f} {time.time() - t0:.2f}s "
              f"({'cold: streamed+packed' if svc.queries == 1 else 'cached buckets'})")
    print(f"service: {svc.queries} queries, "
          f"occupancy {svc.occupancy():.2f}, "
          f"engine_choices={svc.stats['engine_choices']}")


if __name__ == "__main__":
    main()
