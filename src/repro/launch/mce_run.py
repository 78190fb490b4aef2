"""Distributed MCE launcher: the paper's RMCE over a device mesh.

Usage:
  python -m repro.launch.mce_run --graph ba:n=2000,m=6 --backend pivot
  python -m repro.launch.mce_run --graph rgg:n=5000 --no-global-red
  python -m repro.launch.mce_run --graph er:n=300,p=0.2 --ckpt /tmp/mce.json
  python -m repro.launch.mce_run --graph ba:n=5000,m=8 --engine auto

Before shipping changes to anything this launcher dispatches (driver,
engine, kernels), run the repo's static analyzer — it catches the bug
classes this codebase has actually shipped (vmap-unsafe kernel
accumulators, tracer leaks into Python control flow, donation
use-after-free, layering violations):

  PYTHONPATH=src python -m repro.analysis src/repro --strict

(or `mce_lint src/repro --strict` once installed). See DESIGN.md §7 for
the rule families and the suppression syntax.
"""
from __future__ import annotations

import argparse
import time

from repro.core.engine import EngineConfig
from repro.core.driver import DistributedMCE
from repro.graph import generators as gen
from repro.launch import compile_cache


def _num(v: str):
    """int where possible, float fallback — '1e-3' and '2.5' both parse."""
    try:
        return int(v)
    except ValueError:
        return float(v)


def parse_graph(desc: str):
    """'family:key=val,...' -> CSRGraph."""
    fam, _, rest = desc.partition(":")
    kw = {}
    if rest:
        for kv in rest.split(","):
            k, _, v = kv.partition("=")
            kw[k] = _num(v)
    if fam == "er":
        return gen.erdos_renyi(int(kw.get("n", 500)), kw.get("p", 0.1),
                               seed=int(kw.get("seed", 0)))
    if fam == "ba":
        return gen.barabasi_albert(int(kw.get("n", 2000)),
                                   int(kw.get("m", 4)),
                                   seed=int(kw.get("seed", 0)))
    if fam == "rgg":
        return gen.random_geometric(int(kw.get("n", 2000)),
                                    seed=int(kw.get("seed", 0)))
    if fam == "road":
        return gen.grid_road(int(kw.get("side", 64)),
                             seed=int(kw.get("seed", 0)))
    if fam == "caveman":
        return gen.caveman(int(kw.get("c", 50)), int(kw.get("k", 8)),
                           seed=int(kw.get("seed", 0)))
    if fam == "kron":
        return gen.kronecker(int(kw.get("scale", 12)),
                             int(kw.get("ef", 8)), seed=int(kw.get("seed", 0)))
    raise ValueError(f"unknown graph family {fam}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="ba:n=2000,m=6")
    ap.add_argument("--backend",
                    choices=("pivot", "rcd", "revised", "hybrid"),
                    default="pivot",
                    help="hybrid: pivot branching plus per-node early "
                         "termination / X-domination pruning and a "
                         "density-triggered vertex-branch switch "
                         "(DESIGN.md §2.7)")
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--no-global-red", dest="gred", action="store_false")
    ap.add_argument("--no-dynamic-red", dest="dred", action="store_false")
    ap.add_argument("--no-x-red", dest="xred", action="store_false")
    ap.add_argument("--materialize", action="store_true",
                    help="legacy mode: pack every bucket before device step 1")
    ap.add_argument("--stream-roots", type=int, default=1024,
                    help="streamed bucket flush size (part of the elastic "
                         "schedule identity — keep it fixed across restarts)")
    ap.add_argument("--split-threshold", type=int, default=None)
    ap.add_argument("--engine", choices=("perroot", "persistent", "auto"),
                    default="perroot",
                    help="perroot: lock-step vmap over chunk roots; "
                         "persistent: lane-refill work queue (one while_loop "
                         "per shard, exhausted lanes claim the next root); "
                         "auto: per-bucket choice from the root-cost skew")
    ap.add_argument("--lanes", type=int, default=64,
                    help="persistent engine: resident DFS lanes per shard")
    ap.add_argument("--no-steal", dest="steal", action="store_false",
                    help="persistent engine: disable lane work-stealing "
                         "(idle lanes adopting half of a victim lane's "
                         "shallowest splittable branch set)")
    ap.add_argument("--steal-victim", choices=("branchiest", "deepest"),
                    default="branchiest",
                    help="steal victim policy: 'branchiest' picks the lane "
                         "with the largest donation-slot branch set, "
                         "'deepest' the legacy deepest lane (pure "
                         "scheduling — counters/sets bit-identical)")
    args = ap.parse_args()
    compile_cache.enable()

    g = parse_graph(args.graph)
    print(f"graph: n={g.n} m={g.m}")
    t0 = time.time()
    drv = DistributedMCE(
        g, chunk=args.chunk, ckpt_path=args.ckpt,
        cfg=EngineConfig(dynamic_red=args.dred, backend=args.backend,
                         steal=args.steal, steal_victim=args.steal_victim),
        global_red=args.gred, x_red=args.xred,
        streaming=not args.materialize, stream_roots=args.stream_roots,
        split_threshold=args.split_threshold,
        engine=args.engine, lanes=args.lanes)
    init_s = time.time() - t0
    t0 = time.time()
    res = drv.run(resume=args.resume)
    run_s = time.time() - t0
    print(f"maximal cliques: {res.cliques} "
          f"(pre-reported {res.pre_reported}, calls {res.calls}, "
          f"branches {res.branches})")
    if res.iters_exhausted:
        print("WARNING: max_iters hit — counts are a lower bound; "
              "raise EngineConfig.max_iters")
    tm = drv.stream.timings if drv.stream is not None else {}
    stage_str = " ".join(f"{k} {v:.2f}s" for k, v in tm.items())
    n_buckets = (drv.stream.num_buckets if drv.stream is not None
                 else len(drv.prep.buckets))
    print(f"prep stages: {stage_str or f'(materialized in {init_s:.2f}s)'}")
    spans = " ".join(f"{k.split('.')[1]} {v:.2f}s"
                     for k, v in drv.stats["spans"].items())
    print(f"run {run_s:.2f}s  shards={drv.n_shards} buckets={n_buckets} "
          f"chunks={drv.stats['chunks']}  host: {spans}")
    if args.engine == "auto":
        print(f"engine choices: {drv.stats['engine_choices']}")
    lc = drv.last_counters
    if lc.get("lane_iters"):
        print(f"lane occupancy: {lc['live_iters'] / lc['lane_iters']:.2f} "
              f"(live {lc['live_iters']} / capacity {lc['lane_iters']})")
    if lc.get("steals") or lc.get("entry_terms"):
        print(f"queue: steals={lc.get('steals', 0)} "
              f"entry_terms={lc.get('entry_terms', 0)}")


if __name__ == "__main__":
    main()
