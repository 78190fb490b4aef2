"""Chip smoke: the MCE service's normal path on TPU, checked against the host oracle.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # root-parallel path on four chips + 4->2 resume

One chip: a seeded `er:n=4000,p=0.03` graph (240,367 edges; buckets of 32,
64 and 128 vertices) goes through `parse_graph` -> `MCEService` -> one
`query()` per engine path, each run cold and then warm on the cached
buckets. Together the queries reach every bitset kernel on the chip; each
must count exactly the cliques `oracle.bk_pivot` enumerates on the host,
and its compiled chunk step must hold the query's Pallas kernels
(`tpu_custom_call`s named after their `pallas_call`).

Four chips: a perroot and a persistent query on a 4-device mesh, with a
check that every chunk step spreads its roots over all four devices and a
line of each bucket's loop trips per shard, then a run cut after a few
chunks on four devices and resumed from its checkpoint cursor on two.

Everything runs in this one process, which holds the chips; it starts no
other. The compile cache follows `JAX_COMPILATION_CACHE_DIR` (else
`.jax_cache/`). Exits non-zero, printing no result, when JAX finds no TPU.
The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time
from collections import Counter

ROOT = os.path.dirname(os.path.abspath(__file__))
GRAPH = "er:n=4000,p=0.03,seed=1"
CHUNK_ELASTIC = 128       # several chunks per bucket: the cut lands mid-bucket
CHUNKS_BEFORE_CUT = 2


class Preempted(Exception):
    pass


_KERNEL = re.compile(
    r'%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"')


def kernel_calls(hlo_text: str) -> Counter:
    """Pallas kernels in a compiled TPU program, by `pallas_call` name."""
    return Counter(_KERNEL.findall(hlo_text))


def queries():
    """(label, cfg, engine, kernels its compiled step must hold)."""
    from repro.core.engine import EngineConfig

    return [
        ("pivot/perroot", EngineConfig(backend="pivot"), "perroot",
         {"frame_step", "and_popcount_rows", "and_popcount_argmax"}),
        ("pivot/persistent", EngineConfig(backend="pivot"), "persistent",
         {"frame_step", "and_popcount_rows", "and_popcount_argmax"}),
        ("hybrid/perroot", EngineConfig(backend="hybrid"), "perroot",
         {"clique_counts"}),
        ("rcd/perroot", EngineConfig(backend="rcd"), "perroot",
         {"and_popcount_many"}),
    ]


def run_query(svc, label, cfg, engine, kernels, want, runs=2):
    """Run one query `runs` times; return its failures (empty when right)."""
    fails = []
    for i in range(runs):
        t0 = time.perf_counter()
        res = svc.query(cfg, engine=engine)
        dt = time.perf_counter() - t0
        # driver.dispatch holds the step's compile on a cold call, and
        # driver.settle the wait for the device
        drv = {k: round(v, 4)
               for k, v in svc.last_driver.stats["spans"].items()}
        print(f"{label} run {i} ({'cold' if i == 0 else 'warm'}): "
              f"cliques={res.cliques} oracle={want} calls={res.calls} "
              f"{dt:.3f}s driver={drv} stats={res.stats}", flush=True)
        if res.cliques != want or res.iters_exhausted:
            fails.append(f"{label}: {res.cliques} cliques (oracle {want}), "
                         f"iters_exhausted={res.iters_exhausted}")
    compiled = svc.last_driver.compiled_step()
    calls = kernel_calls(compiled.as_text())
    print(f"{label} step: {sum(calls.values())} tpu_custom_call "
          f"{dict(calls)}", flush=True)
    if not calls or not kernels <= set(calls):
        fails.append(f"{label}: step kernels {dict(calls)} miss "
                     f"{sorted(kernels - set(calls))}")
    return fails, compiled


def one_chip(g, want, devices):
    import numpy as np
    from jax.sharding import Mesh

    from repro.launch.mce_service import MCEService

    svc = MCEService(g, mesh=Mesh(np.array(devices[:1]), ("data",)))
    fails = []
    for label, cfg, engine, kernels in queries():
        fails += run_query(svc, label, cfg, engine, kernels, want)[0]
    print(f"prep stages: {svc.stream.timings}", flush=True)
    return fails


def four_chips(g, want, devices):
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.driver import DistributedMCE, DriverCheckpoint
    from repro.launch.mce_service import MCEService

    mesh4 = Mesh(np.array(devices[:4]), ("data",))
    svc = MCEService(g, mesh=mesh4)
    fails = []
    for label, cfg, engine, kernels in queries()[:2]:
        f, compiled = run_query(svc, label + "/4chips", cfg, engine,
                                kernels, want, runs=1)
        fails += f
        shard_trips = {shape: per["shard_trips"] for shape, per in
                       svc.last_driver.stats["buckets"].items()}
        print(f"{label}/4chips trips per shard by (u_pad, x_pad, engine): "
              f"{shard_trips}", flush=True)
        # every input of the step is split over the 4 devices, one
        # shard-row of roots each
        for sh, x in zip(compiled.input_shardings[0],
                         compiled.args_info[0]):
            if (len(sh.device_set) != 4
                    or sh.shard_shape(x.shape)[0] * 4 != x.shape[0]):
                fails.append(f"{label}/4chips: step input {x.shape} is "
                             f"not spread over 4 devices ({sh})")

    label, cfg = "pivot/perroot", queries()[0][1]
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = os.path.join(tmp, "mce.json")
        drv = DistributedMCE(prep=svc.stream, mesh=mesh4, chunk=CHUNK_ELASTIC,
                             ckpt_path=ckpt, cfg=cfg)
        run_chunk, done = drv._run_chunk, [0]

        def cut_after(*a):        # a preemption after CHUNKS_BEFORE_CUT chunks
            if done[0] == CHUNKS_BEFORE_CUT:
                raise Preempted
            done[0] += 1
            return run_chunk(*a)

        drv._run_chunk = cut_after
        try:
            drv.run(resume=False)
            fails.append("4->2 resume: the run ended before the cut")
        except Preempted:
            pass
        cur = DriverCheckpoint.load(ckpt)
        print(f"4 chips cut after {done[0]} dispatched chunks: checkpoint "
              f"cursor bucket {cur.bucket} roots_done {cur.roots_done} "
              f"cliques so far {cur.counters['cliques']}", flush=True)
        mesh2 = Mesh(np.array(devices[:2]), ("data",))
        t0 = time.perf_counter()
        res = DistributedMCE(prep=svc.stream, mesh=mesh2,
                             chunk=CHUNK_ELASTIC, ckpt_path=ckpt,
                             cfg=cfg).run(resume=True)
        print(f"{label} resumed on 2 chips: cliques={res.cliques} "
              f"oracle={want} {time.perf_counter() - t0:.3f}s", flush=True)
    if res.cliques != want or res.iters_exhausted:
        fails.append(f"4->2 resume: {res.cliques} cliques (oracle {want})")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"need {args.chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import oracle
    from repro.launch import compile_cache
    from repro.launch.mce_run import parse_graph

    print(f"compile cache: {compile_cache.enable()}")
    print(f"devices: {devices[:args.chips]}")
    g = parse_graph(GRAPH)
    t0 = time.perf_counter()
    want = sum(1 for _ in oracle.bk_pivot(g))
    print(f"graph {GRAPH}: n={g.n} m={g.m}; host oracle {want} maximal "
          f"cliques in {time.perf_counter() - t0:.2f}s", flush=True)

    phase = four_chips if args.chips == 4 else one_chip
    fails = phase(g, want, devices)
    for f in fails:
        print(f"FAIL {f}", flush=True)
    print(json.dumps({"ok": not fails, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
