"""Persistent lane-refill engine vs lock-step vmap on a skewed-root workload.

The lock-step comparator mirrors the driver's per-root path: cost-descending
chunks of `chunk` roots, one vmapped `run_bucket` per chunk — every lane in
a chunk spins (masked) until the chunk's slowest root finishes, so one
unsplit hub root stalls its whole chunk. The persistent engine walks the
same cost-descending queue with `lanes` resident DFS states; a lane whose
subtree exhausts claims the next root on device, so the hub monopolizes one
lane while the rest drain the queue.

Workload: a sparse BA graph with one planted dense blob (`--blob`,
`--blob-p`) packed into a SINGLE bucket size, so the hub root and the tail
share one queue. `split_threshold` is intentionally unset: the hub staying
unsplit is the lock-step worst case this engine exists for.

`--stream` switches to the multi-bucket workload: the same skewed root
population split into a cost-descending sequence of same-shape slabs (the
`PrepStream` bucket sequence shape). The per-bucket comparator drains the
persistent queue at every slab boundary — lanes idle behind the slab's
slowest subtree (the hub) while the next slab's roots wait on the host.
The bucket-spanning engine (`run_stream_persistent`) carries lane state
across the boundary, so claimed-out slabs hand refills straight to the
next slab's queue and idle lanes steal from the hub at the tail. Records
`perbucket_idle` (the per-bucket path's idle lane-trip fraction — the
capacity the spanning engine reclaims), `steals`, and the end-to-end
`speedup` (per-bucket over spanning), and asserts exact clique-count AND enumerated-set parity
between the two paths before writing anything.

Emits BENCH_engine.json (last run at top level + full history under
"runs" — see benchmarks/bench_record.py):
  {graph, n, m, roots, iters_total, iters_hub,
   lockstep_s, persistent_s, speedup,
   lockstep_occupancy, persistent_occupancy, lanes, chunk,
   runs: [{commit, date, ...same metrics}, ...]}
and with --stream:
  {graph, n, m, roots, slabs, lanes, perbucket_s, stream_s, speedup,
   perbucket_idle, stream_occupancy, steals, cliques, enumerated, ...}

  PYTHONPATH=src python -m benchmarks.perf_engine --out BENCH_engine.json
  PYTHONPATH=src python -m benchmarks.perf_engine --stream
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np


def skewed_graph(n: int, m: int, blob: int, blob_p: float, seed: int = 7):
    from repro.graph import generators as gen
    from repro.graph.csr import from_edge_list

    g = gen.barabasi_albert(n, m, seed=seed)
    rng = np.random.default_rng(seed)
    extra = [(i, j) for i in range(blob) for j in range(i + 1, blob)
             if rng.random() < blob_p]
    e = np.concatenate([g.edges().astype(np.int64),
                        np.array(extra, np.int64)])
    key = e[:, 0] * n + e[:, 1]
    e = e[np.unique(key, return_index=True)[1]]
    return from_edge_list(n, e)


def run(n: int = 4000, m: int = 8, blob: int = 40, blob_p: float = 0.6,
        bucket: int = 64, chunk: int = 256, lanes: int = 16,
        out_json: str | None = "BENCH_engine.json"):
    from repro.core.driver import canonical_order, estimate_costs
    from repro.core.engine import (EngineConfig, prepare, run_bucket,
                                   run_bucket_persistent)

    g = skewed_graph(n, m, blob, blob_p)
    print(f"graph ba:n={n},m={m} + blob({blob},p={blob_p}): "
          f"n={g.n} m={g.m}", flush=True)
    prep = prepare(g, bucket_sizes=(bucket,))
    (bk,) = prep.buckets
    order = canonical_order(estimate_costs(bk))
    R = bk.num_roots
    cfg = EngineConfig()
    arrs = (bk.a[order], bk.p0[order], bk.x_rows[order],
            bk.x_alive0[order], bk.rsz0[order])

    # ---- lock-step comparator: cost-desc chunks, pad the last chunk ------
    def chunk_args(lo: int):
        hi = min(lo + chunk, R)
        pad = chunk - (hi - lo)
        parts = []
        for arr in arrs:
            sl = arr[lo:hi]
            if pad:
                fill = np.ones(pad, np.int32) if arr is arrs[-1] else \
                    np.zeros((pad,) + arr.shape[1:], arr.dtype)
                sl = np.concatenate([sl, fill])
            parts.append(jnp.asarray(sl))
        return parts, pad

    def lockstep():
        tot = {k: 0 for k in ("cliques", "calls", "branches", "sum_px")}
        live = spin = 0
        for lo in range(0, R, chunk):
            parts, pad = chunk_args(lo)
            out = run_bucket(*parts, cfg)
            iters = np.asarray(out["iters"])
            live += int(iters.sum())
            spin += chunk * int(iters.max())
            for k in tot:
                tot[k] += int(np.asarray(out[k]).sum())
            tot["calls"] -= pad        # empty pad roots: one call each
        return tot, live, spin

    def persistent():
        out = run_bucket_persistent(*(jnp.asarray(x) for x in arrs), cfg,
                                    lanes=lanes)
        tot = {k: int(np.asarray(out[k]).sum())
               for k in ("cliques", "calls", "branches", "sum_px")}
        live = int(out["live_iters"])
        spin = lanes * int(out["iters"])
        return tot, live, spin

    # warmup compiles both paths; second pass measures steady state
    t_lock, t_pers = [], []
    for it in range(2):
        t0 = time.perf_counter()
        lock_tot, lock_live, lock_spin = lockstep()
        t_lock.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        pers_tot, pers_live, pers_spin = persistent()
        t_pers.append(time.perf_counter() - t0)
        assert lock_tot == pers_tot, (lock_tot, pers_tot)

    # per-root iteration profile (skew evidence)
    iters = []
    for lo in range(0, R, chunk):
        parts, pad = chunk_args(lo)
        out = run_bucket(*parts, cfg)
        it_arr = np.asarray(out["iters"])
        iters.append(it_arr[:chunk - pad] if pad else it_arr)
    iters = np.concatenate(iters)

    lock_occ = lock_live / lock_spin
    pers_occ = pers_live / pers_spin
    speedup = t_lock[-1] / t_pers[-1]
    row = dict(graph=f"ba:n={n},m={m}+blob({blob},p={blob_p})",
               n=g.n, m=g.m, roots=R, bucket=bucket,
               chunk=chunk, lanes=lanes,
               iters_total=int(iters.sum()), iters_hub=int(iters.max()),
               lockstep_s=t_lock[-1], persistent_s=t_pers[-1],
               speedup=speedup,
               lockstep_occupancy=lock_occ,
               persistent_occupancy=pers_occ,
               cliques=lock_tot["cliques"])
    print(f"roots={R} iters: total={row['iters_total']} "
          f"hub={row['iters_hub']} "
          f"(hub is {row['iters_hub'] / row['iters_total']:.0%} of all work)",
          flush=True)
    print(f"lock-step  : {t_lock[-1]:.2f}s occupancy={lock_occ:.2f} "
          f"(chunk={chunk})", flush=True)
    print(f"persistent : {t_pers[-1]:.2f}s occupancy={pers_occ:.2f} "
          f"(lanes={lanes})", flush=True)
    print(f"speedup: {speedup:.2f}x", flush=True)
    if out_json:
        from benchmarks.bench_record import append_run
        append_run(out_json, row)   # appends to "runs", keeps top-level compat
    return row


def run_stream(n: int = 4000, m: int = 6, blob: int = 60,
               blob_p: float = 0.7, bucket: int = 64, slabs: int = 10,
               lanes: int = 32, out_cap: int = 4096,
               out_json: str | None = "BENCH_engine.json"):
    """Multi-bucket workload: bucket-spanning engine vs per-bucket drains.

    The baseline is the pre-spanning engine exactly as the driver ran it:
    one `run_bucket_persistent` launch per slab with stealing off — every
    slab boundary drains the queue, so the hub's subtree serializes one
    lane while the other `lanes-1` idle until the drain completes. The
    spanning path runs the same slab sequence through
    `run_stream_persistent` with stealing on. Both paths are asserted to
    exact clique-count AND enumerated-set parity before any metric is
    recorded (stealing and spanning are pure scheduling)."""
    import jax

    from repro.core.driver import canonical_order
    from repro.core.engine import (EngineConfig, estimate_costs, prepare,
                                   run_bucket_persistent,
                                   run_stream_persistent)

    g = skewed_graph(n, m, blob, blob_p)
    print(f"graph ba:n={n},m={m} + blob({blob},p={blob_p}): "
          f"n={g.n} m={g.m}", flush=True)
    prep = prepare(g, bucket_sizes=(bucket,))
    (bk,) = prep.buckets
    total = bk.num_roots - bk.n_pad          # pad no-op roots: not scheduled
    # PrepStream flush semantics: slabs are ARRIVAL-order (degeneracy-order)
    # chunks of the root population, each sorted cost-descending internally
    # — the stream is never globally cost-sorted, so the hub lands deep in
    # one mid-stream slab and its subtree is that slab's entire drain
    costs = estimate_costs(bk)[:total]
    per = -(-total // slabs)
    arrs = (bk.a, bk.p0, bk.x_rows, bk.x_alive0, bk.rsz0)
    slab_list = []
    for lo in range(0, total, per):
        sub = lo + canonical_order(costs[lo:lo + per])
        slab_list.append(tuple(jnp.asarray(arr[sub]) for arr in arrs))
    bases = np.cumsum([0] + [s[0].shape[0] for s in slab_list])
    cfg_base = EngineConfig(steal=False)     # the pre-spanning engine
    cfg_span = EngineConfig(steal=True)

    def perbucket(cfg):
        tot = {k: 0 for k in ("cliques", "calls", "branches", "sum_px")}
        live = cap = 0
        for slab in slab_list:
            L = min(lanes, slab[0].shape[0])
            out = run_bucket_persistent(*slab, cfg, lanes=L)
            for k in tot:
                tot[k] += int(np.asarray(out[k]).sum())
            live += int(out["live_iters"])
            cap += L * int(out["iters"])
        return tot, live, cap

    def spanning(cfg):
        outs, spans = run_stream_persistent(slab_list, cfg, lanes=lanes)
        tot = {k: sum(int(np.asarray(o[k]).sum()) for o in outs)
               for k in ("cliques", "calls", "branches", "sum_px")}
        live = sum(int(o["live_iters"]) for o in outs)
        cap = sum(int(o["iters"]) * int(np.asarray(o["calls"]).shape[0])
                  for o in outs)
        steals = sum(int(o["steals"]) for o in outs)
        return tot, live, cap, steals, len(spans)

    # warmup compiles both paths; second pass measures steady state
    t_pb, t_st = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        pb_tot, pb_live, pb_cap = perbucket(cfg_base)
        t_pb.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        st_tot, st_live, st_cap, steals, n_spans = spanning(cfg_span)
        t_st.append(time.perf_counter() - t0)
        assert pb_tot == st_tot, (pb_tot, st_tot)

    # enumerated-set parity (untimed): same roots, same cliques, lane and
    # boundary scheduling free — compare (stream-global root, members) sets
    def enum_sets():
        ecfg_b = EngineConfig(steal=False, out_cap=out_cap)
        ecfg_s = EngineConfig(steal=True, out_cap=out_cap)
        pb = set()
        for si, slab in enumerate(slab_list):
            L = min(lanes, slab[0].shape[0])
            out = run_bucket_persistent(*slab, ecfg_b, lanes=L)
            out = jax.tree.map(np.asarray, out)
            assert not out["overflow"].any(), "raise --out-cap"
            for l in range(out["out_n"].shape[0]):
                for k in range(int(out["out_n"][l])):
                    pb.add((int(bases[si]) + int(out["out_root"][l, k]),
                            out["out_rows"][l, k].tobytes()))
        st = set()
        outs, _ = run_stream_persistent(slab_list, ecfg_s, lanes=lanes)
        for out in outs:
            out = jax.tree.map(np.asarray, out)
            assert not out["overflow"].any(), "raise --out-cap"
            for l in range(out["out_n"].shape[0]):
                for k in range(int(out["out_n"][l])):
                    st.add((int(out["out_root"][l, k]),
                            out["out_rows"][l, k].tobytes()))
        return pb, st

    pb_set, st_set = enum_sets()
    assert pb_set == st_set, (
        f"enumerated-set divergence: {len(pb_set - st_set)} only-perbucket, "
        f"{len(st_set - pb_set)} only-stream")
    assert len(pb_set) == pb_tot["cliques"]

    perbucket_idle = 1.0 - pb_live / pb_cap
    stream_occ = st_live / st_cap
    speedup = t_pb[-1] / t_st[-1]
    row = dict(graph=f"ba:n={n},m={m}+blob({blob},p={blob_p})",
               n=g.n, m=g.m, roots=total, slabs=len(slab_list),
               lanes=lanes, bucket=bucket,
               perbucket_s=t_pb[-1], stream_s=t_st[-1], speedup=speedup,
               perbucket_idle=perbucket_idle,
               stream_occupancy=stream_occ, steals=steals,
               spans=n_spans, cliques=pb_tot["cliques"],
               enumerated=len(pb_set))
    print(f"roots={total} slabs={len(slab_list)} spans={n_spans} "
          f"cliques={row['cliques']} (enumerated parity: {len(pb_set)} "
          f"sets equal)", flush=True)
    print(f"per-bucket : {t_pb[-1]:.2f}s idle={perbucket_idle:.2f} "
          f"(drains at every slab boundary, no stealing)", flush=True)
    print(f"spanning   : {t_st[-1]:.2f}s occupancy={stream_occ:.2f} "
          f"steals={steals} ({speedup:.2f}x over per-bucket)", flush=True)
    if out_json:
        from benchmarks.bench_record import append_run
        append_run(out_json, row)
    return row


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    # unset size knobs resolve per mode: the single-bucket workload keeps
    # its historical shape (trajectory comparability); --stream defaults a
    # bit smaller with a denser blob so the hub dominates a slab
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--m", type=int, default=None)
    ap.add_argument("--blob", type=int, default=None)
    ap.add_argument("--blob-p", type=float, default=None)
    ap.add_argument("--bucket", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--lanes", type=int, default=None)
    ap.add_argument("--out", default="BENCH_engine.json")
    ap.add_argument("--stream", action="store_true",
                    help="multi-bucket workload: bucket-spanning engine "
                         "vs per-bucket persistent drains")
    ap.add_argument("--slabs", type=int, default=10)
    ap.add_argument("--out-cap", type=int, default=4096)
    a = ap.parse_args()
    if a.stream:
        run_stream(a.n or 4000, a.m or 6, a.blob or 60,
                   a.blob_p if a.blob_p is not None else 0.7,
                   a.bucket, a.slabs, a.lanes or 32, a.out_cap, a.out)
    else:
        run(a.n or 4000, a.m or 8, a.blob or 40,
            a.blob_p if a.blob_p is not None else 0.6,
            a.bucket, a.chunk, a.lanes or 16, a.out)
