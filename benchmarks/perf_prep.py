"""Host ingest throughput: streaming vectorized prep vs the pre-refactor path.

Measures end-to-end host preparation (reduce + order + stage + pack) of
the streaming pipeline against a frozen copy of the pre-refactor
`prepare()` — the per-vertex `np.isin` row packer and the unmemoized
X-reduction, vendored below so the baseline cannot silently inherit
later optimizations. Also runs the double-buffered distributed driver
once to record its host seconds per stage.

Emits BENCH_prep.json:
  {graph, n, m, roots, legacy_prep_s, stream_prep_s, speedup,
   stage_timings, driver_spans, host_pack_s}

  PYTHONPATH=src python -m benchmarks.perf_prep \
      [--graph ba:n=20000,m=8] [--overlap-graph ba:n=4000,m=6] \
      [--out BENCH_prep.json]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

WORD = 32


# ---------------------------------------------------------------------------
# Frozen pre-refactor baseline (PR 4 state) — do NOT modernize this code;
# it is the measurement yardstick. Shared helpers the old prepare() called
# (global_reduce_host, degeneracy_order, x_prune_roots) are vendored at
# their pre-refactor state too, so later optimizations to the live copies
# cannot silently inflate the baseline.
# ---------------------------------------------------------------------------

def _common_neighbor_exists_legacy(adj, u, v, exclude=-1):
    a, b = adj[u], adj[v]
    if len(a) > len(b):
        a, b = b, a
    for w in a:
        if w != exclude and w in b:
            return w
    return -1


def _global_reduce_host_legacy(g):
    """Pre-refactor global_reduce_host: full-range python cascade."""
    from repro.graph.csr import from_edge_list

    adj = {v: set(g.neighbors(v).tolist()) for v in range(g.n)}
    reported = []
    alive = np.ones(g.n, dtype=bool)

    def kill_edge(a, b):
        adj[a].discard(b)
        adj[b].discard(a)

    def kill_vertex(v):
        for u in list(adj[v]):
            adj[u].discard(v)
        adj[v].clear()
        alive[v] = False

    queue = [v for v in range(g.n) if len(adj[v]) <= 2]
    in_q = set(queue)
    qi = 0
    while qi < len(queue):
        v = queue[qi]
        qi += 1
        in_q.discard(v)
        if not alive[v]:
            continue
        d = len(adj[v])
        if d > 2:
            continue
        neighbors = list(adj[v])
        if d == 0:
            alive[v] = False
        elif d == 1:
            (u,) = neighbors
            reported.append(frozenset((v, u)))
            kill_vertex(v)
            if alive[u] and len(adj[u]) <= 2 and u not in in_q:
                queue.append(u); in_q.add(u)
        else:
            u, w = neighbors
            if w in adj[u]:
                reported.append(frozenset((v, u, w)))
                other = _common_neighbor_exists_legacy(adj, u, w, exclude=v)
                kill_vertex(v)
                if other < 0:
                    kill_edge(u, w)
            else:
                reported.append(frozenset((v, u)))
                reported.append(frozenset((v, w)))
                kill_vertex(v)
            for t in (u, w):
                if alive[t] and len(adj[t]) <= 2 and t not in in_q:
                    queue.append(t); in_q.add(t)

    visited = set()
    edge_stack = [(u, v) for u in range(g.n) if alive[u]
                  for v in adj[u] if u < v]
    for (u, v) in edge_stack:
        if v not in adj[u]:
            continue
        if (u, v) in visited:
            continue
        w = _common_neighbor_exists_legacy(adj, u, v)
        if w < 0:
            reported.append(frozenset((u, v)))
            kill_edge(u, v)
            sub_q = [t for t in (u, v) if alive[t] and len(adj[t]) <= 2]
            while sub_q:
                t = sub_q.pop()
                if not alive[t] or len(adj[t]) > 2:
                    continue
                nbs = list(adj[t])
                if len(nbs) == 0:
                    alive[t] = False
                elif len(nbs) == 1:
                    reported.append(frozenset((t, nbs[0])))
                    kill_vertex(t)
                    sub_q.extend(x for x in nbs
                                 if alive[x] and len(adj[x]) <= 2)
                else:
                    a, b = nbs
                    if b in adj[a]:
                        reported.append(frozenset((t, a, b)))
                        other = _common_neighbor_exists_legacy(adj, a, b,
                                                               exclude=t)
                        kill_vertex(t)
                        if other < 0:
                            kill_edge(a, b)
                    else:
                        reported.append(frozenset((t, a)))
                        reported.append(frozenset((t, b)))
                        kill_vertex(t)
                    sub_q.extend(x for x in nbs
                                 if alive[x] and len(adj[x]) <= 2)
        else:
            visited.add((min(u, v), max(u, v)))
            visited.add((min(u, w), max(u, w)))
            visited.add((min(v, w), max(v, w)))

    edges = [(u, v) for u in range(g.n) if alive[u] for v in adj[u] if u < v]
    g2 = from_edge_list(g.n, np.array(edges, dtype=np.int64)
                        if edges else np.zeros((0, 2), np.int64))
    return g2, reported


def _degeneracy_order_legacy(g):
    """Pre-refactor degeneracy_order: per-vertex numpy slice + tolist."""
    n = g.n
    if n == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z, 0
    deg = g.degrees().astype(np.int64).copy()
    max_deg = int(deg.max())
    bin_start = np.zeros(max_deg + 2, dtype=np.int64)
    np.add.at(bin_start, deg + 1, 1)
    bin_start = np.cumsum(bin_start)
    bin_cur = bin_start[:-1].copy()
    vert = np.empty(n, dtype=np.int64)
    pos = np.empty(n, dtype=np.int64)
    for v in range(n):
        p = bin_cur[deg[v]]
        vert[p] = v
        pos[v] = p
        bin_cur[deg[v]] += 1
    bin_ = bin_start[:-1].copy()
    dptr, dind = g.indptr, g.indices
    degeneracy = 0
    deg_list = deg.tolist()
    pos_list = pos.tolist()
    bin_list = bin_.tolist()
    vert_list = vert.tolist()
    for i in range(n):
        v = vert_list[i]
        dv = deg_list[v]
        if dv > degeneracy:
            degeneracy = dv
        for u in dind[dptr[v]:dptr[v + 1]].tolist():
            du = deg_list[u]
            if du > dv:
                pu = pos_list[u]
                pw = bin_list[du]
                w = vert_list[pw]
                if u != w:
                    vert_list[pu] = w
                    vert_list[pw] = u
                    pos_list[u] = pw
                    pos_list[w] = pu
                bin_list[du] = pw + 1
                deg_list[u] = du - 1
    order = np.asarray(vert_list, dtype=np.int64)
    rank = np.empty(n, dtype=np.int64)
    rank[order] = np.arange(n)
    return order, rank, degeneracy


def _pack_bits_legacy(ids, words):
    out = np.zeros(words, dtype=np.uint32)
    if len(ids):
        np.bitwise_or.at(out, ids // WORD,
                         np.uint32(1) << (ids % WORD).astype(np.uint32))
    return out


def _stage_subproblem_legacy(staged, bucket_sizes, base, p_set, x_set,
                             adj_sorted, rank):
    p_ids = np.array(sorted(p_set, key=lambda u: rank[u]), dtype=np.int64)
    u_size = len(p_ids)
    bucket = next((b for b in bucket_sizes if u_size <= b), None)
    if bucket is None:
        raise ValueError(f"universe {u_size} exceeds largest bucket")
    words = bucket // WORD
    a_rows = np.zeros((bucket, words), dtype=np.uint32)
    for j, u in enumerate(p_ids):
        mask = np.isin(p_ids, adj_sorted[int(u)], assume_unique=True)
        a_rows[j] = _pack_bits_legacy(np.nonzero(mask)[0].astype(np.int64),
                                      words)
    xr = []
    for x in sorted(x_set, key=lambda u: rank[u]):
        mask = np.isin(p_ids, adj_sorted[int(x)], assume_unique=True)
        if mask.any():
            xr.append(_pack_bits_legacy(np.nonzero(mask)[0].astype(np.int64),
                                        words))
    staged[bucket].append(dict(root=base[0], base=tuple(base),
                               p0=_pack_bits_legacy(np.arange(u_size), words),
                               a=a_rows, x_rows=xr, universe=p_ids))


def _x_prune_roots_legacy(adj, order, rank):
    """Pre-memoization x-reduction: nu_plus rebuilt per (root, u) pair."""
    from repro.core.xreduction import resolve_keeps

    n = len(adj)
    ignore_id = np.full(n, n, dtype=np.int64)
    ignore_wit = np.full(n, -1, dtype=np.int64)
    kept = []
    for i in range(n):
        v = int(order[i])
        P = {u for u in adj[v] if rank[u] > i}
        X_full = {u for u in adj[v] if rank[u] < i}
        kept.append(resolve_keeps(X_full, i, ignore_id, ignore_wit, rank))
        for u in P:
            nu_plus = {w for w in adj[u] if rank[w] > rank[u]}
            if (P - {u}) <= nu_plus:
                if rank[u] < ignore_id[v]:
                    ignore_id[v] = rank[u]
                    ignore_wit[v] = u
            elif nu_plus <= P:
                if i < ignore_id[u]:
                    ignore_id[u] = i
                    ignore_wit[u] = v
    return kept


def legacy_prepare(g, bucket_sizes=(32, 64, 128, 256, 512, 1024)):
    """The pre-refactor prepare(): serial host cascade + per-row packing."""
    g_work, _reported = _global_reduce_host_legacy(g)
    order, rank, _lam = _degeneracy_order_legacy(g_work)
    adj = [set(g_work.neighbors(v).tolist()) for v in range(g_work.n)]
    adj_sorted = [g_work.neighbors(v) for v in range(g_work.n)]
    kept_x = _x_prune_roots_legacy(adj, order, rank)
    staged = {b: [] for b in bucket_sizes}
    n_roots = 0
    for i in range(g_work.n):
        v = int(order[i])
        if not adj[v]:
            continue
        p_ids = np.array(sorted((u for u in adj[v] if rank[u] > i),
                                key=lambda u: rank[u]), dtype=np.int64)
        if len(p_ids) == 0:
            continue
        _stage_subproblem_legacy(staged, bucket_sizes, (v,),
                                 set(p_ids.tolist()), kept_x[i],
                                 adj_sorted, rank)
        n_roots += 1
    return staged, n_roots


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def run(graph_desc: str = "ba:n=20000,m=8",
        overlap_graph: str = "caveman:c=400,k=8",
        out_json: str | None = "BENCH_prep.json"):
    from repro.core.driver import DistributedMCE
    from repro.core.engine import PrepStream
    from repro.launch.mce_run import parse_graph

    g = parse_graph(graph_desc)
    print(f"graph {graph_desc}: n={g.n} m={g.m}", flush=True)

    t0 = time.perf_counter()
    _, legacy_roots = legacy_prepare(g)
    legacy_s = time.perf_counter() - t0
    print(f"legacy prepare(): {legacy_s:.2f}s ({legacy_roots} roots)",
          flush=True)

    t0 = time.perf_counter()
    stream = PrepStream(g, stream_roots=1024, cache=False)
    n_roots = sum(b.num_roots for b in stream)
    stream_s = time.perf_counter() - t0
    print(f"streaming prep:   {stream_s:.2f}s ({n_roots} roots) "
          f"stages={ {k: round(v, 2) for k, v in stream.timings.items()} }",
          flush=True)
    speedup = legacy_s / stream_s

    og = parse_graph(overlap_graph)
    # warmup pass populates the jit cache; the measured pass re-packs a
    # fresh stream against warm executables = steady state
    DistributedMCE(og, chunk=128, stream_roots=256).run()
    drv = DistributedMCE(og, chunk=128, stream_roots=256)
    res = drv.run()
    spans = {k: round(v, 4) for k, v in drv.stats["spans"].items()}
    print(f"driver run {overlap_graph}: cliques={res.cliques} "
          f"host_pack={drv.stats['host_pack_s']:.2f}s spans={spans}",
          flush=True)

    row = dict(graph=graph_desc, n=g.n, m=g.m, roots=n_roots,
               legacy_prep_s=legacy_s, stream_prep_s=stream_s,
               speedup=speedup,
               stage_timings=stream.timings,
               overlap_graph=overlap_graph,
               driver_spans=dict(drv.stats["spans"]),
               host_pack_s=drv.stats["host_pack_s"])
    print(f"host-prep speedup: {speedup:.1f}x", flush=True)
    if out_json:
        from benchmarks.bench_record import append_run
        append_run(out_json, row)   # appends to "runs", keeps top-level compat
    return row


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="ba:n=20000,m=8")
    ap.add_argument("--overlap-graph", default="caveman:c=400,k=8")
    ap.add_argument("--out", default="BENCH_prep.json")
    args = ap.parse_args()
    run(args.graph, args.overlap_graph, args.out)
