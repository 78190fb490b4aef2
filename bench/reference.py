"""Plain reference: the number of maximal cliques of an undirected graph.

Bron–Kerbosch with Tomita's pivot (the vertex of P ∪ X with most neighbours
in P), one top-level call per vertex in a degeneracy order (Eppstein, Löffler
& Strash): root v gets P = its later neighbours and X = its earlier ones.
Each root's sets are bit masks over its own neighbourhood, as Python ints.
Convention of the system under test: a maximal clique has at least two
vertices, so an isolated vertex counts for nothing.

Imports nothing of the program; reads only the CSR arrays the benchmark
generated.
"""
from __future__ import annotations

import numpy as np


def peel_order(n: int, indptr: np.ndarray, indices: np.ndarray
               ) -> tuple[list[int], int]:
    """(a degeneracy order, the degeneracy): repeatedly remove a vertex of
    least remaining degree."""
    deg = np.diff(indptr).tolist()
    nbrs = [indices[indptr[v]:indptr[v + 1]].tolist() for v in range(n)]
    buckets: dict[int, set] = {}
    for v, d in enumerate(deg):
        buckets.setdefault(d, set()).add(v)
    removed = [False] * n
    order: list[int] = []
    best = d = 0
    for _ in range(n):
        d = max(d - 1, 0)
        while not buckets.get(d):
            d += 1
        v = buckets[d].pop()
        removed[v] = True
        order.append(v)
        best = max(best, d)
        for u in nbrs[v]:
            if not removed[u]:
                buckets[deg[u]].discard(u)
                deg[u] -= 1
                buckets.setdefault(deg[u], set()).add(u)
    return order, best


def _count(p: int, x: int, nb: list[int]) -> int:
    """Maximal cliques below one call: R ∪ {a maximal clique of P} with no
    vertex of X adjacent to all of it."""
    if not p:
        return 0 if x else 1
    best, pivot, rest = -1, 0, p | x
    while rest:
        low = rest & -rest
        i = low.bit_length() - 1
        rest ^= low
        c = (p & nb[i]).bit_count()
        if c > best:
            best, pivot = c, i
    found = 0
    branch = p & ~nb[pivot]
    while branch:
        low = branch & -branch
        i = low.bit_length() - 1
        branch ^= low
        found += _count(p & nb[i], x & nb[i], nb)
        p ^= low
        x |= low
    return found


def count_maximal_cliques(n: int, indptr: np.ndarray,
                          indices: np.ndarray) -> int:
    order, _ = peel_order(n, indptr, indices)
    rank = [0] * n
    for i, v in enumerate(order):
        rank[v] = i
    adj = [set(indices[indptr[v]:indptr[v + 1]].tolist()) for v in range(n)]
    total = 0
    for v in order:
        later = [u for u in adj[v] if rank[u] > rank[v]]
        if not later:
            continue            # isolated, or every clique of v is counted
        local = later + [u for u in adj[v] if rank[u] < rank[v]]
        index = {u: i for i, u in enumerate(local)}
        nb = []
        for u in local:
            m = 0
            for w in adj[u]:
                j = index.get(w)
                if j is not None:
                    m |= 1 << j
            nb.append(m)
        p = (1 << len(later)) - 1
        total += _count(p, ((1 << len(local)) - 1) ^ p, nb)
    return total
