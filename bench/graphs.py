"""The benchmark's own graph generators, kept apart from the program's.

`kronecker` and `erdos_renyi` draw exactly the random numbers of the
program's `repro.graph.generators`, so one generator seed gives one graph in
both. `build` turns a configuration's `graph` entry into an undirected simple
graph in CSR form. The configuration fixes the generator's seed, so every run
gets the same graph with the same labels: the engine's work depends on the
labels (pivot ties, root order, lane scheduling), so a relabelling per run
seed would change the work from run to run.
"""
from __future__ import annotations

import numpy as np


def kronecker(scale: int, edge_factor: int, seed: int,
              a: float, b: float, c: float) -> tuple[int, np.ndarray]:
    """Graph500 / R-MAT edge list: 2**scale vertices, edge_factor * n draws."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for bit in range(scale):
        r = rng.random(m)
        go_right = (r > a) & (r <= a + b)
        go_down = (r > a + b) & (r <= a + b + c)
        go_diag = r > a + b + c
        src += (go_down | go_diag).astype(np.int64) << bit
        dst += (go_right | go_diag).astype(np.int64) << bit
    return n, np.stack([src, dst], axis=1)


def erdos_renyi(n: int, p: float, seed: int) -> tuple[int, np.ndarray]:
    """G(n, p) edge list: each of the n(n-1)/2 pairs kept with chance p."""
    rng = np.random.default_rng(seed)
    iu, ju = np.triu_indices(n, k=1)
    mask = rng.random(len(iu)) < p
    return n, np.stack([iu[mask], ju[mask]], axis=1)


GENERATORS = {"kronecker": kronecker, "erdos_renyi": erdos_renyi}


def simple_csr(n: int, edges: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(indptr int64, indices int32) of the undirected simple graph: self
    loops and duplicate edges dropped, both directions stored, rows sorted."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    lo = np.minimum(e[:, 0], e[:, 1])
    hi = np.maximum(e[:, 0], e[:, 1])
    key = np.unique(lo * n + hi)
    lo, hi = key // n, key % n
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    order = np.lexsort((dst, src))
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, src + 1, 1)
    return np.cumsum(indptr), dst[order].astype(np.int32)


def build(graph: dict) -> tuple[int, np.ndarray, np.ndarray]:
    """A configuration's graph. `graph` holds `generator` (a key of
    GENERATORS) and that generator's keyword arguments, its seed among them.
    Returns (n, indptr, indices)."""
    params = {k: v for k, v in graph.items() if k != "generator"}
    n, edges = GENERATORS[graph["generator"]](**params)
    return (n, *simple_csr(n, edges))

