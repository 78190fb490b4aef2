"""The lock-step per-root engine at U=256 (W=8 words): every root of a small
seeded G(n,p) packed at U=256, through `MCEService` with
`engine="perroot"`.

The count must equal the benchmark's plain reference and the host oracle;
the work counters (cliques, calls, branches, sum_px) must equal the
persistent queue's on the same packed buckets. The occupancy counters
differ by engine and are not compared.
"""
import os
import sys

import pytest

from repro.core import oracle
from repro.core.engine import EngineConfig
from repro.graph.csr import CSRGraph
from repro.launch.mce_service import MCEService

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import graphs, reference  # noqa: E402

GRAPH = {"generator": "erdos_renyi", "n": 300, "p": 0.1, "seed": 1}


@pytest.fixture(scope="module")
def service():
    n, indptr, indices = graphs.build(GRAPH)
    return n, indptr, indices, MCEService(CSRGraph(indptr, indices),
                                          bucket_sizes=(256,))


def test_lockstep_at_u256_matches_reference_and_persistent(service):
    n, indptr, indices, svc = service
    cfg = EngineConfig(backend="pivot")
    lock = svc.query(cfg, engine="perroot")
    buckets = svc.last_driver.stats["buckets"]
    queue = svc.query(cfg, engine="persistent")

    want = reference.count_maximal_cliques(n, indptr, indices)
    assert want == len(oracle.bk_pivot(CSRGraph(indptr, indices)))
    assert lock.cliques == want and not lock.iters_exhausted
    assert {u for u, _, eng in buckets if eng == "perroot"} == {256}
    assert sum(b["calls"] for b in buckets.values()) > 0
    assert [(r.cliques, r.calls, r.branches, r.sum_px)
            for r in (lock, queue)] == \
        [(want, queue.calls, queue.branches, queue.sum_px)] * 2
