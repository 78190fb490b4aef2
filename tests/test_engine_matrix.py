"""Both bucket walks under every backend with dynamic reduction off.

The benchmark cells run RMCE with every reduction on; the lock-step walk
(`perroot`) and the lane queue (`persistent`) must stay exact without
Lemmas 5/7/8 too, where the walks branch over larger P sets and the
pivot backends score pivots from the frame-step degrees alone. Each case
counts or enumerates one graph with `dynamic_red=False` and checks the
answer against `oracle.bk_pivot` and against the `dynamic_red=True`
count of the same walk; the queue's counters also equal the lock-step
walk's (refill and steal are pure scheduling).

Kept in its own file so that `--dist loadfile` gives it a worker of its
own.
"""
import functools

import pytest

from repro.core import oracle
from repro.core.engine import run

from test_persistent_engine import GRAPHS

ENGINES = ["perroot", "persistent"]
BACKENDS = ["pivot", "revised", "rcd", "hybrid"]


def _run(gname, engine, backend, dynamic_red, enumerate_cliques=False):
    return run(GRAPHS[gname](), engine=engine, backend=backend,
               dynamic_red=dynamic_red, enumerate_cliques=enumerate_cliques,
               lanes=5)


@functools.lru_cache(maxsize=None)
def _oracle(gname):
    return frozenset(oracle.bk_pivot(GRAPHS[gname]()))


@functools.lru_cache(maxsize=None)
def _count(gname, engine, backend, dynamic_red):
    res = _run(gname, engine, backend, dynamic_red)
    assert not res.iters_exhausted
    return res.cliques, res.calls, res.branches, res.sum_px


@pytest.mark.parametrize("mode", ["count", "enumerate"])
@pytest.mark.parametrize("gname", sorted(GRAPHS))
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("engine", ENGINES)
def test_walk_without_dynamic_reduction(engine, backend, gname, mode):
    want = _oracle(gname)
    with_red = _count(gname, engine, backend, True)
    if mode == "count":
        got = _count(gname, engine, backend, False)
        assert got[0] == len(want)
        if engine == "persistent":
            assert got == _count(gname, "perroot", backend, False)
    else:
        res = _run(gname, engine, backend, False, enumerate_cliques=True)
        assert not res.overflow and not res.iters_exhausted
        assert set(res.enumerated) == want
        got = (res.cliques,)
    assert got[0] == with_red[0]
