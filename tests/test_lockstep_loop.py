"""The lock-step bucket walk (`run_lockstep`): one while_loop over the
batch of roots with a masked lane step, against the per-root reference
`jax.vmap(run_root)`.

Every per-root stat must be equal element for element, with and without
a `max_iters` that truncates, and the enumerated sets must equal the host
oracle's. The walk runs through `run_bucket` (its jitted form), whose
compiled programs `run` reuses here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import oracle
from repro.core.engine import (EngineConfig, prepare, run, run_bucket,
                               run_root)
from repro.graph import generators as gen

GRAPHS = {
    "er60": lambda: gen.erdos_renyi(60, 0.3, seed=11),
    "ba70": lambda: gen.barabasi_albert(70, 6, seed=12),
}
STATS = ("cliques", "calls", "branches", "sum_px", "iters", "truncated")
ENUM = ("out_rows", "out_sizes", "out_n", "overflow")


def _bucket_args(g):
    (b,) = prepare(g, bucket_sizes=(64,)).buckets
    return tuple(jnp.asarray(x)
                 for x in (b.a, b.p0, b.x_rows, b.x_alive0, b.rsz0))


def _both(args, cfg):
    ref = jax.jit(lambda *xs: jax.vmap(
        lambda *r: run_root(*r, cfg))(*xs))(*args)
    new = run_bucket(*args, cfg)
    return ({k: np.asarray(v) for k, v in ref.items()},
            {k: np.asarray(v) for k, v in new.items()})


@pytest.mark.parametrize("truncate", [False, True],
                         ids=["whole", "truncated"])
@pytest.mark.parametrize("backend", ["pivot", "hybrid", "rcd"])
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_lockstep_matches_vmapped_run_root(gname, backend, truncate):
    args = _bucket_args(GRAPHS[gname]())
    cfg = EngineConfig(backend=backend)
    if truncate:
        full = run_bucket(*args, cfg)
        cfg = EngineConfig(backend=backend,
                           max_iters=max(int(full["iters"].max()) // 4, 2))
    ref, new = _both(args, cfg)
    assert bool(ref["truncated"].any()) == truncate
    for k in STATS:
        np.testing.assert_array_equal(new[k], ref[k], err_msg=k)


@pytest.mark.parametrize("backend", ["pivot", "hybrid", "rcd"])
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_lockstep_enumerates_oracle_sets(gname, backend):
    g = GRAPHS[gname]()
    ref, new = _both(_bucket_args(g),
                     EngineConfig(backend=backend, out_cap=256))
    for k in ENUM:
        np.testing.assert_array_equal(new[k], ref[k], err_msg=k)
    res = run(g, backend=backend, enumerate_cliques=True, out_cap=256,
              bucket_sizes=(64,), engine="perroot")
    assert not res.overflow and not res.iters_exhausted
    assert set(res.enumerated) == set(oracle.bk_pivot(g))
