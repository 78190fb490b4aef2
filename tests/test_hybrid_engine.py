"""Hybrid branching + early termination: oracle parity and edge hardening.

The hybrid backend adds two checks on top of pivot branching — emit P∪R
without recursing when P∪X is already a clique (unless an X vertex
dominates P), and switch to vertex branching on dense subproblems — so
parity must hold on cliques AND enumerated sets across every dispatch
path: the lock-step per-root walk, the persistent lane-refill queue
(side-effects gated on the live mask), and the auto policy. Also covers
the ISSUE-8 bugfix sweep: `choose_engine` degenerate cost vectors,
`root_cost_skew` clamping, and `MCEService.query` falsy-override
rejection.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import oracle
from repro.core.driver import DistributedMCE
from repro.core.engine import (BACKENDS, EngineConfig, choose_engine,
                               prepare, root_cost_skew, run, run_bucket,
                               run_bucket_persistent)
from repro.launch.mce_service import MCEService
from repro.graph import generators as gen

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

GRAPHS = {
    "er": lambda: gen.erdos_renyi(60, 0.3, seed=0),
    "ba": lambda: gen.barabasi_albert(80, 5, seed=1),
    "caveman": lambda: gen.caveman(8, 6, seed=2),
}


# ---------------------------------------------------------------------------
# Oracle parity across graphs × engines × dynamic reduction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine,lanes", [("perroot", 64),
                                          ("persistent", 7),
                                          ("auto", 16)])
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_hybrid_matches_oracle_counts(gname, engine, lanes):
    g = GRAPHS[gname]()
    res = run(g, backend="hybrid", engine=engine, lanes=lanes)
    assert res.cliques == len(oracle.bk_pivot(g))
    assert not res.iters_exhausted


@pytest.mark.parametrize("dyn", [True, False])
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_hybrid_enumerates_same_sets(gname, dyn):
    """Early termination emits cliques from a different code path (the
    fused clique test, not the leaf report) — the SETS must still match
    the oracle exactly, both with Lemma 8 on and off."""
    g = GRAPHS[gname]()
    res = run(g, backend="hybrid", enumerate_cliques=True, dynamic_red=dyn)
    assert not res.overflow
    assert set(res.enumerated) == set(oracle.bk_pivot(g))


@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_hybrid_persistent_matches_perroot_counters(gname):
    """Lane interleaving must not change what the ET check reports: the
    persistent queue reproduces the per-root counters bit-for-bit."""
    g = GRAPHS[gname]()
    ref = run(g, backend="hybrid", engine="perroot")
    res = run(g, backend="hybrid", engine="persistent", lanes=5)
    assert (res.cliques, res.calls, res.branches, res.sum_px) == \
           (ref.cliques, ref.calls, ref.branches, ref.sum_px)


def test_hybrid_prunes_calls_on_community_graph():
    """The tentpole's win condition: with Lemma 8 off, a pivot walk strips
    each caveman community clique one vertex per call; the ET check emits
    it in one. ≥20% fewer calls at exact clique parity."""
    g = GRAPHS["caveman"]()
    rp = run(g, backend="pivot", dynamic_red=False)
    rh = run(g, backend="hybrid", dynamic_red=False)
    assert rh.cliques == rp.cliques == len(oracle.bk_pivot(g))
    assert rh.calls <= 0.8 * rp.calls


# ---------------------------------------------------------------------------
# max_iters truncation surfaces under hybrid too
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("runner", ["perroot", "persistent"])
def test_hybrid_truncation_flag(runner):
    import jax.numpy as jnp
    g = gen.erdos_renyi(50, 0.3, seed=4)
    prep = prepare(g, bucket_sizes=(64,))
    (b,) = prep.buckets
    args = (jnp.asarray(b.a), jnp.asarray(b.p0), jnp.asarray(b.x_rows),
            jnp.asarray(b.x_alive0), jnp.asarray(b.rsz0))
    full = run_bucket(*args, EngineConfig(backend="hybrid"))
    assert int(full["truncated"].sum()) == 0
    need = int(full["iters"].max())
    cfg = EngineConfig(backend="hybrid", max_iters=max(need // 4, 2))
    if runner == "perroot":
        out = run_bucket(*args, cfg)
        assert int(out["truncated"].sum()) > 0
        assert int(out["cliques"].sum()) < int(full["cliques"].sum())
    else:
        out = run_bucket_persistent(*args, cfg, lanes=4)
        assert int(out["truncated"]) == 1


def test_hybrid_run_surfaces_iters_exhausted_flag():
    res = run(gen.erdos_renyi(60, 0.3, seed=5), backend="hybrid")
    assert res.iters_exhausted is False


# ---------------------------------------------------------------------------
# Backend validation (satellite: bogus backends used to run as pivot)
# ---------------------------------------------------------------------------

def test_run_rejects_unknown_backend():
    with pytest.raises(ValueError, match="backend"):
        run(GRAPHS["er"](), backend="bogus")


def test_driver_rejects_unknown_backend():
    with pytest.raises(ValueError, match="backend"):
        DistributedMCE(GRAPHS["er"](), cfg=EngineConfig(backend="bogus"))


def test_hybrid_in_backends_registry():
    assert "hybrid" in BACKENDS


# ---------------------------------------------------------------------------
# choose_engine / root_cost_skew edge hardening (satellite bugfix)
# ---------------------------------------------------------------------------

def test_root_cost_skew_degenerate_inputs():
    assert root_cost_skew(np.zeros(0)) == 1.0          # empty bucket
    assert root_cost_skew(np.zeros(17)) == 1.0         # all-pad / all-zero
    assert root_cost_skew(np.full(5, np.nan)) == 1.0
    assert root_cost_skew(np.array([np.inf, 1.0])) == 1.0
    assert root_cost_skew(np.array([-3.0, -1.0])) == 1.0
    # near-zero mean must clamp to n, not explode to max/eps
    tiny = np.array([1.0] + [1e-300] * 7)
    assert root_cost_skew(tiny) == 8.0
    uniform = np.full(12, 3.5)
    assert root_cost_skew(uniform) == pytest.approx(1.0)


def test_choose_engine_degenerate_cost_vectors_route_perroot():
    """Empty/all-pad buckets used to crash on a length-0 max or misroute
    via skew = max/1e-12; they must answer perroot without raising."""
    assert choose_engine(np.zeros(0))[0] == "perroot"
    assert choose_engine(np.zeros(64))[0] == "perroot"
    assert choose_engine(np.full(64, np.nan))[0] == "perroot"
    # all-but-one-zero: skew clamps to n_roots, still a real skew -> the
    # policy may pick persistent, but it must not crash and lanes stay pow2
    eng, lanes = choose_engine(np.array([5.0] + [0.0] * 63))
    assert eng in ("perroot", "persistent")
    assert lanes & (lanes - 1) == 0


def test_choose_engine_memoized_skew_clamped_and_nan_safe():
    assert choose_engine(skew=float("nan"), n_roots=64)[0] == "perroot"
    # a memoized skew beyond n_roots is float noise: clamped, not trusted
    big = choose_engine(skew=1e9, n_roots=64, lanes=64)
    legit = choose_engine(skew=64.0, n_roots=64, lanes=64)
    assert big == legit


def test_driver_cost_skew_memo_matches_choose_engine():
    """The driver memoizes root_cost_skew per bucket for cached replays;
    a replay (skew= path) must route exactly like the fresh run
    (costs= path) on a degenerate all-zero bucket."""
    costs = np.zeros(64)
    fresh = choose_engine(costs)
    replay = choose_engine(skew=root_cost_skew(costs), n_roots=64)
    assert fresh == replay == ("perroot", 64)


# ---------------------------------------------------------------------------
# MCEService falsy-override rejection (satellite bugfix)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def service():
    return MCEService(gen.barabasi_albert(150, 4, seed=11),
                      chunk=64, stream_roots=64)


def test_service_explicit_engine_override_still_works(service):
    res = service.query(engine="perroot", lanes=8)
    assert res.cliques == len(oracle.bk_pivot(
        gen.barabasi_albert(150, 4, seed=11)))


def test_service_rejects_falsy_engine_override(service):
    """engine='' used to silently fall back to the service default via
    `engine or self.engine`; now it's a loud caller error."""
    with pytest.raises(ValueError, match="engine override"):
        service.query(engine="")
    with pytest.raises(ValueError, match="engine override"):
        service.query(engine="bogus")


def test_service_rejects_bad_lanes_override(service):
    with pytest.raises(ValueError, match="lanes override"):
        service.query(lanes=0)          # used to fall back silently
    with pytest.raises(ValueError, match="lanes override"):
        service.query(lanes=-4)
    with pytest.raises(ValueError, match="lanes override"):
        service.query(lanes=True)       # bool is not a lane count
    with pytest.raises(ValueError, match="lanes override"):
        service.query(lanes="16")


# ---------------------------------------------------------------------------
# Mid-queue elastic restart with the hybrid backend
# ---------------------------------------------------------------------------

def run_py(code: str, devices: int, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_midqueue_elastic_restart_hybrid(tmp_path):
    """Preempt the hybrid driver mid-queue under 4 shards, resume under 2:
    the checkpoint cursor replays exactly the remaining roots, and the ET
    check must not double-report cliques across the restart boundary."""
    ck = str(tmp_path / "hybrid.json")
    out4 = run_py(f"""
        from repro.core.driver import DistributedMCE
        from repro.core.engine import EngineConfig
        from repro.graph import barabasi_albert
        g = barabasi_albert(400, 6, seed=9)
        drv = DistributedMCE(g, chunk=16, ckpt_path={ck!r},
                             cfg=EngineConfig(backend="hybrid"),
                             bucket_sizes=(32, 64), stream_roots=64,
                             engine="persistent", lanes=8)
        n = 0
        orig = drv._run_chunk
        def failing(*args):
            global n
            if n >= 3: raise RuntimeError("preempted")
            n += 1
            return orig(*args)
        drv._run_chunk = failing
        try:
            drv.run()
        except RuntimeError:
            pass
        print("PARTIAL_OK")
    """, devices=4)
    assert "PARTIAL_OK" in out4
    out2 = run_py(f"""
        from repro.core.driver import DistributedMCE
        from repro.core import bitset_engine, oracle
        from repro.core.engine import EngineConfig
        from repro.graph import barabasi_albert
        g = barabasi_albert(400, 6, seed=9)
        ref = bitset_engine.run(g, bucket_sizes=(32, 64))
        drv = DistributedMCE(g, chunk=16, ckpt_path={ck!r},
                             cfg=EngineConfig(backend="hybrid"),
                             bucket_sizes=(32, 64), stream_roots=64,
                             engine="persistent", lanes=8)
        res = drv.run(resume=True)
        print("CLIQUES", res.cliques, ref.cliques)
        assert res.cliques == ref.cliques
        assert not res.iters_exhausted
    """, devices=2)
    assert "CLIQUES" in out2
