"""Windowed persistent lanes: VMEM-resident stack windows inside the
bucket-spanning megakernel (DESIGN.md §2.6 WINDOW).

Parity contract: windowing is pure scheduling. A windowed persistent run
must reproduce the unwindowed persistent AND per-root counters
bit-for-bit (cliques, calls, branches, sum_px) and the same enumerated
clique sets, with steals, staged refills, dynamic reduction, hybrid
early termination, and bounded-window spills all happening *inside* the
window trips.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import oracle
from repro.core.engine import (EngineConfig, choose_engine, run,
                               run_bucket, run_bucket_persistent)
from repro.launch.mce_service import MCEService
from repro.graph import generators as gen

from test_persistent_engine import (GRAPHS, _bucket_args, run_py,
                                    skewed_graph, _HUB_GRAPH_SRC)


def _counters(res):
    return (res.cliques, res.calls, res.branches, res.sum_px)


def _wtrips(stats):
    return stats["window_spills"] + stats["window_hits"]


# ---------------------------------------------------------------------------
# Windowed vs unwindowed vs perroot parity matrix (engine-step window path:
# dynamic reduction ON, so every backend runs the full dfs_step contract
# from inside the resident window)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["pivot", "rcd", "hybrid"])
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_windowed_persistent_matches_perroot_counts(backend, gname):
    g = GRAPHS[gname]()
    ref = run(g, backend=backend, engine="perroot")
    plain = run(g, backend=backend, engine="persistent", lanes=7)
    win = run(g, backend=backend, engine="persistent", lanes=7,
              window_steps=8)
    assert _counters(win) == _counters(plain) == _counters(ref)
    assert win.cliques == len(oracle.bk_pivot(g))
    if ref.branches > 0:
        # caveman roots all complete inside their entry call (branches=0,
        # entry_terms=calls): no lane ever steps, so no trip is tallied
        assert _wtrips(win.stats) > 0
    assert _wtrips(plain.stats) == 0
    assert not win.iters_exhausted


@pytest.mark.parametrize("steps", [4, 32])
def test_windowed_step_count_is_pure_scheduling(steps):
    """Different K walk the same tree: only the trip boundaries move."""
    g = GRAPHS["ba"]()
    ref = run(g, engine="persistent", lanes=8)
    res = run(g, engine="persistent", lanes=8, window_steps=steps)
    assert _counters(res) == _counters(ref)
    assert _wtrips(res.stats) > 0


# ---------------------------------------------------------------------------
# Window contract beyond counting: enumeration buffers inside the window
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_windowed_enumerates_same_sets(gname):
    g = GRAPHS[gname]()
    ref = run(g, enumerate_cliques=True, engine="perroot")
    res = run(g, enumerate_cliques=True, engine="persistent", lanes=5,
              window_steps=8)
    assert not res.overflow and not ref.overflow
    assert set(res.enumerated) == set(ref.enumerated)
    assert set(res.enumerated) == set(oracle.bk_pivot(g))


# ---------------------------------------------------------------------------
# Refill during a window: the staged in-trip pool (counting mode) must
# hand dead lanes fresh roots mid-trip without perturbing any counter
# ---------------------------------------------------------------------------

def test_refill_during_window_regression():
    """Many more roots than lanes: every lane dies and restages from the
    in-trip pool dozens of times; the queue cursor must stay a prefix
    cursor and the counters must match the unwindowed queue exactly."""
    g = skewed_graph()
    ref = run(g, bucket_sizes=(64,), engine="persistent", lanes=8)
    res = run(g, bucket_sizes=(64,), engine="persistent", lanes=8,
              window_steps=16)
    assert _counters(res) == _counters(ref)
    assert res.cliques == len(oracle.bk_pivot(g))
    assert _wtrips(res.stats) > 0
    assert res.stats["entry_terms"] == ref.stats["entry_terms"]


def test_hybrid_entry_terms_inside_window():
    """Hybrid early termination fires for staged roots consumed mid-trip;
    the entry_terms tally must survive windowing bit-for-bit."""
    g = GRAPHS["caveman"]()
    ref = run(g, backend="hybrid", engine="persistent", lanes=8)
    res = run(g, backend="hybrid", engine="persistent", lanes=8,
              window_steps=8)
    assert _counters(res) == _counters(ref)
    assert res.stats["entry_terms"] == ref.stats["entry_terms"]
    assert res.stats["entry_terms"] > 0


# ---------------------------------------------------------------------------
# Steal during a window: the in-trip multi-way split must stay parity-exact
# ---------------------------------------------------------------------------

def test_steal_during_window_parity_and_counters():
    """Stealing from inside a window trip (multi-way rank partition of
    the victim's donation slot) is pure scheduling: counters identical
    windowed/unwindowed and with steals on/off, steal counter live."""
    g = skewed_graph(blob=40, p=0.6)
    on = run(g, bucket_sizes=(64,), engine="persistent", lanes=8,
             steal=True, window_steps=16)
    off = run(g, bucket_sizes=(64,), engine="persistent", lanes=8,
              steal=False, window_steps=16)
    plain = run(g, bucket_sizes=(64,), engine="persistent", lanes=8,
                steal=True)
    assert _counters(on) == _counters(off) == _counters(plain)
    assert on.cliques == len(oracle.bk_pivot(g))
    assert on.stats["steals"] > 0
    assert off.stats["steals"] == 0


def test_steal_during_window_enumerates_same_sets():
    g = skewed_graph(blob=40, p=0.6)
    on = run(g, enumerate_cliques=True, bucket_sizes=(64,),
             engine="persistent", lanes=8, steal=True, window_steps=8)
    off = run(g, enumerate_cliques=True, bucket_sizes=(64,),
              engine="persistent", lanes=8, steal=False, window_steps=8)
    assert not on.overflow and not off.overflow
    assert set(on.enumerated) == set(off.enumerated)
    assert set(on.enumerated) == set(oracle.bk_pivot(g))


# ---------------------------------------------------------------------------
# Steal victim policy knob (branchiest vs deepest): bit-identical either way
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window_steps", [0, 8])
def test_steal_victim_policies_bit_identical(window_steps):
    g = skewed_graph(blob=40, p=0.6)
    br = run(g, bucket_sizes=(64,), engine="persistent", lanes=8,
             steal=True, steal_victim="branchiest",
             window_steps=window_steps)
    de = run(g, bucket_sizes=(64,), engine="persistent", lanes=8,
             steal=True, steal_victim="deepest",
             window_steps=window_steps)
    assert _counters(br) == _counters(de)
    assert br.cliques == len(oracle.bk_pivot(g))
    assert br.stats["steals"] > 0
    assert de.stats["steals"] > 0


# ---------------------------------------------------------------------------
# Bounded window_frames: spill/re-center traffic with a window shallower
# than the walk (the spill-slot regression fixture)
# ---------------------------------------------------------------------------

def test_bounded_window_frames_parity_and_spills():
    """window_frames=4 on a walk deeper than 4: every trip that tops out
    must flush, re-center on the live frame, and reload — counters still
    bit-identical, and the spill counter proves the boundary was hit."""
    g = gen.erdos_renyi(60, 0.3, seed=0)
    args = _bucket_args(g)
    ref = run_bucket(*args, EngineConfig())
    cfg = EngineConfig(window_steps=8, window_frames=4)
    out = run_bucket_persistent(*args, cfg, lanes=8)
    for k in ("cliques", "calls", "branches", "sum_px"):
        assert int(out[k].sum()) == int(ref[k].sum()), k
    assert int(out["window_spills"]) > 0
    # full-depth window on the same bucket: same counters again
    full = run_bucket_persistent(*args,
                                 dataclasses.replace(cfg, window_frames=0),
                                 lanes=8)
    for k in ("cliques", "calls", "branches", "sum_px"):
        assert int(full[k].sum()) == int(ref[k].sum()), k


def test_bounded_window_frames_with_steals():
    # engine-level reference (run() would fold in the host pre-reported
    # cliques the packed bucket never sees)
    g = skewed_graph(blob=40, p=0.6)
    args = _bucket_args(g)
    ref = run_bucket_persistent(*args, EngineConfig(), lanes=8)
    cfg = EngineConfig(window_steps=8, window_frames=6)
    out = run_bucket_persistent(*args, cfg, lanes=8)
    for k in ("cliques", "calls", "branches", "sum_px"):
        assert int(out[k].sum()) == int(ref[k].sum()), k
    assert int(out["steals"]) > 0


# ---------------------------------------------------------------------------
# choose_engine steal-policy boundary (the steal flag halves the skew
# threshold: stealing de-serializes moderate-skew buckets)
# ---------------------------------------------------------------------------

def test_choose_engine_steal_halves_skew_threshold():
    n = 64
    # moderate skew: between thr/2 and thr -> the flag decides
    mid = np.array([3.0] + [1.0] * (n - 1))
    skew = float(mid.max() / mid.mean())
    assert 2.0 < skew < 4.0
    assert choose_engine(mid)[0] == "perroot"
    assert choose_engine(mid, steal=True)[0] == "persistent"
    # below even the halved threshold: perroot either way
    low = np.array([1.8] + [1.0] * (n - 1))
    assert float(low.max() / low.mean()) < 2.0
    assert choose_engine(low)[0] == "perroot"
    assert choose_engine(low, steal=True)[0] == "perroot"
    # above the full threshold: persistent either way, same lane sizing
    high = np.array([1000.0] + [1.0] * (n - 1))
    assert choose_engine(high) == choose_engine(high, steal=True)
    assert choose_engine(high, steal=True)[0] == "persistent"
    # tiny buckets stay lock-step no matter how skewed or steal-capable
    tiny = np.array([99.0, 1.0, 1.0])
    assert choose_engine(tiny, steal=True)[0] == "perroot"
    # memoized-skew callers hit the same boundary
    assert choose_engine(skew=skew, n_roots=n, steal=True)[0] == "persistent"
    assert choose_engine(skew=skew, n_roots=n, steal=False)[0] == "perroot"


# ---------------------------------------------------------------------------
# Service surfacing: boundary_stall / occupancy / window counters
# ---------------------------------------------------------------------------

def test_service_surfaces_window_stats():
    g = skewed_graph()
    svc = MCEService(g, chunk=64, stream_roots=128,
                     engine="persistent", lanes=8)
    ref = svc.query()                                 # unwindowed baseline
    assert _wtrips(ref.stats) == 0
    assert svc.boundary_stall() == 0.0
    res = svc.query(EngineConfig(window_steps=8))
    assert res.cliques == ref.cliques
    assert _wtrips(res.stats) > 0
    assert svc.stats["window_spills"] == res.stats["window_spills"]
    assert svc.stats["window_hits"] == res.stats["window_hits"]
    assert 0.0 <= svc.boundary_stall() <= 1.0
    assert 0.0 < svc.occupancy() <= 1.0
    # a second unwindowed query must not move the window counters
    before = (svc.stats["window_spills"], svc.stats["window_hits"])
    svc.query()
    assert (svc.stats["window_spills"], svc.stats["window_hits"]) == before


# ---------------------------------------------------------------------------
# Mid-stream elastic restart (4 -> 2 shards) with a live window
# ---------------------------------------------------------------------------

def test_midstream_elastic_restart_with_live_window(tmp_path):
    """Preempt the windowed persistent driver mid-stream under 4 shards,
    resume under 2: window trips flush to the HBM stack at checkpoint
    boundaries, so the elastic cursor must land on exactly the remaining
    roots with zero count drift."""
    ck = str(tmp_path / "windowed.json")
    out4 = run_py(_HUB_GRAPH_SRC + f"""
        from repro.core.driver import DistributedMCE
        from repro.core.engine import EngineConfig
        drv = DistributedMCE(g, chunk=16, ckpt_path={ck!r},
                             bucket_sizes=(32, 64), stream_roots=64,
                             cfg=EngineConfig(window_steps=8),
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
    out2 = run_py(_HUB_GRAPH_SRC + f"""
        from repro.core.driver import DistributedMCE
        from repro.core import bitset_engine
        from repro.core.engine import EngineConfig
        ref = bitset_engine.run(g, bucket_sizes=(32, 64))
        drv = DistributedMCE(g, chunk=16, ckpt_path={ck!r},
                             bucket_sizes=(32, 64), stream_roots=64,
                             cfg=EngineConfig(window_steps=8),
                             engine="persistent", lanes=8)
        res = drv.run(resume=True)
        print("CLIQUES", res.cliques, ref.cliques)
        wt = (int(drv.last_counters.get("window_spills", 0))
              + int(drv.last_counters.get("window_hits", 0)))
        print("WTRIPS", wt)
        assert res.cliques == ref.cliques
        assert res.calls == ref.calls
        assert not res.iters_exhausted
        assert wt > 0
    """, devices=2)
    assert "CLIQUES" in out2
