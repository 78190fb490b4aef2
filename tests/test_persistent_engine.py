"""Persistent device-resident BK engine: lane-refill work queue.

Parity contract: the persistent engine must reproduce the per-root
engine's counters bit-for-bit (cliques, calls, branches, sum_px) AND the
same enumerated clique sets — lanes interleave roots, so any masking bug
in the dead-lane/refill path shows up as a count or set diff here.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import oracle
from repro.core.driver import DistributedMCE
from repro.core.engine import (PIVOT_BACKENDS, EngineConfig, PrepStream,
                               choose_engine, estimate_costs, prepare, run,
                               run_bucket, run_bucket_persistent,
                               run_stream_persistent)
from repro.launch.mce_service import MCEService
from repro.graph import generators as gen
from repro.graph.csr import from_edge_list

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

GRAPHS = {
    "er": lambda: gen.erdos_renyi(60, 0.3, seed=0),
    "ba": lambda: gen.barabasi_albert(80, 5, seed=1),
    "caveman": lambda: gen.caveman(8, 6, seed=2),
}


def skewed_graph(n=300, m=3, blob=24, p=0.7, seed=7):
    """Sparse BA graph with one planted dense blob: a single hub root's
    subtree dwarfs every other root — the lock-step worst case."""
    g = gen.barabasi_albert(n, m, seed=seed)
    rng = np.random.default_rng(seed)
    extra = [(i, j) for i in range(blob) for j in range(i + 1, blob)
             if rng.random() < p]
    e = np.concatenate([g.edges().astype(np.int64),
                        np.array(extra, np.int64)])
    key = e[:, 0] * n + e[:, 1]
    e = e[np.unique(key, return_index=True)[1]]
    return from_edge_list(n, e)


# ---------------------------------------------------------------------------
# Engine-level parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["pivot", "rcd", "revised"])
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_persistent_matches_perroot_counts(backend, gname):
    g = GRAPHS[gname]()
    ref = run(g, backend=backend, engine="perroot")
    res = run(g, backend=backend, engine="persistent", lanes=7)
    assert (res.cliques, res.calls, res.branches, res.sum_px) == \
           (ref.cliques, ref.calls, ref.branches, ref.sum_px)
    assert res.cliques == len(oracle.bk_pivot(g))
    assert not res.iters_exhausted


@pytest.mark.parametrize("backend", ["pivot", "revised", "rcd", "hybrid"])
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_persistent_enumerates_same_sets(gname, backend):
    g = GRAPHS[gname]()
    ref = run(g, backend=backend, enumerate_cliques=True, engine="perroot")
    res = run(g, backend=backend, enumerate_cliques=True,
              engine="persistent", lanes=5)
    assert not res.overflow and not ref.overflow
    assert set(res.enumerated) == set(ref.enumerated)
    assert set(res.enumerated) == set(oracle.bk_pivot(g))


def test_skewed_root_regression():
    """One unsplit hub root + many tiny roots in ONE bucket: exhausted
    lanes must refill from the queue while the hub lane keeps walking."""
    g = skewed_graph()
    ref = run(g, bucket_sizes=(64,), engine="perroot")
    res = run(g, bucket_sizes=(64,), engine="persistent", lanes=8)
    assert (res.cliques, res.calls, res.branches, res.sum_px) == \
           (ref.cliques, ref.calls, ref.branches, ref.sum_px)
    assert res.cliques == len(oracle.bk_pivot(g))


def test_persistent_lanes_exceed_roots():
    """lanes > queue length: surplus lanes stay dead and contribute
    nothing (run() clamps, but the kernel must tolerate it directly)."""
    g = gen.erdos_renyi(40, 0.25, seed=3)
    prep = prepare(g, bucket_sizes=(64,))
    (b,) = prep.buckets
    cfg = EngineConfig()
    args = (jnp.asarray(b.a), jnp.asarray(b.p0), jnp.asarray(b.x_rows),
            jnp.asarray(b.x_alive0), jnp.asarray(b.rsz0))
    ref = run_bucket(*args, cfg)
    out = run_bucket_persistent(*args, cfg, lanes=b.num_roots + 13)
    for k in ("cliques", "calls", "branches", "sum_px"):
        assert int(out[k].sum()) == int(ref[k].sum()), k
    assert int(out["claimed"]) == b.num_roots
    assert int(out["truncated"]) == 0


# ---------------------------------------------------------------------------
# max_iters truncation flag (satellite: run_root used to truncate silently)
# ---------------------------------------------------------------------------

def _bucket_args(g, bucket_sizes=(64,)):
    prep = prepare(g, bucket_sizes=bucket_sizes)
    (b,) = prep.buckets
    return (jnp.asarray(b.a), jnp.asarray(b.p0), jnp.asarray(b.x_rows),
            jnp.asarray(b.x_alive0), jnp.asarray(b.rsz0))


@pytest.mark.parametrize("runner", ["perroot", "persistent"])
def test_truncation_flag_set_when_iters_exhausted(runner):
    g = gen.erdos_renyi(50, 0.3, seed=4)
    args = _bucket_args(g)
    full = run_bucket(*args, EngineConfig())
    assert int(full["truncated"].sum()) == 0
    need = int(full["iters"].max())
    cfg = EngineConfig(max_iters=max(need // 4, 2))
    if runner == "perroot":
        out = run_bucket(*args, cfg)
        assert int(out["truncated"].sum()) > 0
        assert int(out["cliques"].sum()) < int(full["cliques"].sum())
    else:
        out = run_bucket_persistent(*args, cfg, lanes=4)
        assert int(out["truncated"]) == 1


def test_run_surfaces_iters_exhausted_flag():
    g = gen.erdos_renyi(60, 0.3, seed=5)
    res = run(g)
    assert res.iters_exhausted is False


# ---------------------------------------------------------------------------
# Remainder-flush pow2 padding (compile-count hygiene)
# ---------------------------------------------------------------------------

def test_remainder_flush_pads_to_pow2_fraction():
    g = gen.barabasi_albert(500, 5, seed=6)
    sr = 64
    stream = PrepStream(g, bucket_sizes=(32, 64), stream_roots=sr)
    buckets = list(stream)
    assert buckets
    for b in buckets:
        assert b.num_roots <= sr
        assert sr % b.num_roots == 0, \
            f"flush of {b.num_roots} roots is not a pow2 fraction of {sr}"
        real = b.num_roots - b.n_pad
        if b.n_pad:
            # pads are empty no-op roots appended at the tail
            for r in range(real, b.num_roots):
                assert b.bases[r] == (-1,)
                assert len(b.universes[r]) == 0
        # padding is minimal: the next smaller pow2 would not fit
        if b.num_roots < sr:
            assert real > b.num_roots // 2

    # executable-count: every bucket of a size runs through ONE compile
    # per distinct (u_pad, root-count) pair — pow2 padding caps that at
    # O(log stream_roots) instead of one per ragged remainder
    jax.clear_caches()
    cfg = EngineConfig()
    for b in buckets:
        run_bucket(jnp.asarray(b.a), jnp.asarray(b.p0),
                   jnp.asarray(b.x_rows), jnp.asarray(b.x_alive0),
                   jnp.asarray(b.rsz0), cfg)
    distinct = {(b.u_pad, b.num_roots, b.x_rows.shape[1]) for b in buckets}
    assert run_bucket._cache_size() <= len(distinct)


def test_padded_stream_counts_match_unpadded():
    g = gen.barabasi_albert(500, 5, seed=6)
    ref = run(g, bucket_sizes=(32, 64))        # stream_roots=0: no padding
    cfgs = dict(bucket_sizes=(32, 64), stream_roots=64)
    drv = DistributedMCE(g, chunk=16, **cfgs)
    res = drv.run()
    assert res.cliques == ref.cliques
    assert res.calls == ref.calls


# ---------------------------------------------------------------------------
# Driver integration + mid-queue elastic restart
# ---------------------------------------------------------------------------

def test_driver_persistent_matches_perroot():
    g = gen.barabasi_albert(400, 5, seed=3)
    ref = DistributedMCE(g, chunk=64, stream_roots=128).run()
    res = DistributedMCE(g, chunk=64, stream_roots=128,
                         engine="persistent", lanes=16).run()
    assert (res.cliques, res.calls, res.branches, res.sum_px) == \
           (ref.cliques, ref.calls, ref.branches, ref.sum_px)


# ---------------------------------------------------------------------------
# engine="auto": per-bucket choice from root-cost skew
# ---------------------------------------------------------------------------

def test_choose_engine_policy():
    uniform = np.full(64, 10.0)
    assert choose_engine(uniform) == ("perroot", 64)
    skewed = np.array([1000.0] + [1.0] * 63)
    eng, lanes = choose_engine(skewed, lanes=64)
    assert eng == "persistent"
    assert lanes == 16          # largest pow2 <= 64/4, floor 8, cap 64
    assert lanes & (lanes - 1) == 0
    # tiny buckets stay lock-step regardless of skew
    assert choose_engine(np.array([99.0, 1.0, 1.0]))[0] == "perroot"
    # the memoized-skew path must agree with the costs path
    skew = float(skewed.max() / skewed.mean())
    assert choose_engine(skew=skew, n_roots=64, lanes=64) == (eng, lanes)
    # degenerate inputs fall back to lock-step
    assert choose_engine(np.zeros(0))[0] == "perroot"
    assert choose_engine(skew=None, n_roots=None)[0] == "perroot"


def test_choose_engine_steal_halves_skew_threshold():
    """The steal flag halves the skew threshold: stealing de-serializes
    moderate-skew buckets."""
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


def test_auto_picks_persistent_on_skewed_bucket():
    g = skewed_graph()
    prep = prepare(g, bucket_sizes=(64,))
    for b in prep.buckets:
        costs = estimate_costs(b)[:b.num_roots - b.n_pad]
        if costs.size and float(costs.max() / costs.mean()) >= 4.0:
            break
    else:
        pytest.fail("skewed_graph produced no skewed bucket")
    assert choose_engine(costs)[0] == "persistent"


def test_auto_matches_explicit_engines_on_skewed_graph():
    """Parity: auto must reproduce the explicit engines' counters exactly
    on the skewed-root fixture — the choice only moves work between
    equivalent execution strategies."""
    g = skewed_graph()
    ref = run(g, bucket_sizes=(64,), engine="perroot")
    res = run(g, bucket_sizes=(64,), engine="auto", lanes=16)
    assert (res.cliques, res.calls, res.branches, res.sum_px) == \
           (ref.cliques, ref.calls, ref.branches, ref.sum_px)
    assert res.cliques == len(oracle.bk_pivot(g))


def test_driver_auto_matches_explicit_and_records_choices():
    g = skewed_graph()
    ref = DistributedMCE(g, chunk=64, stream_roots=128).run()
    drv = DistributedMCE(g, chunk=64, stream_roots=128,
                         engine="auto", lanes=16)
    res = drv.run()
    assert (res.cliques, res.calls, res.branches, res.sum_px) == \
           (ref.cliques, ref.calls, ref.branches, ref.sum_px)
    picks = drv.stats["engine_choices"]
    assert picks["perroot"] + picks["persistent"] > 0
    assert picks["persistent"] > 0     # the hub bucket must trip the queue


def test_explicit_engine_flag_overrides_auto_policy():
    """engine='perroot'/'persistent' are hard overrides: no auto choice
    is recorded and every chunk runs the requested engine."""
    g = skewed_graph()
    drv = DistributedMCE(g, chunk=64, stream_roots=128, engine="perroot")
    drv.run()
    assert drv.stats["engine_choices"] == {"perroot": 0, "persistent": 0}


# ---------------------------------------------------------------------------
# MCEService occupancy stats (satellite: lane occupancy + truncation
# counters accumulate across cached-bucket replays)
# ---------------------------------------------------------------------------

def test_service_stats_accumulate_across_cached_replays():
    g = gen.barabasi_albert(200, 4, seed=11)
    svc = MCEService(g, chunk=64, stream_roots=64)
    r1 = svc.query()
    after_one = {k: svc.stats[k]
                 for k in ("live_iters", "lane_iters", "truncated")}
    assert r1.stats["live_iters"] == after_one["live_iters"]
    assert after_one["live_iters"] > 0
    assert after_one["lane_iters"] >= after_one["live_iters"]
    assert after_one["truncated"] == 0
    r2 = svc.query()                       # replays the CACHED buckets
    assert r2.cliques == r1.cliques
    # identical packed buckets -> identical per-query counters, so the
    # service totals are exactly double after the cached replay
    for k, v in after_one.items():
        assert svc.stats[k] == 2 * v, k
    assert 0.0 < svc.occupancy() <= 1.0
    assert svc.queries == 2


def test_service_persistent_engine_occupancy_and_choice_counters():
    g = skewed_graph()
    svc = MCEService(g, chunk=64, stream_roots=128, engine="auto", lanes=16)
    res = svc.query()
    assert res.cliques == len(oracle.bk_pivot(g))
    assert svc.stats["engine_choices"]["persistent"] > 0
    assert 0.0 < svc.occupancy() <= 1.0
    # per-query override beats the service default
    res2 = svc.query(engine="perroot")
    assert res2.cliques == res.cliques
    assert res2.stats["engine_choices"] == {"perroot": 0, "persistent": 0}


def run_py(code: str, devices: int, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_midqueue_elastic_restart_persistent(tmp_path):
    """Preempt the persistent driver mid-queue under 4 shards, resume
    under 2: the canonical cost-descending cursor (= persistent queue
    order) must land the restart on exactly the remaining roots."""
    ck = str(tmp_path / "persistent.json")
    out4 = run_py(f"""
        from repro.core.driver import DistributedMCE
        from repro.graph import barabasi_albert
        g = barabasi_albert(400, 6, seed=9)
        drv = DistributedMCE(g, chunk=16, ckpt_path={ck!r},
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
        from repro.core import bitset_engine
        from repro.graph import barabasi_albert
        g = barabasi_albert(400, 6, seed=9)
        ref = bitset_engine.run(g, bucket_sizes=(32, 64))
        drv = DistributedMCE(g, chunk=16, ckpt_path={ck!r},
                             bucket_sizes=(32, 64), stream_roots=64,
                             engine="persistent", lanes=8)
        res = drv.run(resume=True)
        print("CLIQUES", res.cliques, ref.cliques)
        assert res.cliques == ref.cliques
        assert res.calls == ref.calls
        assert not res.iters_exhausted
    """, devices=2)
    assert "CLIQUES" in out2


# ---------------------------------------------------------------------------
# Bucket-spanning stream + lane work stealing (DESIGN.md §2.6 STEAL)
# ---------------------------------------------------------------------------

def plant_hub(g, blob=18, p=0.85, seed=17):
    """Densify the first `blob` vertices of an existing graph into a
    near-clique hub (same recipe as skewed_graph, applied in place)."""
    rng = np.random.default_rng(seed)
    extra = [(i, j) for i in range(blob) for j in range(i + 1, blob)
             if rng.random() < p]
    e = np.concatenate([g.edges().astype(np.int64),
                        np.array(extra, np.int64)])
    key = e[:, 0] * g.n + e[:, 1]
    e = e[np.unique(key, return_index=True)[1]]
    return from_edge_list(g.n, e)


# run(plant_hub(GRAPHS[g]()), bucket_sizes=(32, 64), lanes=8)'s queue
# counters, as a loop that runs the refill and steal conds on every trip
# counts them; `phase` is the number of trips on which one was due
STREAM_TRIPS = {
    "ba": dict(iters=17, live_iters=116, lane_iters=136, steals=1,
               entry_terms=48, phase=15),
    "caveman": dict(iters=9, live_iters=59, lane_iters=72, steals=0,
                    entry_terms=39, phase=7),
    "er": dict(iters=51, live_iters=396, lane_iters=408, steals=1,
               entry_terms=13, phase=29),
}


@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_stream_spanning_matches_perroot_on_hub_graphs(gname):
    """Multi-bucket stream with a planted hub: the spanning engine (lane
    state carried across same-shape bucket boundaries, steals on) must
    reproduce the per-root counters exactly."""
    g = plant_hub(GRAPHS[gname]())
    ref = run(g, bucket_sizes=(32, 64), engine="perroot")
    res = run(g, bucket_sizes=(32, 64), engine="persistent", lanes=8)
    assert (res.cliques, res.calls, res.branches, res.sum_px) == \
           (ref.cliques, ref.calls, ref.branches, ref.sum_px)
    assert res.cliques == len(oracle.bk_pivot(g))
    assert res.stats["spans"] >= 1
    assert not res.iters_exhausted
    want = dict(STREAM_TRIPS[gname])
    phase = want.pop("phase")
    assert {k: res.stats[k] for k in want} == want
    # every segment after a span's first (the next slab's, and the last
    # slab's drain) starts with an outer trip on which no phase may be due
    slabs = len(prepare(g, bucket_sizes=(32, 64)).buckets)
    assert phase <= res.stats["phase_trips"] <= phase + slabs
    assert res.stats["phase_trips"] <= res.stats["iters"]


@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_stream_spanning_enumerates_same_sets_on_hub_graphs(gname):
    """Enumerated-set parity through the stream-global out_root decode:
    lanes cross bucket boundaries mid-subtree and may adopt stolen branch
    sets, so each emitted clique's root index must still decode to the
    right (bucket, local root) universe."""
    g = plant_hub(GRAPHS[gname]())
    ref = run(g, enumerate_cliques=True, bucket_sizes=(32, 64),
              engine="perroot")
    res = run(g, enumerate_cliques=True, bucket_sizes=(32, 64),
              engine="persistent", lanes=6)
    assert not res.overflow and not ref.overflow
    assert set(res.enumerated) == set(ref.enumerated)
    assert set(res.enumerated) == set(oracle.bk_pivot(g))


STEAL_BACKENDS = list(PIVOT_BACKENDS)   # 'rcd' has no branch set to split


@pytest.mark.parametrize("backend", STEAL_BACKENDS)
def test_steal_on_off_parity_and_steal_counter(backend):
    """Stealing is pure scheduling: identical counters either way, with
    the steal counter live on the hub fixture and pinned to zero off."""
    # blob=40/p=0.6: big enough that graph reduction does not collapse
    # the hub, so idle lanes really do adopt stolen branch sets
    g = skewed_graph(blob=40, p=0.6)
    on = run(g, backend=backend, bucket_sizes=(64,), engine="persistent",
             lanes=8, steal=True)
    off = run(g, backend=backend, bucket_sizes=(64,), engine="persistent",
              lanes=8, steal=False)
    assert (on.cliques, on.calls, on.branches, on.sum_px) == \
           (off.cliques, off.calls, off.branches, off.sum_px)
    assert on.cliques == len(oracle.bk_pivot(g))
    assert on.stats["steals"] > 0
    assert off.stats["steals"] == 0


@pytest.mark.parametrize("backend", STEAL_BACKENDS)
def test_steal_enumerates_same_sets(backend):
    g = skewed_graph(blob=40, p=0.6)
    on = run(g, backend=backend, enumerate_cliques=True, bucket_sizes=(64,),
             engine="persistent", lanes=8, steal=True)
    off = run(g, backend=backend, enumerate_cliques=True,
              bucket_sizes=(64,), engine="persistent", lanes=8, steal=False)
    assert not on.overflow and not off.overflow
    assert set(on.enumerated) == set(off.enumerated)
    assert set(on.enumerated) == set(oracle.bk_pivot(g))


@pytest.mark.parametrize("backend", STEAL_BACKENDS)
def test_steal_victim_policies_bit_identical(backend):
    """The steal victim policy (branchiest vs deepest) is pure
    scheduling: bit-identical counters either way."""
    g = skewed_graph(blob=40, p=0.6)
    br = run(g, backend=backend, bucket_sizes=(64,), engine="persistent",
             lanes=8, steal=True, steal_victim="branchiest")
    de = run(g, backend=backend, bucket_sizes=(64,), engine="persistent",
             lanes=8, steal=True, steal_victim="deepest")
    assert (br.cliques, br.calls, br.branches, br.sum_px) == \
           (de.cliques, de.calls, de.branches, de.sum_px)
    assert br.cliques == len(oracle.bk_pivot(g))
    assert br.stats["steals"] > 0
    assert de.stats["steals"] > 0


# The queue's counters on the hub fixture (skewed_graph(blob=40, p=0.6),
# one U=64 bucket, 8 lanes), as a loop that runs the refill and steal
# conds on every trip counts them; `phase` is the number of those trips
# on which a refill was due or a steal could move work.
QUEUE_TRIPS = {
    ("pivot", True, None): dict(
        iters=216, live_iters=1657, claimed=85, steals=9, entry_terms=50,
        truncated=0, cliques=1344, calls=1188, branches=1103, sum_px=7590,
        phase=38),
    ("pivot", False, None): dict(
        iters=229, live_iters=1648, claimed=85, steals=0, entry_terms=50,
        truncated=0, cliques=1344, calls=1188, branches=1103, sum_px=7590,
        phase=30),
    ("pivot", True, 40): dict(
        iters=40, live_iters=320, claimed=54, steals=0, entry_terms=46,
        truncated=1, cliques=349, calls=262, branches=208, sum_px=1443,
        phase=7),
    ("rcd", True, None): dict(
        iters=315, live_iters=2419, claimed=85, steals=0, entry_terms=50,
        truncated=0, cliques=1344, calls=1773, branches=1688, sum_px=12211,
        phase=33),
    ("hybrid", True, None): dict(
        iters=215, live_iters=1648, claimed=85, steals=13, entry_terms=57,
        truncated=0, cliques=1344, calls=1188, branches=1103, sum_px=7590,
        phase=42),
    ("hybrid", False, 40): dict(
        iters=40, live_iters=320, claimed=54, steals=0, entry_terms=46,
        truncated=1, cliques=353, calls=264, branches=210, sum_px=1450,
        phase=7),
}


@pytest.mark.parametrize(
    "backend,steal,max_iters", sorted(QUEUE_TRIPS, key=str),
    ids=[f"{b}-steal_{'on' if s else 'off'}-{'cut' if m else 'drain'}"
         for b, s, m in sorted(QUEUE_TRIPS, key=str)])
def test_queue_trips_unchanged_by_the_inner_loop(backend, steal, max_iters):
    """The inner loop runs only the trips on which the refill and steal
    conds would be the identity, so every counter, a `max_iters` cut
    included, is the one-cond-per-trip walk's; `phase_trips` counts the
    trips on which a phase was due. A drained walk's useful lane steps
    are the lock-step walk's, plus one per entry-terminated root and one
    pop per stolen branch set."""
    g = skewed_graph(blob=40, p=0.6)
    args = _bucket_args(g)
    kw = {} if max_iters is None else {"max_iters": max_iters}
    cfg = EngineConfig(backend=backend, steal=steal, **kw)
    out = run_bucket_persistent(*args, cfg, lanes=8)
    want = dict(QUEUE_TRIPS[backend, steal, max_iters])
    assert int(out["phase_trips"]) == want.pop("phase")
    got = {k: int(np.asarray(out[k]).sum()) for k in want}
    assert got == want
    assert int(out["phase_trips"]) <= int(out["iters"])
    if max_iters is None:
        ref = run_bucket(*args, EngineConfig(backend=backend))
        assert int(out["cliques"].sum()) == int(ref["cliques"].sum())
        assert int(out["live_iters"]) == (int(ref["iters"].sum())
                                          + got["entry_terms"]
                                          + got["steals"])


def test_hybrid_entry_terms_counted_in_refill():
    """Hybrid early termination inside the persistent refill: dense-blob
    roots complete within their entry call and must be tallied."""
    g = GRAPHS["caveman"]()
    ref = run(g, backend="hybrid", engine="perroot")
    res = run(g, backend="hybrid", engine="persistent", lanes=8)
    assert (res.cliques, res.calls, res.branches, res.sum_px) == \
           (ref.cliques, ref.calls, ref.branches, ref.sum_px)
    assert res.stats["entry_terms"] > 0


# ---------------------------------------------------------------------------
# run_stream_persistent: span formation and the stream-global root index
# ---------------------------------------------------------------------------

def test_stream_persistent_single_span_across_same_shape_slabs():
    """Two same-shape slabs form ONE span: no drain at their boundary,
    and the merged counters match the single-bucket reference."""
    g = GRAPHS["er"]()
    args = _bucket_args(g)
    h = args[0].shape[0] // 2
    slab1 = tuple(x[:h] for x in args)
    slab2 = tuple(x[h:] for x in args)
    outs, spans = run_stream_persistent([slab1, slab2], EngineConfig(),
                                        lanes=4)
    assert spans == [(0, 2)]
    ref = run_bucket(*args, EngineConfig())
    for k in ("cliques", "calls", "branches", "sum_px"):
        assert int(outs[0][k].sum()) == int(ref[k].sum()), k
    assert int(outs[0]["truncated"]) == 0


def test_stream_persistent_shape_change_flushes_span():
    """A shape change must flush the open span (different frame shapes
    cannot share one compiled loop); the per-span outputs still sum to
    the per-slab reference."""
    g = gen.erdos_renyi(150, 0.4, seed=3)
    prep = prepare(g, bucket_sizes=(32, 64))
    slabs = [tuple(jnp.asarray(x) for x in
                   (b.a, b.p0, b.x_rows, b.x_alive0, b.rsz0))
             for b in prep.buckets]
    sigs = [(s[0].shape[1], s[0].shape[2], s[2].shape[1]) for s in slabs]
    assert len(set(sigs)) >= 2, "fixture must mix bucket shapes"
    outs, spans = run_stream_persistent(slabs, EngineConfig(), lanes=8)
    # spans tile [0, len(slabs)) contiguously, one per run of equal sigs
    assert spans[0][0] == 0 and spans[-1][1] == len(slabs)
    for (alo, ahi), (blo, bhi) in zip(spans, spans[1:]):
        assert ahi == blo
    for lo, hi in spans:
        assert len({sigs[i] for i in range(lo, hi)}) == 1
    want = 0
    for s in slabs:
        out = run_bucket_persistent(*s, EngineConfig(),
                                    lanes=min(8, s[0].shape[0]))
        want += int(out["cliques"].sum())
    assert sum(int(o["cliques"].sum()) for o in outs) == want


def test_stream_persistent_out_root_is_stream_global():
    """Enumeration across a span boundary: out_root must index into the
    whole stream (slab prefix sums), not restart at 0 per slab."""
    g = GRAPHS["ba"]()
    args = _bucket_args(g)
    r = args[0].shape[0]
    h = r // 2
    slab1 = tuple(x[:h] for x in args)
    slab2 = tuple(x[h:] for x in args)
    cfg = EngineConfig(out_cap=2048)
    outs, spans = run_stream_persistent([slab1, slab2], cfg, lanes=4)
    assert spans == [(0, 2)]
    out = jax.tree.map(np.asarray, outs[0])
    assert not out["overflow"].any()
    roots = {int(out["out_root"][l, k])
             for l in range(out["out_n"].shape[0])
             for k in range(int(out["out_n"][l]))}
    assert roots and all(0 <= x < r for x in roots)
    assert max(roots) >= h, "second slab's cliques must carry global ids"


# ---------------------------------------------------------------------------
# Mid-stream elastic restart (4 -> 2 shards) through a bucket boundary
# with steals in flight
# ---------------------------------------------------------------------------

# indented to match the f-string bodies below: run_py dedents the
# concatenation, so both halves must share one indentation level
_HUB_GRAPH_SRC = """
        import numpy as np
        from repro.graph import barabasi_albert
        from repro.graph.csr import from_edge_list
        _g = barabasi_albert(300, 3, seed=7)
        _rng = np.random.default_rng(7)
        _extra = [(i, j) for i in range(24) for j in range(i + 1, 24)
                  if _rng.random() < 0.7]
        _e = np.concatenate([_g.edges().astype(np.int64),
                             np.array(_extra, np.int64)])
        _key = _e[:, 0] * 300 + _e[:, 1]
        _e = _e[np.unique(_key, return_index=True)[1]]
        g = from_edge_list(300, _e)
"""


def test_midstream_elastic_restart_with_steals(tmp_path):
    """Preempt the persistent driver mid-stream under 4 shards — past a
    bucket-size boundary, on the hub fixture so steals are in flight —
    then resume under 2: the elastic cursor must land on exactly the
    remaining roots, and the settled steal counter must show the queue
    actually stole across the run."""
    ck = str(tmp_path / "spanning.json")
    out4 = run_py(_HUB_GRAPH_SRC + f"""
        from repro.core.driver import DistributedMCE
        drv = DistributedMCE(g, chunk=16, ckpt_path={ck!r},
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
    out2 = run_py(_HUB_GRAPH_SRC + f"""
        from repro.core.driver import DistributedMCE
        from repro.core import bitset_engine
        ref = bitset_engine.run(g, bucket_sizes=(32, 64))
        drv = DistributedMCE(g, chunk=16, ckpt_path={ck!r},
                             bucket_sizes=(32, 64), stream_roots=64,
                             engine="persistent", lanes=8)
        res = drv.run(resume=True)
        print("CLIQUES", res.cliques, ref.cliques)
        print("STEALS", int(drv.last_counters.get("steals", 0)))
        assert res.cliques == ref.cliques
        assert res.calls == ref.calls
        assert not res.iters_exhausted
        assert int(drv.last_counters.get("steals", 0)) > 0
    """, devices=2)
    assert "CLIQUES" in out2
