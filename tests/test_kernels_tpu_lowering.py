"""Compile the bitset kernels for a described TPU v5e, without the chip.

Every parity test runs the Pallas kernels in interpret mode, which
accepts programs the chip's compiler refuses (uint32 -> float32 casts,
unsigned reductions, misaligned blocks, scratch that overflows VMEM).
Here each kernel, and the driver's jitted chunk step, goes through the
real TPU compiler (`jax.jit(...).lower(...).compile()`) against a
described `v5e:2x2` topology: shapes only, nothing runs. Cases:

* the original shapes, plain and vmapped (the engine's lane step vmaps
  `dfs_step`, so the pallas_calls compile with the batch axis
  prepended to the grid);
* every kernel at the engine's real bucket widths: W = U/32 for
  U in 32..1024 (`configs/rmce.py`), chunks of 1024 roots;
* the chunk step `_sharded_counts` on a one-device mesh at the
  `web_sparse` and `dense_core` shapes, for each engine path;
* the persistent chunk step at the graph500_s12 benchmark's U=64 shape,
  whose lane step and refill may hold no element gather of a `pred` array,
  and whose innermost loop trip holds no cond and no NOT of the lane X
  rows;
* the lock-step and the persistent chunk steps at every bucket width,
  each under its own HLO module name: the two widest buckets of
  G(2000, 0.1) (U=256 lock-step, U=128 persistent) and the widths no
  benchmark cell runs yet, and the U=256 lock-step one with no select
  over its whole stack;
* the chunk step of the four-chip cell's U=64 bucket on the described
  host's four chips, for each engine path, with no collective.

The topology is described inside a module fixture (never at import),
which skips the file where libtpu cannot describe it.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitset_ops import kernel as bk

U32, I32, BOOL = jnp.uint32, jnp.int32, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described device's program cannot be read back from the
    # persistent cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def S(one_chip):
    """Shape on the described chip: S(shape, dtype=uint32)."""
    return lambda shape, dt=U32: jax.ShapeDtypeStruct(shape, dt,
                                                      sharding=one_chip)


def _compile_tpu(f, *args):
    text = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# Default block sizes, K forcing both multi-tile grids and pad remainders.
K, W, M = 515, 8, 33


def test_lower_and_popcount_rows(S):
    _compile_tpu(lambda r, m: bk.and_popcount_rows(r, m),
                 S((K, W)), S((W,)))


def test_lower_and_popcount_argmax(S):
    _compile_tpu(lambda r, m, v: bk.and_popcount_argmax(r, m, v),
                 S((K, W)), S((W,)), S((K,), BOOL))


def test_lower_and_popcount_many(S):
    _compile_tpu(lambda r, ms: bk.and_popcount_many(r, ms),
                 S((K, W)), S((M, W)))


@pytest.mark.parametrize("k,m,w", [
    (100, 300, 32),               # shrinks bm with bk == K (single k tile)
    (600, 300, 32),               # shrinks bm with multiple k tiles
    (2000, 8, 512),               # bm floor reached, shrinks bk to 128
])
def test_lower_and_popcount_many_vmem_clamp(S, k, m, w):
    """Shapes that trip the VMEM tile clamp must still produce
    compilable blocks (shrunk dims 8-/128-divisible or full-array)."""
    _compile_tpu(lambda r, ms: bk.and_popcount_many(r, ms),
                 S((k, w)), S((m, w)))


def test_lower_clique_counts(S):
    _compile_tpu(lambda r, m, p, x: bk.clique_counts(r, m, p, x),
                 S((K, W)), S((W,)), S((K,), BOOL), S((K,), BOOL))


def test_lower_frame_step(S):
    _compile_tpu(lambda r, p, x, wr: bk.frame_step(r, p, x, wr),
                 S((K, W)), S((W,)), S((W,)), S((W,)))


# Vmapped: the engine's lane step vmaps dfs_step, so on TPU the
# pallas_calls compile with the batch axis prepended to the grid — compile
# exactly that.

B = 3


def test_lower_vmapped_and_popcount_rows(S):
    _compile_tpu(jax.vmap(bk.and_popcount_rows), S((B, K, W)), S((B, W)))


def test_lower_vmapped_and_popcount_argmax(S):
    _compile_tpu(jax.vmap(bk.and_popcount_argmax),
                 S((B, K, W)), S((B, W)), S((B, K), BOOL))


def test_lower_vmapped_and_popcount_many(S):
    _compile_tpu(jax.vmap(bk.and_popcount_many), S((B, K, W)), S((B, M, W)))


def test_lower_vmapped_clique_counts(S):
    _compile_tpu(jax.vmap(bk.clique_counts), S((B, K, W)), S((B, W)),
                 S((B, K), BOOL), S((B, K), BOOL))


def test_lower_vmapped_frame_step(S):
    _compile_tpu(jax.vmap(bk.frame_step), S((B, K, W)), S((B, W)),
                 S((B, W)), S((B, W)))


# ---------------------------------------------------------------------------
# Real bucket widths: each kernel as the engine calls it, at every bucket
# size the service packs (U = 32..1024 vertices, W = U/32 words)
# ---------------------------------------------------------------------------

CHUNK = 1024              # roots per chunk step (configs/rmce.py)
LANES = 64                # persistent-engine lanes (driver default)
UNIVERSES = (32, 64, 128, 256, 512, 1024)


def _real_case(name, S, u):
    """(fn, shapes) of one kernel at universe u: per-root calls vmapped
    over a chunk of roots, X rows padded to u as in the rmce cells."""
    w, xc, c = u // 32, u, CHUNK
    if name == "and_popcount_rows":
        return jax.vmap(bk.and_popcount_rows), (S((c, u, w)), S((c, w)))
    if name == "and_popcount_argmax":
        return (jax.vmap(bk.and_popcount_argmax),
                (S((c, u, w)), S((c, w)), S((c, u), BOOL)))
    if name == "and_popcount_many":
        # the rcd maximality test: P against X0 ∪ universe non-neighbours
        return (jax.vmap(bk.and_popcount_many),
                (S((c, 1, w)), S((c, xc + u, w))))
    if name == "clique_counts":
        return (jax.vmap(bk.clique_counts),
                (S((c, u + xc, w)), S((c, w)), S((c, u + xc), BOOL),
                 S((c, u + xc), BOOL)))
    if name == "frame_step":
        return (jax.vmap(bk.frame_step),
                (S((c, u, w)), S((c, w)), S((c, w)), S((c, w))))
    raise KeyError(name)


@pytest.mark.parametrize("u", UNIVERSES, ids=lambda u: f"U{u}")
@pytest.mark.parametrize("name", [
    "and_popcount_rows", "and_popcount_argmax", "and_popcount_many",
    "clique_counts", "frame_step"])
def test_compile_real_width(S, name, u):
    f, shapes = _real_case(name, S, u)
    _compile_tpu(f, *shapes)


def test_compile_and_popcount_many_vmem_clamp_widest(S):
    """(1024, 1024) rows x masks at the widest bucket (W = 32): the VMEM
    tile clamp must shrink the blocks to something that compiles."""
    _compile_tpu(bk.and_popcount_many, S((CHUNK, 32)), S((CHUNK, 32)))


# ---------------------------------------------------------------------------
# The driver's chunk step on a one-device mesh of the described chip
# ---------------------------------------------------------------------------

# (roots per step, U pad, X rows pad): configs/rmce.py's regimes
CELLS = {"web_sparse": (1024, 64, 64), "dense_core": (128, 1024, 1024)}


def _engine(name):
    from repro.core.engine import EngineConfig
    if name == "persistent":   # the benchmark cells' queue program
        return "persistent", EngineConfig(backend="pivot")
    return "perroot", EngineConfig(backend=name)


@pytest.mark.parametrize("engine,kernel", [
    ("pivot", "frame_step"), ("persistent", "frame_step"),
    ("hybrid", "clique_counts")])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_compile_chunk_step(topo, monkeypatch, cell, engine, kernel):
    """The jitted chunk step the driver dispatches, with the TPU kernel
    dispatch the chip takes (the test steers `ops` onto its TPU branch;
    `jax.default_backend()` here is the CPU)."""
    from repro.core import driver
    from repro.kernels.bitset_ops import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    chunk, u, xc = CELLS[cell]
    w = u // 32
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    sh = NamedSharding(mesh, P("data"))

    def S(shape, dt=U32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    eng, cfg = _engine(engine)
    text = driver._sharded_counts.lower(
        S((1, chunk, u, w)), S((1, chunk, w)), S((1, chunk, xc, w)),
        S((1, chunk, xc), BOOL), S((1, chunk), I32),
        cfg=cfg, mesh=mesh, axis=("data",), engine=eng,
        lanes=LANES).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"%{kernel}" in text, f"{kernel} missing from the {cell} step"


# ---------------------------------------------------------------------------
# The graph500_s12 benchmark's U=64 chunk step: no element gathers of
# boolean lane vectors in the engine's step or refill
# ---------------------------------------------------------------------------

# a pred gather whose every slice is one element (no offset dims)
_GATHER = re.compile(r"= pred\[[^\]]*\]\S* gather\(.*offset_dims=\{\}")
_PHASE = re.compile(r'op_name="[^"]*engine\.(step|refill)')


@pytest.fixture(scope="module")
def graph500_u64(topo):
    """The persistent U=64 chunk program of graph500_s12 (602 roots, 512 X
    rows, 64 lanes), compiled once for the tests that read it."""
    from repro.core import driver
    from repro.core.engine import EngineConfig
    from repro.kernels.bitset_ops import ops

    chunk, u, xc = 602, 64, 512
    w = u // 32
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    sh = NamedSharding(mesh, P("data"))

    def S(shape, dt=U32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        return driver._sharded_counts.lower(
            S((1, chunk, u, w)), S((1, chunk, w)), S((1, chunk, xc, w)),
            S((1, chunk, xc), BOOL), S((1, chunk), I32),
            cfg=EngineConfig(backend="pivot"), mesh=mesh, axis=("data",),
            engine="persistent", lanes=LANES).compile().as_text()


def test_chunk_step_has_no_phase_pred_gathers(graph500_u64):
    """Lemma 7's partner lookups are row tests on `A & P` (DESIGN.md §4):
    the persistent U=64 chunk program (602 roots, 512 X rows, 64 lanes)
    keeps no element gather of a `pred` array under `engine.step` or
    `engine.refill`, where a gather by the partner index costs one lookup
    per lane and vertex. (The refill's row take of a root's alive X mask
    moves whole rows and is not matched.)"""
    text = graph500_u64
    assert "%frame_step" in text
    bad = [ln.strip()[:160] for ln in text.splitlines()
           if _GATHER.search(ln) and _PHASE.search(ln)]
    assert not bad, f"{len(bad)} pred element gathers: {bad[:3]}"


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_CALLEE = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)"
                     r"=\{?(%[\w.\-]+(?:, ?%[\w.\-]+)*)")
_WHILE_BODY = re.compile(r" while\(.*body=%([\w.\-]+)")
# Lemma 8's complement of one lane X-row batch
_X_ROWS_NOT = re.compile(r"= u32\[64,512,2\]\S* not\(")


def _computations(text):
    """{computation name: its instruction lines} of an HLO module."""
    comps, cur = {}, None
    for ln in text.splitlines():
        m = _COMPUTATION.match(ln)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif ln.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(ln)
    return comps


def _reach(comps, root):
    """Every computation `root` runs, itself included."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            for ln in comps.get(c, ()):
                for m in _CALLEE.finditer(ln):
                    todo += re.findall(r"%([\w.\-]+)", m.group(1))
    return seen


def test_queue_inner_trip_has_no_cond_and_no_x_rows_not(graph500_u64):
    """The persistent segment's nested trip (DESIGN.md §2.6): an outer
    while runs the refill and steal phases under conds, and the
    innermost while runs the lane steps. Its body holds no
    `conditional`, so the lane state passes through no cond's identity
    branch, and no NOT of the `u32[64,512,2]` lane X rows, which are
    invariant there and whose complement is hoisted out of it."""
    comps = _computations(graph500_u64)
    bodies = [m.group(1) for lines in comps.values() for ln in lines
              for m in [_WHILE_BODY.search(ln)] if m]
    reach = {b: _reach(comps, b) for b in bodies}
    inner = [b for b in bodies if not any(o in reach[b] for o in bodies
                                          if o != b)]
    outer = [b for b in bodies if any(i in reach[b] for i in inner
                                      if i != b)]
    assert inner and outer, "the segment's loop is not nested"
    for b in inner:
        lines = [ln for c in reach[b] for ln in comps[c]]
        assert any("%frame_step" in ln for ln in lines)
        conds = [ln.strip()[:120] for ln in lines if " conditional(" in ln]
        nots = [ln.strip()[:120] for ln in lines if _X_ROWS_NOT.search(ln)]
        assert not conds, f"{len(conds)} conds in the inner trip: {conds}"
        assert not nots, f"{len(nots)} X-row NOTs in the inner trip: {nots}"


# ---------------------------------------------------------------------------
# The chunk programs at every bucket width, each engine under its own
# module name
# ---------------------------------------------------------------------------

# U -> (roots per chunk, X rows): the benchmark cells' buckets where one
# has that width (graph500_s12 at U=32/64, G(2000, 0.1) at U=128/256),
# `orkut_scale` (configs/rmce.py) at U=512, and a full chunk at U=1024
WIDTHS = {32: (1024, 2048), 64: (602, 512), 128: (690, 256),
          256: (734, 128), 512: (256, 2048), 1024: (CHUNK, 1024)}
MODULES = {"perroot": "jit__lockstep_counts",
           "persistent": "jit__sharded_counts_impl"}
# the persistent U=64 program is compiled by the graph500_u64 fixture
WIDE_CASES = [(e, u) for e in MODULES for u in UNIVERSES
              if (e, u) != ("persistent", 64)]


@pytest.mark.parametrize(
    "engine,u", WIDE_CASES,
    ids=[f"{'lockstep' if e == 'perroot' else e}_u{u}"
         for e, u in WIDE_CASES])
def test_compile_wide_chunk_step(topo, monkeypatch, engine, u):
    """The chunk program of each engine at each bucket width compiles for
    the chip with RMCE's config (`EngineConfig(backend="pivot")`, every
    reduction on), among them G(2000, 0.1)'s U=256 lock-step bucket (734
    roots, 128 X rows) and U=128 persistent bucket (690 roots, 256 X
    rows, 64 lanes). The lock-step program is named apart from the
    persistent one, so a device trace splits their time."""
    from repro.core import driver
    from repro.core.engine import EngineConfig
    from repro.kernels.bitset_ops import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    chunk, xc = WIDTHS[u]
    w = u // 32
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    sh = NamedSharding(mesh, P("data"))

    def S(shape, dt=U32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    text = driver._sharded_counts.lower(
        S((1, chunk, u, w)), S((1, chunk, w)), S((1, chunk, xc, w)),
        S((1, chunk, xc), BOOL), S((1, chunk), I32),
        cfg=EngineConfig(backend="pivot"), mesh=mesh, axis=("data",),
        engine=engine, lanes=LANES).compile().as_text()
    assert re.search(rf"^HloModule {MODULES[engine]}\b", text, re.M)
    assert "%frame_step" in text


@pytest.mark.parametrize("engine", ["persistent", "perroot"])
def test_four_chip_chunk_step_holds_no_collective(topo, monkeypatch, engine):
    """The chunk step of graph500_s12_4chips's U=64 bucket (151 roots and
    512 X rows a chip) on the described host's four chips: each shard's
    counters stay on its chip, so the program holds no collective, and
    each counter is `int32[4]` split over the four devices."""
    from repro.core import driver
    from repro.core.engine import EngineConfig
    from repro.kernels.bitset_ops import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    chunk, u, xc = 151, 64, 512
    w = u // 32
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    sh = NamedSharding(mesh, P("data"))

    def S(shape, dt=U32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    compiled = driver._sharded_counts.lower(
        S((4, chunk, u, w)), S((4, chunk, w)), S((4, chunk, xc, w)),
        S((4, chunk, xc), BOOL), S((4, chunk), I32),
        cfg=EngineConfig(backend="pivot"), mesh=mesh, axis=("data",),
        engine=engine, lanes=LANES).compile()
    text = compiled.as_text()
    assert "%frame_step" in text
    found = [c for c in ("all-reduce", "all-gather", "reduce-scatter",
                         "all-to-all", "collective-permute") if c in text]
    assert not found, found
    outs = compiled.output_shardings
    assert set(outs) == set(driver.COUNTER_KEYS)
    for key, out_sh in outs.items():
        assert len(out_sh.device_set) == 4, key
        assert out_sh.shard_shape((4,)) == (1,), key


# a select whose result has the lock-step stack's leading [roots, U + 2]
_STACK_SELECT = re.compile(r"= [a-z0-9]+\[734,258\b[^ ]* select\(")


def test_lockstep_step_has_no_stack_selects(topo, monkeypatch):
    """The U=256 lock-step chunk program (734 roots, 128 X rows) walks the
    bucket as one while_loop over a masked lane step (`run_lockstep`), so
    no iteration selects the new stack against the old: the program holds
    no select over a `[734,258,…]` stack field, where a while_loop under
    vmap selects every field of its carry in every trip."""
    from repro.core import driver
    from repro.core.engine import EngineConfig
    from repro.kernels.bitset_ops import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    chunk, u, xc = 734, 256, 128
    w = u // 32
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    sh = NamedSharding(mesh, P("data"))

    def S(shape, dt=U32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    text = driver._sharded_counts.lower(
        S((1, chunk, u, w)), S((1, chunk, w)), S((1, chunk, xc, w)),
        S((1, chunk, xc), BOOL), S((1, chunk), I32),
        cfg=EngineConfig(backend="pivot"), mesh=mesh, axis=("data",),
        engine="perroot").compile().as_text()
    assert re.search(r"^HloModule jit__lockstep_counts\b", text, re.M)
    assert "%frame_step" in text
    bad = [ln.strip()[:160] for ln in text.splitlines()
           if _STACK_SELECT.search(ln)]
    assert not bad, f"{len(bad)} whole-stack selects: {bad[:3]}"
