"""Distributed MCE runtime: shard_map fan-out, load balancing, checkpointing.

Deployment model for 1000+ nodes (DESIGN.md §5–§6):

* Root subproblems are independent — MCE is data-parallel over roots. The
  production mesh's `pod` × `data` axes form the root-parallel dimension;
  `model` stays size-1 for MCE (a bitset subtree does not split further
  without work-stealing, which SPMD forbids; instead we over-decompose).
* **Streaming ingest**: the driver consumes `RootBucket`s from a
  `PrepStream` as the host packs them, and runs **double-buffered**: chunk
  *k* is dispatched asynchronously, then the host
  packs and uploads chunk *k+1* while the device works, and only then
  blocks on chunk *k*'s counters. The host never sits between the device
  and its next batch. Each host stage (fetch, gather, upload, dispatch,
  settle) is a span on the profiler's clock (`core/spans.py`), and its
  seconds land in `stats["spans"]`.
* **Straggler mitigation** is static balancing: per bucket, roots are sorted
  by a cost estimate (|P|·2^{λ̂} proxy: universe² × mean row popcount) and
  dealt round-robin across shards, so each shard receives the same cost mass
  (LPT-style). Lockstep waste inside a batch of lanes is bounded by chunking:
  each shard processes `chunk` roots per device step, so a pathological root
  stalls one chunk, not the epoch.
* **Fault tolerance**: after every chunk the accumulated counters + cursor
  are checkpointed host-side. The cursor counts roots completed in the
  *canonical cost-descending order* — a pure function of the prepared graph
  and the stream parameters, NOT of the device count — so an *elastic*
  restart with a different device count resumes at exactly the same root
  (tested in tests/test_distributed.py and tests/test_prep_stream.py).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Iterator, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import (BACKENDS, EngineConfig, MCEResult,
                               PIVOT_BACKENDS, PreparedMCE, PrepStream,
                               RootBucket, choose_engine, estimate_costs,
                               root_cost_skew, run_bucket_persistent,
                               run_lockstep)
from repro.core.spans import span
from repro.graph.csr import CSRGraph

# "truncated" folds each chunk's iters-exhausted flags so a max_iters cutoff
# surfaces as MCEResult.iters_exhausted instead of silently partial counts.
# "live_iters"/"lane_iters" are the occupancy pair (useful lane-trips vs
# lane-trip capacity): occupancy = live/lane. The perroot engine's
# equivalent is Σ per-root iters over max(iters)·lanes — the lock-step walk
# runs every lane until the slowest root finishes, which is exactly the
# idle time the persistent queue reclaims (surfaced per query through
# MCEService.stats). "steals"/"entry_terms" only move on the persistent
# engine (adopted branch-set halves and claims that finished inside their
# entry call); the perroot path zero-fills them so the counter schema —
# and every checkpoint written against it — is engine-independent.
# "trips" counts the chunk loop's trips (lane_iters / lanes) and
# "phase_trips" the persistent queue's trips that ran its refill and steal
# conds (zero on the perroot path, which has none).
# Checkpoints from before a key existed resume via `.get` in `_settle`.
COUNTER_KEYS = ("cliques", "calls", "branches", "sum_px", "truncated",
                "live_iters", "lane_iters", "steals", "entry_terms",
                "trips", "phase_trips")
# the work each chunk program does, folded per (u_pad, x_pad, engine) into
# stats["buckets"]: the operands of a work-based kernel roofline, and the
# loop's trips beside the ones that ran the queue's phases
BUCKET_KEYS = ("calls", "sum_px", "live_iters", "lane_iters", "trips",
               "phase_trips")
# the host stages that `host_pack_s` sums: everything but the settle
HOST_PACK_SPANS = ("driver.fetch", "driver.gather", "driver.upload",
                   "driver.dispatch")


# ---------------------------------------------------------------------------
# Cost-balanced root scheduling (cost model lives in engine.prepare)
# ---------------------------------------------------------------------------


def canonical_order(costs: np.ndarray) -> np.ndarray:
    """Cost-descending stable order — the shard-count-INDEPENDENT schedule.

    Elasticity contract: the checkpoint cursor counts *roots completed in
    this order*; a restart with any device count resumes at the same root."""
    return np.argsort(-costs, kind="stable")


def deal_roots(costs: np.ndarray, n_shards: int) -> List[np.ndarray]:
    """Sort by cost desc, deal round-robin -> per-shard root index lists."""
    order = canonical_order(costs)
    return [order[s::n_shards] for s in range(n_shards)]


# ---------------------------------------------------------------------------
# Sharded bucket execution
# ---------------------------------------------------------------------------

def _graph_fingerprint(g: CSRGraph) -> List[int]:
    """Cheap O(m) identity of a CSR graph for the checkpoint schedule.

    The cursor indexes a bucket sequence that is a pure function of the
    graph too (DESIGN.md §6.4); a position-weighted xor fold of the
    adjacency catches resuming against a different graph, not just
    different stream parameters."""
    idx = g.indices.astype(np.uint64)
    weights = np.arange(1, len(idx) + 1, dtype=np.uint64)
    h = int(np.bitwise_xor.reduce(idx * weights)) if len(idx) else 0
    return [g.n, g.m, h]


def _shard_batch(bucket: RootBucket, idx: np.ndarray, pad_to: int):
    """Gather + pad a per-shard slice of a bucket (pad roots are no-ops)."""
    take = idx[:pad_to] if len(idx) >= pad_to else idx
    pad = pad_to - len(take)
    a = bucket.a[take]
    p0 = bucket.p0[take]
    xr = bucket.x_rows[take]
    xa = bucket.x_alive0[take]
    rz = bucket.rsz0[take]
    if pad:
        w = bucket.a.shape[2]
        a = np.concatenate([a, np.zeros((pad,) + bucket.a.shape[1:], np.uint32)])
        p0 = np.concatenate([p0, np.zeros((pad, w), np.uint32)])  # empty P -> no-op
        xr = np.concatenate([xr, np.zeros((pad,) + bucket.x_rows.shape[1:], np.uint32)])
        xa = np.concatenate([xa, np.zeros((pad, bucket.x_rows.shape[1]), bool)])
        rz = np.concatenate([rz, np.ones(pad, np.int32)])
    return a, p0, xr, xa, rz


def _sharded_counts_impl(a, p0, xr, xa, rz, cfg: EngineConfig, mesh: Mesh,
                         axis, engine: str = "perroot", lanes: int = 64):
    """Run a [n_shards, chunk, ...] batch under shard_map; psum counters.

    `axis` is a mesh axis name or a tuple of axis names (multi-pod: roots
    shard over the flattened ("pod", "data") product). `engine='persistent'`
    runs each shard's chunk through the lane-refill work queue — the
    chunk's cost-descending slice order IS the queue order — instead of
    one lock-step lane per root (`run_lockstep`)."""

    def per_shard(a_s, p_s, xr_s, xa_s, rz_s):
        if engine == "persistent":
            L = min(lanes, a_s.shape[1])
            out = run_bucket_persistent(
                a_s[0], p_s[0], xr_s[0], xa_s[0], rz_s[0], cfg, lanes=L)
            out = dict(out, lane_iters=out["iters"] * L, trips=out["iters"])
        else:
            out = run_lockstep(a_s[0], p_s[0], xr_s[0], xa_s[0], rz_s[0],
                               cfg)
            # lock-step equivalent of the queue's occupancy pair: every
            # lane spins until the slowest root's DFS exhausts
            trips = jnp.max(out["iters"])
            out = dict(out, live_iters=jnp.sum(out["iters"]),
                       lane_iters=trips * a_s.shape[1], trips=trips,
                       steals=jnp.int32(0), entry_terms=jnp.int32(0),
                       phase_trips=jnp.int32(0))
        sums = {k: jnp.sum(out[k]).astype(jnp.int32)[None]
                for k in COUNTER_KEYS}
        return sums

    specs_in = (P(axis), P(axis), P(axis), P(axis), P(axis))
    specs_out = {k: P(axis) for k in COUNTER_KEYS}
    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=specs_in,
                       out_specs=specs_out, check_vma=False)
    out = fn(a, p0, xr, xa, rz)
    return {k: jnp.sum(v) for k, v in out.items()}


def _lockstep_counts(a, p0, xr, xa, rz, cfg: EngineConfig, mesh: Mesh, axis):
    """The lock-step chunk program: `_sharded_counts_impl` on the lock-step
    walk, jitted under a name of its own."""
    return _sharded_counts_impl(a, p0, xr, xa, rz, cfg, mesh, axis,
                                engine="perroot")


class _ChunkStep:
    """The chunk step the driver dispatches, one jitted function per engine,
    so that a device trace names each engine's program apart: the
    persistent queue runs as `jit__sharded_counts_impl`, the lock-step
    walk as `jit__lockstep_counts`. `lanes` only shapes the persistent
    program.

    One program per chunk shape and static arguments. Its inputs are not
    donated: the step returns only scalar counters, so no output could
    alias a chunk buffer, and the driver drops each chunk's arrays after
    the dispatch anyway."""

    def __init__(self):
        self._persistent = jax.jit(
            _sharded_counts_impl,
            static_argnames=("cfg", "mesh", "axis", "engine", "lanes"))
        self._lockstep = jax.jit(_lockstep_counts,
                                 static_argnames=("cfg", "mesh", "axis"))

    def _pick(self, engine: str, lanes: int):
        if engine == "persistent":
            return self._persistent, dict(engine=engine, lanes=lanes)
        return self._lockstep, {}

    def __call__(self, *args, engine: str = "perroot", lanes: int = 64,
                 **kw):
        fn, static = self._pick(engine, lanes)
        return fn(*args, **static, **kw)

    def lower(self, *args, engine: str = "perroot", lanes: int = 64, **kw):
        fn, static = self._pick(engine, lanes)
        return fn.lower(*args, **static, **kw)


_sharded_counts = _ChunkStep()


@dataclasses.dataclass
class DriverCheckpoint:
    bucket: int = 0
    roots_done: int = 0            # cursor in canonical (cost-desc) order —
    counters: dict = dataclasses.field(  # shard-count independent (elastic)
        default_factory=lambda: {k: 0 for k in COUNTER_KEYS})
    schedule: dict = dataclasses.field(default_factory=dict)
    # ^ identity of the bucket sequence the cursor indexes (stream params or
    # materialized bucket shapes). The cursor is only meaningful against the
    # SAME sequence; run() refuses to resume against a different one.

    def save(self, path: str) -> None:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(dataclasses.asdict(self), f)
        os.replace(tmp, path)  # atomic: a torn write never corrupts resume

    @staticmethod
    def load(path: str) -> "DriverCheckpoint":
        with open(path) as f:
            d = json.load(f)
        return DriverCheckpoint(bucket=d["bucket"],
                                roots_done=d["roots_done"],
                                counters=d["counters"],
                                schedule=d.get("schedule", {}))


class DistributedMCE:
    """Chunked, checkpointed, shard_map-parallel MCE over a device mesh.

    Ingest is streaming by default: buckets arrive from a `PrepStream` and
    the run loop keeps one chunk in flight (see module docstring). Pass
    `streaming=False` for the legacy materialize-everything-first mode
    (exposed as `.prep`), or hand in an existing `PrepStream`/`PreparedMCE`
    via `prep=` to reuse packed buckets across runs (launch.mce_service).
    """

    def __init__(self, g: Optional[CSRGraph] = None, *,
                 mesh: Optional[Mesh] = None,
                 axis: str = "data", chunk: int = 1024,
                 ckpt_path: Optional[str] = None,
                 cfg: EngineConfig = EngineConfig(),
                 global_red: bool = True, x_red: bool = True,
                 bucket_sizes: Sequence[int] = (32, 64, 128, 256, 512, 1024),
                 max_x_rows: int = 8192,
                 split_threshold: Optional[int] = None,
                 streaming: bool = True, stream_roots: int = 1024,
                 prep: Union[PrepStream, PreparedMCE, None] = None,
                 engine: str = "perroot", lanes: int = 64):
        if engine not in ("perroot", "persistent", "auto"):
            raise ValueError(f"unknown engine {engine!r}")
        if cfg.backend not in BACKENDS:
            raise ValueError(f"unknown backend {cfg.backend!r} "
                             f"(expected one of {BACKENDS})")
        self.engine = engine
        self.lanes = lanes
        if mesh is None:
            mesh = jax.make_mesh((len(jax.devices()),), ("data",))
            axis = "data"
        self.mesh = mesh
        self.axis = axis if isinstance(axis, (tuple, list)) else (axis,)
        self.axis = tuple(self.axis)
        self.n_shards = int(np.prod([mesh.shape[a] for a in self.axis]))
        self.chunk = chunk
        self.cfg = cfg
        self.ckpt_path = ckpt_path
        # spans: host seconds per driver stage (core/spans.py); buckets:
        # BUCKET_KEYS folded per (u_pad, x_pad, engine) at each settle
        self.stats = {"host_pack_s": 0.0, "chunks": 0, "spans": {},
                      "buckets": {},
                      "engine_choices": {"perroot": 0, "persistent": 0}}
        self.last_counters: dict = {}   # COUNTER_KEYS of the last run()
        self._last_step = None          # (arg shapes, engine kwargs)
        self.prep: Optional[PreparedMCE] = None
        self.stream: Optional[PrepStream] = None
        if prep is not None and g is not None:
            # a prepared stream fixes the graph and every prep-shaping
            # knob; accepting both would silently run against prep's graph
            raise ValueError("pass either a graph or prep=, not both")
        if isinstance(prep, PreparedMCE):
            self.prep = prep
        elif isinstance(prep, PrepStream):
            self.stream = prep
        else:
            if g is None:
                raise ValueError("need a graph or a prepared stream")
            # cache=False: a driver-owned stream is consumed once; caching
            # every packed bucket would recreate materialized-mode peak host
            # memory (pass a PrepStream(cache=True) for service-style reuse)
            stream = PrepStream(g, global_red=global_red, x_red=x_red,
                                bucket_sizes=bucket_sizes,
                                max_x_rows=max_x_rows,
                                split_threshold=split_threshold,
                                stream_roots=stream_roots if streaming else 0,
                                cache=not streaming)
            if streaming:
                self.stream = stream
            else:
                self.prep = stream.materialize()
        if self.stream is not None:
            st = self.stream
            self._schedule = dict(
                mode="stream", graph=_graph_fingerprint(st.g),
                stream_roots=st.stream_roots,
                bucket_sizes=list(st.bucket_sizes),
                split_threshold=st.split_threshold, global_red=st.global_red,
                x_red=st.x_red, max_x_rows=st.max_x_rows)
        else:
            self._schedule = dict(
                mode="materialized", n=self.prep.n,
                buckets=[[b.u_pad, b.num_roots] for b in self.prep.buckets])

    # ---- bucket source (streamed or materialized) ------------------------

    def _buckets(self) -> Iterator[RootBucket]:
        if self.stream is not None:
            return iter(self.stream)
        return iter(self.prep.buckets)

    def run(self, resume: bool = True) -> MCEResult:
        state = DriverCheckpoint()
        if self.stream is not None:
            self.stream.front()
            pre0 = len(self.stream.pre_reported)
        else:
            pre0 = len(self.prep.pre_reported)
        state.counters["cliques"] = pre0
        if resume and self.ckpt_path and os.path.exists(self.ckpt_path):
            state = DriverCheckpoint.load(self.ckpt_path)
            if state.schedule and state.schedule != self._schedule:
                raise ValueError(
                    "checkpoint schedule mismatch: the cursor was written "
                    f"against {state.schedule} but this driver runs "
                    f"{self._schedule}; resume with identical stream "
                    "parameters (device count may differ — that is the "
                    "elastic dimension)")
        state.schedule = self._schedule

        window = self.n_shards * self.chunk
        spans = self.stats["spans"]
        pending: Optional[tuple] = None
        src = self._buckets()
        b = -1
        k = 0                               # chunks dispatched by this run
        while True:
            # streaming: the host packs here, overlapped with the device
            with span("driver.fetch", spans, bucket=b + 1, chunk=k):
                bucket = next(src, None)
            if bucket is None:
                break
            b += 1
            if b < state.bucket:
                continue                    # resume: replayed, not re-run
            # pad roots (remainder-flush pow2 padding) sit at the bucket's
            # tail; scheduling only the real prefix drops their no-op calls
            total = bucket.num_roots - bucket.n_pad
            if bucket.cost_order is None:   # memo: cached-bucket replays
                costs = estimate_costs(bucket)[:total]
                bucket.cost_order = canonical_order(costs)
                # same hardened skew as choose_engine's costs= path, so
                # memoized replays and fresh runs can't diverge (and an
                # all-zero/degenerate proxy can't explode to max/1e-12)
                bucket.cost_skew = (root_cost_skew(costs) if total else 1.0)
            order = bucket.cost_order
            eng_b, lanes_b = self.engine, self.lanes
            if self.engine == "auto":
                # the skew memo avoids re-deriving costs on cached replays;
                # the choice is a pure function of the bucket, so replays
                # and resumes land on the same engine
                eng_b, lanes_b = choose_engine(
                    skew=bucket.cost_skew, n_roots=total, lanes=self.lanes,
                    steal=bool(self.cfg.steal)
                    and self.cfg.backend in PIVOT_BACKENDS)
                self.stats["engine_choices"][eng_b] += 1
            done = state.roots_done if b == state.bucket else 0
            while done < total:
                hi = min(done + window, total)
                out, n_pad = self._run_chunk(bucket, order[done:hi],
                                             eng_b, lanes_b, b, k)
                if pending is not None:
                    self._settle(pending, state)
                pending = (out, n_pad, b, hi, k,
                           (bucket.u_pad, bucket.x_pad, eng_b))
                done = hi
                k += 1
        if pending is not None:
            self._settle(pending, state)
        self.stats["host_pack_s"] = sum(spans.get(s, 0.0)
                                        for s in HOST_PACK_SPANS)

        late = len(self.stream.late_reported) if self.stream is not None else 0
        self.last_counters = dict(state.counters)
        return MCEResult(cliques=state.counters["cliques"] + late,
                         calls=state.counters["calls"],
                         branches=state.counters["branches"],
                         sum_px=state.counters["sum_px"],
                         pre_reported=pre0 + late,
                         iters_exhausted=state.counters.get("truncated", 0) > 0)

    # ---- chunk pipeline --------------------------------------------------

    def _run_chunk(self, bucket: RootBucket, window: np.ndarray,
                   engine: str, lanes: int, b: int, k: int):
        """Gather/pad + upload + *asynchronously* dispatch one chunk.

        `engine`/`lanes` are per-bucket: under engine="auto" the driver
        resolves them from the bucket's cost skew before each chunk; `b`
        and `k` are the bucket and chunk ids its spans carry.
        Returns (unrealized device counters, n_pad); the caller settles the
        previous chunk after dispatching this one, so host pack/upload of
        chunk k+1 overlaps device execution of chunk k."""
        spans = self.stats["spans"]
        with span("driver.gather", spans, bucket=b, chunk=k):
            slices = [window[s::self.n_shards] for s in range(self.n_shards)]
            pad_to = max(len(s) for s in slices)
            parts = [_shard_batch(bucket, s, pad_to) for s in slices]
            n_pad = sum(pad_to - len(s) for s in slices)
            stacked = [np.stack([p[i] for p in parts]) for i in range(5)]
        sharding = NamedSharding(self.mesh, P(self.axis))
        with span("driver.upload", spans, bucket=b, chunk=k):
            a, p0, xr, xa, rz = (jax.device_put(t, sharding) for t in stacked)
        # a cold shape traces and compiles (or loads from the cache) here
        with span("driver.dispatch", spans, bucket=b, chunk=k):
            out = _sharded_counts(a, p0, xr, xa, rz, self.cfg, self.mesh,
                                  self.axis, engine=engine, lanes=lanes)
        self._last_step = (
            tuple(jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
                  for x in (a, p0, xr, xa, rz)),
            dict(engine=engine, lanes=lanes))
        return out, n_pad

    def compiled_step(self):
        """The compiled program of the last chunk step this driver ran, to
        inspect (`as_text()`, `input_shardings`); compiles it again."""
        shapes, kw = self._last_step
        return _sharded_counts.lower(*shapes, cfg=self.cfg, mesh=self.mesh,
                                     axis=self.axis, **kw).compile()

    def _settle(self, pending, state: DriverCheckpoint) -> None:
        """Block on a dispatched chunk, fold counters, checkpoint cursor."""
        out, n_pad, b, hi, k, shape = pending
        with span("driver.settle", self.stats["spans"], bucket=b, chunk=k):
            out = {key: int(np.asarray(v)) for key, v in out.items()}
            self.stats["chunks"] += 1
            # padded no-op roots contribute exactly one call each; remove
            # them so distributed counters match the single-host run
            # bit-for-bit
            out["calls"] = out["calls"] - n_pad
            for key in COUNTER_KEYS:
                # .get: checkpoints written before a counter key existed
                # resume cleanly (the missing key starts from zero)
                state.counters[key] = state.counters.get(key, 0) + out[key]
            per = self.stats["buckets"].setdefault(
                shape, {key: 0 for key in BUCKET_KEYS})
            for key in BUCKET_KEYS:
                per[key] += out[key]
            state.bucket, state.roots_done = b, hi
            if self.ckpt_path:
                state.save(self.ckpt_path)
