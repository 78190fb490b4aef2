"""CPU tests of the lock-step path's per-layer metrics on plain data:
`engine.lockstep_share` (device self time of the lock-step chunk programs)
and `engine.lockstep_occupancy` (the lock-step buckets' lane counters)."""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import phases, spec  # noqa: E402

SHARE = spec.load_metric("engine.lockstep_share")
OCCUPANCY = spec.load_metric("engine.lockstep_occupancy")


def traced(program_ns: dict, idle_ns: float = 0.0):
    """A run context whose traced query reduced to `program_ns`."""
    busy = sum(program_ns.values())
    p = phases.Phases(busy_ns=busy, phase_ns={phases.CONTROL: busy},
                      kernel_ns=0.0, program_ns=program_ns,
                      stage_idle={phases.NO_SPAN: idle_ns}, n_ops=4,
                      scoped=False, has_kernels=False)
    return types.SimpleNamespace(trace=object(), phases=p, queries=[])


def query(buckets: dict) -> dict:
    return {"driver": {"buckets": buckets}}


def counts(live, lane):
    return {"calls": 1, "sum_px": 1, "live_iters": live, "lane_iters": lane}


def test_lockstep_share_reads_the_lockstep_programs_only():
    ctx = traced({"jit__sharded_counts_impl(11)": 300.0,
                  "jit__lockstep_counts(12)": 600.0,
                  "jit__lockstep_counts(13)": 100.0})
    assert SHARE.read(ctx) == pytest.approx(70.0)


def test_lockstep_share_is_none_without_a_lockstep_program():
    assert SHARE.read(traced({"jit__sharded_counts_impl(11)": 5.0})) is None
    assert SHARE.read(types.SimpleNamespace(trace=None, queries=[])) is None


def test_lockstep_occupancy_sums_the_perroot_buckets_of_every_query():
    q = query({(256, 128, "perroot"): counts(30, 40),
               (128, 256, "persistent"): counts(9, 1000)})
    q2 = query({(256, 128, "perroot"): counts(30, 40),
                (32, 256, "perroot"): counts(10, 40)})
    ctx = types.SimpleNamespace(trace=None, queries=[q, q2])
    assert OCCUPANCY.read(ctx) == pytest.approx(100.0 * 70 / 120)


def test_lockstep_occupancy_is_none_without_a_perroot_bucket():
    q = query({(64, 512, "persistent"): counts(9, 10)})
    assert OCCUPANCY.read(types.SimpleNamespace(queries=[q])) is None
    assert OCCUPANCY.read(types.SimpleNamespace(queries=[])) is None
