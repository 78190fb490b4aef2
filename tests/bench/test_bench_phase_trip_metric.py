"""CPU tests of `engine.phase_trip_share`, the share of the persistent
queue's loop trips that ran its refill and steal conds, on plain data."""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import spec  # noqa: E402

SHARE = spec.load_metric("engine.phase_trip_share")


def ctx_of(*bucket_dicts):
    return types.SimpleNamespace(
        trace=None, queries=[{"driver": {"buckets": b}} for b in bucket_dicts])


def counts(trips, phase_trips=None, lanes=64):
    b = {"calls": 1, "sum_px": 1, "live_iters": trips * lanes,
         "lane_iters": trips * lanes, "trips": trips}
    if phase_trips is not None:
        b["phase_trips"] = phase_trips
    return b


def test_phase_trip_share_sums_the_persistent_buckets_of_every_query():
    q = {(64, 512, "persistent"): counts(15667, 5490),
         (32, 256, "persistent"): counts(268, 198),
         (256, 128, "perroot"): counts(668, 0)}
    q2 = {(64, 512, "persistent"): counts(100, 20)}
    assert SHARE.read(ctx_of(q, q2)) == pytest.approx(
        100.0 * (5490 + 198 + 20) / (15667 + 268 + 100))


def test_phase_trip_share_is_none_without_a_persistent_bucket():
    q = {(256, 128, "perroot"): counts(668, 0)}
    assert SHARE.read(ctx_of(q)) is None
    assert SHARE.read(ctx_of()) is None
    assert SHARE.read(types.SimpleNamespace(
        trace=None, queries=[{"driver": {}}])) is None


def test_phase_trip_share_is_none_where_the_program_counts_no_phase_trips():
    """A driver that folds no `phase_trips` (and no `trips`) into its
    buckets gives nothing, and raises nothing."""
    q = {(64, 512, "persistent"): {"calls": 1, "sum_px": 1,
                                   "live_iters": 9, "lane_iters": 10}}
    assert SHARE.read(ctx_of(q)) is None
