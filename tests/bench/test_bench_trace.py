"""CPU tests of the benchmark's trace reduction."""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import spec, trace  # noqa: E402


def test_merge_and_gaps():
    merged = trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert trace.gaps_between(merged, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert trace.clip(merged, 2, 6) == [(2, 3), (5, 6)]


def test_reduce_plain_data():
    """Two chips, two traced queries; busy union, top ops, gaps by host span."""
    host = [("query", 100, 100), ("query", 250, 50),
            ("PjitFunction(step)", 200, 40)]
    ops = {0: [("fusion.1", 90, 30),           # 10 ns before the window
               ("frame_step.3", 120, 30),
               ("frame_step.3", 140, 20),      # overlaps the one above
               ("copy.2", 260, 40)],
           1: [("and_popcount_rows.1", 100, 200)]}
    s = trace.reduce(ops, host)
    assert s.window_ns == 200
    assert s.busy_ns == [20 + 40 + 40, 200]
    assert s.n_ops == 5
    assert s.busy_s == pytest.approx((100 + 200) / 2 * 1e-9)
    # chip 0 idles from 160 to 260; the host is then in its step call
    assert s.gaps == [("PjitFunction(step)", 100)]
    assert s.top_ops[0] == ("and_popcount_rows.1", 200)
    assert dict(s.top_ops)["frame_step.3"] == 50
    bd = s.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_reduce_without_query_or_chip_is_nothing():
    assert trace.reduce({0: [("a", 0, 1)]}, []) is None
    assert trace.reduce({}, [("query", 0, 1)]) is None


def test_idle_share_reader():
    ctx = type("Ctx", (), {})()
    ctx.trace = trace.reduce({0: [("k.1", 0, 1000), ("k.1", 2000, 1000)]},
                             [("query", 0, 4000)])
    assert spec.load_metric("device.idle_share").read(ctx) == \
        pytest.approx(50.0)
    ctx.trace = None
    assert spec.load_metric("device.idle_share").read(ctx) is None
