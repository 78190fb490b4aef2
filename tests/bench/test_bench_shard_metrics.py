"""CPU tests of the root-parallel cell's metrics: `engine.shard_balance`,
`engine.shard_occupancy` and `device.busy_spread` on plain data, and a run
of a four-chip cell on four virtual CPU devices."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import spec, trace  # noqa: E402

BALANCE = spec.load_metric("engine.shard_balance")
SHARD_OCC = spec.load_metric("engine.shard_occupancy")
OCC = spec.load_metric("engine.occupancy")
SPREAD = spec.load_metric("device.busy_spread")
TRAFFIC = os.path.join(ROOT, "bench", "traffic", "recount.json")
METRICS = ("engine.shard_balance", "engine.shard_occupancy",
           "device.busy_spread")


def bucket(live, shard_lane):
    """A bucket's counters as the driver folds them, `shard_lane` being
    its per-shard lane-trips; the live ones are dealt in proportion."""
    lane = sum(shard_lane)
    b = {"calls": 1, "sum_px": 1, "live_iters": live, "lane_iters": lane,
         "trips": lane // 64, "phase_trips": 0}
    for key in ("calls", "sum_px", "trips", "phase_trips"):
        b["shard_" + key] = [0] * len(shard_lane)
    b["shard_live_iters"] = [live * s // lane for s in shard_lane]
    b["shard_lane_iters"] = list(shard_lane)
    return b


def query(*buckets):
    per = {(32 * (i + 1), 2, "persistent"): b for i, b in enumerate(buckets)}
    return {"stats": {"live_iters": sum(b["live_iters"] for b in buckets),
                      "lane_iters": sum(b["lane_iters"] for b in buckets)},
            "driver": {"buckets": per}}


def ctx_of(*queries, busy_ns=None):
    t = (trace.Summary(window_ns=1000.0, busy_ns=list(busy_ns), top_ops=[],
                       gaps=[], n_ops=1) if busy_ns is not None else None)
    return types.SimpleNamespace(chips=1, trace=t, queries=list(queries))


def test_one_shard_is_balanced_and_its_occupancy_is_the_engines():
    ctx = ctx_of(query(bucket(900, [1024]), bucket(50, [128])))
    assert BALANCE.read(ctx) == pytest.approx(100.0)
    assert SHARD_OCC.read(ctx) == pytest.approx(OCC.read(ctx))
    assert SHARD_OCC.read(ctx) == pytest.approx(100.0 * 950 / 1152)


def test_four_even_shards():
    ctx = ctx_of(query(bucket(3000, [1024] * 4)),
                 query(bucket(3000, [1024] * 4)))
    assert BALANCE.read(ctx) == pytest.approx(100.0)
    assert SHARD_OCC.read(ctx) == pytest.approx(OCC.read(ctx))
    assert SHARD_OCC.read(ctx) == pytest.approx(100.0 * 3000 / 4096)


def test_four_uneven_shards_count_the_wait_for_the_slowest():
    """The issue's U=64 bucket of graph500 on four shards, and a bucket
    split evenly beside it: the slowest shard sets each bucket's span."""
    lanes = [5214 * 64, 5288 * 64, 5569 * 64, 4627 * 64]
    ctx = ctx_of(query(bucket(913_000, lanes), bucket(700, [768] * 4)))
    top = 5569 * 64 + 768
    mean = sum(lanes) / 4 + 768
    assert BALANCE.read(ctx) == pytest.approx(100.0 * mean / top)
    assert SHARD_OCC.read(ctx) == pytest.approx(
        100.0 * 913_700 / (4 * top))
    assert SHARD_OCC.read(ctx) < OCC.read(ctx)
    assert BALANCE.read(ctx) < 100.0


def test_buckets_without_shard_counters_give_nothing():
    """A driver that keeps no per-shard counters, as before they existed,
    gives nothing, and raises nothing."""
    old = {"calls": 1, "sum_px": 1, "live_iters": 9, "lane_iters": 10,
           "trips": 1, "phase_trips": 0}
    ctx = ctx_of({"stats": {"live_iters": 9, "lane_iters": 10},
                  "driver": {"buckets": {(32, 2, "persistent"): old}}})
    assert BALANCE.read(ctx) is None
    assert SHARD_OCC.read(ctx) is None
    assert BALANCE.read(ctx_of()) is None
    assert SHARD_OCC.read(ctx_of()) is None
    assert SHARD_OCC.read(ctx_of({"driver": {}})) is None


def test_busy_spread_reads_the_per_chip_busy_union():
    assert SPREAD.read(ctx_of(busy_ns=[800.0, 700.0, 600.0, 800.0])) == \
        pytest.approx(25.0)
    assert SPREAD.read(ctx_of(busy_ns=[500.0] * 4)) == pytest.approx(0.0)
    assert SPREAD.read(ctx_of(busy_ns=[500.0])) == pytest.approx(0.0)


def test_busy_spread_without_a_device_trace_gives_nothing():
    assert SPREAD.read(ctx_of()) is None
    assert SPREAD.read(ctx_of(busy_ns=[])) is None
    assert SPREAD.read(ctx_of(busy_ns=[0.0, 0.0])) is None


def test_the_new_metrics_are_listed_for_the_four_chip_cell_only():
    bench = spec.load_benchmark()
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["graph500_s12_4chips.recount"]
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    for name in METRICS:
        assert listed[name] == four
    cell = spec.load_cell(four[0])
    assert set(METRICS) <= {m["name"] for m in cell.per_layer}


def test_a_four_chip_tiny_kronecker_cell_is_correct_on_cpu():
    """`harness.run_cell` on four virtual CPU devices, traced: every
    answer is the reference's, and the per-shard counters are read (the
    CPU has no device plane, so `device.busy_spread` stays out)."""
    code = f"""
        import json, sys, time
        sys.path.insert(0, {ROOT!r})
        import jax
        from bench import harness, spec
        bench = spec.load_benchmark()
        per_layer = [m for m in bench["per_layer"]
                     if m["name"] in {list(METRICS)!r}
                     or m["name"] == "engine.occupancy"]
        with open({TRAFFIC!r}) as f:
            traffic = json.load(f)
        cell = spec.Cell(
            name="tiny_kron.recount", chips=4,
            config={{"graph": {{"generator": "kronecker", "scale": 8,
                                "edge_factor": 16, "seed": 1, "a": 0.57,
                                "b": 0.19, "c": 0.19}}}},
            traffic=traffic, end_to_end=bench["end_to_end"],
            per_layer=per_layer)
        devices = jax.devices()[:4]
        assert len(devices) == 4, devices
        out = harness.run_cell(cell, 2**31 + 17, 0.2, True, devices,
                               time.perf_counter(), log=lambda s: None)
        print("RESULT " + json.dumps(out))
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert run.returncode == 0, run.stderr[-3000:]
    line = [ln for ln in run.stdout.splitlines() if ln.startswith("RESULT ")]
    out = json.loads(line[-1][len("RESULT "):])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert out["device"]["count"] == 4
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert "device.busy_spread" not in m
    assert 0 < m["engine.shard_balance"] <= 100
    assert 0 < m["engine.shard_occupancy"] <= m["engine.occupancy"] <= 100
