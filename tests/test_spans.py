"""Host spans and device scopes: the program's names on the profiler's
clock (core/spans.py), and the per-bucket counters the driver folds."""
from __future__ import annotations

import contextlib
import glob
import os
import re

import jax
import pytest

from repro.core import driver
from repro.core.driver import HOST_PACK_SPANS, DistributedMCE
from repro.core.engine import EngineConfig
from repro.core.spans import span
from repro.graph.generators import erdos_renyi
from repro.launch.mce_service import MCEService

SCOPES = ("engine.refill", "engine.steal", "engine.step",
          "kernels.bitset_ops")
PREP_SPANS = ("prep.reduce", "prep.order", "prep.stage", "prep.pack")
DRIVER_SPANS = HOST_PACK_SPANS + ("driver.settle",)
DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def tiny_graph():
    return erdos_renyi(120, 0.3, seed=1)


def strip_metadata(hlo: str) -> str:
    """Optimized HLO text without op metadata and the debug tables."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line in DEBUG_TABLES:
            skip = True
        elif not line.strip():
            skip = False
        if not skip:
            out.append(re.sub(r",? ?metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def compiled_step(engine: str, monkeypatch, scopes: bool) -> str:
    """The tiny graph's last chunk program, traced afresh, as HLO text."""
    jax.clear_caches()
    if not scopes:
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
    drv = DistributedMCE(tiny_graph(), chunk=64, engine=engine, lanes=8,
                         cfg=EngineConfig(backend="pivot"))
    drv.run()
    text = drv.compiled_step().as_text()
    monkeypatch.undo()
    jax.clear_caches()
    return text


@pytest.mark.parametrize("engine,want", [
    ("persistent", SCOPES), ("perroot", ("engine.step", "kernels.bitset_ops"))])
def test_chunk_program_carries_the_scopes_as_metadata_only(engine, want,
                                                          monkeypatch):
    """The scopes reach the HLO `op_name` of the chunk program; with the
    metadata stripped the program is the one built without them. The
    per-root engine has no refill or steal phase."""
    text = compiled_step(engine, monkeypatch, scopes=True)
    names = "\n".join(re.findall(r'op_name="([^"]*)"', text))
    assert {s for s in SCOPES if s in names} == set(want)
    bare = compiled_step(engine, monkeypatch, scopes=False)
    assert not any(s in bare for s in SCOPES)
    assert strip_metadata(text) == strip_metadata(bare)


def test_span_adds_its_seconds_to_the_accumulator():
    acc = {"stage": 1.0}
    with span("prep.stage", acc, "stage"):
        pass
    with span("driver.fetch", acc, bucket=0, chunk=0):
        pass
    assert acc["stage"] > 1.0 and acc["driver.fetch"] > 0.0


def _host_events(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return [(e.name, dict(e.stats) if e.name.startswith("driver.") else {})
            for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU" for line in p.lines
            for e in line.events]


def test_query_trace_holds_every_host_span(tmp_path):
    """One traced `MCEService.query` writes every prep and driver stage on
    the host's timeline, the driver's with bucket and chunk ids, and the
    driver's span seconds add up to host_pack_s plus settle."""
    svc = MCEService(tiny_graph(), chunk=64, stream_roots=32,
                     engine="persistent", lanes=8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = svc.query(EngineConfig(backend="pivot"))
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    names = {n for n, _ in events}
    assert set(PREP_SPANS + DRIVER_SPANS) <= names
    for name, ids in events:
        if name.startswith("driver."):
            assert {"bucket", "chunk"} <= set(ids), name
    stats = svc.last_driver.stats
    assert set(stats["spans"]) == set(DRIVER_SPANS)
    assert stats["host_pack_s"] == pytest.approx(
        sum(stats["spans"][s] for s in HOST_PACK_SPANS))
    assert sum(stats["spans"].values()) == pytest.approx(
        stats["host_pack_s"] + stats["spans"]["driver.settle"])
    assert set(svc.stream.timings) == {"reduce", "order", "stage", "pack"}
    assert all(t > 0 for t in svc.stream.timings.values())
    assert res.cliques > 0 and stats["chunks"] > 1


def test_driver_folds_counters_per_bucket_shape():
    """stats["buckets"] splits the run's work by chunk program: its sums
    are the run's counters."""
    drv = DistributedMCE(tiny_graph(), chunk=16, stream_roots=32,
                         engine="auto", lanes=8)
    res = drv.run()
    per = drv.stats["buckets"]
    assert per and all(len(k) == 3 and k[2] in ("perroot", "persistent")
                       for k in per)
    for key in driver.BUCKET_KEYS:
        assert sum(v[key] for v in per.values()) == drv.last_counters[key]
    assert sum(v["calls"] for v in per.values()) == res.calls
