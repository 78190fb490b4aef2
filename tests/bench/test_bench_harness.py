"""CPU tests of the chip benchmark under bench/: its files, its yardstick
(generators, reference count) and its run, driven here on a tiny graph."""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import control, graphs, harness, reference, spec  # noqa: E402

BENCH = spec.load_benchmark()
TINY_GRAPH = {"generator": "erdos_renyi", "n": 120, "p": 0.3, "seed": 1}
SEED = 2**31 + 17        # the driver's seeds pass 32 signed bits


@pytest.fixture
def jax_config():
    """Put back what a run sets in JAX's global config (the compile cache
    and the source-path regex), so later tests in this worker see none of
    it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_hlo_source_file_canonicalization_regex")
    saved = {k: getattr(jax.config, k) for k in names}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def tiny_cell(per_layer=(), root=spec.ROOT) -> spec.Cell:
    with open(os.path.join(root, "bench", "traffic", "recount.json")) as f:
        traffic = json.load(f)
    e2e = [m for m in BENCH["end_to_end"]]
    return spec.Cell(name="tiny.recount", chips=1,
                     config={"graph": TINY_GRAPH},
                     traffic=traffic, end_to_end=e2e,
                     per_layer=list(per_layer), root=root)


def run_tiny(cell, traced=False, seconds=0.2, seed=SEED):
    import jax

    return harness.run_cell(cell, seed, seconds, traced, jax.devices()[:1],
                            time.perf_counter(), log=lambda s: None)


# ---- the files --------------------------------------------------------------

def test_every_benchmark_file_loads():
    names = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips in (1, 4)
        assert {"setup_s"} <= {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert cell.config["graph"]["generator"] in graphs.GENERATORS
        assert cell.traffic["queries"]
    for m in BENCH["per_layer"]:
        assert callable(spec.load_metric(m["name"]).read)
        assert set(m.get("workloads", names)) <= names
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert spec.load_cell  # configs load through their cells above
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        spec.load_peaks("TPU v4")


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_generator_copies_give_the_configured_graph(config):
    """n, m and degeneracy as the configuration states them, and the same
    degree sequence as the program's generator."""
    from repro.graph import generators as program_gen

    conf = spec.load_cell(next(w["name"] for w in BENCH["workloads"]
                               if w["config"] == config)).config
    want = conf["expect"]
    n, indptr, indices = graphs.build(conf["graph"])
    assert (n, len(indices) // 2) == (want["n"], want["m"])
    assert reference.peel_order(n, indptr, indices)[1] == want["degeneracy"]
    g = conf["graph"]
    if g["generator"] == "kronecker":
        prog = program_gen.kronecker(g["scale"], g["edge_factor"],
                                     seed=g["seed"], a=g["a"], b=g["b"],
                                     c=g["c"])
    else:
        prog = program_gen.erdos_renyi(g["n"], g["p"], seed=g["seed"])
    np.testing.assert_array_equal(np.diff(indptr), prog.degrees())


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_reference_counts_the_configured_cliques(config):
    conf = spec.load_cell(next(w["name"] for w in BENCH["workloads"]
                               if w["config"] == config)).config
    n, indptr, indices = graphs.build(conf["graph"])
    assert reference.count_maximal_cliques(n, indptr, indices) == \
        conf["expect"]["maximal_cliques"]


@pytest.mark.parametrize("graph", [
    {"generator": "erdos_renyi", "n": 60, "p": 0.4, "seed": 5},
    {"generator": "kronecker", "scale": 7, "edge_factor": 8, "seed": 2,
     "a": 0.57, "b": 0.19, "c": 0.19},
])
def test_reference_agrees_with_the_host_oracle(graph):
    from repro.core import oracle
    from repro.graph.csr import CSRGraph

    n, indptr, indices = graphs.build(graph)
    want = len(oracle.bk_pivot(CSRGraph(indptr, indices)))
    assert reference.count_maximal_cliques(n, indptr, indices) == want


# ---- the run ----------------------------------------------------------------

def test_entry_point_refuses_a_cpu(capsys):
    from bench import run

    name = BENCH["workloads"][0]["name"]
    assert run.main(["--workload", name, "--seed", str(SEED),
                     "--seconds", "1"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_recount_run_is_correct_on_cpu(jax_config):
    out = run_tiny(tiny_cell())
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"setup_s", "query_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["checks"]["count_gap"] == {"value": 0, "limit": 0}


def test_traced_run_reports_program_metrics_on_cpu(jax_config):
    """On the CPU there is no device plane: trace metrics stay out of the
    line, the program's counters and spans are read."""
    out = run_tiny(tiny_cell(BENCH["per_layer"]), traced=True)
    assert out["correct"]
    assert {"prep_s", "driver.host_s", "engine.occupancy"} <= \
        set(out["metrics"])
    assert 0 < out["metrics"]["engine.occupancy"]["value"] <= 100


def _zero_counts(real):
    def step(a, p0, xr, xa, rz, cfg, mesh, axis, engine, lanes):
        out = real(a, p0, xr, xa, rz, cfg, mesh, axis, engine=engine,
                   lanes=lanes)
        return {k: v * 0 for k, v in out.items()}
    return step


def _half_batch(real):
    def step(a, p0, xr, xa, rz, cfg, mesh, axis, engine, lanes):
        keep = np.arange(p0.shape[1]) < p0.shape[1] // 2
        p0 = p0 * keep[None, :, None].astype(p0.dtype)
        return real(a, p0, xr, xa, rz, cfg, mesh, axis, engine=engine,
                    lanes=lanes)
    return step


def _answer_off_by_one(real):
    def step(a, p0, xr, xa, rz, cfg, mesh, axis, engine, lanes):
        out = real(a, p0, xr, xa, rz, cfg, mesh, axis, engine=engine,
                   lanes=lanes)
        return dict(out, cliques=out["cliques"] + 1)
    return step


@pytest.mark.parametrize("fault", [_zero_counts, _half_batch,
                                   _answer_off_by_one],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch, jax_config):
    from repro.core import driver

    monkeypatch.setattr(driver, "_sharded_counts",
                        fault(driver._sharded_counts))
    out = run_tiny(tiny_cell())
    assert not out["correct"] and out["failed"] == out["attempted"]
    assert out["checks"]["count_gap"]["value"] > 0


def test_control_is_not_correct(jax_config):
    """The control (every query capped by max_iters) fails the comparison
    on three seeds, as it must on the chip."""
    cell = control.truncated(tiny_cell(), max_iters=8)
    for seed in (SEED, SEED + 1, SEED + 2):
        out = run_tiny(cell, seed=seed)
        assert not out["correct"]
        assert out["checks"]["count_gap"]["value"] > 0
        assert out["checks"]["truncated"]["value"] > 0


# ---- data-driven: a new cell is new files -----------------------------------

def test_new_config_traffic_and_metric_are_new_files(tmp_path, jax_config):
    """A cell, configuration, traffic mix and per-layer metric added as new
    files plus BENCHMARK.json entries are found, with no existing file
    edited."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    with open(os.path.join(root, "bench", "configs", "tiny_gnp.json"),
              "w") as f:
        json.dump({"graph": TINY_GRAPH}, f)
    with open(os.path.join(root, "bench", "traffic", "twice.json"), "w") as f:
        json.dump({"queries": [{"cfg": {"backend": "pivot"},
                                "engine": "auto"},
                               {"cfg": {"backend": "pivot"},
                                "engine": "perroot"}]}, f)
    with open(os.path.join(root, "bench", "metrics", "queries.count.py"),
              "w") as f:
        f.write("def read(ctx):\n    return len(ctx.queries)\n")
    bench["configs"].append({"name": "tiny_gnp", "source": "test",
                             "file": "bench/configs/tiny_gnp.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_gnp.twice", "config": "tiny_gnp",
                               "traffic": "twice", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "queries.count", "unit": "queries",
                               "better": "higher", "source": "host_clock",
                               "layer": "service", "moves": "query_s",
                               "workloads": ["tiny_gnp.twice"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = spec.load_cell("tiny_gnp.twice", root=root)
    assert [m["name"] for m in cell.per_layer if "workloads" in m] == \
        ["queries.count"]
    out = run_tiny(cell, traced=True)
    assert out["correct"] and out["attempted"] >= 1
    assert out["metrics"]["queries.count"]["value"] == out["attempted"]
    for w in BENCH["workloads"]:      # the old cells do not report it
        assert "queries.count" not in {
            m["name"] for m in spec.load_cell(w["name"], root=root).per_layer}
