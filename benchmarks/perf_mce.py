"""§Perf hillclimb measurements for the MCE engine cells (paper's technique).

Measures the trip-count-weighted per-DFS-iteration roofline terms of the
shard_map'ed counting kernel on the production mesh, current engine vs the
flag-gated paper-faithful degree pass (reuse_degrees=False). The step
returns its counters per shard, with no cross-shard reduction.

Iterations 2 (straight-line masked DFS, no lax.cond→select) and 3 (packed
bitset X-alive stacks) are structural rewrites; their before/after numbers
were measured during the hillclimb and are recorded in EXPERIMENTS.md §Perf.

  XLA_FLAGS=--xla_force_host_platform_device_count=512 \
      PYTHONPATH=src python -m benchmarks.perf_mce
"""
from __future__ import annotations

import json
import sys


def run(out_json: str | None = None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.engine import EngineConfig
    from repro.core.driver import _sharded_counts
    from repro.launch.hlo_cost import analyze
    from repro.launch.mesh import data_axes, make_production_mesh

    mesh = make_production_mesh(multi_pod=False)
    dp = data_axes(mesh)
    n_shards = 1
    for a in dp:
        n_shards *= mesh.shape[a]

    cells = [("web_sparse", 1024, 64, 64), ("social_mid", 512, 256, 256),
             ("dense_core", 128, 1024, 1024), ("orkut_scale", 256, 512, 2048)]
    rows = []
    for name, r, u, xc in cells:
        w = u // 32
        sp = P(dp)

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(
                shape, dt, sharding=NamedSharding(mesh, sp))

        args = (sds((n_shards, r, u, w), jnp.uint32),
                sds((n_shards, r, w), jnp.uint32),
                sds((n_shards, r, xc, w), jnp.uint32),
                sds((n_shards, r, xc), jnp.bool_),
                sds((n_shards, r), jnp.int32))
        for label, cfg in [
                ("paper-3sweep", EngineConfig(max_iters=1 << 20,
                                              reuse_degrees=False)),
                ("opt-reuse-deg", EngineConfig(max_iters=1 << 20,
                                               reuse_degrees=True))]:
            def fn(a_, p_, x_, l_, z_, cfg=cfg):
                return _sharded_counts(a_, p_, x_, l_, z_, cfg, mesh, dp)

            with mesh:
                c = jax.jit(fn).lower(*args).compile()
            wk = analyze(c.as_text())
            print(f"{name:12s} {label:14s} flops/iter={wk['flops']:.4e} "
                  f"bytes/iter={wk['bytes']:.4e} "
                  f"tm/iter={wk['bytes']/819e9*1e3:.3f}ms", flush=True)
            rows.append(dict(cell=name, variant=label, flops=wk["flops"],
                             bytes=wk["bytes"], link=wk["link"]))
    if out_json:
        with open(out_json, "w") as f:
            json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    run(sys.argv[1] if len(sys.argv) > 1 else None)
