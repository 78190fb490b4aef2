"""Layered bitset Bron–Kerbosch MCE engine (DESIGN.md §2).

The CPU paper's recursive, pointer-chasing search is re-derived as
fixed-shape bitset dataflow, split into swappable layers:

* `prepare`    — fixed-shape containers + one-shot materializing API
* `pipeline`   — staged streaming ingest (reduce → order → stage → pack),
                 yielding `RootBucket`s incrementally (`PrepStream`)
* `frames`     — frame/stack layout, config, counter carry
* `reductions` — dynamic degree-0/1/|P|−1 lemmas as pure frame functions
* `pivot`      — pivot/branch-selection strategies behind one interface
* `loop`       — the `lax.while_loop` DFS driver + single-host `run()`

All bitset set algebra dispatches through `repro.kernels.bitset_ops.ops`
(Pallas on TPU, jnp elsewhere) — the single choke point for the paper's
73.6%-of-time set intersections. `repro.core.bitset_engine` remains as a
thin re-export shim for existing callers.
"""
from repro.core.engine.frames import (BACKENDS, EngineConfig,  # noqa: F401
                                      Frame, FrameStack, PIVOT_BACKENDS)
from repro.core.engine.loop import (MCEResult, choose_engine,  # noqa: F401
                                    dfs_step, enter_call, root_cost_skew,
                                    run, run_bucket, run_bucket_persistent,
                                    run_lockstep, run_root,
                                    run_stream_persistent)
from repro.core.engine.pipeline import PrepStream, RootSpec  # noqa: F401
from repro.core.engine.prepare import (PreparedMCE, RootBucket,  # noqa: F401
                                       estimate_costs, prepare)
