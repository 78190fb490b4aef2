"""Dispatching wrapper for bitset set algebra — the engine's ONLY entry point.

Layering contract (DESIGN.md §3): every module outside `kernels/bitset_ops`
that needs bitset algebra (AND+popcount sweeps, fused pivot-select, batched
X-subset tests) calls this module. Nothing outside this package may import
`ref` or `kernel` directly (enforced by tests/test_engine_layering.py), so
there is exactly one choke point to measure, swap, and accelerate.

On TPU the Pallas kernels are used for the 2-D shapes the engine's hot loop
emits; on CPU (this container) the pure-jnp ref is both the oracle and the
execution path (the Pallas kernels are validated in interpret mode by
tests). The engine's semantics never depend on the path taken.

Batching: the `ndim` guards below only catch *explicit* leading batch dims
(a caller handing in a 3-D array falls back to ref). They can NOT catch
`jax.vmap` — inside vmap the per-example tracer is 2-D, so the pallas path
is taken and jax's pallas batching rule prepends the batch axis to the
kernel grid. That IS the engine's real call pattern (`loop.step_lanes`
vmaps `dfs_step` over lanes), so the kernels are written batch-safe (no
`program_id` reads, no revisited output blocks — see kernel.py) and vmap
parity is tested per kernel in tests/test_bitset_ops_dispatch.py.

Every public entry point traces under the device scope `kernels.bitset_ops`
(`jax.named_scope`), so a profiler trace can sum this layer's device time;
the scope is metadata only.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.bitset_ops import kernel, ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _scoped(fn):
    """Trace `fn` under the device scope `kernels.bitset_ops`."""
    @functools.wraps(fn)
    def inner(*args, **kwargs):
        with jax.named_scope("kernels.bitset_ops"):
            return fn(*args, **kwargs)
    return inner


@_scoped
def popcount_words(bits: jnp.ndarray) -> jnp.ndarray:
    """Total set-bit count over the trailing word axis: (..., W) -> (...)."""
    return jnp.sum(jax.lax.population_count(bits), axis=-1).astype(jnp.int32)


@_scoped
def and_popcount_rows(rows: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """popcount(rows & mask) per row; dispatches pallas on TPU, jnp elsewhere.

    Explicit leading batch dims take the ref path; under jax.vmap the
    tracer is 2-D so the pallas path is taken and the pallas_call itself
    is batched (see module docstring).
    """
    if _on_tpu() and rows.ndim == 2:
        return kernel.and_popcount_rows(rows, mask, interpret=False)
    return ref.and_popcount_rows(rows, mask)


@_scoped
def and_rows(rows: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """rows & mask broadcast over the row axis (materialised intersection)."""
    return ref.and_rows(rows, mask)


@_scoped
def and_popcount_argmax(rows: jnp.ndarray, mask: jnp.ndarray,
                        valid: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused pivot-select: (first-argmax, max) of popcount(rows & mask) over
    `valid` rows; invalid rows score -1. On TPU the AND+popcount+masking
    fuse in one Pallas pass and the argmax runs in jnp on the scores."""
    if _on_tpu() and rows.ndim == 2 and valid is not None:
        return kernel.and_popcount_argmax(rows, mask, valid, interpret=False)
    return ref.and_popcount_argmax(rows, mask, valid)


@_scoped
def and_popcount_many(rows: jnp.ndarray, masks: jnp.ndarray) -> jnp.ndarray:
    """out[m, k] = popcount(rows[k] & masks[m]) — one row matrix against an
    (M, W) batch of masks (the X-subset maximality-test shape)."""
    if _on_tpu() and rows.ndim == 2 and masks.ndim == 2:
        return kernel.and_popcount_many(rows, masks, interpret=False)
    return ref.and_popcount_many(rows, masks)


@_scoped
def clique_counts(rows: jnp.ndarray, mask: jnp.ndarray, in_p: jnp.ndarray,
                  in_x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused early-termination census (hybrid backend): (n_full, n_dom).

    n_full = #{k : in_p[k] ∧ popcount(rows[k] & mask) == popcount(mask)−1},
    n_dom  = #{k : in_x[k] ∧ popcount(rows[k] & mask) == popcount(mask)}.
    With rows = adjacency ∪ X0 rows and mask = P: P induces a clique iff
    n_full == |P|, and some forbidden vertex dominates P iff n_dom > 0 —
    one row-vs-mask batch popcount decides emit-and-pop vs recurse."""
    if _on_tpu() and rows.ndim == 2:
        return kernel.clique_counts(rows, mask, in_p, in_x, interpret=False)
    return ref.clique_counts(rows, mask, in_p, in_x)


@_scoped
def frame_step(rows: jnp.ndarray, p: jnp.ndarray, xp: jnp.ndarray,
               wrow: jnp.ndarray):
    """Fused BK frame step: (childp, childxp, deg, partner).

    childp = p & wrow, childxp = xp & wrow, deg[k] = popcount(rows[k] &
    childp), partner[k] = the surviving bit index where deg[k] == 1 (the
    Lemma-7 partner; garbage elsewhere). One kernel pass replaces the
    engine's separate child-AND, degree-sweep, and partner-extraction
    passes over the (K, W) adjacency."""
    if _on_tpu() and rows.ndim == 2:
        return kernel.frame_step(rows, p, xp, wrow, interpret=False)
    return ref.frame_step(rows, p, xp, wrow)
