"""R2 good twin: the tile-local pivot kernel that replaced the PR-1 bug.

Each grid step writes only its own output block, exactly once, from its
own inputs — idempotent and batch-safe; the argmax over tile scores runs
in jnp outside the kernel (the current bitset_ops design).
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pivot_kernel(rows_ref, mask_ref, score_ref):
    anded = rows_ref[...] & mask_ref[...]
    pc = jax.lax.population_count(anded).astype(jnp.int32)
    score_ref[...] = jnp.sum(pc, axis=1, keepdims=True)


def pivot_scores(rows, mask):
    k, w = rows.shape
    return pl.pallas_call(
        _pivot_kernel,
        grid=(k // 8,),
        in_specs=[pl.BlockSpec((8, w), lambda i: (i, 0)),
                  pl.BlockSpec((1, w), lambda i: (0, 0))],
        out_shape=jax.ShapeDtypeStruct((k, 1), jnp.int32),
        out_specs=pl.BlockSpec((8, 1), lambda i: (i, 0)),
    )(rows, mask)
