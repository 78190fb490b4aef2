"""R2 bad fixture: minimized copy of the PR-1 cross-grid pivot kernel.

The original bug: pivot scores were accumulated into the output block
across grid steps, with a `program_id(0) == 0` init. Under `jax.vmap`
the batching rule prepends the batch axis to the grid, so program_id(0)
became the *batch* index — every batch member after the first skipped
the init and folded its scores into the previous member's accumulator.
Wrong pivots, wrong (but plausible) clique counts.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pivot_kernel(rows_ref, mask_ref, best_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        best_ref[...] = jnp.zeros_like(best_ref)            # EXPECT-R2

    anded = rows_ref[...] & mask_ref[...]
    pc = jax.lax.population_count(anded).astype(jnp.int32)
    score = jnp.sum(pc, axis=1, keepdims=True)
    best_ref[...] = jnp.maximum(best_ref[...], score)       # EXPECT-R2


def pivot_scores(rows, mask):
    k, w = rows.shape
    return pl.pallas_call(
        _pivot_kernel,
        grid=(k // 8,),
        in_specs=[pl.BlockSpec((8, w), lambda i: (i, 0)),
                  pl.BlockSpec((1, w), lambda i: (0, 0))],
        out_shape=jax.ShapeDtypeStruct((1, 128), jnp.int32),
        out_specs=pl.BlockSpec((1, 128), lambda i: (0, 0)),
    )(rows, mask)
