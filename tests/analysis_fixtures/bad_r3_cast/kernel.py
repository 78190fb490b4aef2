"""R3 bad fixture: unsigned <-> float casts inside a kernel.

Mosaic refuses `uint32 -> float32` (and back): float32 accumulation of
a uint32 popcount kept every bitset kernel from compiling for the chip.
The cast is flagged in the kernel body and in a module helper the
kernel calls, whose body runs inside the kernel too.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _row_counts(x):
    pc = jax.lax.population_count(x).astype(jnp.float32)     # EXPECT-R3
    return jnp.sum(pc, axis=1, keepdims=True)


def _degree_kernel(rows_ref, mask_ref, deg_ref, words_ref):
    anded = jnp.bitwise_and(rows_ref[...], mask_ref[...])
    deg_ref[...] = _row_counts(anded).astype(jnp.int32)
    low = jnp.bitwise_and(anded, jnp.uint32(0) - anded)
    pos = jax.lax.population_count(low - jnp.uint32(1))
    wi = jax.lax.broadcasted_iota(jnp.float32, anded.shape, 1)
    words_ref[...] = wi * 32.0 + pos.astype(jnp.float32)     # EXPECT-R3
    words_ref[...] = jax.lax.convert_element_type(           # EXPECT-R3
        pos, jnp.float32)


def degrees(rows, mask):
    k, w = rows.shape
    return pl.pallas_call(
        _degree_kernel,
        grid=(k // 8,),
        in_specs=[pl.BlockSpec((8, w), lambda i: (i, 0)),
                  pl.BlockSpec((1, w), lambda i: (0, 0))],
        out_shape=(jax.ShapeDtypeStruct((k, 1), jnp.int32),
                   jax.ShapeDtypeStruct((k, w), jnp.float32)),
        out_specs=(pl.BlockSpec((8, 1), lambda i: (i, 0)),
                   pl.BlockSpec((8, w), lambda i: (i, 0))),
    )(rows, mask[None, :])
