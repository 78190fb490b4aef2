"""R3 good twin: counts leave the unsigned domain through int32.

uint32 -> int32 and int32 -> float32 casts compile, and int32 reduces
over any axis, so the same kernel hops through int32 and passes clean.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _popcount(x):
    return jax.lax.population_count(x).astype(jnp.int32)


def _row_counts(x):
    return jnp.sum(_popcount(x), axis=1, keepdims=True)


def _degree_kernel(rows_ref, mask_ref, deg_ref, words_ref):
    anded = jnp.bitwise_and(rows_ref[...], mask_ref[...])
    deg_ref[...] = _row_counts(anded)
    low = jnp.bitwise_and(anded, jnp.uint32(0) - anded)
    pos = jax.lax.population_count(low - jnp.uint32(1)).astype(jnp.int32)
    wi = jax.lax.broadcasted_iota(jnp.int32, anded.shape, 1)
    words_ref[...] = (wi * 32 + pos).astype(jnp.float32)


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
