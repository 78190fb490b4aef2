"""R3 bad fixture: unsigned reduction + misaligned literal BlockSpec.

Mosaic refuses reductions over unsigned ints (`jnp.sum` on the uint32
popcount output) and block shapes whose trailing dims are neither
(8, 128)-multiples nor equal to the array dims.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _degree_kernel(rows_ref, mask_ref, deg_ref):
    anded = rows_ref[...] & mask_ref[...]
    pc = jax.lax.population_count(anded)
    deg_ref[...] = jnp.sum(pc, axis=1, keepdims=True)       # EXPECT-R3


def degrees(rows, mask):
    k, w = rows.shape
    return pl.pallas_call(
        _degree_kernel,
        grid=(k // 8,),
        in_specs=[pl.BlockSpec((8, 120), lambda i: (i, 0)),  # EXPECT-R3
                  pl.BlockSpec((1, w), lambda i: (0, 0))],
        out_shape=jax.ShapeDtypeStruct((k, 1), jnp.int32),
        out_specs=pl.BlockSpec((8, 1), lambda i: (i, 0)),
    )(rows, mask)


def _windowed_kernel(rows_ref, out_ref, acc_ref, stats_ref):
    acc_ref[...] = rows_ref[...]
    out_ref[...] = acc_ref[...]


def _lanes_kernel(rows_ref, rsz_ref, out_ref):
    out_ref[0] = rows_ref[0] + rsz_ref[0, 0]


def lanes(rows, rsz):
    # per-lane scalar row WITHOUT memory_space=SMEM: the (1, 8) literal
    # block lands in VMEM where the 128-multiple tiling rule applies
    l, k, w = rows.shape
    return pl.pallas_call(
        _lanes_kernel,
        grid=(l,),
        in_specs=[pl.BlockSpec((1, k, w), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, 8), lambda i: (i, 0))],  # EXPECT-R3
        out_shape=jax.ShapeDtypeStruct((l, k, w), jnp.int32),
        out_specs=pl.BlockSpec((1, k, w), lambda i: (i, 0, 0)),
    )(rows, rsz)


def windowed(rows, t):
    k, w = rows.shape
    return pl.pallas_call(
        _windowed_kernel,
        in_specs=[pl.BlockSpec((k, w), lambda: (0, 0))],
        out_shape=jax.ShapeDtypeStruct((k, w), jnp.uint32),
        out_specs=pl.BlockSpec((k, w), lambda: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, 100), jnp.uint32),   # EXPECT-R3
            pltpu.VMEM((t, 128), jnp.uint32),   # EXPECT-R3
        ],
    )(rows)
