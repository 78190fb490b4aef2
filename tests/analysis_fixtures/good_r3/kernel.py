"""R3 good twin: int32 counts (int32 reduces over any axis), aligned
blocks, literal (8, 128)-aligned VMEM scratch (SMEM scalar scratch is
exempt)."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _degree_kernel(rows_ref, mask_ref, deg_ref):
    anded = rows_ref[...] & mask_ref[...]
    pc = jax.lax.population_count(anded).astype(jnp.int32)
    deg_ref[...] = jnp.sum(pc, axis=1, keepdims=True)


def degrees(rows, mask):
    k, w = rows.shape
    return pl.pallas_call(
        _degree_kernel,
        grid=(k // 8,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0)),
                  pl.BlockSpec((1, w), lambda i: (0, 0))],
        out_shape=jax.ShapeDtypeStruct((k, 1), jnp.int32),
        out_specs=pl.BlockSpec((8, 1), lambda i: (i, 0)),
    )(rows, mask)


def _windowed_kernel(rows_ref, out_ref, acc_ref, idx_ref):
    acc_ref[...] = rows_ref[...]
    out_ref[...] = acc_ref[...]


def _lanes_kernel(rows_ref, rsz_ref, out_ref, ctl_ref, acc_ref, loc_ref):
    acc_ref[...] = rows_ref[0]
    out_ref[0] = acc_ref[...]
    ctl_ref[0, 0, 0] = rsz_ref[0, 0, 0]


def lanes(rows, rsz):
    l, k, w = rows.shape
    return pl.pallas_call(
        _lanes_kernel,
        grid=(l,),
        in_specs=[pl.BlockSpec((1, k, w), lambda i: (i, 0, 0)),
                  # per-lane scalar row: Mosaic checks the LAST TWO block
                  # dims even in SMEM, so the lane axis is the mapped
                  # leading dim and the trailing (1, 8) block matches the
                  # (l, 1, 8) array's trailing dims exactly
                  pl.BlockSpec((1, 1, 8), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM)],
        out_shape=(jax.ShapeDtypeStruct((l, k, w), jnp.uint32),
                   jax.ShapeDtypeStruct((l, 1, 8), jnp.int32)),
        out_specs=(pl.BlockSpec((1, k, w), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 1, 8), lambda i: (i, 0, 0),
                                memory_space=pltpu.SMEM)),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.uint32),   # per-lane resident window
            pltpu.SMEM((8,), jnp.int32),
        ],
    )(rows, rsz)


def windowed(rows):
    k, w = rows.shape
    return pl.pallas_call(
        _windowed_kernel,
        in_specs=[pl.BlockSpec((k, w), lambda: (0, 0))],
        out_shape=jax.ShapeDtypeStruct((k, w), jnp.uint32),
        out_specs=pl.BlockSpec((k, w), lambda: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.uint32),   # resident window: literal
            pltpu.SMEM((8,), jnp.int32),        # scalar memory: exempt
        ],
    )(rows)
