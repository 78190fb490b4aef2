"""Pallas TPU kernels: fused AND + popcount set algebra over bitset rows.

Three fused primitives back the MCE engine's inner loop (see DESIGN.md §3):

* `and_popcount_rows`  — out[k] = popcount(rows[k] & mask); the deg_P sweep.
* `and_popcount_argmax` — the pivot-select: AND + popcount + validity
  masking fused in one VMEM pass over the row tile; the final (K,)→scalar
  argmax is a jnp reduction on the (K, 1) int32 scores (negligible traffic
  next to the (K, W) row load the kernel fuses away).
* `and_popcount_many`  — one row matrix against an (M, W) batch of masks;
  the X-subset maximality test shape.
* `frame_step`         — fused BK child-set + degree + Lemma-7 partner pass.
* `clique_counts`      — the hybrid backend's early-termination census:
  per-row AND+popcount against P plus the is-it-|P|/|P|−1 comparisons fused
  in one pass; the two scalar counts reduce in jnp outside.

All are tiled so each grid step keeps a (BK, W) row tile + the mask(s) in
VMEM. On TPU the AND+popcount pipeline runs on the VPU (8×128 lanes); W is
padded to the 128-lane boundary by the caller so loads are aligned.

Two structural rules keep the kernels correct and compilable beyond the
interpret-mode tests:

* **Batch-safety.** The engine reaches these kernels under `jax.vmap`
  (`loop.step_lanes` vmaps `dfs_step`; per-example tracers are 2-D so the
  ops dispatcher takes the pallas path and the pallas batching rule
  prepends the batch axis to the grid). Kernel bodies therefore must not
  read `pl.program_id` or accumulate across grid steps in revisited output
  blocks — under vmap `program_id(0)` becomes the batch index and such
  state goes wrong silently. Each grid step writes only its own block;
  cross-tile reductions happen in jnp outside the `pallas_call`.
  Enforced by the vmap parity tests in tests/test_bitset_ops_dispatch.py.
* **Mosaic-compilable shapes/ops.** The installed Mosaic (jax 0.9.0)
  refuses casts between uint32 and float32, reductions over unsigned
  ints, and a float iota; it accepts uint32 <-> int32 and int32 ->
  float32 casts and reduces int32 over either axis. So every popcount
  leaves the unsigned domain as int32 (`_popcount`) and all counts, sums
  and index selections stay in int32. Every block keeps its
  last two dims (8, 128)-divisible or equal to the full array dims.
  Enforced without hardware by tests/test_kernels_tpu_lowering.py, which
  compiles every kernel (plain and vmapped, at the engine's bucket widths)
  for a described TPU v5e, and by mce_lint R3.

Every entry point runs the compiled kernel unless its caller passes
`interpret=True` (the CPU parity tests do); each `pallas_call` carries a
stable `name`, which is how a compiled step's kernels are identified.

These kernels exist because the ops execute once per BK tree node over the
whole row matrix — the paper's measurement that set intersections are 73.6%
of MCE time maps exactly onto this module.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_K = 256
DEFAULT_BLOCK_M = 256


def _popcount(x):
    """Per-word set-bit count as int32. Mosaic refuses uint32 -> float32
    casts and unsigned reductions, so every count leaves the unsigned
    domain through int32 first."""
    return jax.lax.population_count(x).astype(jnp.int32)


def _pcsum(x):
    """(R, W) uint32 -> (R, 1) int32 row popcounts, summed in int32."""
    return jnp.sum(_popcount(x), axis=1, keepdims=True)


def _and_popcount_kernel(rows_ref, mask_ref, out_ref):
    rows = rows_ref[...]                      # (BK, W) uint32
    mask = mask_ref[...]                      # (1, W) uint32
    out_ref[...] = _pcsum(jnp.bitwise_and(rows, mask))


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def and_popcount_rows(rows: jnp.ndarray, mask: jnp.ndarray,
                      block_k: int = DEFAULT_BLOCK_K,
                      interpret: bool = False) -> jnp.ndarray:
    """Pallas path. rows: (K, W) uint32, mask: (W,) uint32 -> (K,) int32."""
    k, w = rows.shape
    bk = min(block_k, k)
    # pad K to a multiple of the block
    k_pad = -(-k // bk) * bk
    if k_pad != k:
        rows = jnp.pad(rows, ((0, k_pad - k), (0, 0)))
    grid = (k_pad // bk,)
    out = pl.pallas_call(
        _and_popcount_kernel,
        out_shape=jax.ShapeDtypeStruct((k_pad, 1), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, w), lambda i: (i, 0)),      # row tile in VMEM
            pl.BlockSpec((1, w), lambda i: (0, 0)),       # mask replicated
        ],
        out_specs=pl.BlockSpec((bk, 1), lambda i: (i, 0)),
        name="and_popcount_rows",
        interpret=interpret,
    )(rows, mask[None, :])
    return out[:k, 0]


def _and_popcount_argmax_kernel(rows_ref, mask_ref, valid_ref, scores_ref):
    rows = rows_ref[...]                      # (BK, W) uint32
    mask = mask_ref[...]                      # (1, W) uint32
    valid = valid_ref[...]                    # (BK, 1) int32 (0/1)
    counts = _pcsum(jnp.bitwise_and(rows, mask))        # (BK, 1)
    scores_ref[...] = jnp.where(valid != 0, counts, jnp.int32(-1))


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def and_popcount_argmax(rows: jnp.ndarray, mask: jnp.ndarray,
                        valid: jnp.ndarray,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False):
    """Fused pivot-select. rows: (K, W) uint32, mask: (W,) uint32,
    valid: (K,) bool -> (idx int32, best int32) with invalid rows scoring -1.

    The kernel fuses AND + popcount + validity masking per row tile; the
    argmax over the resulting (K,) scores runs in jnp outside the
    `pallas_call`. No grid step carries state (no `program_id`, no
    revisited output blocks), so vmap's batched-grid lowering — the
    engine's real call pattern — stays correct; jnp.argmax tie-breaking
    (first max wins, all-invalid -> (0, -1)) matches the ref by
    construction.
    """
    k, w = rows.shape
    bk = min(block_k, k)
    k_pad = -(-k // bk) * bk
    valid_i = valid.astype(jnp.int32)
    if k_pad != k:
        rows = jnp.pad(rows, ((0, k_pad - k), (0, 0)))
        valid_i = jnp.pad(valid_i, (0, k_pad - k))   # pad rows are invalid
    grid = (k_pad // bk,)
    scores = pl.pallas_call(
        _and_popcount_argmax_kernel,
        out_shape=jax.ShapeDtypeStruct((k_pad, 1), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, w), lambda i: (i, 0)),
            pl.BlockSpec((1, w), lambda i: (0, 0)),
            pl.BlockSpec((bk, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bk, 1), lambda i: (i, 0)),
        name="and_popcount_argmax",
        interpret=interpret,
    )(rows, mask[None, :], valid_i[:, None])[:k, 0]
    return jnp.argmax(scores).astype(jnp.int32), jnp.max(scores)


def _frame_step_kernel(rows_ref, p_ref, xp_ref, wrow_ref,
                       childp_ref, childxp_ref, deg_ref, partner_ref):
    rows = rows_ref[...]                      # (BK, W) uint32
    p = p_ref[...]                            # (1, W) uint32
    xp = xp_ref[...]                          # (1, W) uint32
    wrow = wrow_ref[...]                      # (1, W) uint32
    childp = jnp.bitwise_and(p, wrow)
    # (1, W) output blocks are revisited by every grid step but each write
    # is the same full-block value (idempotent), so the batched-grid
    # lowering under vmap stays correct — no cross-step accumulation.
    childp_ref[...] = childp
    childxp_ref[...] = jnp.bitwise_and(xp, wrow)
    anded = jnp.bitwise_and(rows, childp)
    deg_ref[...] = _pcsum(anded)
    # per-word lowest-set-bit position; summed contributions are exact when
    # exactly one bit survives (the Lemma-7 partner), garbage otherwise
    low = jnp.bitwise_and(anded, jnp.uint32(0) - anded)
    pos = _popcount(low - jnp.uint32(1))
    wi = jax.lax.broadcasted_iota(jnp.int32, anded.shape, 1) * 32
    contrib = jnp.where(anded != 0, wi + pos, 0)
    partner_ref[...] = jnp.sum(contrib, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def frame_step(rows: jnp.ndarray, p: jnp.ndarray, xp: jnp.ndarray,
               wrow: jnp.ndarray, block_k: int = DEFAULT_BLOCK_K,
               interpret: bool = False):
    """Fused BK frame step (see ref.frame_step for the contract).

    rows: (K, W) uint32, p/xp/wrow: (W,) uint32 ->
    (childp (W,), childxp (W,), deg (K,) int32, partner (K,) int32).

    One VMEM pass per row tile fuses the child-set ANDs, the AND+popcount
    degree sweep, and the Lemma-7 partner extraction that the engine's hot
    loop previously issued as separate passes over A.
    """
    k, w = rows.shape
    bk = min(block_k, k)
    k_pad = -(-k // bk) * bk
    if k_pad != k:
        rows = jnp.pad(rows, ((0, k_pad - k), (0, 0)))
    grid = (k_pad // bk,)
    childp, childxp, deg, partner = pl.pallas_call(
        _frame_step_kernel,
        out_shape=(jax.ShapeDtypeStruct((1, w), jnp.uint32),
                   jax.ShapeDtypeStruct((1, w), jnp.uint32),
                   jax.ShapeDtypeStruct((k_pad, 1), jnp.int32),
                   jax.ShapeDtypeStruct((k_pad, 1), jnp.int32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, w), lambda i: (i, 0)),      # row tile in VMEM
            pl.BlockSpec((1, w), lambda i: (0, 0)),
            pl.BlockSpec((1, w), lambda i: (0, 0)),
            pl.BlockSpec((1, w), lambda i: (0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, w), lambda i: (0, 0)),
                   pl.BlockSpec((1, w), lambda i: (0, 0)),
                   pl.BlockSpec((bk, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bk, 1), lambda i: (i, 0))),
        name="frame_step",
        interpret=interpret,
    )(rows, p[None, :], xp[None, :], wrow[None, :])
    return childp[0], childxp[0], deg[:k, 0], partner[:k, 0]


def _clique_counts_kernel(rows_ref, mask_ref, inp_ref, inx_ref,
                          full_ref, dom_ref):
    rows = rows_ref[...]                      # (BK, W) uint32
    mask = mask_ref[...]                      # (1, W) uint32
    inp = inp_ref[...]                        # (BK, 1) int32 (0/1)
    inx = inx_ref[...]                        # (BK, 1) int32 (0/1)
    pc = _pcsum(jnp.bitwise_and(rows, mask))    # (BK, 1)
    msize = _pcsum(mask)                        # (1, 1)
    # per-row 0/1 flags; the two scalar counts reduce in jnp outside the
    # pallas_call (batch-safety: each grid step writes only its own block)
    full_ref[...] = ((inp != 0) & (pc == msize - 1)).astype(jnp.int32)
    dom_ref[...] = ((inx != 0) & (pc == msize)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def clique_counts(rows: jnp.ndarray, mask: jnp.ndarray, in_p: jnp.ndarray,
                  in_x: jnp.ndarray, block_k: int = DEFAULT_BLOCK_K,
                  interpret: bool = False):
    """Fused early-termination census (see ref.clique_counts for the
    contract). rows: (K, W) uint32, mask: (W,) uint32, in_p/in_x: (K,) bool
    -> (n_full, n_dom) int32 scalars.

    One VMEM pass per row tile fuses the AND+popcount sweep against P with
    the ==|P| / ==|P|−1 comparisons; the kernel emits per-row 0/1 flags and
    the final counts are jnp sums over the (K,) flag vectors (negligible
    traffic next to the fused-away (K, W) row load, and keeps every grid
    step independent — vmap's batched-grid lowering stays correct)."""
    k, w = rows.shape
    bk = min(block_k, k)
    k_pad = -(-k // bk) * bk
    inp_i = in_p.astype(jnp.int32)
    inx_i = in_x.astype(jnp.int32)
    if k_pad != k:
        # pad rows are all-zero AND carry 0 selectors, so they never count
        rows = jnp.pad(rows, ((0, k_pad - k), (0, 0)))
        inp_i = jnp.pad(inp_i, (0, k_pad - k))
        inx_i = jnp.pad(inx_i, (0, k_pad - k))
    grid = (k_pad // bk,)
    full, dom = pl.pallas_call(
        _clique_counts_kernel,
        out_shape=(jax.ShapeDtypeStruct((k_pad, 1), jnp.int32),
                   jax.ShapeDtypeStruct((k_pad, 1), jnp.int32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, w), lambda i: (i, 0)),      # row tile in VMEM
            pl.BlockSpec((1, w), lambda i: (0, 0)),       # mask replicated
            pl.BlockSpec((bk, 1), lambda i: (i, 0)),
            pl.BlockSpec((bk, 1), lambda i: (i, 0)),
        ],
        out_specs=(pl.BlockSpec((bk, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bk, 1), lambda i: (i, 0))),
        name="clique_counts",
        interpret=interpret,
    )(rows, mask[None, :], inp_i[:, None], inx_i[:, None])
    return (jnp.sum(full[:k, 0]).astype(jnp.int32),
            jnp.sum(dom[:k, 0]).astype(jnp.int32))


def _and_popcount_many_kernel(rows_ref, masks_ref, out_ref):
    rows = rows_ref[...]                      # (BK, W) uint32
    masks = masks_ref[...]                    # (BM, W) uint32
    anded = jnp.bitwise_and(rows[None, :, :], masks[:, None, :])
    out_ref[...] = jnp.sum(_popcount(anded), axis=2)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k",
                                             "interpret"))
def and_popcount_many(rows: jnp.ndarray, masks: jnp.ndarray,
                      block_m: int = DEFAULT_BLOCK_M,
                      block_k: int = DEFAULT_BLOCK_K,
                      interpret: bool = False) -> jnp.ndarray:
    """Batched-mask path. rows: (K, W), masks: (M, W) -> (M, K) int32
    with out[m, k] = popcount(rows[k] & masks[m])."""
    k, w = rows.shape
    m, wm = masks.shape
    assert w == wm, f"word-width mismatch {w} vs {wm}"
    bk = min(block_k, k)
    bm = min(block_m, m)
    # VMEM budget: the kernel body materialises (BM, BK, W) uint32 words +
    # int32 counts (8 B/elem); cap the tile at ~4 MiB so wide-W buckets
    # (e.g. W=32 at 256×256 blocks) don't blow VMEM on the compiled path.
    # Shrink bm first (Mosaic needs a shrunk second-minor block dim to stay
    # 8-divisible), then bk in 128-lane multiples (the out block's last dim
    # must be 128-divisible unless it equals the padded array dim) — shapes
    # that trip this clamp are covered by test_kernels_tpu_lowering.py.
    max_elems = 1 << 19
    while bm * bk * w > max_elems and bm > 8:
        bm = max(8, (bm // 2 + 7) & ~7)
    while bm * bk * w > max_elems and bk > 128:
        bk = max(128, (bk // 2 + 127) & ~127)
    k_pad = -(-k // bk) * bk
    m_pad = -(-m // bm) * bm
    if k_pad != k:
        rows = jnp.pad(rows, ((0, k_pad - k), (0, 0)))
    if m_pad != m:
        masks = jnp.pad(masks, ((0, m_pad - m), (0, 0)))
    grid = (m_pad // bm, k_pad // bk)
    out = pl.pallas_call(
        _and_popcount_many_kernel,
        out_shape=jax.ShapeDtypeStruct((m_pad, k_pad), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, w), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, w), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        name="and_popcount_many",
        interpret=interpret,
    )(rows, masks)
    return out[:m, :k]
