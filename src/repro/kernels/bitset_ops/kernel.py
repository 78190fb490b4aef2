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
from jax.experimental.pallas import tpu as pltpu


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


# ===========================================================================
# dfs_step_window — K fused BK frame-steps with the top-T stack frames in
# VMEM scratch (DESIGN.md §2.6/§3)
# ===========================================================================

# Literal VMEM scratch geometry for the stack window. The scratch shapes
# must be (8, 128)-aligned literals (mce_lint R3): 8 frames × 128 words
# bounds the eligible problem at U ≤ 4096 vertices per root universe.
WINDOW_FRAMES = 8
WINDOW_WORDS = 128


def _window_walk(a, xr, eye, alive0, read_a, read_x,
                 sp_ref, sb_ref, sxp_ref, srb_ref, srsz_ref,
                 t, w, u, xc, d0, steps):
    """Shared fori body of the window kernels: up to `steps` masked DFS
    frame-steps over the VMEM scratch window.

    `a`/`xr`/`eye`/`alive0` are the materialized per-invocation constants;
    `read_a(i)`/`read_x(i)` load one (1, W) row via a ref dynamic slice
    (the per-root and lane-batched kernels differ only in ref rank, which
    these closures absorb). Every count and selection stays in int32:
    Mosaic has no float iota and no uint32 -> float32 cast, and it reduces
    int32 over either axis. Argmax/first-bit selections use the
    min-of-masked-iota idiom so tie-breaking matches jnp.argmax (first
    occurrence wins) bit-for-bit. Returns the final (dloc, done, calls,
    branches, sum_px, cliques, steps_done) state."""
    big = jnp.int32(1 << 30)
    iw = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    iu = jax.lax.broadcasted_iota(jnp.int32, (u, 1), 0)
    ix = jax.lax.broadcasted_iota(jnp.int32, (xc, 1), 0)

    def step(_, s):
        dl, done, calls, branches, spx, clq, sdone = s
        d = jnp.clip(dl, 0, t - 1)
        fP = sp_ref[pl.ds(d, 1), :w]                       # (1, w)
        fB = sb_ref[pl.ds(d, 1), :w]
        fXp = sxp_ref[pl.ds(d, 1), :w]
        fRb = srb_ref[pl.ds(d, 1), :w]
        frsz = srsz_ref[d]
        has_branch = jnp.max(jnp.where(fB != 0, 1, 0)) > 0
        blocked = has_branch & (dl >= t - 1)
        act = (done == 0) & ~blocked & (dl >= 0)
        done = jnp.where(blocked | (dl < 0), jnp.int32(1), done)

        # first set bit of B: per-word low-bit position, min over words
        low = jnp.bitwise_and(fB, jnp.uint32(0) - fB)
        pos = _popcount(low - jnp.uint32(1))
        cand = jnp.where(fB != 0, iw * 32 + pos, big)
        wv = jnp.clip(jnp.min(cand), 0, u - 1)
        wbit = jnp.where(iw == wv // 32,
                         jnp.uint32(1) << (wv % 32).astype(jnp.uint32),
                         jnp.uint32(0))
        wrow = read_a(wv)
        childP = jnp.bitwise_and(fP, wrow)
        childXp = jnp.bitwise_and(fXp, wrow)
        childRb = jnp.bitwise_or(fRb, wbit)

        deg = _pcsum(jnp.bitwise_and(a, childP))           # (u, 1)
        # gather-free P ∪ X membership: one-hot rows AND the member bitset
        inpool = _pcsum(jnp.bitwise_and(
            eye, jnp.bitwise_or(childP, childXp))) > 0
        pcx = _pcsum(jnp.bitwise_and(xr, childP))          # (xc, 1)
        # closed-form alive set from Rb (see ref.dfs_step_window)
        pc_rb = jnp.sum(_pcsum(childRb))
        alive = jnp.where(
            (alive0 > 0) & (_pcsum(jnp.bitwise_and(xr, childRb)) == pc_rb),
            1, 0)

        # enter_call, restricted: counts + leaf report + pivot branch set
        en = act & has_branch
        en_i = en.astype(jnp.int32)
        branches = branches + en_i
        calls = calls + en_i
        pc_p = jnp.sum(_pcsum(childP))
        pc_x = jnp.sum(_pcsum(childXp))
        nal = jnp.sum(alive)
        spx = spx + (pc_p + pc_x + nal) * en_i
        p_empty = pc_p == 0
        x_empty = (nal == 0) & (pc_x == 0)
        crsz = frsz + 1
        clq = clq + (p_empty & x_empty & (crsz >= 2) & en).astype(jnp.int32)
        push = ~p_empty & en

        su_s = jnp.where(inpool, deg, -1)
        su = jnp.max(su_s)
        best_u = jnp.min(jnp.where(su_s == su, iu, big))
        sx_s = jnp.where(alive > 0, pcx, -1)
        sx = jnp.max(sx_s)
        best_x = jnp.min(jnp.where(sx_s == sx, ix, big))
        use_x = sx > su
        rowu = read_a(best_u)
        rowx = read_x(jnp.clip(best_x, 0, xc - 1))
        pivot_row = jnp.where(use_x, rowx, rowu)
        childB = jnp.bitwise_and(childP, jnp.bitwise_not(pivot_row))

        # current frame: P \ w, X ∪ w, B \ w (identity when not branching)
        nwbit = jnp.bitwise_not(wbit)
        sp_ref[pl.ds(d, 1), :w] = jnp.where(
            en, jnp.bitwise_and(fP, nwbit), fP)
        sxp_ref[pl.ds(d, 1), :w] = jnp.where(
            en, jnp.bitwise_or(fXp, wbit), fXp)
        sb_ref[pl.ds(d, 1), :w] = jnp.where(
            en, jnp.bitwise_and(fB, nwbit), fB)
        # child frame at d+1 (clamped; identity unless descended into)
        cd = jnp.clip(d + 1, 0, t - 1)
        sp_ref[pl.ds(cd, 1), :w] = jnp.where(
            push, childP, sp_ref[pl.ds(cd, 1), :w])
        sb_ref[pl.ds(cd, 1), :w] = jnp.where(
            push, childB, sb_ref[pl.ds(cd, 1), :w])
        sxp_ref[pl.ds(cd, 1), :w] = jnp.where(
            push, childXp, sxp_ref[pl.ds(cd, 1), :w])
        srb_ref[pl.ds(cd, 1), :w] = jnp.where(
            push, childRb, srb_ref[pl.ds(cd, 1), :w])
        srsz_ref[cd] = jnp.where(push, crsz, srsz_ref[cd])

        dl = jnp.where(act,
                       jnp.where(has_branch,
                                 jnp.where(push, dl + 1, dl), dl - 1), dl)
        sdone = sdone + act.astype(jnp.int32)
        return dl, done, calls, branches, spx, clq, sdone

    z = jnp.int32(0)
    return jax.lax.fori_loop(0, steps, step, (d0, z, z, z, z, z, z))


def _dfs_step_window_kernel(a_ref, xr_ref, eye_ref, alive_ref,
                            winp_ref, winb_ref, winxp_ref, winrb_ref,
                            winrsz_ref, dloc_ref,
                            outp_ref, outb_ref, outxp_ref, outrb_ref,
                            outrsz_ref, ctl_ref,
                            sp_ref, sb_ref, sxp_ref, srb_ref, srsz_ref,
                            *, steps):
    """One invocation = up to `steps` masked DFS frame-steps.

    The window frames live in VMEM scratch for the whole invocation (the
    per-frame |R| sizes and the control scalars ride in SMEM); the HBM
    stack is untouched until the engine wrapper writes the returned
    window back. The step loop itself is `_window_walk`, shared with the
    lane-batched variant below.
    """
    t, w = winp_ref.shape
    u = a_ref.shape[0]
    xc = xr_ref.shape[0]
    sp_ref[:, :w] = winp_ref[...]
    sb_ref[:, :w] = winb_ref[...]
    sxp_ref[:, :w] = winxp_ref[...]
    srb_ref[:, :w] = winrb_ref[...]
    for i in range(t):
        srsz_ref[i] = winrsz_ref[0, i]
    s = _window_walk(a_ref[...], xr_ref[...], eye_ref[...],
                     alive_ref[...],
                     lambda i: a_ref[pl.ds(i, 1), :],
                     lambda i: xr_ref[pl.ds(i, 1), :],
                     sp_ref, sb_ref, sxp_ref, srb_ref, srsz_ref,
                     t, w, u, xc, dloc_ref[0, 0], steps)
    z = jnp.int32(0)
    outp_ref[...] = sp_ref[:, :w]
    outb_ref[...] = sb_ref[:, :w]
    outxp_ref[...] = sxp_ref[:, :w]
    outrb_ref[...] = srb_ref[:, :w]
    for i in range(t):
        outrsz_ref[0, i] = srsz_ref[i]
    ctl_ref[0, 0] = s[0]
    ctl_ref[0, 1] = s[2]
    ctl_ref[0, 2] = s[3]
    ctl_ref[0, 3] = s[4]
    ctl_ref[0, 4] = s[5]
    ctl_ref[0, 5] = s[6]
    ctl_ref[0, 6] = z
    ctl_ref[0, 7] = z


def _dfs_step_window_lanes_kernel(a_ref, xr_ref, eye_ref, alive_ref,
                                  winp_ref, winb_ref, winxp_ref, winrb_ref,
                                  winrsz_ref, dloc_ref,
                                  outp_ref, outb_ref, outxp_ref, outrb_ref,
                                  outrsz_ref, ctl_ref,
                                  sp_ref, sb_ref, sxp_ref, srb_ref,
                                  srsz_ref, *, steps):
    """Lane-batched window walk: one grid step = one lane's K frame-steps.

    Every input/output block is that lane's plane of the (L, …) array —
    the (1, U, W) adjacency, (1, XC, W) X rows, (1, T, W) windows in
    VMEM, and the per-lane scalars (dloc in, rsz, ctl out) in
    (1, 1, ·) SMEM lane rows. The (8, 128) VMEM scratch window is
    re-initialized from
    the lane's own block at the top of every grid step and written back
    at the end — no state crosses grid steps (no `pl.program_id` reads,
    no revisited blocks), so the batched-grid lowering under `jax.vmap`
    stays correct and lanes never observe each other: a lane that stops
    on underflow/overflow simply burns the rest of its own grid step
    without stalling its neighbors.
    """
    t, w = winp_ref.shape[1], winp_ref.shape[2]
    u = a_ref.shape[1]
    xc = xr_ref.shape[1]
    sp_ref[:, :w] = winp_ref[0]
    sb_ref[:, :w] = winb_ref[0]
    sxp_ref[:, :w] = winxp_ref[0]
    srb_ref[:, :w] = winrb_ref[0]
    for i in range(t):
        srsz_ref[i] = winrsz_ref[0, 0, i]
    s = _window_walk(a_ref[0], xr_ref[0], eye_ref[...],
                     alive_ref[0],
                     lambda i: a_ref[0, pl.ds(i, 1), :],
                     lambda i: xr_ref[0, pl.ds(i, 1), :],
                     sp_ref, sb_ref, sxp_ref, srb_ref, srsz_ref,
                     t, w, u, xc, dloc_ref[0, 0, 0], steps)
    z = jnp.int32(0)
    outp_ref[0] = sp_ref[:, :w]
    outb_ref[0] = sb_ref[:, :w]
    outxp_ref[0] = sxp_ref[:, :w]
    outrb_ref[0] = srb_ref[:, :w]
    for i in range(t):
        outrsz_ref[0, 0, i] = srsz_ref[i]
    ctl_ref[0, 0, 0] = s[0]
    ctl_ref[0, 0, 1] = s[2]
    ctl_ref[0, 0, 2] = s[3]
    ctl_ref[0, 0, 3] = s[4]
    ctl_ref[0, 0, 4] = s[5]
    ctl_ref[0, 0, 5] = s[6]
    ctl_ref[0, 0, 6] = z
    ctl_ref[0, 0, 7] = z


@functools.partial(jax.jit, static_argnames=("steps", "interpret"))
def dfs_step_window(a: jnp.ndarray, x_rows: jnp.ndarray, eye: jnp.ndarray,
                    alive0: jnp.ndarray, winP: jnp.ndarray,
                    winB: jnp.ndarray, winXp: jnp.ndarray,
                    winRb: jnp.ndarray, winrsz: jnp.ndarray,
                    dloc: jnp.ndarray, steps: int = 16,
                    interpret: bool = False):
    """Pallas path for ref.dfs_step_window (same contract).

    The (T, W) window frames are copied into VMEM scratch once, mutated
    in place across up to `steps` frame-steps, and written back to the
    output refs at the end — the kernel's whole point is that the stack
    state does NOT round-trip HBM between steps. The adjacency, X rows,
    eye, and alive inputs stay resident in VMEM across the invocation;
    the |R| sizes and control scalars (dloc in, ctl out) ride in SMEM.
    """
    t, w = winP.shape
    assert t == WINDOW_FRAMES, f"window must have {WINDOW_FRAMES} frames"
    assert w <= WINDOW_WORDS, f"word width {w} exceeds {WINDOW_WORDS}"
    u = a.shape[0]
    xc = x_rows.shape[0]

    def vmem(shape):
        return pl.BlockSpec(shape, lambda: tuple(0 for _ in shape))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    outs = pl.pallas_call(
        functools.partial(_dfs_step_window_kernel, steps=steps),
        out_shape=(jax.ShapeDtypeStruct((t, w), jnp.uint32),
                   jax.ShapeDtypeStruct((t, w), jnp.uint32),
                   jax.ShapeDtypeStruct((t, w), jnp.uint32),
                   jax.ShapeDtypeStruct((t, w), jnp.uint32),
                   jax.ShapeDtypeStruct((1, t), jnp.int32),
                   jax.ShapeDtypeStruct((1, 8), jnp.int32)),
        in_specs=[vmem((u, w)), vmem((xc, w)), vmem((u, w)),
                  vmem((xc, 1)), vmem((t, w)), vmem((t, w)),
                  vmem((t, w)), vmem((t, w)), smem, smem],
        out_specs=(vmem((t, w)), vmem((t, w)), vmem((t, w)), vmem((t, w)),
                   smem, smem),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.uint32),
            pltpu.VMEM((8, 128), jnp.uint32),
            pltpu.VMEM((8, 128), jnp.uint32),
            pltpu.VMEM((8, 128), jnp.uint32),
            pltpu.SMEM((8,), jnp.int32),
        ],
        name="dfs_step_window",
        interpret=interpret,
    )(a, x_rows, eye, alive0.astype(jnp.int32)[:, None], winP, winB,
      winXp, winRb, winrsz.astype(jnp.int32)[None],
      jnp.asarray(dloc, jnp.int32)[None, None])
    outP, outB, outXp, outRb, outrsz, ctl = outs
    return outP, outB, outXp, outRb, outrsz[0], ctl[0]


@functools.partial(jax.jit, static_argnames=("steps", "interpret"))
def dfs_step_window_lanes(a: jnp.ndarray, x_rows: jnp.ndarray,
                          eye: jnp.ndarray, alive0: jnp.ndarray,
                          winP: jnp.ndarray, winB: jnp.ndarray,
                          winXp: jnp.ndarray, winRb: jnp.ndarray,
                          winrsz: jnp.ndarray, dloc: jnp.ndarray,
                          steps: int = 16, interpret: bool = False):
    """Pallas path for ref.dfs_step_window_lanes (same contract).

    The grid runs over lanes: each grid step walks one lane's window for
    up to `steps` frame-steps entirely in the shared (8, 128) VMEM
    scratch, touching only that lane's blocks of the (L, …) inputs and
    outputs. Per-lane scalars — the window-local depth in, the per-frame
    |R| sizes, and the ctl row out — ride in SMEM lane rows shaped
    (1, 1, T)/(1, 1, 1)/(1, 1, 8) over (L, 1, ·) arrays: Mosaic checks
    the LAST TWO dims of every block (even SMEM) against the array dims,
    so the lane axis is the mapped leading dim and the trailing (1, ·)
    matches the array exactly. a: (L, U, W); x_rows: (L, XC, W); eye:
    (U, W) shared; alive0: (L, XC); winP/winB/winXp/winRb: (L, T, W);
    winrsz: (L, T); dloc: (L,). Returns the updated lane windows plus
    ctl (L, 8).
    """
    l, t, w = winP.shape
    assert t == WINDOW_FRAMES, f"window must have {WINDOW_FRAMES} frames"
    assert w <= WINDOW_WORDS, f"word width {w} exceeds {WINDOW_WORDS}"
    u = a.shape[1]
    xc = x_rows.shape[1]

    def lane(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda i: (i,) + (0,) * len(shape))

    def smem(cols):
        # (1, 1, cols) lane rows of an (L, 1, cols) array: Mosaic requires
        # the last TWO block dims to be 8/128-divisible or equal to the
        # array dims, so per-lane scalars carry a middle singleton — the
        # lane axis is Mapped, the trailing (1, cols) matches exactly.
        return pl.BlockSpec((1, 1, cols), lambda i: (i, 0, 0),
                            memory_space=pltpu.SMEM)

    outs = pl.pallas_call(
        functools.partial(_dfs_step_window_lanes_kernel, steps=steps),
        grid=(l,),
        out_shape=(jax.ShapeDtypeStruct((l, t, w), jnp.uint32),
                   jax.ShapeDtypeStruct((l, t, w), jnp.uint32),
                   jax.ShapeDtypeStruct((l, t, w), jnp.uint32),
                   jax.ShapeDtypeStruct((l, t, w), jnp.uint32),
                   jax.ShapeDtypeStruct((l, 1, t), jnp.int32),
                   jax.ShapeDtypeStruct((l, 1, 8), jnp.int32)),
        in_specs=[lane((u, w)), lane((xc, w)),
                  pl.BlockSpec((u, w), lambda i: (0, 0)),
                  lane((xc, 1)), lane((t, w)), lane((t, w)),
                  lane((t, w)), lane((t, w)), smem(t), smem(1)],
        out_specs=(lane((t, w)), lane((t, w)), lane((t, w)), lane((t, w)),
                   smem(t), smem(8)),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.uint32),
            pltpu.VMEM((8, 128), jnp.uint32),
            pltpu.VMEM((8, 128), jnp.uint32),
            pltpu.VMEM((8, 128), jnp.uint32),
            pltpu.SMEM((8,), jnp.int32),
        ],
        name="dfs_step_window_lanes",
        interpret=interpret,
    )(a, x_rows, eye, alive0.astype(jnp.int32)[..., None], winP, winB,
      winXp, winRb, winrsz.astype(jnp.int32)[:, None, :],
      jnp.asarray(dloc, jnp.int32)[:, None, None])
    outP, outB, outXp, outRb, outrsz, ctl = outs
    return outP, outB, outXp, outRb, outrsz[:, 0], ctl[:, 0]
