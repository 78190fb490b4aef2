"""Pure-jnp oracle for the bitset AND+popcount kernels."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def and_popcount_rows(rows: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """popcount(rows & mask) reduced over the word axis.

    rows: (..., K, W) uint32, mask: (..., W) uint32 -> (..., K) int32.
    This is `|N(u) ∩ P|` for every u at once — the MCE set-intersection
    hot spot in bitset form.
    """
    anded = jnp.bitwise_and(rows, mask[..., None, :])
    return jnp.sum(jax.lax.population_count(anded), axis=-1).astype(jnp.int32)


def and_rows(rows: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """rows & mask broadcast over the row axis (materialised intersection)."""
    return jnp.bitwise_and(rows, mask[..., None, :])


def and_popcount_argmax(rows: jnp.ndarray, mask: jnp.ndarray,
                        valid: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused pivot-select: argmax over popcount(rows & mask) scores.

    rows: (..., K, W) uint32, mask: (..., W) uint32, valid: (..., K) bool.
    Returns (idx, best): int32 index of the first best-scoring valid row and
    its score; invalid rows score -1 (so all-invalid -> best == -1, idx == 0).
    Matches jnp.argmax tie-breaking (first occurrence wins).
    """
    scores = and_popcount_rows(rows, mask)
    if valid is not None:
        scores = jnp.where(valid, scores, jnp.int32(-1))
    idx = jnp.argmax(scores, axis=-1).astype(jnp.int32)
    best = jnp.take_along_axis(scores, idx[..., None], axis=-1)[..., 0]
    return idx, best


def frame_step(rows: jnp.ndarray, p: jnp.ndarray, xp: jnp.ndarray,
               wrow: jnp.ndarray):
    """Fused BK frame step: child-set construction + degree/partner sweep.

    rows: (..., K, W) uint32 adjacency, p/xp/wrow: (..., W) uint32.
    Returns (childp, childxp, deg, partner):
      childp  = p  & wrow                      (..., W)  child candidate set
      childxp = xp & wrow                      (..., W)  child forbidden set
      deg[k]  = popcount(rows[k] & childp)     (..., K)  child degree vector
      partner[k] = Σ_words (32·w + lowest-set-bit-pos) over nonzero words of
      rows[k] & childp — the exact bit index when deg[k] == 1 (the Lemma-7
      partner), deterministic garbage otherwise. Callers only read partner
      where deg == 1.
    """
    childp = jnp.bitwise_and(p, wrow)
    childxp = jnp.bitwise_and(xp, wrow)
    anded = jnp.bitwise_and(rows, childp[..., None, :])
    deg = jnp.sum(jax.lax.population_count(anded), axis=-1).astype(jnp.int32)
    low = jnp.bitwise_and(anded, jnp.uint32(0) - anded)
    pos = jax.lax.population_count(low - jnp.uint32(1)).astype(jnp.int32)
    wi = 32 * jnp.arange(anded.shape[-1], dtype=jnp.int32)
    contrib = jnp.where(anded != 0, wi + pos, jnp.int32(0))
    partner = jnp.sum(contrib, axis=-1).astype(jnp.int32)
    return childp, childxp, deg, partner


def clique_counts(rows: jnp.ndarray, mask: jnp.ndarray, in_p: jnp.ndarray,
                  in_x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused early-termination census for the hybrid backend.

    rows: (..., K, W) uint32, mask: (..., W) uint32 (the candidate set P),
    in_p/in_x: (..., K) bool row selectors -> (n_full, n_dom), both
    (...,) int32:
      n_full = |{k : in_p[k] ∧ popcount(rows[k] & mask) == popcount(mask)−1}|
      n_dom  = |{k : in_x[k] ∧ popcount(rows[k] & mask) == popcount(mask)}|
    With rows = adjacency ∪ X0 rows, in_p selecting P members and in_x the
    forbidden rows, P induces a clique iff n_full == |P| (each member is
    adjacent to the |P|−1 others; self-bits are absent from adjacency rows)
    and some forbidden vertex dominates P (P ⊆ N(x)) iff n_dom > 0.
    """
    pc = and_popcount_rows(rows, mask)
    msize = jnp.sum(jax.lax.population_count(mask),
                    axis=-1).astype(jnp.int32)
    full = in_p & (pc == (msize - 1)[..., None])
    dom = in_x & (pc == msize[..., None])
    return (jnp.sum(full.astype(jnp.int32), axis=-1),
            jnp.sum(dom.astype(jnp.int32), axis=-1))


def and_popcount_many(rows: jnp.ndarray, masks: jnp.ndarray) -> jnp.ndarray:
    """One row matrix against a batch of masks.

    rows: (..., K, W) uint32, masks: (..., M, W) uint32 -> (..., M, K) int32
    with out[m, k] = popcount(rows[k] & masks[m]). This is the X-subset
    maximality test shape: `P ⊆ N(x)` for every forbidden row x is
    `and_popcount_many(P[None, :], ~x_rows)[:, 0] == 0`.
    """
    anded = jnp.bitwise_and(rows[..., None, :, :], masks[..., :, None, :])
    return jnp.sum(jax.lax.population_count(anded), axis=-1).astype(jnp.int32)
