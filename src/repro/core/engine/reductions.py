"""Dynamic (per-call) reductions as pure functions on frames (DESIGN.md §4).

The paper's Lemmas 5 (degree-0), 7 (relaxed degree-1) and 8 (degree-|P|−1)
become bitset algebra over the frame: every degree vector is one fused
AND+popcount sweep through `bitset_ops.ops`, every report is a masked
multi-row append to the carry, and Lemma 7's lookups at a vertex's partner
are row tests on `A & P` rather than gathers by the partner index. No
control flow — callers gate side-effects with `enable` so the DFS body
stays straight-line under vmap.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.engine import frames as fr
from repro.kernels.bitset_ops import ops as bitops


class ReducedFrame(NamedTuple):
    """Post-reduction frame pieces + degree info reusable by pivot select."""
    P: jnp.ndarray
    Xp: jnp.ndarray
    xal: jnp.ndarray
    Rb: jnp.ndarray
    rsz: jnp.ndarray
    degP2: jnp.ndarray      # deg over the Lemma-5/7-reduced P (pre-Lemma-8)
    n_full: jnp.ndarray     # |full| absorbed by Lemma 8


def dynamic_reduce(carry, cfg, ctx: fr.RootContext, P, Xp, xal, rsz, Rb,
                   enable, pre=None):
    """Apply Lemmas 5/7/8 to the call (R, P, X); report advance cliques.

    Returns (carry, ReducedFrame). All clique reports are gated by `enable`;
    the frame outputs are well-defined garbage when enable is False (the
    caller's stack write lands in a dead slot).

    `pre` is the optional (degP, partner) pair from the fused frame-step
    kernel — the DFS body already swept A against this call's P to build
    it, so passing it here removes the first AND+popcount sweep and the
    Lemma-7 partner extraction from this function. The rows `A & P` are
    built either way: Lemma 7 tests its partners on them."""
    U = ctx.u
    XC = ctx.xc
    A, x_rows, eye, eye_x = ctx.A, ctx.x_rows, ctx.eye, ctx.eye_x
    xal_mask = fr.bitset_to_mask(xal, XC)

    NP = bitops.and_rows(A, P)                         # (U, W) rows ∩ P
    if pre is None:
        degP = bitops.and_popcount_rows(A, P)          # (U,)
        partner = fr.single_bit_index_rows(NP)
    else:
        degP, partner = pre
    in_p = fr.bitset_to_mask(P, U)
    xp_mask = fr.bitset_to_mask(Xp, U)
    marked_bits = fr.or_reduce(x_rows, xal_mask) | fr.or_reduce(A, xp_mask)
    marked = fr.bitset_to_mask(marked_bits, U)

    # dynamic degree-zero (Lemma 5)
    deg0 = in_p & (degP == 0)
    rep0 = deg0 & ~marked
    carry = fr.report_multi(carry, cfg, Rb[None, :] | eye,
                            jnp.full((U,), rsz + 1, jnp.int32),
                            rep0 & enable)
    Xp = Xp | fr.mask_to_bitset(rep0, eye)

    # relaxed dynamic degree-one (Lemma 7). Where deg1[k], row NP[k] holds
    # one bit, the partner's, so v[partner[k]] == any_bit(NP[k] & V) for a
    # mask v with bitset V: the partner lookups below are row tests, not
    # per-element gathers, and every one is gated by deg1. Only the mutual
    # pair's order reads the partner index (valid where deg == 1).
    deg1 = in_p & (degP == 1)
    pclip = jnp.clip(partner, 0, U - 1)
    partner_deg1 = deg1 & fr.any_bit(NP & fr.mask_to_bitset(deg1, eye))
    mutual_skip = partner_deg1 & (pclip < jnp.arange(U))
    cond = deg1 & ~mutual_skip & (~marked | fr.any_bit(NP & ~marked_bits))
    pair_rows = Rb[None, :] | eye | NP       # NP[k] == eye[partner] if cond
    carry = fr.report_multi(carry, cfg, pair_rows,
                            jnp.full((U,), rsz + 2, jnp.int32),
                            cond & enable)
    rem1 = cond | (partner_deg1
                   & fr.any_bit(NP & fr.mask_to_bitset(cond, eye)))
    Xp = Xp | fr.mask_to_bitset(rem1, eye)
    removed = deg0 | rem1
    P = P & ~fr.mask_to_bitset(removed, eye)

    # dynamic degree-(|P|-1) (Lemma 8)
    degP2 = bitops.and_popcount_rows(A, P)
    in_p2 = fr.bitset_to_mask(P, U)
    psize = fr.popcount(P)
    full = in_p2 & (degP2 == psize - 1) & (psize > 0)
    any_full = jnp.any(full)
    n_full = jnp.sum(full.astype(jnp.int32))
    full_bits = fr.mask_to_bitset(full, eye)
    common = fr.and_reduce(A, full)                      # C(S) over universe
    sub_ok = bitops.and_popcount_rows(jnp.bitwise_not(x_rows), full_bits) == 0
    P, Xp, xal, Rb, rsz = (
        jnp.where(any_full, P & ~full_bits, P),
        jnp.where(any_full, Xp & common, Xp),
        jnp.where(any_full, xal & fr.mask_to_bitset(sub_ok, eye_x), xal),
        jnp.where(any_full, Rb | full_bits, Rb),
        jnp.where(any_full, rsz + n_full, rsz),
    )
    return carry, ReducedFrame(P=P, Xp=Xp, xal=xal, Rb=Rb, rsz=rsz,
                               degP2=degP2, n_full=n_full)
