"""Global reduction (§4), dynamic reduction (§5), X-reduction (§6) unit tests."""
import numpy as np
import pytest
from _hyp import given, strategies as st  # optional-hypothesis shim

import jax
import jax.numpy as jnp

from repro.core import oracle
from repro.core.engine import frames as fr
from repro.core.engine.reductions import ReducedFrame, dynamic_reduce
from repro.core.global_reduction import (_batch_lemma3, global_reduce_host,
                                         global_reduce_jnp, reduce_prepass)
from repro.core.xreduction import x_prune_roots
from repro.graph import (complete_graph, degeneracy_order, erdos_renyi,
                         from_edge_list, grid_road, random_geometric)
from repro.kernels.bitset_ops import ops as bitops


@st.composite
def any_graph(draw):
    n = draw(st.integers(2, 14))
    p = draw(st.floats(0.05, 0.9))
    seed = draw(st.integers(0, 10**6))
    return erdos_renyi(n, p, seed=seed)


@given(any_graph())
def test_global_reduction_completeness(g):
    """mc(G) = mc(G') + α(ΔV, ΔE) with exact multiset equality."""
    ref = oracle.maximal_cliques_brute(g)
    red = global_reduce_host(g)
    rest = set(oracle.bk_pivot(red.graph))
    reported = set(red.reported)
    assert reported | rest == ref
    assert not (reported & rest), "advance-reported cliques re-enumerated"
    assert len(reported) + len(rest) == len(ref)


def test_road_graph_fully_reduced():
    """Paper Fig 8: degeneracy-2 road networks vanish under global reduction."""
    g = grid_road(20, drop_frac=0.1, seed=0)
    red = global_reduce_host(g)
    assert red.graph.m == 0
    assert set(red.reported) == oracle_set(g)


def oracle_set(g):
    return set(oracle.bk_pivot(g))


def test_dense_graph_untouched():
    """Paper Fig 8 (sc-delaunay): min-degree>2 triangle-rich graphs survive."""
    g = complete_graph(8)
    red = global_reduce_host(g)
    assert red.graph.m == g.m and not red.reported


def test_nontriangle_edge_rule():
    # two triangles joined by a bridge edge: the bridge is non-triangle
    edges = [(0, 1), (1, 2), (0, 2), (3, 4), (4, 5), (3, 5), (2, 3)]
    g = from_edge_list(6, np.array(edges))
    red = global_reduce_host(g)
    assert frozenset((2, 3)) in red.reported


def test_degree2_cases():
    # case 1: deg-2, neighbors non-adjacent -> two 2-cliques
    g = from_edge_list(5, np.array([(0, 1), (0, 2), (1, 3), (2, 4),
                                    (3, 4), (1, 4), (3, 2)]))
    ref = oracle.maximal_cliques_brute(g)
    red = global_reduce_host(g)
    assert set(red.reported) | set(oracle.bk_pivot(red.graph)) == ref


@given(any_graph())
def test_global_reduce_jnp_masks(g):
    """Device-path deg≤1 peel: masks kill exactly the 1-core complement."""
    if g.m == 0:
        return
    ei = g.edge_index()
    av, ae = global_reduce_jnp(jnp.asarray(ei[0]), jnp.asarray(ei[1]), g.n)
    av, ae = np.asarray(av), np.asarray(ae)
    # surviving vertices have >= 2 surviving neighbors (2-core condition)
    deg = np.zeros(g.n, int)
    np.add.at(deg, ei[0][ae], 1)
    assert np.all(deg[av] >= 2)
    assert not np.any(deg[~av] > 0) or True  # dead vertices keep no edges
    assert np.all(~ae | (av[ei[0]] & av[ei[1]]))


@given(any_graph())
def test_batch_lemma3_preserves_cliques(g):
    """One conflict-free deg-2 batch = some sequential Lemma-3 order:
    reported ∪ mc(G') must equal mc(G) exactly, with no overlap."""
    ref = oracle.maximal_cliques_brute(g)
    g2, segs, _changed = _batch_lemma3(g)
    reported = {frozenset(int(x) for x in row)
                for s in segs for row in s.tolist()}
    rest = set(oracle.bk_pivot(g2))
    assert reported | rest == ref
    assert not (reported & rest)
    assert len(reported) + len(rest) == len(ref)


@given(any_graph())
def test_batch_lemma3_selection_is_conflict_free(g):
    """Selected vertices (first column of every report row) must have
    pairwise-disjoint CLOSED neighborhoods — the property that makes the
    batch order-independent."""
    _g2, segs, _ = _batch_lemma3(g)
    owned = {}
    for s in segs:
        for row in s.tolist():
            v = int(row[0])
            for t in row:
                assert owned.setdefault(int(t), v) == v, \
                    f"vertex {t} touched by two selected deg-2 vertices"


@given(any_graph())
def test_reduce_prepass_with_lemma3_completeness(g):
    """Full vectorized prepass (peel + batch Lemma 3 + edge sweep) then
    the host cascade: exact multiset equality against brute force."""
    ref = oracle.maximal_cliques_brute(g)
    residual, reports = reduce_prepass(g)
    red = global_reduce_host(residual)
    rest = set(oracle.bk_pivot(red.graph))
    pre = set(reports) | set(red.reported)
    assert pre | rest == ref
    assert not (pre & rest)
    assert len(reports) + len(red.reported) + len(rest) == len(ref)


@pytest.mark.parametrize("seed", range(8))
def test_batch_lemma3_parity_seeded(seed):
    """Deterministic pin of the batch Lemma-3 invariants (the @given
    variants above only run where hypothesis is installed)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 60))
    g = erdos_renyi(n, float(rng.uniform(0.03, 0.3)), seed=seed)
    ref = set(oracle.bk_pivot(g))
    g2, segs, _ = _batch_lemma3(g)
    reported = {frozenset(int(x) for x in row)
                for s in segs for row in s.tolist()}
    rest = set(oracle.bk_pivot(g2))
    assert reported | rest == ref
    assert not (reported & rest)
    residual, reports = reduce_prepass(g)
    red = global_reduce_host(residual)
    assert (set(reports) | set(red.reported)
            | set(oracle.bk_pivot(red.graph))) == ref


def test_batch_lemma3_triangle_edge_cases():
    # v=0 deg-2 with adjacent neighbors (1,2); 1-2 also in a second
    # triangle with 3 -> edge (1,2) must SURVIVE
    g = from_edge_list(4, np.array([(0, 1), (0, 2), (1, 2), (1, 3), (2, 3)]))
    g2, segs, ch = _batch_lemma3(g)
    assert ch
    rep = {frozenset(int(x) for x in r) for s in segs for r in s.tolist()}
    assert frozenset((0, 1, 2)) in rep
    e2 = {frozenset(e) for e in g2.edges().tolist()}
    assert frozenset((1, 2)) in e2
    # lone triangle: edge (u, w) has no other common neighbor -> deleted
    g = from_edge_list(5, np.array([(0, 1), (0, 2), (1, 2), (1, 3), (2, 4)]))
    g2, segs, ch = _batch_lemma3(g)
    rep = {frozenset(int(x) for x in r) for s in segs for r in s.tolist()}
    assert frozenset((0, 1, 2)) in rep
    e2 = {frozenset(e) for e in g2.edges().tolist()}
    assert frozenset((1, 2)) not in e2


@given(any_graph())
def test_x_reduction_preserves_cliques(g):
    """Lemma 9 via Algorithm 8 + witness chains: same clique set."""
    ref = set(oracle.rmce(g, global_red=False, dynamic_red=False, x_red=False))
    got = set(oracle.rmce(g, global_red=False, dynamic_red=False, x_red=True))
    assert got == ref


@given(any_graph())
def test_x_reduction_only_shrinks(g):
    adj = [set(g.neighbors(v).tolist()) for v in range(g.n)]
    order, rank, _ = degeneracy_order(g)
    kept = x_prune_roots(adj, order, rank)
    for i in range(g.n):
        v = int(order[i])
        x_full = {u for u in adj[v] if rank[u] < i}
        assert kept[i] <= x_full


def test_x_reduction_actually_prunes():
    """On clustered graphs the forbidden set shrinks (paper Fig 10)."""
    g = random_geometric(400, seed=5)
    s = oracle.MCEStats()
    oracle.rmce(g, stats=s, collect=False)
    assert s.sum_x_after < s.sum_x_before


@given(any_graph())
def test_dynamic_reduction_only(g):
    ref = oracle.maximal_cliques_brute(g)
    got = set(oracle.rmce(g, global_red=False, dynamic_red=True, x_red=False))
    assert got == ref


# ---------------------------------------------------------------------------
# Dynamic reduction: Lemma 7's row tests against the partner-index gathers
# ---------------------------------------------------------------------------

def _dynamic_reduce_gather(carry, cfg, ctx, P, Xp, xal, rsz, Rb, enable,
                           pre=None):
    """`dynamic_reduce` with Lemma 7's partner lookups as element gathers
    by the partner index (`v[pclip]`), the form the row tests replace."""
    U, XC = ctx.u, ctx.xc
    A, x_rows, eye, eye_x = ctx.A, ctx.x_rows, ctx.eye, ctx.eye_x
    xal_mask = fr.bitset_to_mask(xal, XC)
    if pre is None:
        degP = bitops.and_popcount_rows(A, P)
        partner = fr.single_bit_index_rows(bitops.and_rows(A, P))
    else:
        degP, partner = pre
    in_p = fr.bitset_to_mask(P, U)
    xp_mask = fr.bitset_to_mask(Xp, U)
    marked_bits = fr.or_reduce(x_rows, xal_mask) | fr.or_reduce(A, xp_mask)
    marked = fr.bitset_to_mask(marked_bits, U)
    deg0 = in_p & (degP == 0)
    rep0 = deg0 & ~marked
    carry = fr.report_multi(carry, cfg, Rb[None, :] | eye,
                            jnp.full((U,), rsz + 1, jnp.int32),
                            rep0 & enable)
    Xp = Xp | fr.mask_to_bitset(rep0, eye)
    deg1 = in_p & (degP == 1)
    pclip = jnp.clip(partner, 0, U - 1)
    partner_deg1 = deg1 & deg1[pclip]
    mutual_skip = partner_deg1 & (pclip < jnp.arange(U))
    cond = deg1 & ~mutual_skip & (~marked | ~marked[pclip])
    carry = fr.report_multi(carry, cfg, Rb[None, :] | eye | eye[pclip],
                            jnp.full((U,), rsz + 2, jnp.int32),
                            cond & enable)
    rem1 = cond | (partner_deg1 & cond[pclip])
    Xp = Xp | fr.mask_to_bitset(rem1, eye)
    P = P & ~fr.mask_to_bitset(deg0 | rem1, eye)
    degP2 = bitops.and_popcount_rows(A, P)
    in_p2 = fr.bitset_to_mask(P, U)
    psize = fr.popcount(P)
    full = in_p2 & (degP2 == psize - 1) & (psize > 0)
    any_full = jnp.any(full)
    n_full = jnp.sum(full.astype(jnp.int32))
    full_bits = fr.mask_to_bitset(full, eye)
    common = fr.and_reduce(A, full)
    sub_ok = bitops.and_popcount_rows(jnp.bitwise_not(x_rows), full_bits) == 0
    return carry, ReducedFrame(
        P=jnp.where(any_full, P & ~full_bits, P),
        Xp=jnp.where(any_full, Xp & common, Xp),
        xal=jnp.where(any_full, xal & fr.mask_to_bitset(sub_ok, eye_x), xal),
        Rb=jnp.where(any_full, Rb | full_bits, Rb),
        rsz=jnp.where(any_full, rsz + n_full, rsz),
        degP2=degP2, n_full=n_full)


def _pack(bits):
    """(..., n) bool -> (..., ceil(n/32)) uint32, bit i in word i // 32."""
    n = bits.shape[-1]
    pad = np.zeros(bits.shape[:-1] + (-(-n // 32) * 32,), bool)
    pad[..., :n] = bits
    return np.packbits(pad, axis=-1, bitorder="little").view("<u4")


def _lemma7_frames(u, xc, seed, n=24):
    """`n` random frames over a U-vertex universe, each with planted
    mutual degree-one pairs and one-way degree-one vertices in P, marks
    from alive X0 rows and from Xp at a frame's own density, and every
    third frame disabled. Returns the packed batch and bool `parts`."""
    rng = np.random.default_rng(seed)
    parts = {k: [] for k in ("A", "x_rows", "P", "Xp", "xal", "Rb")}
    rsz, enable = [], []
    for i in range(n):
        adj = np.triu(rng.random((u, u)) < rng.uniform(0.02, 0.3), 1)
        adj = adj | adj.T
        role = rng.choice(4, size=u, p=[0.55, 0.15, 0.1, 0.2])
        in_p = role == 0
        pidx = rng.permutation(np.flatnonzero(in_p))
        k = len(pidx) // 4
        for j in range(0, 2 * (k // 2), 2):          # mutual pairs
            a, b = pidx[j], pidx[j + 1]
            adj[[a, b], :] &= ~in_p
            adj[:, [a, b]] &= ~in_p[:, None]
            adj[a, b] = adj[b, a] = True
        for c in pidx[k:2 * k]:                       # one-way: c -> d
            d = pidx[rng.integers(2 * k, len(pidx))]
            adj[c, :] &= ~in_p
            adj[:, c] &= ~in_p
            adj[c, d] = adj[d, c] = True
        parts["A"].append(adj)
        parts["x_rows"].append(rng.random((xc, u)) < rng.uniform(0.0, 0.08))
        parts["P"].append(in_p)
        parts["Xp"].append(role == 1)
        parts["xal"].append(rng.random(xc) < rng.uniform(0.0, 0.6))
        parts["Rb"].append(role == 2)
        rsz.append(int(rng.integers(1, 6)))
        enable.append(i % 3 != 2)
    parts = {k: np.stack(v) for k, v in parts.items()}
    batch = {k: _pack(v) for k, v in parts.items()}
    return batch, parts, np.array(rsz, np.int32), np.array(enable)


def _lemma7_coverage(parts):
    """Rows of the batch in each Lemma-7 case, counted in numpy."""
    A, P = parts["A"], parts["P"]
    deg = (A & P[:, None, :]).sum(-1)
    deg1 = P & (deg == 1)
    partner = np.argmax(A & P[:, None, :], axis=-1)
    marked = (np.any(parts["x_rows"] & parts["xal"][..., None], axis=1)
              | np.any(A & parts["Xp"][..., None], axis=1))
    pmarked = np.take_along_axis(marked, partner, axis=-1)
    pdeg1 = np.take_along_axis(deg1, partner, axis=-1)
    return {"mutual": int((deg1 & pdeg1).sum()),
            "one_way": int((deg1 & ~pdeg1).sum()),
            "both_marked": int((deg1 & marked & pmarked).sum()),
            "partner_unmarked": int((deg1 & marked & ~pmarked).sum())}


@pytest.mark.parametrize("pre", ["none", "frame_step"])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("u", [32, 64, 128])
def test_dynamic_reduce_row_tests_match_gathers(u, seed, pre):
    """Lemma 7 by row tests on `A & P` equals the partner-index gather
    form bit for bit: every counter and enumerated row of the carry and
    every ReducedFrame field, over a vmapped batch of frames, with the
    degrees and partners from `frame_step` or from `dynamic_reduce`
    itself."""
    xc = 40
    batch, parts, rsz, enable = _lemma7_frames(u, xc, seed)
    cov = _lemma7_coverage(parts)
    assert min(cov.values()) > 0, cov
    cfg = fr.EngineConfig(out_cap=48)

    def make(fn):
        def one(a, x_rows, P, Xp, xal, Rb, rsz, enable, out_n):
            ctx = fr.make_context(a, x_rows)
            carry = fr.carry_init(cfg, ctx.words, track_root=True)
            carry = dict(carry, out_n=out_n, calls=out_n + 1,
                         cur_root=out_n * 7)
            pre_ = None
            if pre == "frame_step":
                full = jnp.full_like(P, 0xFFFFFFFF)
                _, _, deg, partner = bitops.frame_step(a, P, Xp, full)
                pre_ = (deg, partner)
            return fn(carry, cfg, ctx, P, Xp, xal, rsz, Rb, enable, pre=pre_)
        return jax.jit(jax.vmap(one))

    # empty buffers on even lanes, one row short of full on odd lanes
    out_n = np.where(np.arange(len(rsz)) % 2, cfg.out_cap - 1, 0).astype(
        np.int32)
    args = (batch["A"], batch["x_rows"], batch["P"], batch["Xp"],
            batch["xal"], batch["Rb"], rsz, enable, out_n)
    got_carry, got = make(dynamic_reduce)(*args)
    want_carry, want = make(_dynamic_reduce_gather)(*args)
    assert sorted(got_carry) == sorted(want_carry)
    for k in want_carry:
        np.testing.assert_array_equal(np.asarray(got_carry[k]),
                                      np.asarray(want_carry[k]), err_msg=k)
    for k in want._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, k)),
                                      np.asarray(getattr(want, k)),
                                      err_msg=k)
    # the batch reports advance cliques and overflows some lanes' buffers
    assert np.any(np.asarray(want_carry["cliques"]) > 0)
    assert np.any(np.asarray(want_carry["overflow"]))
