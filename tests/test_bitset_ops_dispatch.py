"""bitset_ops layer: fused-kernel parity edge cases + dispatcher routing.

Covers the shapes the Pallas path must survive — K not a multiple of
block_k, W at/over the 128-lane pad boundary, and jax.vmap over the kernel
(the engine's real call pattern: the batching rule prepends the batch axis
to the grid, which a kernel reading program_id or revisiting output blocks
gets silently wrong) — plus the dispatch contract: 2-D on TPU goes to the
kernel, explicit leading batch dims fall back to ref.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bitset_ops import kernel as bk
from repro.kernels.bitset_ops import ops, ref


def _rand(shape, seed):
    return np.random.default_rng(seed).integers(0, 2**32, shape,
                                                dtype=np.uint32)


# --------------------------------------------------------------------------
# and_popcount_argmax: fused AND + popcount + argmax
# --------------------------------------------------------------------------

@pytest.mark.parametrize("k,w,block_k", [
    (1, 1, 256), (7, 4, 4), (100, 8, 32), (515, 4, 256),  # K % block_k != 0
    (64, 128, 64),                                        # W at lane boundary
    (33, 160, 16),                                        # W over the boundary
])
def test_and_popcount_argmax_parity(k, w, block_k):
    rng = np.random.default_rng(k * 100 + w)
    rows = jnp.asarray(_rand((k, w), k + w))
    mask = jnp.asarray(_rand((w,), k * w + 1))
    valid = jnp.asarray(rng.random(k) < 0.7)
    gi, gb = bk.and_popcount_argmax(rows, mask, valid, block_k=block_k,
                                    interpret=True)
    wi, wb = ref.and_popcount_argmax(rows, mask, valid)
    assert int(gb) == int(wb)
    assert int(gi) == int(wi)


def test_and_popcount_argmax_all_invalid():
    rows = jnp.asarray(_rand((13, 2), 5))
    mask = jnp.asarray(_rand((2,), 6))
    valid = jnp.zeros(13, bool)
    gi, gb = bk.and_popcount_argmax(rows, mask, valid, block_k=4,
                                    interpret=True)
    assert int(gb) == -1          # all-invalid sentinel score


def test_and_popcount_argmax_tie_breaks_first():
    # identical rows -> identical scores; first valid index must win, same
    # as jnp.argmax in the ref (the engine's pivot choice depends on this)
    rows = jnp.asarray(np.tile(_rand((1, 4), 7), (20, 1)))
    mask = jnp.asarray(_rand((4,), 8))
    valid = jnp.ones(20, bool)
    gi, _ = bk.and_popcount_argmax(rows, mask, valid, block_k=8,
                                   interpret=True)
    wi, _ = ref.and_popcount_argmax(rows, mask, valid)
    assert int(gi) == int(wi) == 0


# --------------------------------------------------------------------------
# and_popcount_many: one row matrix vs a batch of masks
# --------------------------------------------------------------------------

@pytest.mark.parametrize("k,m,w", [
    (1, 1, 1), (7, 5, 4), (100, 33, 8),
    (300, 17, 4),                 # K % block_k != 0 (block_k=256)
    (5, 300, 4),                  # M % block_m != 0
    (9, 9, 128), (3, 4, 136),     # W at / over the 128-lane boundary
    (600, 300, 32),               # trips the VMEM tile clamp (bm*bk*w cap)
])
def test_and_popcount_many_parity(k, m, w):
    rows = jnp.asarray(_rand((k, w), k * m))
    masks = jnp.asarray(_rand((m, w), k + m + w))
    got = bk.and_popcount_many(rows, masks, interpret=True)
    want = ref.and_popcount_many(rows, masks)
    assert got.shape == (m, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_and_popcount_many_python_int_crosscheck():
    rows = _rand((6, 3), 1)
    masks = _rand((4, 3), 2)
    want = ref.and_popcount_many(jnp.asarray(rows), jnp.asarray(masks))
    for mi in range(4):
        m_int = int.from_bytes(masks[mi].tobytes(), "little")
        for ki in range(6):
            r_int = int.from_bytes(rows[ki].tobytes(), "little")
            assert int(want[mi, ki]) == bin(r_int & m_int).count("1")


# --------------------------------------------------------------------------
# and_popcount_rows: existing kernel, new edge shapes
# --------------------------------------------------------------------------

@pytest.mark.parametrize("k,w,block_k", [
    (515, 128, 256),              # K % block_k != 0, W at lane boundary
    (40, 136, 16),                # W over the lane boundary
    (1, 256, 256),
])
def test_and_popcount_rows_pad_boundaries(k, w, block_k):
    rows = jnp.asarray(_rand((k, w), k))
    mask = jnp.asarray(_rand((w,), w))
    got = bk.and_popcount_rows(rows, mask, block_k=block_k, interpret=True)
    want = ref.and_popcount_rows(rows, mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------
# vmap parity: the engine's lane step vmaps dfs_step, so on TPU the kernels run
# with a batched grid — inside vmap the per-example tracer is 2-D and the
# ops dispatcher takes the pallas path (the ndim guard cannot see vmap).
# These tests run the batching rule in interpret mode; they fail for any
# kernel that accumulates across grid steps keyed on program_id (the
# batch axis is prepended to the grid, so program_id(0) becomes the batch
# index and only batch element 0 would initialise its output).
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,k,w,block_k", [
    (3, 100, 8, 32),              # several tiles per example
    (4, 33, 4, 16),               # K % block_k != 0
    (2, 7, 128, 4),               # W at the lane boundary
])
def test_vmap_and_popcount_rows_parity(b, k, w, block_k):
    rows = jnp.asarray(_rand((b, k, w), b + k))
    mask = jnp.asarray(_rand((b, w), b * k))
    got = jax.vmap(lambda r, m: bk.and_popcount_rows(
        r, m, block_k=block_k, interpret=True))(rows, mask)
    want = ref.and_popcount_rows(rows, mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,k,w,block_k", [
    (3, 100, 8, 32),
    (4, 33, 4, 16),               # K % block_k != 0
    (5, 256, 2, 64),
])
def test_vmap_and_popcount_argmax_parity(b, k, w, block_k):
    rng = np.random.default_rng(b * k + w)
    rows = jnp.asarray(_rand((b, k, w), b + k + w))
    mask = jnp.asarray(_rand((b, w), b * k + 1))
    valid = jnp.asarray(rng.random((b, k)) < 0.7)
    gi, gb = jax.vmap(lambda r, m, v: bk.and_popcount_argmax(
        r, m, v, block_k=block_k, interpret=True))(rows, mask, valid)
    wi, wb = ref.and_popcount_argmax(rows, mask, valid)
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(wb))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_vmap_and_popcount_argmax_every_batch_element_initialised():
    """Regression: per-example answers must not depend on batch position.
    Identical examples stacked B times must all return batch element 0's
    answer (an accumulator keyed on program_id(0) under vmap initialises
    only batch 0 and offsets tile_arg by the batch index)."""
    rows1 = _rand((40, 4), 11)
    mask1 = _rand((4,), 12)
    valid1 = np.random.default_rng(13).random(40) < 0.7
    b = 4
    rows = jnp.asarray(np.broadcast_to(rows1, (b, 40, 4)))
    mask = jnp.asarray(np.broadcast_to(mask1, (b, 4)))
    valid = jnp.asarray(np.broadcast_to(valid1, (b, 40)))
    gi, gb = jax.vmap(lambda r, m, v: bk.and_popcount_argmax(
        r, m, v, block_k=8, interpret=True))(rows, mask, valid)
    wi, wb = ref.and_popcount_argmax(jnp.asarray(rows1), jnp.asarray(mask1),
                                     jnp.asarray(valid1))
    np.testing.assert_array_equal(np.asarray(gi), np.full(b, int(wi)))
    np.testing.assert_array_equal(np.asarray(gb), np.full(b, int(wb)))


@pytest.mark.parametrize("b,k,m,w", [
    (3, 100, 33, 8),
    (2, 300, 17, 4),              # K % block_k != 0
    (4, 5, 9, 128),               # W at the lane boundary
])
def test_vmap_and_popcount_many_parity(b, k, m, w):
    rows = jnp.asarray(_rand((b, k, w), k * m))
    masks = jnp.asarray(_rand((b, m, w), k + m + w))
    got = jax.vmap(lambda r, ms: bk.and_popcount_many(
        r, ms, interpret=True))(rows, masks)
    want = ref.and_popcount_many(rows, masks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------
# clique_counts: fused is-P-a-clique / X-domination counts
# --------------------------------------------------------------------------

@pytest.mark.parametrize("k,w,block_k", [
    (1, 1, 256), (7, 4, 4), (100, 8, 32), (515, 4, 256),  # K % block_k != 0
    (64, 128, 64),                # W at the lane boundary
    (33, 160, 16),                # W over the boundary
])
def test_clique_counts_parity(k, w, block_k):
    rng = np.random.default_rng(k * 100 + w + 7)
    rows = jnp.asarray(_rand((k, w), k + w + 7))
    mask = jnp.asarray(_rand((w,), k * w + 8))
    in_p = rng.random(k) < 0.5
    in_x = ~in_p & (rng.random(k) < 0.5)
    got = bk.clique_counts(rows, mask, jnp.asarray(in_p), jnp.asarray(in_x),
                           block_k=block_k, interpret=True)
    want = ref.clique_counts(rows, mask, jnp.asarray(in_p),
                             jnp.asarray(in_x))
    assert (int(got[0]), int(got[1])) == (int(want[0]), int(want[1]))


@pytest.mark.parametrize("b,k,w,block_k", [
    (3, 100, 8, 32),
    (4, 33, 4, 16),               # K % block_k != 0
    (2, 7, 128, 4),               # W at the lane boundary
])
def test_vmap_clique_counts_parity(b, k, w, block_k):
    rng = np.random.default_rng(b + k + w)
    rows = jnp.asarray(_rand((b, k, w), b * k + 9))
    mask = jnp.asarray(_rand((b, w), b + k + 10))
    in_p = rng.random((b, k)) < 0.5
    in_x = ~in_p & (rng.random((b, k)) < 0.5)
    gf, gd = jax.vmap(lambda r, m, p, x: bk.clique_counts(
        r, m, p, x, block_k=block_k, interpret=True))(
        rows, mask, jnp.asarray(in_p), jnp.asarray(in_x))
    wf, wd = ref.clique_counts(rows, mask, jnp.asarray(in_p),
                               jnp.asarray(in_x))
    np.testing.assert_array_equal(np.asarray(gf), np.asarray(wf))
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(wd))


def test_vmap_clique_counts_every_batch_element_initialised():
    """Distinct stacked examples must each get their own counts (a kernel
    whose pad-handling or output blocks depended on program_id(0) would
    bleed counts across batch elements under vmap)."""
    b = 4
    rng = np.random.default_rng(51)
    rows = jnp.asarray(_rand((b, 40, 4), 52))
    mask = jnp.asarray(_rand((b, 4), 53))
    in_p = rng.random((b, 40)) < 0.5
    in_x = ~in_p & (rng.random((b, 40)) < 0.5)
    gf, gd = jax.vmap(lambda r, m, p, x: bk.clique_counts(
        r, m, p, x, block_k=8, interpret=True))(
        rows, mask, jnp.asarray(in_p), jnp.asarray(in_x))
    for bi in range(b):
        wf, wd = ref.clique_counts(rows[bi], mask[bi],
                                   jnp.asarray(in_p[bi]),
                                   jnp.asarray(in_x[bi]))
        assert int(gf[bi]) == int(wf)
        assert int(gd[bi]) == int(wd)


def test_dispatch_clique_counts(monkeypatch):
    """2-D on TPU routes to the kernel; batch dims fall back to ref."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    calls = []

    def fake(rows, mask, in_p, in_x, interpret):
        calls.append(("clique", interpret))
        return ref.clique_counts(rows, mask, in_p, in_x)

    monkeypatch.setattr(ops.kernel, "clique_counts", fake)
    rows = jnp.asarray(_rand((6, 2), 61))
    mask = jnp.asarray(_rand((2,), 62))
    in_p = jnp.asarray(np.array([1, 0, 1, 0, 1, 0], bool))
    ops.clique_counts(rows, mask, in_p, ~in_p)
    assert calls == [("clique", False)]
    calls.clear()

    def boom(*a, **k):
        raise RuntimeError("pallas kernel must not be called for 3-D")

    monkeypatch.setattr(ops.kernel, "clique_counts", boom)
    rows3 = jnp.asarray(_rand((2, 6, 2), 63))
    mask2 = jnp.asarray(_rand((2, 2), 64))
    in_p3 = jnp.asarray(np.random.default_rng(65).random((2, 6)) < 0.5)
    gf, gd = ops.clique_counts(rows3, mask2, in_p3, ~in_p3)
    wf, wd = ref.clique_counts(rows3, mask2, in_p3, ~in_p3)
    np.testing.assert_array_equal(np.asarray(gf), np.asarray(wf))
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(wd))


# --------------------------------------------------------------------------
# frame_step: fused child-set + degree + Lemma-7 partner step
# --------------------------------------------------------------------------

@pytest.mark.parametrize("k,w,block_k", [
    (1, 1, 256), (7, 4, 4), (100, 8, 32), (515, 4, 256),  # K % block_k != 0
    (64, 128, 64),                # W at the lane boundary
    (33, 160, 16),                # W over the boundary
])
def test_frame_step_parity(k, w, block_k):
    rows = jnp.asarray(_rand((k, w), k + w))
    p = jnp.asarray(_rand((w,), k * w + 1))
    xp = jnp.asarray(_rand((w,), k * w + 2))
    wrow = jnp.asarray(_rand((w,), k * w + 3))
    got = bk.frame_step(rows, p, xp, wrow, block_k=block_k, interpret=True)
    want = ref.frame_step(rows, p, xp, wrow)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_frame_step_python_int_crosscheck():
    """Independent oracle: deg vs python big-ints, partner exact at deg 1."""
    rows = _rand((40, 3), 21)
    p = _rand((3,), 22)
    xp = _rand((3,), 23)
    wrow = _rand((3,), 24)
    childp, childxp, deg, partner = ref.frame_step(
        jnp.asarray(rows), jnp.asarray(p), jnp.asarray(xp), jnp.asarray(wrow))
    p_int = int.from_bytes(p.tobytes(), "little")
    w_int = int.from_bytes(wrow.tobytes(), "little")
    cp_int = int.from_bytes(np.asarray(childp).tobytes(), "little")
    assert cp_int == p_int & w_int
    assert (int.from_bytes(np.asarray(childxp).tobytes(), "little")
            == int.from_bytes(xp.tobytes(), "little") & w_int)
    for ki in range(40):
        r_int = int.from_bytes(rows[ki].tobytes(), "little")
        anded = r_int & cp_int
        assert int(deg[ki]) == bin(anded).count("1")
        if int(deg[ki]) == 1:
            assert int(partner[ki]) == anded.bit_length() - 1


def test_vmap_frame_step_parity():
    b, k, w = 3, 100, 8
    rows = jnp.asarray(_rand((b, k, w), 31))
    p = jnp.asarray(_rand((b, w), 32))
    xp = jnp.asarray(_rand((b, w), 33))
    wrow = jnp.asarray(_rand((b, w), 34))
    got = jax.vmap(lambda r, pp, xx, ww: bk.frame_step(
        r, pp, xx, ww, block_k=32, interpret=True))(rows, p, xp, wrow)
    want = ref.frame_step(rows, p, xp, wrow)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_vmap_frame_step_every_batch_element_initialised():
    """The (1, W) child-set output blocks are revisited by every grid step;
    under vmap each batch element must still get its own (idempotent)
    value — stacked distinct examples must match per-example refs."""
    b = 4
    rows = jnp.asarray(_rand((b, 40, 4), 41))
    p = jnp.asarray(_rand((b, 4), 42))
    xp = jnp.asarray(_rand((b, 4), 43))
    wrow = jnp.asarray(_rand((b, 4), 44))
    got = jax.vmap(lambda r, pp, xx, ww: bk.frame_step(
        r, pp, xx, ww, block_k=8, interpret=True))(rows, p, xp, wrow)
    for bi in range(b):
        want = ref.frame_step(rows[bi], p[bi], xp[bi], wrow[bi])
        for g, r in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g[bi]), np.asarray(r))


# --------------------------------------------------------------------------
# dispatcher routing: TPU 2-D -> kernel, batch dims -> ref fallback
# --------------------------------------------------------------------------

def test_dispatch_batch_dims_fall_back_to_ref(monkeypatch):
    """Even when the backend claims TPU, an explicit >2-D array must take
    the ref path (the pallas wrappers are written for 2-D operands; vmap
    batching is a separate, tested path — see the vmap tests above)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    sentinel = RuntimeError("pallas kernel must not be called for 3-D")

    def boom(*a, **k):
        raise sentinel

    monkeypatch.setattr(ops.kernel, "and_popcount_rows", boom)
    monkeypatch.setattr(ops.kernel, "and_popcount_many", boom)
    rows3 = jnp.asarray(_rand((2, 9, 4), 3))
    mask2 = jnp.asarray(_rand((2, 4), 4))
    want = ref.and_popcount_rows(rows3, mask2)
    np.testing.assert_array_equal(
        np.asarray(ops.and_popcount_rows(rows3, mask2)), np.asarray(want))
    masks3 = jnp.asarray(_rand((2, 5, 4), 5))
    np.testing.assert_array_equal(
        np.asarray(ops.and_popcount_many(rows3, masks3)),
        np.asarray(ref.and_popcount_many(rows3, masks3)))


def test_dispatch_2d_routes_to_kernel_on_tpu(monkeypatch):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    calls = []

    def fake_rows(rows, mask, interpret):
        calls.append(("rows", interpret))
        return ref.and_popcount_rows(rows, mask)

    def fake_argmax(rows, mask, valid, interpret):
        calls.append(("argmax", interpret))
        return ref.and_popcount_argmax(rows, mask, valid)

    def fake_many(rows, masks, interpret):
        calls.append(("many", interpret))
        return ref.and_popcount_many(rows, masks)

    monkeypatch.setattr(ops.kernel, "and_popcount_rows", fake_rows)
    monkeypatch.setattr(ops.kernel, "and_popcount_argmax", fake_argmax)
    monkeypatch.setattr(ops.kernel, "and_popcount_many", fake_many)
    rows = jnp.asarray(_rand((6, 2), 1))
    mask = jnp.asarray(_rand((2,), 2))
    ops.and_popcount_rows(rows, mask)
    ops.and_popcount_argmax(rows, mask, jnp.ones(6, bool))
    ops.and_popcount_many(rows, jnp.asarray(_rand((3, 2), 3)))
    assert calls == [("rows", False), ("argmax", False), ("many", False)]


def test_dispatch_cpu_uses_ref():
    """On this container (CPU) the dispatcher must take the jnp ref path."""
    assert not ops._on_tpu()
    rows = jnp.asarray(_rand((6, 2), 1))
    mask = jnp.asarray(_rand((2,), 2))
    np.testing.assert_array_equal(
        np.asarray(ops.and_popcount_rows(rows, mask)),
        np.asarray(ref.and_popcount_rows(rows, mask)))
