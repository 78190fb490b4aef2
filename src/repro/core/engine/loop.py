"""The `lax.while_loop` DFS driver + single-host API (DESIGN.md §2.5).

Composes the layers: `prepare` stages host-side buckets, `reductions`
applies the per-call lemmas, `pivot` picks branch sets, and this module
owns call entry, the explicit stack walk, the lock-step and persistent
bucket walks over lanes, and the end-to-end `run()`.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import frames as fr
from repro.core.engine import pivot as piv
from repro.core.engine import reductions as red
from repro.core.engine.frames import U32, WORD, EngineConfig, Frame, FrameStack
from repro.core.engine.prepare import (_unpack_bits_np, estimate_costs,
                                       prepare)
from repro.core.spans import scoped
from repro.graph.csr import CSRGraph
from repro.kernels.bitset_ops import ops as bitops


# ===========================================================================
# Call-entry: dynamic reduction + leaf report + branch-set construction
# ===========================================================================

def enter_call(carry, cfg, ctx: fr.RootContext, P, Xp, xal, rsz, Rb,
               enable=None, pre=None):
    """BK call entry for (R, P, X). Returns (carry, push?, Frame).

    `enable` gates every carry side-effect (counter bumps, clique reports):
    the DFS body runs enter_call unconditionally (straight-line, no
    lax.cond — see run_root) and masks it out on pop-only iterations.

    `pre` is the fused frame-step kernel's (deg, partner) pair over this
    call's P — the DFS body computes it while constructing the child sets,
    so dynamic reduction (and pivot scoring when reduction is off) reuses
    it instead of re-sweeping A."""
    XC = ctx.xc
    enable = jnp.bool_(True) if enable is None else enable
    en_i = enable.astype(jnp.int32)
    carry = dict(carry, calls=carry["calls"] + en_i)
    carry["sum_px"] = (carry["sum_px"] + (fr.popcount(P) + fr.popcount(Xp)
                       + fr.popcount(xal)) * en_i)

    # ---- dynamic reduction (paper Lemmas 5, 7, 8) ----
    if cfg.dynamic_red:
        carry, rf = red.dynamic_reduce(carry, cfg, ctx, P, Xp, xal, rsz, Rb,
                                       enable, pre=pre)
        P, Xp, xal, Rb, rsz = rf.P, rf.Xp, rf.xal, rf.Rb, rf.rsz
    else:
        rf = None

    # ---- leaf report ----
    p_empty = ~fr.any_bit(P)
    x_empty = ~fr.any_bit(xal) & ~fr.any_bit(Xp)
    carry = fr.report_single(carry, cfg, Rb, rsz,
                             p_empty & x_empty & (rsz >= 2) & enable)
    push = ~p_empty & enable

    # ---- hybrid early termination + X-domination pruning (§2.7) ----
    if cfg.backend == "hybrid":
        # P a clique -> report R ∪ P and pop; P dominated by a forbidden
        # vertex -> pop silently. Reports are gated by `enable`, so every
        # dispatch path (run_root vmap, persistent refill/lane step) gets
        # the live-mask gating for free.
        carry, stop = piv.hybrid_early_term(carry, cfg, ctx, P, Xp, xal,
                                            Rb, rsz, enable)
        push = push & ~stop

    # ---- branch set (pivot backends; rcd recomputes per visit) ----
    if cfg.backend in fr.PIVOT_BACKENDS:
        B = piv.branch_set(cfg, ctx, P, Xp, xal, rf,
                           deg=None if pre is None else pre[0])
    else:
        B = jnp.zeros_like(P)
    return carry, push, Frame(P=P, B=B, Xp=Xp, Rb=Rb, rsz=rsz, xal=xal)


# ===========================================================================
# Shared DFS step + per-root DFS driver
# ===========================================================================

def dfs_step(cfg, ctx: fr.RootContext, depth, stack, carry, live=None):
    """One straight-line masked DFS step — no lax.cond.

    Under vmap a cond lowers to SELECT over both branch results, which
    copies every stack buffer per iteration (measured: >40% of the
    engine's HBM bytes). Instead, branch work always executes with its
    carry side-effects gated by `has_branch`, and stack writes land in
    frames that are DEAD on the pop path (slots > new depth), so they
    need no gating at all. (§Perf iteration 2, EXPERIMENTS.md.)

    `live=None` is the per-root path (depth is known >= 0 inside the
    while loop). The lane walks (`step_lanes`) pass a per-lane `live`: a
    dead lane reads/writes clamped slot max(depth, 0), every side-effect
    is masked off, and its depth passes through unchanged (until a refill
    revives it, in the persistent engine). Dead-lane stack writes are
    harmless: the slot write stores the frame's own values back, and the
    child push lands one slot above the lane's depth, which is dead (a
    lane cut by max_iters keeps its top frame) or overwritten by the next
    real push before any read (pushes always precede descends)."""
    lv = jnp.bool_(True) if live is None else live
    d = depth if live is None else jnp.maximum(depth, 0)
    f = stack.read(d)

    if cfg.backend in fr.PIVOT_BACKENDS:
        has_branch = fr.any_bit(f.B) & lv
        w = fr.first_bit_index(f.B)
    else:
        # rcd: clique test decides report-and-pop vs min-degree branch
        hb, w = piv.rcd_select(ctx, f.P)
        has_branch = hb & lv

    # ---- pop path: rcd maximality check + report (gated) ----
    if cfg.backend == "rcd":
        carry = piv.rcd_maximality_report(carry, cfg, ctx, f.P, f.Xp,
                                          f.xal, f.Rb, f.rsz,
                                          has_branch | ~lv)

    # ---- branch path: always computed, side-effects gated ----
    wbit = ctx.eye[w]
    # fused frame step: child sets + child degree sweep + Lemma-7 partner
    # in one kernel pass over A (threaded into enter_call as `pre`)
    childP, childXp, deg, partner = bitops.frame_step(ctx.A, f.P, f.Xp,
                                                      ctx.A[w])
    # X0 rows stay alive iff adjacent to w (bit w of their row)
    row_word = jax.lax.dynamic_index_in_dim(
        ctx.x_rows, w // WORD, axis=1, keepdims=False)
    adj_w = ((row_word >> (w % WORD).astype(U32)) & U32(1)) != 0
    childxal = f.xal & fr.mask_to_bitset(adj_w, ctx.eye_x)
    carry = dict(carry,
                 branches=carry["branches"] + has_branch.astype(jnp.int32))
    carry, push, child = enter_call(carry, cfg, ctx, childP, childXp,
                                    childxal, f.rsz + 1, f.Rb | wbit,
                                    enable=has_branch, pre=(deg, partner))
    # update current frame (dead slot on the pop path — no gating):
    # P \ w, X ∪ w, B \ w
    cur = dict(P=jnp.where(has_branch, f.P & ~wbit, f.P),
               Xp=jnp.where(has_branch, f.Xp | wbit, f.Xp))
    if cfg.backend in fr.PIVOT_BACKENDS:
        cur["B"] = jnp.where(has_branch, f.B & ~wbit, f.B)
    stack = stack.write(d, **cur)
    # write child frame (slot depth+1 is dead unless pushed)
    nd = d + 1
    stack = stack.push(nd, child)
    new_depth = jnp.where(has_branch, jnp.where(push, nd, d), d - 1)
    if live is not None:
        new_depth = jnp.where(lv, new_depth, depth)
    return new_depth, stack, carry


def _enter_root(ctx: fr.RootContext, p0, x_alive0, rsz0, cfg: EngineConfig):
    """One root's entry call: its first depth (0, or -1 when the root
    finished inside the call), its stack with the root frame in slot 0,
    and its counters."""
    words = ctx.words
    # root frame: R = {v} (rsz=1), Rb covers universe additions only
    carry0, push0, frame0 = enter_call(
        fr.carry_init(cfg, words), cfg, ctx, p0, jnp.zeros(words, U32),
        fr.mask_to_bitset(x_alive0, ctx.eye_x), rsz0.astype(jnp.int32),
        jnp.zeros(words, U32))
    stack0 = FrameStack.alloc(ctx.u + 2, words, ctx.xc_words).push(0, frame0)
    return jnp.where(push0, jnp.int32(0), jnp.int32(-1)), stack0, carry0


def step_lanes(cfg: EngineConfig, A, x_rows, depth, live, stack, carry,
               eye, eye_x):
    """One masked `dfs_step` on every lane of an (L, …) batch: lane l walks
    its own root context (A[l], x_rows[l]), and a lane whose `live` is
    False changes nothing (`dfs_step`'s dead-lane contract). Shared by the
    lock-step walk and the persistent engine's step."""
    def lane_step(a_l, xr_l, d_l, lv_l, stk_l, car_l):
        ctx = fr.RootContext(A=a_l, x_rows=xr_l, eye=eye, eye_x=eye_x)
        return dfs_step(cfg, ctx, d_l, stk_l, car_l, live=lv_l)

    return jax.vmap(lane_step)(A, x_rows, depth, live, stack, carry)


def run_root(a, p0, x_rows, x_alive0, rsz0, cfg: EngineConfig):
    """Run the full BK subtree of one root. Returns the final carry dict
    plus `iters` (loop iterations used) and `truncated` (1 iff the walk
    hit cfg.max_iters with frames still live — the counts are partial).

    The reference walk of one root: `run_lockstep` runs a bucket of them
    in one loop, and tests/test_lockstep_loop.py holds the two equal."""
    ctx = fr.make_context(a, x_rows)
    depth0, stack0, carry0 = _enter_root(ctx, p0, x_alive0, rsz0, cfg)

    def cond(s):
        return (s[0] >= 0) & (s[1] < cfg.max_iters)

    @scoped("engine.step")
    def body(s):
        depth, it, stack, carry = s
        depth, stack, carry = dfs_step(cfg, ctx, depth, stack, carry)
        return depth, it + 1, stack, carry

    state = (depth0, jnp.int32(0), stack0, carry0)
    depth, it, _stack, carry = jax.lax.while_loop(cond, body, state)
    return dict(carry, iters=it, truncated=(depth >= 0).astype(jnp.int32))


def run_lockstep(a, p0, x_rows, x_alive0, rsz0, cfg: EngineConfig):
    """Walk a bucket of N roots lock-step, one lane per root: per-root
    stats equal to `jax.vmap(run_root)`'s, element for element.

    Every root enters under vmap; then ONE `lax.while_loop` runs over the
    (N, …) batch while any lane is live (depth >= 0 and under
    cfg.max_iters), each trip a masked `dfs_step` per lane (`step_lanes`).
    A `while_loop` under vmap would do the same work, but its batching
    rule, for a per-lane predicate, ends every trip with a select of the
    new against the old value of every carry leaf, i.e. the whole DFS
    stack, and the copies that keep both alive (DESIGN.md §2.5). Here a
    finished lane's step is masked inside `dfs_step` instead, so the
    stack is only updated in place. `iters` counts each lane's live
    trips and `truncated` flags lanes cut by cfg.max_iters."""
    N, U, words = a.shape
    XC = x_rows.shape[1]
    eye = fr.eye_bits(U, words)
    eye_x = fr.eye_bits(XC, max(-(-XC // WORD), 1))
    depth0, stack0, carry0 = jax.vmap(
        lambda a_r, xr_r, p_r, xa_r, rz_r: _enter_root(
            fr.RootContext(A=a_r, x_rows=xr_r, eye=eye, eye_x=eye_x),
            p_r, xa_r, rz_r, cfg))(a, x_rows, p0, x_alive0, rsz0)

    def live_of(depth, it):
        return (depth >= 0) & (it < cfg.max_iters)

    def cond(s):
        return jnp.any(live_of(s[0], s[1]))

    @scoped("engine.step")
    def body(s):
        depth, it, stack, carry = s
        live = live_of(depth, it)
        depth, stack, carry = step_lanes(cfg, a, x_rows, depth, live, stack,
                                         carry, eye, eye_x)
        return depth, it + live.astype(jnp.int32), stack, carry

    state = (depth0, jnp.zeros((N,), jnp.int32), stack0, carry0)
    depth, it, _stack, carry = jax.lax.while_loop(cond, body, state)
    return dict(carry, iters=it, truncated=(depth >= 0).astype(jnp.int32))


@partial(jax.jit, static_argnames=("cfg",))
def run_bucket(a, p0, x_rows, x_alive0, rsz0, cfg: EngineConfig):
    """The lock-step walk of a bucket (`run_lockstep`), jitted. Returns a
    dict of per-root stats."""
    return run_lockstep(a, p0, x_rows, x_alive0, rsz0, cfg)


# ===========================================================================
# Persistent bucket engine: lane-refill work queue + lane work stealing
# (DESIGN.md §2.6)
# ===========================================================================

def _persistent_state0(cfg: EngineConfig, lanes: int, U: int, words: int,
                       XC: int):
    """Fresh lane state for one same-shape span of the root stream."""
    # depth never exceeds U (= D − 2): every push consumes a P vertex
    D = U + 2
    xc_words = max(-(-XC // WORD), 1)
    track = bool(cfg.out_cap)
    carry0 = jax.tree.map(
        lambda x: jnp.zeros((lanes,) + x.shape, x.dtype),
        fr.carry_init(cfg, words, track_root=track))
    stack0 = jax.tree.map(
        lambda x: jnp.zeros((lanes,) + x.shape, x.dtype),
        FrameStack.alloc(D, words, xc_words))
    return (jnp.int32(0),                        # it: loop trips
            jnp.int32(0),                        # cp: queue claim counter
            jnp.int32(0),                        # ls: Σ useful lane steps
            jnp.int32(0),                        # st: steal count
            jnp.int32(0),                        # et: entry-terminated roots
            jnp.int32(0),                        # pt: phase (outer) trips
            jnp.full((lanes,), jnp.int32(-1)),   # per-lane DFS depth
            jnp.zeros((lanes, U, words), U32),   # per-lane adjacency context
            jnp.zeros((lanes, XC, words), U32),  # per-lane X0 rows
            stack0, carry0)


@partial(jax.jit, static_argnames=("cfg", "lanes", "drain"))
def _persistent_segment(a, p0, x_rows, x_alive0, rsz0, root_base, state,
                        cfg: EngineConfig, lanes: int, drain: bool):
    """One jitted (nested) loop draining one root slab into a lane state.

    `drain=True` runs until every lane's subtree exhausts (the classic
    per-bucket persistent loop). `drain=False` returns as soon as the
    queue is claimed out (`cp >= R`) with lanes still live — the stream
    caller (`run_stream_persistent`) then re-enters with the NEXT slab and
    the same lane state, so live lanes never drain at a bucket boundary.
    `root_base` offsets `cur_root` so enumerated cliques decode against
    the stream-global root index.

    A trip runs up to three phases over the (L, …) lane state: the
    refill (`engine.refill`: exhausted lanes claim the next queue roots),
    the steal once the queue is claimed out (`engine.steal`: an idle
    lane adopts half of a live lane's branch set), and one masked
    `dfs_step` on every lane (`step_lanes`, `engine.step`). Refill and
    steal are pure scheduling: counters and enumerated sets are
    bit-identical to the lock-step walk's.

    The loop is nested (DESIGN.md §2.6). An outer trip runs one cond:
    the refill, then the steal if the refill claimed the queue out, when
    a refill is due, else the steal. An inner `while_loop` follows, whose
    first trip is the outer trip's step and whose further trips are bare
    steps, taken for as long as no refill is due and no steal could move
    work. On those trips refill and steal would be the identity, so the
    lane steps are those of a loop that runs both on every trip, trip
    for trip, without passing the lane state through a cond's identity
    branch. The root contexts `al`, `xrl` are invariant in the inner
    loop, so work on them alone (Lemma 8's complement of the X rows) is
    hoisted out of it. `pt` counts the outer trips."""
    R, U, words = a.shape
    XC = x_rows.shape[1]
    eye = fr.eye_bits(U, words)
    xc_words = max(-(-XC // WORD), 1)
    eye_x = fr.eye_bits(XC, xc_words)
    # 'rcd' carries no branch set at rest — nothing to split, never steals
    can_steal = bool(cfg.steal) and cfg.backend in fr.PIVOT_BACKENDS
    if cfg.steal_victim not in ("branchiest", "deepest"):
        raise ValueError(f"unknown steal_victim {cfg.steal_victim!r} "
                         "(expected 'branchiest' or 'deepest')")

    def more(it, cp, depth):
        left = ((cp < R) | jnp.any(depth >= 0)) if drain else (cp < R)
        return left & (it < cfg.max_iters)

    def cond(s):
        return more(s[0], s[1], s[6])

    def refill_due(cp, depth):
        return (cp < R) & jnp.any(depth < 0)

    def steal_on(cp, depth):
        # only once the queue can no longer feed the idle lane — while
        # roots remain, claiming is strictly cheaper than splitting
        return jnp.any(depth < 0) & jnp.any(depth >= 0) & (cp >= R)

    def split_slots(depth, B):
        """(branch counts, splittable slots, splittable lanes): a live
        slot whose branch set still has >= 2 branches can be halved."""
        bcnt = fr.popcount(B)                          # (L, D)
        slot_ix = jnp.arange(bcnt.shape[1], dtype=jnp.int32)[None, :]
        live_slot = (slot_ix <= depth[:, None]) & (bcnt >= 2)
        return bcnt, live_slot, (depth >= 0) & jnp.any(live_slot, axis=1)

    @scoped("engine.refill")
    def refill(args):
        """Claim protocol: exhausted lanes take consecutive queue slots."""
        cp, ls, et, depth, al, xrl, stack, carry = args
        exh = depth < 0
        exh_i = exh.astype(jnp.int32)
        offs = jnp.cumsum(exh_i) - exh_i       # exclusive cumsum per lane
        cand = cp + offs
        claim = exh & (cand < R)
        idx = jnp.where(claim, cand, 0)
        a_new = jnp.take(a, idx, axis=0)
        p_new = jnp.take(p0, idx, axis=0)
        xr_new = jnp.take(x_rows, idx, axis=0)
        xa_new = jnp.take(x_alive0, idx, axis=0)
        rz_new = jnp.take(rsz0, idx, axis=0)

        def lane_entry(claim_l, idx_l, a_l, p_l, xr_l, xa_l, rz_l,
                       depth_l, A_l, XR_l, stack_l, carry_l):
            ctx = fr.RootContext(A=a_l, x_rows=xr_l, eye=eye, eye_x=eye_x)
            if "cur_root" in carry_l:
                carry_l = dict(carry_l, cur_root=jnp.where(
                    claim_l, root_base + idx_l, carry_l["cur_root"]))
            xal0 = fr.mask_to_bitset(xa_l, eye_x)
            # hybrid's early-termination/X-domination census runs INSIDE
            # enter_call, i.e. inside this refill cond: a claimed root
            # whose P is already an undominated clique reports here and
            # `push` stays False — it never occupies a lane trip.
            carry_l, push, f0 = enter_call(
                carry_l, cfg, ctx, p_l, jnp.zeros(words, U32), xal0,
                rz_l.astype(jnp.int32), jnp.zeros(words, U32),
                enable=claim_l)
            # merge the fresh root frame into stack slot 0 where claimed
            old0 = stack_l.read(0)
            f0m = Frame(*(jnp.where(claim_l, n, o)
                          for n, o in zip(f0, old0)))
            stack_l = stack_l.push(0, f0m)
            depth_l = jnp.where(claim_l,
                                jnp.where(push, jnp.int32(0), jnp.int32(-1)),
                                depth_l)
            A_l = jnp.where(claim_l, a_l, A_l)
            XR_l = jnp.where(claim_l, xr_l, XR_l)
            return depth_l, A_l, XR_l, stack_l, carry_l

        depth, al, xrl, stack, carry = jax.vmap(lane_entry)(
            claim, idx, a_new, p_new, xr_new, xa_new, rz_new,
            depth, al, xrl, stack, carry)
        cp = cp + jnp.sum(claim.astype(jnp.int32))
        # a claimed root that finished inside its entry call (no push) did
        # its whole subtree's work this trip — count it as a useful trip
        done_entry = jnp.sum((claim & (depth < 0)).astype(jnp.int32))
        ls = ls + done_entry
        et = et + done_entry
        return cp, ls, et, depth, al, xrl, stack, carry

    @scoped("engine.steal")
    def steal(args):
        """STEAL transition (DESIGN.md §2.6): an idle lane adopts half of
        a live lane's shallowest splittable branch set (slot 0 — the true
        bottom of stack — while it still has branches left). The victim is
        picked by `cfg.steal_victim`: 'branchiest' (default) takes the
        lane whose donation slot has the largest remaining branch set —
        the biggest transferable subtree — while 'deepest' keeps the
        legacy deepest-lane heuristic. Either way the steal is pure
        scheduling: counters and enumerated sets are bit-identical.

        The victim keeps the LOW half of B (the bits its own walk would
        process first); the thief's slot-0 frame is exactly the state the
        victim's frame would reach after branching on every kept bit:
        P \\ keep, Xp ∪ keep, B = donated half. Each branch vertex still
        receives exactly one enter_call with an identical (P, Xp, xal)
        state, so calls/branches/sum_px/cliques and the enumerated set are
        bit-identical to the steal-free walk — stealing is pure
        scheduling. The thief also adopts the victim's root context and
        `cur_root`, so enumeration decode follows the work."""
        st, depth, al, xrl, stack, carry = args
        idle = depth < 0
        # donation point: the victim's SHALLOWEST live frame whose branch
        # set still has >= 2 branches — slot 0 (the true bottom of stack)
        # when it has work left, else the next-shallowest. Shallow frames
        # root the largest remaining subtrees, so halving there moves the
        # most work per steal.
        bcnt, live_slot, splittable = split_slots(depth, stack.B)
        do = jnp.any(idle) & jnp.any(splittable)
        # each lane's donation slot is its shallowest splittable frame;
        # score victims by that slot's branch-set size (the work a steal
        # would actually move) under the default 'branchiest' policy
        slot_l = jnp.argmax(live_slot, axis=1).astype(jnp.int32)  # (L,)
        donor = jnp.take_along_axis(bcnt, slot_l[:, None], axis=1)[:, 0]
        if cfg.steal_victim == "deepest":
            victim = jnp.argmax(jnp.where(splittable, depth,
                                          jnp.int32(-1)))
        else:
            victim = jnp.argmax(jnp.where(splittable, donor,
                                          jnp.int32(-1)))
        slot = slot_l[victim]
        thief = jnp.argmax(idle).astype(victim.dtype)
        P0, B0 = stack.P[victim, slot], stack.B[victim, slot]
        Xp0, Rb0 = stack.Xp[victim, slot], stack.Rb[victim, slot]
        rs0, xa0 = stack.rsz[victim, slot], stack.xal[victim, slot]
        # split B at bit rank ceil(|B|/2): keep = lowest-ranked half
        in_b = fr.bitset_to_mask(B0, U)
        ib = in_b.astype(jnp.int32)
        rank = jnp.cumsum(ib) - ib
        keep = fr.mask_to_bitset(
            in_b & (rank < (bcnt[victim, slot] + 1) // 2), eye)
        donate = B0 & ~keep

        def put(arr, lane, d, val):
            return arr.at[lane, d].set(jnp.where(do, val, arr[lane, d]))

        stack = stack._replace(B=put(stack.B, victim, slot, keep))
        stack = stack._replace(
            P=put(stack.P, thief, 0, P0 & ~keep),
            B=put(stack.B, thief, 0, donate),
            Xp=put(stack.Xp, thief, 0, Xp0 | keep),
            Rb=put(stack.Rb, thief, 0, Rb0),
            rsz=put(stack.rsz, thief, 0, rs0),
            xal=put(stack.xal, thief, 0, xa0))
        depth = depth.at[thief].set(
            jnp.where(do, jnp.int32(0), depth[thief]))
        al = al.at[thief].set(jnp.where(do, al[victim], al[thief]))
        xrl = xrl.at[thief].set(jnp.where(do, xrl[victim], xrl[thief]))
        if "cur_root" in carry:
            cr = carry["cur_root"]
            carry = dict(carry, cur_root=cr.at[thief].set(
                jnp.where(do, cr[victim], cr[thief])))
        st = st + do.astype(jnp.int32)
        return st, depth, al, xrl, stack, carry

    def refill_then_steal(args):
        """A refill, then the steal if the refill claimed the queue out."""
        cp, ls, st, et, depth, al, xrl, stack, carry = args
        cp, ls, et, depth, al, xrl, stack, carry = refill(
            (cp, ls, et, depth, al, xrl, stack, carry))
        if can_steal:
            st, depth, al, xrl, stack, carry = jax.lax.cond(
                steal_on(cp, depth), steal, lambda args: args,
                (st, depth, al, xrl, stack, carry))
        return cp, ls, st, et, depth, al, xrl, stack, carry

    def steal_only(args):
        """The steal: with no refill due it moves work exactly when a lane
        idles on an empty queue beside a splittable one (its `do`), and
        changes nothing otherwise."""
        cp, ls, st, et, depth, al, xrl, stack, carry = args
        if can_steal:
            st, depth, al, xrl, stack, carry = steal(
                (st, depth, al, xrl, stack, carry))
        return cp, ls, st, et, depth, al, xrl, stack, carry

    def body(s):
        it, cp, ls, st, et, pt, depth, al, xrl, stack, carry = s
        cp, ls, st, et, depth, al, xrl, stack, carry = jax.lax.cond(
            refill_due(cp, depth), refill_then_steal, steal_only,
            (cp, ls, st, et, depth, al, xrl, stack, carry))

        def stepping(w):
            """The outer trip's own step, then bare steps while no phase
            is due."""
            first, it, _, depth, stack, _ = w
            due = refill_due(cp, depth)
            if can_steal:
                # the splittable test reads every branch set: only while
                # a lane idles on an empty queue
                due = due | jax.lax.cond(
                    steal_on(cp, depth),
                    lambda B: jnp.any(split_slots(depth, B)[2]),
                    lambda B: jnp.bool_(False), stack.B)
            return first | (more(it, cp, depth) & ~due)

        def step(w):
            _, it, ls, depth, stack, carry = w
            ls = ls + jnp.sum((depth >= 0).astype(jnp.int32))
            with jax.named_scope("engine.step"):
                depth, stack, carry = step_lanes(cfg, al, xrl, depth,
                                                 depth >= 0, stack, carry,
                                                 eye, eye_x)
            return jnp.bool_(False), it + 1, ls, depth, stack, carry

        _, it, ls, depth, stack, carry = jax.lax.while_loop(
            stepping, step, (jnp.bool_(True), it, ls, depth, stack, carry))
        return it, cp, ls, st, et, pt + 1, depth, al, xrl, stack, carry

    return jax.lax.while_loop(cond, body, state)


def _persistent_out(state, R: int):
    """Realize a lane state into the public output dict."""
    (it, cp, ls, st, et, pt, depth, _al, _xrl, _stack, carry) = state
    out = dict(carry)
    out["iters"] = it
    out["phase_trips"] = pt
    out["live_iters"] = ls
    out["claimed"] = cp
    out["steals"] = st
    out["entry_terms"] = et
    out["truncated"] = ((cp < R) | jnp.any(depth >= 0)).astype(jnp.int32)
    return out


@partial(jax.jit, static_argnames=("cfg", "lanes"))
def run_bucket_persistent(a, p0, x_rows, x_alive0, rsz0, cfg: EngineConfig,
                          lanes: int = 64):
    """One jitted while_loop over a (LANES, …) batch of DFS states fed by a
    device-resident root work queue.

    The lock-step `run_bucket` keeps one lane per root: every lane spins
    (masked) until the slowest root in the bucket finishes. Here a lane
    whose subtree exhausts (`depth < 0`) claims the next unstarted root
    inside the loop body — shared claim counter + per-lane exclusive-cumsum
    offsets, no host round-trip — and reinitializes its stack in place, so
    lanes stay saturated until the queue drains. Roots are consumed in the
    caller's array order (the driver passes its cost-descending canonical
    order, so the queue order IS the checkpoint cursor order).

    The refill phase sits in a real `lax.cond`: this loop is not under
    vmap (where a cond lowers to SELECT), so trips with no exhausted lane
    skip the (LANES, U, W) root-context gathers entirely, and trips that
    need neither refill nor steal run in an inner loop with no cond
    (`_persistent_segment`). Once the queue is claimed out, the STEAL
    transition (cfg.steal, pivot-family backends) lets an idle lane
    split off half of a live lane's shallowest splittable branch set
    (slot 0 while it has work, else the frame just above it; the lane is
    picked by `cfg.steal_victim`), so a hub root's subtree spreads
    across lanes instead of serializing on one (counters and enumerated
    sets are unchanged — stealing is pure scheduling).

    Returns the per-lane carry dict plus scalars: `iters` (loop trips),
    `phase_trips` (the trips that ran the refill and steal conds),
    `live_iters` (Σ useful lane-trips: live lanes per trip, plus claims
    whose root completed inside its entry call — those do their whole
    subtree's work in the refill; occupancy = live_iters /
    (iters·lanes)), `claimed`, `steals` (adopted branch-set halves),
    `entry_terms` (claims that completed inside their entry call — for
    the hybrid backend this includes every root early-terminated by the
    refill-phase census), and `truncated` (1 iff cfg.max_iters hit with
    work remaining)."""
    R, U, words = a.shape
    XC = x_rows.shape[1]
    state0 = _persistent_state0(cfg, lanes, U, words, XC)
    state = _persistent_segment(a, p0, x_rows, x_alive0, rsz0,
                                jnp.int32(0), state0, cfg=cfg, lanes=lanes,
                                drain=True)
    return _persistent_out(state, R)


def run_stream_persistent(slabs, cfg: EngineConfig, lanes: int = 64):
    """Bucket-spanning persistent engine over a stream of root slabs.

    `slabs` is an iterable of `(a, p0, x_rows, x_alive0, rsz0)` tuples in
    the caller's (canonical cost-descending) root order. Consecutive slabs
    sharing a shape signature `(U, words, XC)` form a SPAN: the lane state
    (stacks, contexts, counters) carries across their boundary, so lanes
    that are mid-subtree when slab k's queue is claimed out keep running
    while slab k+1's queue feeds the refills — the loop spans the whole
    span instead of draining and re-launching per bucket. Each non-final
    slab runs a `drain=False` segment (returns as soon as its queue is
    claimed out); the span's last slab re-enters with `drain=True`. A
    shape change flushes the span (different frame/stack shapes cannot
    share a compiled loop — those boundaries still re-launch).

    Segments dispatch asynchronously: the host can stage slab k+1 (pack +
    device_put) while the device drains slab k — the driver's §6.4
    double-buffered overlap contract, applied to the root queue itself.

    `cur_root` is offset by the stream-global root base (slab-order prefix
    sums over slab lengths), so `out_root` decodes against the whole
    stream. Returns `(outs, spans)`: `outs[i]` is the i-th span's output
    dict (same schema as `run_bucket_persistent`) and `spans[i] = (lo,
    hi)` its slab index range."""
    outs, spans = [], []
    state = None
    sig = None
    prev = None          # last slab fed to the open span (drain target)
    lanes_g = lanes
    root_base = 0
    lo = 0
    n = 0
    for k, slab in enumerate(slabs):
        n = k + 1
        a = slab[0]
        s = (a.shape[1], a.shape[2], slab[2].shape[1])
        if state is not None and s != sig:
            # shape change: drain the open span and flush its output
            state = _persistent_segment(
                *prev, jnp.int32(root_base - prev[0].shape[0]), state,
                cfg=cfg, lanes=lanes_g, drain=True)
            outs.append(_persistent_out(state, prev[0].shape[0]))
            spans.append((lo, k))
            state, prev = None, None
        if state is None:
            sig = s
            lo = k
            lanes_g = max(1, min(lanes, a.shape[0]))
            state = _persistent_state0(cfg, lanes_g, *s)
        else:
            # re-arm the claim counter for the new slab; everything else
            # (lane depths, stacks, contexts, counters) carries over
            state = (state[0], jnp.int32(0)) + state[2:]
        state = _persistent_segment(*slab, jnp.int32(root_base), state,
                                    cfg=cfg, lanes=lanes_g, drain=False)
        prev = slab
        root_base += a.shape[0]
    if state is not None:
        state = _persistent_segment(
            *prev, jnp.int32(root_base - prev[0].shape[0]), state,
            cfg=cfg, lanes=lanes_g, drain=True)
        outs.append(_persistent_out(state, prev[0].shape[0]))
        spans.append((lo, n))
    return outs, spans


# ===========================================================================
# High-level API
# ===========================================================================

def root_cost_skew(costs) -> float:
    """max/mean skew of a per-root cost proxy, hardened for edge buckets.

    Degenerate inputs (empty, all-zero/all-pad, NaN/inf costs) answer 1.0
    — "uniform", which routes to perroot downstream — instead of crashing
    on a length-0 max or exploding to max/1e-12 on an all-but-zero mean.
    The skew is clamped to n_roots: max/mean ≤ n holds for any nonnegative
    vector, so anything larger is float-noise from a near-zero mean and
    would otherwise misroute trivial buckets to the persistent engine.
    Shared by `choose_engine` and the driver's per-bucket memo so cached
    replays and fresh runs always agree."""
    costs = np.asarray(costs, dtype=np.float64)
    n = int(costs.size)
    if n == 0:
        return 1.0
    m = float(costs.max())
    mean = float(costs.mean())
    if not np.isfinite(m) or m <= 0.0 or mean <= 0.0:
        return 1.0
    return min(m / mean, float(n))


def choose_engine(costs: Optional[np.ndarray] = None, *, lanes: int = 64,
                  skew: Optional[float] = None,
                  n_roots: Optional[int] = None,
                  skew_threshold: float = 4.0, min_roots: int = 16,
                  steal: bool = False):
    """Pick (engine, lanes) for one bucket from its root-cost skew.

    skew = max/mean of the per-root cost proxy (`prepare.estimate_costs`).
    A uniform bucket (skew < threshold) runs the lock-step per-root walk:
    every lane finishes together, so a work queue would add claim overhead
    and win nothing. A skewed bucket runs the persistent lane-refill
    queue — that is exactly the regime where lock-step lanes idle behind
    the one hub root. Lanes are sized so the queue actually refills
    (>= ~4 roots per lane on average), clamped to [8, lanes]; tiny
    buckets (< min_roots) stay on perroot where one compile per shape is
    cheaper than the queue machinery.

    `steal=True` declares that the config the bucket will actually run
    with can steal (cfg.steal on AND a pivot-family backend): lane work
    stealing splits a hub root's subtree across lanes once the queue
    drains, which de-serializes exactly the moderate-skew buckets the
    plain threshold routes to perroot — so the effective skew threshold
    halves. Callers that can't steal (rcd, cfg.steal off) must pass
    False and keep the conservative boundary.

    Callers treat explicit engine= flags as overrides; this is only the
    `engine="auto"` policy, kept in the engine layer so both the
    single-host `run()` and the distributed driver share it (the driver
    imports the engine, never the reverse — DESIGN.md §6). Pass
    `skew=`/`n_roots=` instead of `costs` when the skew is already
    memoized (the driver caches it on the bucket for cached replays).
    Edge buckets never crash or misroute: empty/all-pad/degenerate cost
    vectors score skew 1.0 and the skew is clamped to n_roots either way
    (`root_cost_skew`)."""
    if costs is not None:
        costs = np.asarray(costs, dtype=np.float64)
        n_roots = int(costs.size)
        skew = root_cost_skew(costs)   # 1.0 on empty/all-pad/degenerate
    if skew is None or n_roots is None or not np.isfinite(skew):
        return "perroot", lanes
    skew = min(skew, float(max(n_roots, 1)))   # memoized-skew callers too
    thr = skew_threshold / 2.0 if steal else skew_threshold
    if n_roots < min_roots or skew < thr:
        return "perroot", lanes
    per_lane = max(1, n_roots // 4)
    refill_lanes = 1 << (per_lane.bit_length() - 1)   # largest pow2 <= n/4
    return "persistent", max(8, min(lanes, refill_lanes))


@dataclasses.dataclass
class MCEResult:
    cliques: int
    calls: int
    branches: int
    sum_px: int
    pre_reported: int
    enumerated: Optional[List[frozenset]] = None
    overflow: bool = False
    iters_exhausted: bool = False
    stats: Optional[dict] = None   # service layer: per-query occupancy
    # counters (live_iters/lane_iters/truncated/engine_choices) — see
    # launch.mce_service.MCEService


def run(g: CSRGraph, *, global_red: bool = True, dynamic_red: bool = True,
        x_red: bool = True, backend: str = "pivot",
        enumerate_cliques: bool = False, out_cap: int = 4096,
        bucket_sizes: Sequence[int] = (32, 64, 128, 256, 512, 1024),
        max_x_rows: int = 8192,
        split_threshold: Optional[int] = None,
        engine: str = "perroot", lanes: int = 64,
        steal: bool = True, steal_victim: str = "branchiest") -> MCEResult:
    """End-to-end single-host MCE: prepare on host, run buckets on device.

    `engine='persistent'` routes each bucket through the lane-refill work
    queue (`run_bucket_persistent` with min(lanes, roots) lanes); the
    default 'perroot' path vmaps one lock-step lane per root.
    `engine='auto'` picks per bucket from the root-cost skew
    (`choose_engine`); the explicit flags remain hard overrides."""
    if engine not in ("perroot", "persistent", "auto"):
        raise ValueError(f"unknown engine {engine!r}")
    if backend not in fr.BACKENDS:
        raise ValueError(f"unknown backend {backend!r} "
                         f"(expected one of {fr.BACKENDS})")
    prep = prepare(g, global_red=global_red, x_red=x_red,
                   bucket_sizes=bucket_sizes, max_x_rows=max_x_rows,
                   split_threshold=split_threshold)
    cfg = EngineConfig(dynamic_red=dynamic_red, backend=backend,
                       out_cap=out_cap if enumerate_cliques else 0,
                       steal=steal, steal_victim=steal_victim)
    total = MCEResult(cliques=len(prep.pre_reported), calls=0, branches=0,
                      sum_px=0, pre_reported=len(prep.pre_reported),
                      enumerated=list(prep.pre_reported) if enumerate_cliques else None)
    if engine == "persistent":
        # bucket-spanning path: consecutive same-shape buckets share one
        # lane state (run_stream_persistent) — no drain at their boundary
        slabs = [tuple(jnp.asarray(x) for x in
                       (b.a, b.p0, b.x_rows, b.x_alive0, b.rsz0))
                 for b in prep.buckets]
        outs, spans = run_stream_persistent(slabs, cfg, lanes=lanes)
        prefix = np.cumsum([0] + [b.num_roots for b in prep.buckets])
        total.stats = dict(iters=0, phase_trips=0, live_iters=0,
                           lane_iters=0, steals=0, entry_terms=0,
                           spans=len(spans))
        for out, (lo, hi) in zip(outs, spans):
            out = jax.tree.map(np.asarray, out)
            total.stats["iters"] += int(out["iters"])
            total.stats["phase_trips"] += int(out["phase_trips"])
            total.stats["live_iters"] += int(out["live_iters"])
            # carry is per-lane, so its leading dim is this span's lanes
            total.stats["lane_iters"] += (int(out["iters"])
                                          * int(out["calls"].shape[0]))
            total.stats["steals"] += int(out["steals"])
            total.stats["entry_terms"] += int(out["entry_terms"])
            total.cliques += int(out["cliques"].sum())
            # padded no-op roots (compile-count hygiene) are one call each
            total.calls += (int(out["calls"].sum())
                            - sum(b.n_pad for b in prep.buckets[lo:hi]))
            total.branches += int(out["branches"].sum())
            total.sum_px += int(out["sum_px"].sum())
            total.iters_exhausted |= bool(out["truncated"].any())
            if enumerate_cliques:
                total.overflow |= bool(out["overflow"].any())
                # out_root carries the stream-global root index; decode it
                # back to (bucket, local root) via the slab prefix sums
                for l in range(out["out_n"].shape[0]):
                    for k in range(int(out["out_n"][l])):
                        r = int(out["out_root"][l, k])
                        bi = int(np.searchsorted(prefix, r,
                                                 side="right")) - 1
                        bucket = prep.buckets[bi]
                        rloc = r - int(prefix[bi])
                        uni = bucket.universes[rloc]
                        base = [int(b) for b in bucket.bases[rloc]]
                        members = _unpack_bits_np(out["out_rows"][l, k])
                        total.enumerated.append(frozenset(
                            base + [int(uni[m]) for m in members]))
        return total
    for bucket in prep.buckets:
        args = (jnp.asarray(bucket.a), jnp.asarray(bucket.p0),
                jnp.asarray(bucket.x_rows), jnp.asarray(bucket.x_alive0),
                jnp.asarray(bucket.rsz0))
        eng_b, lanes_b = engine, lanes
        if engine == "auto":
            total_real = bucket.num_roots - bucket.n_pad
            eng_b, lanes_b = choose_engine(
                estimate_costs(bucket)[:total_real], lanes=lanes,
                steal=steal and backend in fr.PIVOT_BACKENDS)
        if eng_b == "persistent":
            out = run_bucket_persistent(*args, cfg,
                                        lanes=min(lanes_b, bucket.num_roots))
        else:
            out = run_bucket(*args, cfg)
        out = jax.tree.map(np.asarray, out)
        total.cliques += int(out["cliques"].sum())
        # padded no-op roots (compile-count hygiene) are one call each
        total.calls += int(out["calls"].sum()) - bucket.n_pad
        total.branches += int(out["branches"].sum())
        total.sum_px += int(out["sum_px"].sum())
        total.iters_exhausted |= bool(out["truncated"].any())
        if enumerate_cliques:
            total.overflow |= bool(out["overflow"].any())
            if eng_b == "persistent":
                # lanes interleave roots; out_root maps each clique back
                for l in range(out["out_n"].shape[0]):
                    for k in range(int(out["out_n"][l])):
                        r = int(out["out_root"][l, k])
                        uni = bucket.universes[r]
                        base = [int(b) for b in bucket.bases[r]]
                        members = _unpack_bits_np(out["out_rows"][l, k])
                        total.enumerated.append(frozenset(
                            base + [int(uni[m]) for m in members]))
            else:
                for r in range(bucket.num_roots):
                    uni = bucket.universes[r]
                    base = [int(b) for b in bucket.bases[r]]
                    for k in range(int(out["out_n"][r])):
                        bits = out["out_rows"][r, k]
                        members = _unpack_bits_np(bits)
                        clique = frozenset(base + [int(uni[m])
                                                   for m in members])
                        total.enumerated.append(clique)
    return total
