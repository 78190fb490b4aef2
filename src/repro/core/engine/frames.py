"""Frame & stack layout for the bitset BK engine (DESIGN.md §2.3).

A BK call is a fixed-shape *frame* of bitsets over the root's local
universe; the explicit DFS stack is one pre-allocated buffer per frame
field, depth-indexed. Everything here is shape/layout plumbing — the
search semantics live in `reductions`, `pivot`, and `loop`.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.bitset_ops import ops as bitops

WORD = 32
U32 = jnp.uint32
FULL = jnp.uint32(0xFFFFFFFF)


# ===========================================================================
# Small bitset helpers (device) — index/layout glue; all popcount/AND set
# algebra over row matrices goes through repro.kernels.bitset_ops.ops.
# ===========================================================================

def popcount(bits):
    return bitops.popcount_words(bits)


def any_bit(bits):
    return jnp.any(bits != 0, axis=-1)


def first_bit_index(bits):
    nz = bits != 0
    w = jnp.argmax(nz)
    word = bits[w]
    low = word & (U32(0) - word)
    pos = jax.lax.population_count(low - U32(1))
    return (w * WORD + pos).astype(jnp.int32)


def test_bit(bits, index):
    word = bits[index // WORD]
    return ((word >> (index % WORD).astype(U32)) & U32(1)) != 0


def bitset_to_mask(bits, u):
    idx = jnp.arange(u)
    words = bits[idx // WORD]
    return ((words >> (idx % WORD).astype(U32)) & U32(1)) != 0


def eye_bits(u, words):
    """(U, W) constant: EYE[i] = bitset with only bit i."""
    idx = jnp.arange(u)
    col = jnp.arange(words)
    return jnp.where(col[None, :] == (idx[:, None] // WORD),
                     U32(1) << (idx[:, None] % WORD).astype(U32), U32(0))


def mask_to_bitset(mask, eye):
    return jnp.bitwise_or.reduce(
        jnp.where(mask[:, None], eye, U32(0)), axis=0)


def or_reduce(rows, sel):
    return jnp.bitwise_or.reduce(
        jnp.where(sel[:, None], rows, U32(0)), axis=0)


def and_reduce(rows, sel):
    # De Morgan (AND-reduce = ~OR-reduce of complements): jnp's bitwise_and
    # reduction builds a signed -1 identity that overflows uint32 on numpy≥2.
    return jnp.bitwise_not(jnp.bitwise_or.reduce(
        jnp.where(sel[:, None], jnp.bitwise_not(rows), U32(0)), axis=0))


def single_bit_index_rows(rows):
    nz = rows != 0
    word_idx = jnp.argmax(nz, axis=1)
    word = jnp.take_along_axis(rows, word_idx[:, None], axis=1)[:, 0]
    low = word & (U32(0) - word)
    pos = jax.lax.population_count(low - U32(1))
    return (word_idx * WORD + pos).astype(jnp.int32)


# ===========================================================================
# Engine configuration
# ===========================================================================

BACKENDS = ("pivot", "rcd", "revised", "hybrid")
# Backends that precompute a branch set B at call entry ('rcd' re-selects
# per visit instead); 'hybrid' is pivot-family with a per-node
# vertex-branching override plus early termination (DESIGN.md §2.7).
PIVOT_BACKENDS = ("pivot", "revised", "hybrid")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    dynamic_red: bool = True
    backend: str = "pivot"          # one of BACKENDS
    out_cap: int = 0                # >0: enumerate into a fixed buffer
    max_iters: int = 1 << 30
    # §Perf: reuse the post-reduction degree vector for pivot scoring via
    # deg_P''(u) = deg_P'(u) − |full| (full vertices neighbor all of P'),
    # eliminating one of the three AND+popcount sweeps over A per call.
    reuse_degrees: bool = True
    # 'hybrid' branch selection: switch from pivot- to vertex-branching
    # (B = P) when the induced density 2|E[P]| / (|P|·(|P|−1)) reaches this
    # threshold — near-clique nodes early-terminate in their children, so
    # the pivot sweep's pruning buys nothing there (DESIGN.md §2.7).
    hybrid_density: float = 0.9
    # Persistent-engine lane work stealing (DESIGN.md §2.6 STEAL): when the
    # root queue is drained and a lane idles, it adopts half of the deepest
    # live lane's bottom-of-stack branch set. Pure scheduling — counters and
    # enumerated sets are bit-identical either way (pivot-family backends
    # only; 'rcd' carries no branch set and never steals).
    steal: bool = True
    # Steal victim policy: 'branchiest' (default) picks the lane whose
    # donation slot has the largest remaining branch set — the biggest
    # transferable subtree — 'deepest' keeps the legacy deepest-lane
    # heuristic. Pure scheduling either way (bit-identical counters/sets).
    steal_victim: str = "branchiest"


# ===========================================================================
# Per-root constant context + per-call frame + DFS stack
# ===========================================================================

class RootContext(NamedTuple):
    """Per-root constants threaded through the DFS (never stacked)."""
    A: jnp.ndarray          # (U, W) induced adjacency bitsets
    x_rows: jnp.ndarray     # (XC, W) X0 row bitsets
    eye: jnp.ndarray        # (U, W) one-hot bitsets over the universe
    eye_x: jnp.ndarray      # (XC, XCW) one-hot bitsets over X0 rows

    @property
    def u(self) -> int:
        return self.A.shape[0]

    @property
    def words(self) -> int:
        return self.A.shape[1]

    @property
    def xc(self) -> int:
        return self.x_rows.shape[0]

    @property
    def xc_words(self) -> int:
        return self.eye_x.shape[1]


def make_context(a, x_rows) -> RootContext:
    u, words = a.shape
    xc = x_rows.shape[0]
    xc_words = max(-(-xc // WORD), 1)
    return RootContext(A=a, x_rows=x_rows, eye=eye_bits(u, words),
                       eye_x=eye_bits(xc, xc_words))


class Frame(NamedTuple):
    """One BK call: (R, P, X) in bitset form plus the branch set B."""
    P: jnp.ndarray          # (W,)  candidate bitset
    B: jnp.ndarray          # (W,)  branch set (pivot-pruned P)
    Xp: jnp.ndarray         # (W,)  universe members moved into X
    Rb: jnp.ndarray         # (W,)  universe additions to the base clique
    rsz: jnp.ndarray        # ()    |R| including the host-side base
    xal: jnp.ndarray        # (XCW,) packed alive mask over X0 rows


class FrameStack(NamedTuple):
    """Depth-indexed DFS stack: one pre-allocated buffer per Frame field.

    The X0 alive set is carried as a PACKED BITSET (§Perf iteration 3):
    the bool stack (D, XC) dominated the while carry traffic 8:1."""
    P: jnp.ndarray          # (D, W)
    B: jnp.ndarray          # (D, W)
    Xp: jnp.ndarray         # (D, W)
    Rb: jnp.ndarray         # (D, W)
    rsz: jnp.ndarray        # (D,)
    xal: jnp.ndarray        # (D, XCW)

    @staticmethod
    def alloc(depth: int, words: int, xc_words: int) -> "FrameStack":
        return FrameStack(
            P=jnp.zeros((depth, words), U32),
            B=jnp.zeros((depth, words), U32),
            Xp=jnp.zeros((depth, words), U32),
            Rb=jnp.zeros((depth, words), U32),
            rsz=jnp.zeros((depth,), jnp.int32),
            xal=jnp.zeros((depth, xc_words), U32))

    def read(self, d) -> Frame:
        return Frame(P=self.P[d], B=self.B[d], Xp=self.Xp[d], Rb=self.Rb[d],
                     rsz=self.rsz[d], xal=self.xal[d])

    def write(self, d, **fields) -> "FrameStack":
        """Write a subset of frame fields at depth d (others untouched, so
        pop-path-dead slots need no extra stores)."""
        return self._replace(**{k: getattr(self, k).at[d].set(v)
                                for k, v in fields.items()})

    def push(self, d, frame: Frame) -> "FrameStack":
        return self.write(d, **frame._asdict())


# ===========================================================================
# Counter/enumeration carry
# ===========================================================================

def carry_init(cfg: EngineConfig, words: int, track_root: bool = False):
    cap = max(cfg.out_cap, 1)
    carry = dict(
        cliques=jnp.int32(0),
        calls=jnp.int32(0),
        branches=jnp.int32(0),
        sum_px=jnp.int32(0),
        out_rows=jnp.zeros((cap, words), dtype=jnp.uint32),
        out_sizes=jnp.zeros((cap,), dtype=jnp.int32),
        out_n=jnp.int32(0),
        overflow=jnp.bool_(False),
    )
    if track_root and cfg.out_cap:
        # persistent lanes interleave roots, so every enumerated clique
        # records which queue slot produced it (per-root decode needs the
        # root's universe/base); `cur_root` is updated on each lane refill
        carry["cur_root"] = jnp.int32(0)
        carry["out_root"] = jnp.zeros((cap,), dtype=jnp.int32)
    return carry


def report_single(carry, cfg, bits, size, enable):
    cnt = enable.astype(jnp.int32)
    carry = dict(carry, cliques=carry["cliques"] + cnt)
    if cfg.out_cap:
        cap = cfg.out_cap
        pos = jnp.where(enable & (carry["out_n"] < cap), carry["out_n"], cap)
        carry["out_rows"] = carry["out_rows"].at[pos].set(bits, mode="drop")
        carry["out_sizes"] = carry["out_sizes"].at[pos].set(size, mode="drop")
        if "out_root" in carry:
            carry["out_root"] = carry["out_root"].at[pos].set(
                carry["cur_root"], mode="drop")
        carry["overflow"] = carry["overflow"] | (enable & (carry["out_n"] >= cap))
        carry["out_n"] = jnp.minimum(carry["out_n"] + cnt, cap)
    return carry


def report_multi(carry, cfg, rows, sizes, mask):
    cnt = jnp.sum(mask.astype(jnp.int32))
    carry = dict(carry, cliques=carry["cliques"] + cnt)
    if cfg.out_cap:
        cap = cfg.out_cap
        offs = carry["out_n"] + jnp.cumsum(mask.astype(jnp.int32)) - 1
        pos = jnp.where(mask & (offs < cap), offs, cap)
        carry["out_rows"] = carry["out_rows"].at[pos].set(rows, mode="drop")
        carry["out_sizes"] = carry["out_sizes"].at[pos].set(sizes, mode="drop")
        if "out_root" in carry:
            carry["out_root"] = carry["out_root"].at[pos].set(
                carry["cur_root"], mode="drop")
        carry["overflow"] = carry["overflow"] | jnp.any(mask & (offs >= cap))
        carry["out_n"] = jnp.minimum(carry["out_n"] + cnt, cap)
    return carry
