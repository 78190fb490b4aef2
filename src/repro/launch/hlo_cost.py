"""Trip-count-weighted static cost model over post-optimization HLO text.

Why: ``compiled.cost_analysis()`` counts while-loop bodies ONCE, so any
scan-over-layers model under-reports flops/bytes by ~n_layers (verified in
EXPERIMENTS.md §Roofline/Methodology). The optimized HLO carries
``known_trip_count`` on every counted loop, so this walker computes

    total[term] = Σ_computations  multiplier(comp) × raw[term](comp)

with multiplier = product of trip counts along the while/call chain from
ENTRY. Fusion-internal flops are folded into the fusion op's computation;
fusion bytes are operands+outputs of the fusion op (the HBM model — fused
elementwise chains never round-trip memory).

Costs:
  flops — dot: 2·|out|·Π(contracting dims); elementwise/reduce: |elems|;
  bytes — per op: operand bytes + output bytes (free: parameter, tuple,
          get-tuple-element, bitcast, constant, broadcast-of-scalar);
  link  — collective payload × ring factor (see ``collective_link_bytes``).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[\w\[\],{}\s/]*?\)?)\s*"
    r"([a-z][\w\-]*)\(")
_TRIP_RE = re.compile(r'known_trip_count":\{"n":"(\d+)"')
_CALLS_RE = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)="
                       r"(\{[^}]*\}|%?[\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")

FREE_OPS = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
            "after-all", "partition-id", "replica-id", "reshape",
            "custom-call"}
ELEMENTWISE_SKIP_FLOPS = {"parameter", "constant", "get-tuple-element",
                          "tuple", "bitcast", "broadcast", "copy", "reshape",
                          "transpose", "iota", "slice", "concatenate",
                          "reverse", "after-all", "partition-id",
                          "replica-id", "convert", "dynamic-slice",
                          "dynamic-update-slice", "pad", "gather", "scatter",
                          "all-gather", "all-reduce", "reduce-scatter",
                          "all-to-all", "collective-permute", "while",
                          "conditional", "call", "custom-call", "fusion",
                          "dot", "convolution", "reduce", "reduce-window",
                          "sort", "rng", "rng-bit-generator", "copy-start",
                          "copy-done", "optimization-barrier"}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a dict (empty when XLA gives none)."""
    return compiled.cost_analysis() or {}


def shape_elems_bytes(shape_str: str) -> Tuple[int, int]:
    elems_total, bytes_total = 0, 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        b = _DTYPE_BYTES.get(dt)
        if b is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems_total += n
        bytes_total += n * b
    if elems_total == 0 and shape_str.strip().startswith(("f", "s", "u", "p", "b")):
        # scalar like f32[] — regex above catches it with empty dims (n=1)
        pass
    return elems_total, bytes_total


def _shape_dims(shape_str: str) -> List[int]:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Op:
    name: str
    shape: str
    opcode: str
    operands: List[str]
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    params: Dict[str, str]
    ops: List[Op]


def parse_computations(txt: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in txt.splitlines():
        if cur is None:
            stripped = line.strip()
            m = _COMP_HDR.match(stripped)
            if m and line.rstrip().endswith("{") and "->" in line:
                # balance parens to extract the parameter list (types may be
                # tuples containing parens)
                start = m.end() - 1
                depth, end = 0, start
                for i in range(start, len(stripped)):
                    if stripped[i] == "(":
                        depth += 1
                    elif stripped[i] == ")":
                        depth -= 1
                        if depth == 0:
                            end = i
                            break
                params = {}
                plist = stripped[start + 1:end]
                # split top-level commas only (track () AND [] nesting)
                depth = 0
                cur_tok = []
                toks = []
                for ch in plist:
                    if ch in "([{":
                        depth += 1
                    elif ch in ")]}":
                        depth -= 1
                    if ch == "," and depth == 0:
                        toks.append("".join(cur_tok))
                        cur_tok = []
                    else:
                        cur_tok.append(ch)
                if cur_tok:
                    toks.append("".join(cur_tok))
                for p in toks:
                    pname, _, ptype = p.strip().partition(":")
                    if pname:
                        params[pname.strip().lstrip("%")] = ptype.strip()
                cur = Computation(m.group(1), params, [])
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        op = _parse_op_line(line)
        if op is not None:
            cur.ops.append(op)
    return comps


_ASSIGN_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")


def _parse_op_line(line: str) -> Optional[Op]:
    """`%var = TYPE opcode(operands), attrs` — TYPE may be a tuple with
    nested parens and /*index=k*/ comments."""
    m = _ASSIGN_RE.match(line)
    if not m:
        return None
    name = m.group(1)
    rest = line[m.end():]
    if rest.startswith("("):                  # tuple type: balance parens
        depth = 0
        end = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        shape = rest[:end + 1]
        rest = rest[end + 1:]
    else:                                     # plain type: first whitespace
        sp = rest.find(" ")
        if sp < 0:
            return None
        shape = rest[:sp]
        rest = rest[sp:]
    m2 = re.match(r"\s*([a-z][\w\-]*)\(", rest)
    if not m2:
        return None
    opcode = m2.group(1)
    paren = rest[m2.end() - 1:]
    depth = 0
    end = 0
    for i, ch in enumerate(paren):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    operands = _OPERAND_RE.findall(paren[:end + 1])
    return Op(name, shape, opcode, operands, line)


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{([^}]*)\}", line)
    if not m:
        return 1
    first = m.group(1).split("},{")[0]
    return max(1, first.count(",") + 1)


def collective_link_bytes(opcode: str, out_bytes: int, g: int) -> float:
    """Ring-algorithm per-device ICI traffic."""
    if g <= 1:
        return 0.0
    if opcode == "all-reduce":
        return 2.0 * (g - 1) / g * out_bytes
    if opcode == "all-gather":
        return (g - 1) / g * out_bytes          # out = full gathered value
    if opcode == "reduce-scatter":
        return (g - 1) * out_bytes              # in = out × g
    if opcode == "all-to-all":
        return (g - 1) / g * out_bytes
    if opcode == "collective-permute":
        return float(out_bytes)
    return 0.0


@dataclasses.dataclass
class CompCost:
    flops: float = 0.0
    bytes: float = 0.0
    link: float = 0.0
    collectives: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)


def _resolve(comp: Computation, name: str, symbols: Dict[str, str]) -> str:
    if name in symbols:
        return symbols[name]
    return comp.params.get(name, "")


def _fusion_flops(comps, comp_name, memo) -> float:
    """Elementwise + reduce + dot flops inside a fused computation."""
    if comp_name in memo:
        return memo[comp_name]
    comp = comps.get(comp_name)
    if comp is None:
        return 0.0
    total = 0.0
    symbols = {op.name: op.shape for op in comp.ops}
    for op in comp.ops:
        total += _op_flops(comps, comp, op, symbols, memo)
    memo[comp_name] = total
    return total


def _op_flops(comps, comp, op, symbols, fusion_memo) -> float:
    oc = op.opcode
    if oc == "dot":
        out_elems, _ = shape_elems_bytes(op.shape)
        m = _CONTRACT_RE.search(op.line)
        contract = 1
        if m and op.operands:
            lhs_shape = _resolve(comp, op.operands[0], symbols)
            dims = _shape_dims(lhs_shape)
            for idx in m.group(1).split(","):
                if idx and int(idx) < len(dims):
                    contract *= dims[int(idx)]
        return 2.0 * out_elems * contract
    if oc == "convolution":
        out_elems, _ = shape_elems_bytes(op.shape)
        return 2.0 * out_elems * 128          # coarse (unused by our models)
    if oc in ("reduce", "sort"):
        if op.operands:
            in_shape = _resolve(comp, op.operands[0], symbols)
            elems, _ = shape_elems_bytes(in_shape)
            return float(elems)
        return 0.0
    if oc == "reduce-window":
        out_elems, _ = shape_elems_bytes(op.shape)
        m = re.search(r"window=\{size=([\dx]+)", op.line)
        w = 1
        if m:
            for d in m.group(1).split("x"):
                w *= int(d)
        return float(out_elems * w)
    if oc == "fusion":
        m = re.search(r"calls=%?([\w.\-]+)", op.line)
        if m:
            return _fusion_flops(comps, m.group(1), fusion_memo)
        return 0.0
    if oc in ELEMENTWISE_SKIP_FLOPS:
        return 0.0
    out_elems, _ = shape_elems_bytes(op.shape)
    return float(out_elems)                    # generic elementwise


_TRANSPARENT = ("bitcast", "reshape", "transpose", "copy",
                "get-tuple-element", "convert")


def _slice_only_bytes(comp: "Computation", name: str,
                      depth: int = 0) -> Optional[float]:
    """If every use of `name` inside the fused computation reaches a
    (dynamic-)slice through layout-transparent ops, return the sliced bytes
    actually read; else None (full read)."""
    if depth > 6:
        return None
    uses = [op for op in comp.ops if name in op.operands]
    if not uses:
        return 0.0
    total = 0.0
    for u in uses:
        if u.opcode in ("dynamic-slice", "slice"):
            total += shape_elems_bytes(u.shape)[1]
        elif u.opcode in _TRANSPARENT:
            sub = _slice_only_bytes(comp, u.name, depth + 1)
            if sub is None:
                return None
            total += sub
        else:
            return None
    return total


def _fusion_root(comp: "Computation") -> Optional[Op]:
    for op in comp.ops:
        if "ROOT" in op.line:
            return op
    return comp.ops[-1] if comp.ops else None


def _trace_dus(comp: "Computation", root: Op) -> Optional[Op]:
    """Resolve the root through transparent ops to an in-place update op
    (dynamic-update-slice or scatter — both alias their buffer operand)."""
    cur = root
    seen = 0
    by_name = {op.name: op for op in comp.ops}
    while cur is not None and seen < 6:
        if cur.opcode in ("dynamic-update-slice", "scatter"):
            return cur
        if cur.opcode in _TRANSPARENT and cur.operands:
            cur = by_name.get(cur.operands[0])
            seen += 1
            continue
        return None
    return None


def _fusion_param_bytes(comps, called: str, idx: int, full_bytes: float,
                        memo: Dict) -> float:
    """Bytes actually read from fusion parameter `idx` (slice-aware)."""
    key = (called, idx)
    if key in memo:
        return memo[key]
    comp = comps.get(called)
    out = full_bytes
    if comp is not None:
        pnames = list(comp.params)
        if idx < len(pnames):
            sliced = _slice_only_bytes(comp, pnames[idx])
            if sliced is not None:
                out = min(float(sliced), full_bytes)
    memo[key] = out
    return out


def _fusion_dus_info(comps, called: str, memo: Dict):
    """(is_dus_root, update_bytes, buffer_param_index) for a fused comp."""
    key = ("dus", called)
    if key in memo:
        return memo[key]
    comp = comps.get(called)
    res = (False, 0.0, -1)
    if comp is not None:
        root = _fusion_root(comp)
        dus = _trace_dus(comp, root) if root else None
        if dus is not None and len(dus.operands) > 1:
            by_name = {op.name: op for op in comp.ops}
            upd_idx = 2 if dus.opcode == "scatter" else 1
            upd_idx = min(upd_idx, len(dus.operands) - 1)
            upd = by_name.get(dus.operands[upd_idx])
            upd_b = shape_elems_bytes(upd.shape)[1] if upd else 0.0
            # which fusion param is the aliased buffer (operand 0 chain)?
            pidx = -1
            cur = by_name.get(dus.operands[0])
            hops = 0
            while cur is not None and hops < 6:
                if cur.opcode == "parameter":
                    pnames = list(comp.params)
                    if cur.name in pnames:
                        pidx = pnames.index(cur.name)
                    break
                cur = (by_name.get(cur.operands[0])
                       if cur.operands else None)
                hops += 1
            # parameters may appear as comp.params rather than ops
            if pidx < 0 and dus.operands[0] in comp.params:
                pidx = list(comp.params).index(dus.operands[0])
            res = (True, float(upd_b), pidx)
    memo[key] = res
    return res


def _op_bytes(comp, op, symbols, comps=None,
              fusion_bytes_memo: Optional[Dict] = None) -> float:
    oc = op.opcode
    if oc in FREE_OPS or oc == "while" or oc == "conditional" or oc == "call":
        return 0.0
    _, out_b = shape_elems_bytes(op.shape)
    if oc == "broadcast":
        in_b = sum(shape_elems_bytes(_resolve(comp, o, symbols))[1]
                   for o in op.operands)
        return float(out_b + in_b)
    if oc == "dynamic-update-slice":
        upd = (shape_elems_bytes(_resolve(comp, op.operands[1], symbols))[1]
               if len(op.operands) > 1 else out_b)
        return 2.0 * upd
    if oc == "dynamic-slice":
        return 2.0 * out_b
    if oc == "scatter":
        upd = (shape_elems_bytes(_resolve(comp, op.operands[2], symbols))[1]
               if len(op.operands) > 2 else out_b)
        return 2.0 * upd
    if oc == "fusion" and comps is not None:
        m = re.search(r"calls=%?([\w.\-]+)", op.line)
        called = m.group(1) if m else None
        memo = fusion_bytes_memo if fusion_bytes_memo is not None else {}
        is_dus, upd_b, buf_idx = (_fusion_dus_info(comps, called, memo)
                                  if called else (False, 0.0, -1))
        in_b = 0.0
        for i, o in enumerate(op.operands):
            if is_dus and i == buf_idx:
                continue      # aliased in-place buffer: not actually read
            fb = shape_elems_bytes(_resolve(comp, o, symbols))[1]
            in_b += (_fusion_param_bytes(comps, called, i, fb, memo)
                     if called else fb)
        if is_dus:
            return float(in_b + upd_b)   # write = the updated region only
        return float(in_b + out_b)
    in_b = sum(shape_elems_bytes(_resolve(comp, o, symbols))[1]
               for o in op.operands)
    return float(in_b + out_b)


def analyze(txt: str) -> Dict[str, object]:
    """Weighted totals over the module. Returns flops/bytes/link/collectives
    plus the multiplier map (for debugging)."""
    comps = parse_computations(txt)
    fusion_memo: Dict[str, float] = {}

    # raw (unweighted) per-computation costs; record call edges
    raw: Dict[str, CompCost] = {}
    edges: Dict[str, List[Tuple[str, float]]] = {c: [] for c in comps}
    fused: set = set()
    fusion_bytes_memo: Dict = {}
    for cname, comp in comps.items():
        cost = CompCost()
        symbols = {op.name: op.shape for op in comp.ops}
        for op in comp.ops:
            if op.opcode == "while":
                trip = 1.0
                m = _TRIP_RE.search(op.line)
                if m:
                    trip = float(m.group(1))
                for attr in ("body", "condition"):
                    m2 = re.search(attr + r"=%?([\w.\-]+)", op.line)
                    if m2:
                        edges[cname].append((m2.group(1), trip))
                continue
            if op.opcode in ("call", "conditional", "async-start"):
                for m2 in re.finditer(r"(?:to_apply|branch_computations=\{?|"
                                      r"called_computations=\{?)"
                                      r"%?([\w.\-]+)", op.line):
                    edges[cname].append((m2.group(1), 1.0))
                continue
            if op.opcode == "fusion":
                m2 = re.search(r"calls=%?([\w.\-]+)", op.line)
                if m2:
                    fused.add(m2.group(1))
            base = op.opcode.replace("-start", "") \
                if op.opcode.endswith("-start") else op.opcode
            if base in COLLECTIVES:
                _, out_b = shape_elems_bytes(op.shape)
                # async -start ops wrap the result in an extra tuple copy of
                # the input; use the final element heuristically: out_b is
                # tuple (in, out) for -start — halve it.
                if op.opcode.endswith("-start"):
                    out_b = out_b / 2
                g = _group_size(op.line)
                link = collective_link_bytes(base, out_b, g)
                rec = cost.collectives.setdefault(
                    base, dict(count=0, bytes=0.0, link_bytes=0.0))
                rec["count"] += 1
                rec["bytes"] += out_b
                rec["link_bytes"] += link
                cost.link += link
                cost.bytes += 2.0 * out_b     # HBM in+out of the payload
                continue
            if op.opcode.endswith("-done"):
                continue
            cost.flops += _op_flops(comps, comp, op, symbols, fusion_memo)
            cost.bytes += _op_bytes(comp, op, symbols, comps,
                                    fusion_bytes_memo)
        raw[cname] = cost

    # multipliers from ENTRY (last computation in scheduled HLO text is the
    # entry; more robustly: the one named *main* or not referenced anywhere)
    referenced = {t for outs in edges.values() for t, _ in outs}
    entry = None
    for cname in comps:
        if "main" in cname:
            entry = cname
    if entry is None:
        cands = [c for c in comps if c not in referenced and c not in fused]
        entry = cands[-1] if cands else next(iter(comps))

    mult: Dict[str, float] = {c: 0.0 for c in comps}
    mult[entry] = 1.0
    # propagate along edges to fixpoint (computations form a DAG)
    for _ in range(len(comps)):
        changed = False
        for src, outs in edges.items():
            if mult.get(src, 0.0) <= 0:
                continue
            for dst, w in outs:
                if dst in mult:
                    want = mult[src] * w
                    if want > mult[dst]:
                        mult[dst] = want
                        changed = True
        if not changed:
            break

    total = CompCost()
    for cname, cost in raw.items():
        if cname in fused:
            continue                      # folded into fusion op sites
        m = mult.get(cname, 0.0)
        if m <= 0:
            continue
        total.flops += m * cost.flops
        total.bytes += m * cost.bytes
        total.link += m * cost.link
        for k, v in cost.collectives.items():
            rec = total.collectives.setdefault(
                k, dict(count=0, bytes=0.0, link_bytes=0.0))
            rec["count"] += m * v["count"]
            rec["bytes"] += m * v["bytes"]
            rec["link_bytes"] += m * v["link_bytes"]
    return dict(flops=total.flops, bytes=total.bytes, link=total.link,
                collectives=total.collectives, multipliers=mult)
