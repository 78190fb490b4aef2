"""R2/R3 — Pallas kernel-contract rules.

R2 (vmap-unsafe accumulators): inside any function passed to
`pl.pallas_call`, flag

* read-modify-write accumulation into an *output* block
  (`out_ref[...] += x`, `out_ref[...] = out_ref[...] * a + b`) — under
  `jax.vmap` the batching rule prepends the batch axis to the grid and
  cross-step output state is silently wrong (the exact PR-1 pivot-kernel
  corruption; see DESIGN.md §3);
* output writes gated on grid position (`@pl.when(program_id(...) == 0)`
  init / last-step epilogues) — the same hazard's control-flow form:
  under vmap `program_id(0)` becomes the batch index.

VMEM *scratch* operands (classified from `scratch_shapes`) are exempt:
a scratch accumulator over a sequential grid axis is the by-design
flash-attention pattern, and scratch is re-zeroed per batch member.
Writes that are pure functions of grid-invariant inputs (the idempotent
revisited-block pattern frame_step uses) carry no cross-step state and
pass clean.

R3 (Mosaic compilability): flag what the installed TPU compiler refuses
(jax 0.9.0; each refusal checked by compiling for a described v5e):

* reductions over unsigned-integer values (`jnp.sum/max/min/...` over
  the uint32 `population_count` of bitset words, with or without an
  axis) — "Reductions over unsigned integers not implemented". int32
  and bool reductions compile over either axis, so counts hop to int32
  first (`population_count(x).astype(jnp.int32)`);
* casts between unsigned integers and floats (`.astype(jnp.float32)` of
  a uint32 popcount, or back) — "Unsupported cast: uint32 -> float32",
  the fault that kept every bitset kernel off the chip. uint32 <-> int32
  and int32 <-> float32 casts compile, so a float count goes via int32;
* `pl.BlockSpec` shapes built from literals whose trailing dims are
  neither (8, 128)-multiples nor 1 (1 ~ "equals the array dim", which
  is legal; non-literal dims are shape-dependent and skipped; specs
  whose `memory_space=` names SMEM are skipped — Mosaic applies the
  last-two-dims rule to SMEM blocks too, but their legality there
  hinges on "equals the array dims", which this static pass cannot see.
  The lane-batched kernels' per-lane scalar rows satisfy it by carrying
  a middle singleton: (1, 1, K) blocks of (L, 1, K) arrays);
* `pltpu.VMEM` scratch entries in `scratch_shapes` whose trailing dims
  are not (8, 128)-aligned *literals*. Scratch has no backing array to
  borrow dims from, so the BlockSpec "equals the array dim" escape does
  not exist: Mosaic allocates the scratch tile at compile time and a
  traced/derived dim either fails to lower or pads to a tile silently.
  SMEM scratch is scalar memory and exempt.

The dtype rules are static approximations: dtypes are inferred by a
local forward dataflow over the kernel body and over the module's own
functions it calls (`.astype`/iota/`jnp.uint32(...)` fix a kind,
`population_count` keeps its operand's kind and takes an operand the
pass cannot see as unsigned — the bitset words; Python int literals are
weakly typed; anything else unknown stays unknown and is never flagged).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.findings import Finding
from repro.analysis.modindex import (Module, PackageIndex, call_name,
                                     name_endswith)

RULE_VMAP = "R2"
RULE_MOSAIC = "R3"

_FLOAT_NAMES = {"float32", "float64", "float16", "bfloat16", "float_", "float"}
_INT_NAMES = {"int8", "int16", "int32", "int64", "int_", "int"}
_UINT_NAMES = {"uint8", "uint16", "uint32", "uint64"}
_REDUCERS = {"sum", "cumsum", "prod", "mean", "max", "min", "amax", "amin"}
_FLOAT_FNS = {"exp", "log", "sqrt", "rsqrt", "sigmoid", "softmax", "tanh",
              "logaddexp", "erf"}

INT, UINT, FLOAT, BOOL, UNKNOWN = ("int", "uint", "float", "bool",
                                   "unknown")


# ---------------------------------------------------------------------------
# pallas_call discovery + kernel operand classification
# ---------------------------------------------------------------------------

def _literal_len(node: Optional[ast.AST]) -> Optional[int]:
    if node is None:
        return None
    if isinstance(node, (ast.Tuple, ast.List)):
        return len(node.elts)
    if isinstance(node, ast.Call):
        return 1                                   # one ShapeDtypeStruct
    return None


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _kernel_fn_name(arg: ast.AST) -> Optional[str]:
    """First pallas_call arg -> kernel function name (through partial)."""
    if isinstance(arg, ast.Name):
        return arg.id
    if isinstance(arg, ast.Call) and name_endswith(arg, "partial") and arg.args:
        inner = arg.args[0]
        if isinstance(inner, ast.Name):
            return inner.id
    return None


def find_kernels(mod: Module) -> List[Tuple[ast.FunctionDef, Dict[str, str]]]:
    """All (kernel FunctionDef, param-name -> 'in'|'out'|'scratch') pairs
    for kernels this module passes to pl.pallas_call."""
    local_defs: Dict[str, ast.FunctionDef] = {
        n.name: n for n in ast.walk(mod.tree)
        if isinstance(n, ast.FunctionDef)}
    out = []
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call) and
                name_endswith(node, "pallas_call")):
            continue
        if not node.args:
            continue
        fname = _kernel_fn_name(node.args[0])
        fn = local_defs.get(fname) if fname else None
        if fn is None:
            continue
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        n_in = _literal_len(_kw(node, "in_specs"))
        n_out = _literal_len(_kw(node, "out_shape"))
        n_scr = _literal_len(_kw(node, "scratch_shapes")) or 0
        kinds: Dict[str, str] = {}
        if (n_in is not None and n_out is not None and
                n_in + n_out + n_scr == len(params)):
            for i, p in enumerate(params):
                kinds[p] = ("in" if i < n_in else
                            "out" if i < n_in + n_out else "scratch")
        else:
            # cannot classify -> conservatively treat every ref as output
            kinds = {p: "out" for p in params}
        out.append((fn, kinds))
    return out


# ---------------------------------------------------------------------------
# R2: cross-grid accumulators / grid-position-gated output writes
# ---------------------------------------------------------------------------

def _progid_derived_names(fn: ast.FunctionDef) -> set:
    derived = set()
    for node in ast.walk(fn):
        if (isinstance(node, ast.Assign) and isinstance(node.value, ast.Call)
                and name_endswith(node.value, "program_id", "num_programs")):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    derived.add(tgt.id)
    # fixpoint over straight-line derivations (run = ki * bk <= qmax)
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and _uses_progid(node.value,
                                                            derived):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) and tgt.id not in derived:
                        derived.add(tgt.id)
                        changed = True
    return derived


def _uses_progid(expr: ast.AST, derived: set) -> bool:
    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and node.id in derived:
            return True
        if isinstance(node, ast.Call) and name_endswith(node, "program_id",
                                                        "num_programs"):
            return True
    return False


def _sub_base(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Subscript) and isinstance(node.value, ast.Name):
        return node.value.id
    return None


def _reads_ref(expr: ast.AST, ref: str) -> bool:
    for node in ast.walk(expr):
        if (isinstance(node, ast.Subscript) and
                isinstance(node.value, ast.Name) and node.value.id == ref and
                isinstance(node.ctx, ast.Load)):
            return True
    return False


def check_kernel_vmap_safety(mod: Module, fn: ast.FunctionDef,
                             kinds: Dict[str, str]) -> List[Finding]:
    findings: List[Finding] = []
    derived = _progid_derived_names(fn)

    def visit(stmts: Sequence[ast.stmt], gated_on_grid: bool) -> None:
        for st in stmts:
            if isinstance(st, ast.FunctionDef):
                gate = gated_on_grid
                for dec in st.decorator_list:
                    if (isinstance(dec, ast.Call) and
                            name_endswith(dec, "when") and dec.args and
                            _uses_progid(dec.args[0], derived)):
                        gate = True
                visit(st.body, gate)
                continue
            if isinstance(st, ast.AugAssign):
                ref = _sub_base(st.target)
                if ref in kinds and kinds[ref] == "out":
                    findings.append(Finding(
                        rule=RULE_VMAP, path=mod.path, line=st.lineno,
                        col=st.col_offset,
                        message=(f"cross-grid accumulation into output block "
                                 f"`{ref}` — under jax.vmap the batched grid "
                                 f"revisits this block and the accumulator "
                                 f"is silently corrupted (PR-1 pivot-kernel "
                                 f"bug class; DESIGN.md §3)")))
                continue
            if isinstance(st, ast.Assign):
                for tgt in st.targets:
                    ref = _sub_base(tgt)
                    if ref is None or kinds.get(ref) != "out":
                        continue
                    if _reads_ref(st.value, ref):
                        findings.append(Finding(
                            rule=RULE_VMAP, path=mod.path, line=st.lineno,
                            col=st.col_offset,
                            message=(f"read-modify-write of output block "
                                     f"`{ref}` across grid steps — "
                                     f"non-idempotent revisited output "
                                     f"blocks break under jax.vmap (PR-1 "
                                     f"bug class; DESIGN.md §3)")))
                    elif gated_on_grid:
                        findings.append(Finding(
                            rule=RULE_VMAP, path=mod.path, line=st.lineno,
                            col=st.col_offset,
                            message=(f"write to output block `{ref}` gated "
                                     f"on grid position (program_id) — "
                                     f"init/epilogue accumulator pattern; "
                                     f"under vmap program_id(0) becomes the "
                                     f"batch index (DESIGN.md §3)")))
                continue
            if isinstance(st, (ast.If, ast.For, ast.While, ast.With)):
                visit(st.body, gated_on_grid)
                visit(getattr(st, "orelse", []), gated_on_grid)

    visit(fn.body, False)
    return findings


# ---------------------------------------------------------------------------
# R3: integer-axis reductions + misaligned literal BlockSpecs
# ---------------------------------------------------------------------------

def _dtype_kind(node: Optional[ast.AST]) -> str:
    if node is None:
        return UNKNOWN
    name = None
    if isinstance(node, ast.Attribute):
        name = node.attr
    elif isinstance(node, ast.Name):
        name = node.id
    elif isinstance(node, ast.Constant) and isinstance(node.value, str):
        name = node.value
    if name in _FLOAT_NAMES:
        return FLOAT
    if name in _INT_NAMES:
        return INT
    if name in _UINT_NAMES:
        return UINT
    if name in ("bool", "bool_"):
        return BOOL
    return UNKNOWN


def _join(a: str, b: str) -> str:
    if UNKNOWN in (a, b):
        return UNKNOWN
    if FLOAT in (a, b):
        return FLOAT
    if a == b:
        return a
    if BOOL in (a, b):
        return a if b == BOOL else b                # bool promotes
    return INT                                      # int ∨ uint -> int


def _callee(node: ast.Call) -> str:
    """Last name of the callee, also for methods of call results
    (`population_count(x).astype(...)` -> 'astype')."""
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return (call_name(node) or "").rpartition(".")[2]


def _weak(node: ast.AST) -> bool:
    """A Python int literal (or its negation): weakly typed in jax."""
    if isinstance(node, ast.UnaryOp):
        node = node.operand
    return (isinstance(node, ast.Constant) and isinstance(node.value, int)
            and not isinstance(node.value, bool))


class _DtypeFlow:
    """Forward dataflow over a kernel body: name -> INT/FLOAT/BOOL/UNKNOWN."""

    def __init__(self):
        self.env: Dict[str, str] = {}

    def run(self, fn: ast.FunctionDef) -> None:
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                kind = self.infer(node.value)
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.env[tgt.id] = kind

    def infer(self, node: ast.AST) -> str:
        if isinstance(node, ast.Constant):
            if isinstance(node.value, bool):
                return BOOL
            if isinstance(node.value, int):
                return INT
            if isinstance(node.value, float):
                return FLOAT
            return UNKNOWN
        if isinstance(node, ast.Name):
            return self.env.get(node.id, UNKNOWN)
        if isinstance(node, ast.Subscript):
            return self.infer(node.value)
        if isinstance(node, ast.UnaryOp):
            return self.infer(node.operand)
        if isinstance(node, ast.BinOp):
            if isinstance(node.op, ast.Div):
                return FLOAT
            return self._join_weak(node.left, node.right)
        if isinstance(node, (ast.Compare, ast.BoolOp)):
            return BOOL
        if isinstance(node, ast.IfExp):
            return _join(self.infer(node.body), self.infer(node.orelse))
        if isinstance(node, ast.Call):
            return self._infer_call(node)
        return UNKNOWN

    def _join_weak(self, a: ast.AST, b: ast.AST) -> str:
        if _weak(a) and not _weak(b):
            return self.infer(b)
        if _weak(b) and not _weak(a):
            return self.infer(a)
        return _join(self.infer(a), self.infer(b))

    def _infer_call(self, node: ast.Call) -> str:
        name = call_name(node) or ""
        last = _callee(node)
        if last in ("astype", "convert_element_type"):
            return _dtype_kind(node.args[-1] if node.args else None)
        if last in _UINT_NAMES | _INT_NAMES | _FLOAT_NAMES and \
                name.startswith(("jnp.", "np.")):
            return _dtype_kind(ast.Name(id=last))   # jnp.uint32(0)
        if last == "population_count":
            kind = self.infer(node.args[0]) if node.args else UNKNOWN
            return kind if kind in (INT, UINT) else UINT
        if last.startswith("bitwise") or last in ("left_shift",
                                                  "right_shift", "invert"):
            kinds = [self.infer(a) for a in node.args]
            known = [k for k in kinds if k != UNKNOWN]
            out = known[0] if known else UNKNOWN
            for k in known[1:]:
                out = _join(out, k)
            return out
        if last in _FLOAT_FNS:
            return FLOAT
        if last == "where" and len(node.args) == 3:
            return self._join_weak(node.args[1], node.args[2])
        if last in ("broadcasted_iota", "iota"):
            return _dtype_kind(node.args[0] if node.args else None)
        if last in ("zeros", "ones", "full", "arange", "zeros_like",
                    "ones_like", "full_like"):
            dt = _kw(node, "dtype")
            if dt is None and last in ("zeros", "ones", "full", "arange"):
                return INT if last == "arange" and not node.args[1:] else \
                    _dtype_kind(dt)
            return _dtype_kind(dt)
        if last in ("dot", "dot_general", "matmul"):
            return _dtype_kind(_kw(node, "preferred_element_type"))
        if last in ("maximum", "minimum", "abs", "clip", "remainder", "mod"):
            kinds = [self.infer(a) for a in node.args]
            out = kinds[0] if kinds else UNKNOWN
            for k in kinds[1:]:
                out = _join(out, k)
            return out
        if last in _REDUCERS or last in ("max", "min", "amax", "amin"):
            base = (node.func.value if isinstance(node.func, ast.Attribute)
                    and not (call_name(node) or "").startswith(("jnp.", "np.",
                                                                "jax."))
                    else (node.args[0] if node.args else None))
            return self.infer(base) if base is not None else UNKNOWN
        return UNKNOWN


def _reduction_operand(node: ast.Call) -> Optional[ast.AST]:
    """Operand of jnp.sum(x, ...) or x.sum(...); None for builtins."""
    name = call_name(node) or ""
    if not isinstance(node.func, ast.Attribute):
        return None                       # builtin max(a, b) / min(a, b)
    if not name.startswith(("jnp.", "np.", "jax.", "lax.", "numpy.")):
        return node.func.value            # method form: x.sum(axis=1)
    return node.args[0] if node.args else None


def _cast(node: ast.Call) -> Optional[Tuple[ast.AST, str]]:
    """(operand, target kind) of x.astype(T) / convert_element_type(x, T)."""
    last = _callee(node)
    if last == "astype" and isinstance(node.func, ast.Attribute) and node.args:
        return node.func.value, _dtype_kind(node.args[0])
    if last == "convert_element_type" and len(node.args) == 2:
        return node.args[0], _dtype_kind(node.args[1])
    return None


def kernel_helpers(mod: Module, fn: ast.FunctionDef) -> List[ast.FunctionDef]:
    """`fn` plus the module's top-level functions it calls, transitively:
    their bodies run inside the kernel too."""
    top = {n.name: n for n in mod.tree.body if isinstance(n, ast.FunctionDef)}
    out, todo = [], [fn]
    while todo:
        f = todo.pop()
        if any(f is g for g in out):
            continue
        out.append(f)
        for node in ast.walk(f):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                    and node.func.id in top):
                todo.append(top[node.func.id])
    return out


def check_kernel_mosaic(mod: Module, fn: ast.FunctionDef) -> List[Finding]:
    flow = _DtypeFlow()
    flow.run(fn)
    findings: List[Finding] = []

    def flag(node: ast.AST, message: str) -> None:
        findings.append(Finding(rule=RULE_MOSAIC, path=mod.path,
                                line=node.lineno, col=node.col_offset,
                                message=message + " (DESIGN.md §3)"))

    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        cast = _cast(node)
        if cast is not None:
            src, dst = flow.infer(cast[0]), cast[1]
            if {src, dst} == {UINT, FLOAT}:
                flag(node, f"{src} -> {dst} cast inside a Pallas kernel — "
                           f"Mosaic refuses casts between unsigned ints and "
                           f"floats; go through int32")
            continue
        if _callee(node) not in _REDUCERS:
            continue
        operand = _reduction_operand(node)
        if operand is not None and flow.infer(operand) == UINT:
            flag(node, "reduction over unsigned ints inside a Pallas kernel "
                       "— Mosaic refuses it; cast the counts to int32 first "
                       "(int32 reduces over any axis)")
    return findings


def check_blockspecs(mod: Module) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call) and
                name_endswith(node, "BlockSpec") and node.args):
            continue
        mem = _kw(node, "memory_space")
        if mem is not None and "SMEM" in ast.unparse(mem):
            continue                  # SMEM is scalar memory: no tiling
        shape = node.args[0]
        if not isinstance(shape, (ast.Tuple, ast.List)) or len(shape.elts) < 2:
            continue
        dims = shape.elts[-2:]
        if not all(isinstance(d, ast.Constant) and isinstance(d.value, int)
                   for d in dims):
            continue                  # shape-derived dims: caller's contract
        minor2, minor = dims[0].value, dims[1].value
        bad = []
        if minor != 1 and minor % 128 != 0:
            bad.append(f"last dim {minor} is not a multiple of 128")
        if minor2 != 1 and minor2 % 8 != 0:
            bad.append(f"second-minor dim {minor2} is not a multiple of 8")
        if bad:
            findings.append(Finding(
                rule=RULE_MOSAIC, path=mod.path, line=node.lineno,
                col=node.col_offset,
                message=(f"literal BlockSpec shape ({minor2}, {minor}): "
                         f"{'; '.join(bad)} — Mosaic requires (8, 128)-"
                         f"divisible trailing block dims (or dims equal to "
                         f"the array dims; DESIGN.md §3)")))
    return findings


def check_scratch_shapes(mod: Module) -> List[Finding]:
    """VMEM scratch_shapes entries: trailing dims must be (8, 128)-aligned
    literals (no array to inherit dims from — see module docstring)."""
    findings: List[Finding] = []
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call) and
                name_endswith(node, "pallas_call")):
            continue
        scr = _kw(node, "scratch_shapes")
        if scr is None or not isinstance(scr, (ast.Tuple, ast.List)):
            continue
        for entry in scr.elts:
            if not (isinstance(entry, ast.Call) and
                    name_endswith(entry, "VMEM")):
                continue              # SMEM is scalar memory: no tiling
            shape = entry.args[0] if entry.args else None
            if not isinstance(shape, (ast.Tuple, ast.List)) or not shape.elts:
                findings.append(Finding(
                    rule=RULE_MOSAIC, path=mod.path, line=entry.lineno,
                    col=entry.col_offset,
                    message=("VMEM scratch shape is not a literal tuple — "
                             "Mosaic sizes scratch at compile time; spell "
                             "the dims as (8, 128)-aligned int literals "
                             "(DESIGN.md §3)")))
                continue
            dims = shape.elts[-2:]
            mults = (128,) if len(shape.elts) == 1 else (8, 128)
            bad = []
            for d, mult in zip(dims, mults):
                if not (isinstance(d, ast.Constant) and
                        isinstance(d.value, int)):
                    bad.append(f"dim {ast.unparse(d)} is not an int literal")
                elif d.value % mult != 0:
                    bad.append(f"dim {d.value} is not a multiple of {mult}")
            if bad:
                findings.append(Finding(
                    rule=RULE_MOSAIC, path=mod.path, line=entry.lineno,
                    col=entry.col_offset,
                    message=(f"VMEM scratch trailing dims must be (8, 128)-"
                             f"aligned literals: {'; '.join(bad)} — scratch "
                             f"has no backing array dim to equal, so the "
                             f"BlockSpec escape hatch does not apply "
                             f"(DESIGN.md §3)")))
    return findings


def check(index: PackageIndex) -> List[Finding]:
    findings: List[Finding] = []
    for mod in index:
        kernels = {id(fn): (fn, kinds) for fn, kinds in find_kernels(mod)}
        bodies = {}                   # kernel bodies + the helpers they call
        for fn, kinds in kernels.values():
            findings.extend(check_kernel_vmap_safety(mod, fn, kinds))
            bodies.update((id(f), f) for f in kernel_helpers(mod, fn))
        for f in bodies.values():
            findings.extend(check_kernel_mosaic(mod, f))
        findings.extend(check_blockspecs(mod))
        findings.extend(check_scratch_shapes(mod))
    return findings
