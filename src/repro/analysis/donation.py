"""R5 — donation safety: no reads of a donated buffer after the call.

`jax.jit(..., donate_argnums=/donate_argnames=)` lets XLA alias the
argument's device buffer into the output — after the donating call the
python name still points at an invalidated buffer, and touching it
raises (or worse, on some backends silently reads garbage). Steps that
carry state donate it (`launch/serve.py` KV cache, `launch/train.py`
parameters), so the safe idiom is pinned down here:

    params, opt, loss = jit_step(params, opt, batch)   # rebind: OK
    logits, cache = decode(params, cache, tok)         # loop rebind: OK

    out = step(buf)
    x = buf.sum()                                      # R5: read-after-donate

    for _ in range(n):
        out = step(buf)                                # R5: next iteration
                                                       # re-reads donated buf

Detection: donors are names bound to a jit expression carrying donate
kwargs (directly, through `functools.partial(jax.jit, ...)`, through an
alias/IfExp choosing between donor variants, or a decorated def). At
every donor callsite the donated positional/keyword args that are plain
names are traced forward: a Load before any re-Store — including the
implicit repeat of an enclosing loop body — is flagged. Rebinding in the
donating statement itself is the blessed pattern and never flagged.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

from repro.analysis.findings import Finding
from repro.analysis.modindex import Module, PackageIndex, dotted_name

RULE = "R5"


@dataclasses.dataclass(frozen=True)
class Donor:
    argnums: Tuple[int, ...]
    argnames: Tuple[str, ...]


def _donation_kwargs(call: ast.Call) -> Optional[Donor]:
    nums: List[int] = []
    names: List[str] = []
    found = False
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            found = True
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, int):
                    nums.append(n.value)
        elif kw.arg == "donate_argnames":
            found = True
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    names.append(n.value)
    return Donor(tuple(nums), tuple(names)) if found else None


def _donor_from_expr(node: ast.AST) -> Optional[Donor]:
    """Donor spec if `node` is a donating jit expression."""
    if not isinstance(node, ast.Call):
        return None
    name = dotted_name(node.func) or ""
    last = name.rpartition(".")[2]
    if last == "jit":
        return _donation_kwargs(node)
    if last == "partial" and node.args:
        inner = dotted_name(node.args[0]) or ""
        if inner.rpartition(".")[2] == "jit":
            return _donation_kwargs(node)
    # partial(jit, **kw)(f) / jit(**kw)(f): donation lives on the inner call
    if isinstance(node.func, ast.Call):
        return _donor_from_expr(node.func)
    return None


def _collect_donors(scope_body: Sequence[ast.stmt],
                    inherited: Dict[str, Donor]) -> Dict[str, Donor]:
    donors = dict(inherited)
    for st in scope_body:
        if isinstance(st, ast.FunctionDef):
            for dec in st.decorator_list:
                d = _donor_from_expr(dec) if isinstance(dec, ast.Call) \
                    else None
                if d:
                    donors[st.name] = d
        if not isinstance(st, ast.Assign):
            continue
        d = _donor_from_expr(st.value)
        if d is None and isinstance(st.value, ast.Name):
            d = donors.get(st.value.id)            # alias of a donor
        if d is None and isinstance(st.value, ast.IfExp):
            # fn = plain if cpu else donated  (lazy variant pick)
            for branch in (st.value.body, st.value.orelse):
                if isinstance(branch, ast.Name) and branch.id in donors:
                    d = donors[branch.id]
                    break
        if d is not None:
            for tgt in st.targets:
                if isinstance(tgt, ast.Name):
                    donors[tgt.id] = d
    return donors


def _donated_vars(call: ast.Call, donor: Donor) -> List[Tuple[str, int, int]]:
    out = []
    for i in donor.argnums:
        if i < len(call.args) and isinstance(call.args[i], ast.Name):
            a = call.args[i]
            out.append((a.id, call.lineno, call.col_offset))
    for kw in call.keywords:
        if kw.arg in donor.argnames and isinstance(kw.value, ast.Name):
            out.append((kw.value.id, call.lineno, call.col_offset))
    return out


def _stores(stmt: ast.stmt) -> Set[str]:
    return {n.id for n in ast.walk(stmt)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store)}


def _loads(stmt: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(stmt)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}


def _first_use_is_load(after: Sequence[ast.stmt], var: str) -> Optional[int]:
    """Line of the first read of `var` before any re-store, else None."""
    for st in after:
        if isinstance(st, ast.FunctionDef):
            continue
        if var in _loads(st):
            return st.lineno
        if var in _stores(st):
            return None
    return None


class _ScopeChecker:
    def __init__(self, mod: Module, donors: Dict[str, Donor]):
        self.mod = mod
        self.donors = donors
        self.findings: List[Finding] = []

    def scan(self, body: Sequence[ast.stmt], after_outer: Sequence[ast.stmt],
             loop_body: Optional[Sequence[ast.stmt]] = None) -> None:
        for i, st in enumerate(body):
            after = list(body[i + 1:]) + list(after_outer)
            if isinstance(st, ast.FunctionDef):
                inner_donors = _collect_donors(st.body, self.donors)
                checker = _ScopeChecker(self.mod, inner_donors)
                checker.scan(st.body, [])
                self.findings.extend(checker.findings)
                continue
            if isinstance(st, (ast.For, ast.While)):
                self.scan(st.body, after, loop_body=st.body)
                self.scan(st.orelse, after, loop_body=loop_body)
                continue
            if isinstance(st, ast.If):
                self.scan(st.body, after, loop_body=loop_body)
                self.scan(st.orelse, after, loop_body=loop_body)
                self._check_stmt(st.test, st, after, loop_body)
                continue
            if isinstance(st, (ast.With, ast.Try)):
                self.scan(st.body, after, loop_body=loop_body)
                for h in getattr(st, "handlers", []):
                    self.scan(h.body, after, loop_body=loop_body)
                self.scan(getattr(st, "finalbody", []), after,
                          loop_body=loop_body)
                continue
            self._check_stmt(st, st, after, loop_body)

    def _check_stmt(self, expr_root: ast.AST, stmt: ast.stmt,
                    after: Sequence[ast.stmt],
                    loop_body: Optional[Sequence[ast.stmt]]) -> None:
        for node in ast.walk(expr_root):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Name) and
                    node.func.id in self.donors):
                continue
            donor = self.donors[node.func.id]
            rebound = _stores(stmt)
            for var, line, col in _donated_vars(node, donor):
                if var in rebound:
                    continue                       # donate-and-rebind: safe
                read_line = _first_use_is_load(after, var)
                if read_line is None and loop_body is not None:
                    # loop repeats: a donated var never re-stored in the
                    # loop body is consumed again next iteration
                    if not any(var in _stores(s) for s in loop_body):
                        read_line = line           # the call itself re-reads
                if read_line is not None:
                    self.findings.append(Finding(
                        rule=RULE, path=self.mod.path, line=line, col=col,
                        message=(f"`{var}` is donated to "
                                 f"`{node.func.id}()` (donate_argnums/"
                                 f"argnames) but read again at line "
                                 f"{read_line} — its device buffer is "
                                 f"invalidated by XLA aliasing; rebind the "
                                 f"result over `{var}` or drop the read")))


def check_module(mod: Module) -> List[Finding]:
    donors = _collect_donors(mod.tree.body, {})
    checker = _ScopeChecker(mod, donors)
    checker.scan(mod.tree.body, [])
    return checker.findings


def check(index: PackageIndex) -> List[Finding]:
    out: List[Finding] = []
    for mod in index:
        out.extend(check_module(mod))
    return out
