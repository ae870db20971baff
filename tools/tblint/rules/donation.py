"""donation: use-after-donate.

``donate_argnames`` hands a buffer to XLA to scribble over.  The misuse
this rule flags: the donated value is read again after the call without
being rebound from the call's result.  XLA is free to have reused the
buffer: the read returns garbage (or raises a deleted-buffer error,
backend-dependent).

The analysis is module-local and name-level: jitgraph.analyze_wrappers
resolves which call-site names donate which parameters.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from ..core import FileContext, Finding, Rule, register
from ..jitgraph import module_wrappers


def _expr_key(expr: ast.AST) -> Optional[str]:
    """Stable key for a donate-trackable value: a bare local name, or a
    ``self.<attr>`` read.  Anything else is untracked."""
    if isinstance(expr, ast.Name):
        return expr.id
    if (isinstance(expr, ast.Attribute)
            and isinstance(expr.value, ast.Name)
            and expr.value.id == "self"):
        return f"self.{expr.attr}"
    return None


class _FnScan:
    """One linear pass over a function body, in source order."""

    def __init__(self, rule: "DonationRule", ctx: FileContext,
                 fn: ast.FunctionDef) -> None:
        self.rule = rule
        self.ctx = ctx
        self.fn = fn
        self.wrappers = module_wrappers(ctx)
        self.findings: List[Finding] = []
        # (key, donate line): donated values awaiting a rebind or a use.
        self.donated_live: dict = {}

    def _bind(self, target: ast.AST) -> None:
        if isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind(elt)
        key = _expr_key(target)
        if key is not None:
            self.donated_live.pop(key, None)

    def _check_call(self, call: ast.Call, stmt_targets: Set[str]) -> None:
        func_name = None
        if isinstance(call.func, ast.Name):
            func_name = call.func.id
        elif isinstance(call.func, ast.Attribute):
            # self._shard_steps["fast"] / sm.create_transfers are not
            # module-local names; only bare-Name callees resolve.
            return
        info = self.wrappers.get(func_name)
        if info is None or not info.donated:
            return
        for pname, arg in info.donated_args(call):
            key = _expr_key(arg)
            if key is None:
                continue
            if key in stmt_targets:
                continue  # rebound from the result in the same statement
            self.donated_live[key] = (call.lineno, func_name, pname)

    def _check_use(self, expr: ast.AST) -> None:
        """Flag loads of a still-live donated key."""
        for sub in ast.walk(expr):
            key = None
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load):
                key = sub.id
            elif (isinstance(sub, ast.Attribute)
                  and isinstance(sub.ctx, ast.Load)
                  and isinstance(sub.value, ast.Name)
                  and sub.value.id == "self"):
                key = f"self.{sub.attr}"
            if key is not None and key in self.donated_live:
                dline, fname, pname = self.donated_live.pop(key)
                self.findings.append(Finding(
                    self.rule.id, self.ctx.display_path,
                    sub.lineno, sub.col_offset,
                    f"use after donate: {key} was donated to {fname}"
                    f"({pname}=) at line {dline}; XLA may have reused the "
                    "buffer — rebind from the call's result instead",
                ))

    # -- statement walk ------------------------------------------------------

    def run(self) -> List[Finding]:
        self._walk_body(self.fn.body)
        return self.findings

    def _walk_body(self, body) -> None:
        for stmt in body:
            self._walk_stmt(stmt)

    def _stmt_target_keys(self, stmt) -> Set[str]:
        keys: Set[str] = set()
        targets = []
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
        elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            targets = [stmt.target]
        for t in targets:
            elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
            for e in elts:
                key = _expr_key(e)
                if key is not None:
                    keys.add(key)
        return keys

    def _walk_stmt(self, stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested defs get their own scan
        if isinstance(stmt, (ast.If, ast.While)):
            # Compound statements: check only the head expression here;
            # the bodies are walked statement-by-statement below so a
            # rebind inside a branch is seen before later uses.
            self._check_use(stmt.test)
            self._walk_body(stmt.body)
            self._walk_body(stmt.orelse)
            return
        if isinstance(stmt, ast.For):
            self._check_use(stmt.iter)
            self._walk_body(stmt.body)
            self._walk_body(stmt.orelse)
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._check_use(item.context_expr)
            self._walk_body(stmt.body)
            return
        if isinstance(stmt, ast.Try):
            self._walk_body(stmt.body)
            for h in stmt.handlers:
                self._walk_body(h.body)
            self._walk_body(stmt.orelse)
            self._walk_body(stmt.finalbody)
            return
        targets = self._stmt_target_keys(stmt)
        # Uses first (RHS reads happen before the rebind takes effect),
        # except the donating call's own arguments.
        donating_calls = []
        for sub in ast.walk(stmt):
            if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name):
                info = self.wrappers.get(sub.func.id)
                if info is not None and info.donated:
                    donating_calls.append(sub)
        self._check_use(stmt)
        for call in donating_calls:
            self._check_call(call, targets)
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                self._bind(t)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._bind(stmt.target)
        elif isinstance(stmt, ast.AugAssign):
            key = _expr_key(stmt.target)
            if key is not None:
                self.donated_live.pop(key, None)


@register
class DonationRule(Rule):
    id = "donation"
    summary = "use-after-donate: a donated value read before it is rebound"
    rationale = (
        "A donated buffer becomes XLA scratch: reading it afterward "
        "returns garbage or raises a deleted-buffer error, "
        "backend-dependent — the bug class PR 7/11 carry prose proofs "
        "against."
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not module_wrappers(ctx):
            return ()
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.extend(_FnScan(self, ctx, node).run())
        return out
