"""size-class: jit inputs must be padded to stable size classes.

A jitted program is keyed on its input SHAPES (and static-arg values): an
array whose length derives from a data-dependent quantity — a run length,
a batch fill, ``len()`` of a host list — compiles a fresh XLA program per
distinct value, mid-serving.  That is the exact PR 10 recompile bug (the
merkle update program was keyed on the per-commit key count until
machine._merkle_pad introduced power-of-two classes), found after the
fact in bench p99.  The repo discipline: pad to ``batch_lanes`` /
``GROUP_K`` constants or round with ``bit_length()`` size classes.

Heuristic, name-level: a name is VOLATILE when bound from ``len(...)``
(or arithmetic over a volatile name with no stabilizer).  An expression is
STABILIZED when it mentions an attribute constant (``self.batch_lanes``,
``self.GROUP_K`` — attributes are configuration, not data) or a
``bit_length()`` rounding.  A bare ``max(const, n)`` floor is NOT a
stabilizer — it bounds the shape from below but still compiles one
program per distinct size above the floor; pair it with ``bit_length()``
rounding (the ``machine._merkle_pad`` idiom).  The rule fires when a
module-local jitted callable receives (a) an array built by a
constructor whose shape argument is volatile un-stabilized, or (b) a
volatile value on a ``static_argnames`` parameter (every distinct value
is a recompile), or (c) an array built by JOINING a dynamic member list
(``np.concatenate``/``hstack``/``vstack`` over a comprehension, a
volatile slice, or a ``*splat``) — any joined batch: the joined width is
the sum of the members' lengths, ``len()`` of the list that was joined, so
an un-padded joined array compiles one program per distinct membership.
A joined batch's padding must land on the EXISTING jit size classes
(``batch_lanes`` / ``GROUP_K`` attribute pads or ``bit_length()``
rounding)."""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from ..core import FileContext, Finding, Rule, register
from ..jitgraph import _root_name, _terminal_name, module_wrappers

_CONSTRUCTORS = {"zeros", "ones", "empty", "full", "arange", "asarray",
                 "array", "stack", "tile", "repeat"}
#: member-list joiners: the result's leading dim is the SUM of member
#: lengths — the width of a joined batch
_JOINERS = {"concatenate", "concat", "hstack", "vstack"}
_ARRAY_MODULES = {"np", "jnp", "numpy"}
_STABILIZERS = {"bit_length"}


def _is_len_call(expr: ast.AST) -> bool:
    return (isinstance(expr, ast.Call)
            and isinstance(expr.func, ast.Name)
            and expr.func.id == "len")


class _Volatility:
    """Forward name-level volatile-length propagation through one
    function (source order, shallow)."""

    def __init__(self, fn: ast.FunctionDef) -> None:
        self.volatile: Set[str] = set()
        #: names bound to an array whose shape was volatile at build time
        self.volatile_arrays: Set[str] = set()
        self._walk(fn.body)

    @staticmethod
    def _stabilized(expr: ast.AST) -> bool:
        """An attribute constant (self.batch_lanes / cfg.GROUP_K) or a
        bit_length() rounding anywhere in the expression: the shape is
        padded to configuration, not keyed on data."""
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Attribute):
                if sub.attr in _STABILIZERS:
                    return True
                if isinstance(sub.ctx, ast.Load) and not isinstance(
                    sub.value, ast.Call
                ):
                    return True
        return False

    def expr_volatile(self, expr: ast.AST) -> bool:
        """Volatile and NOT stabilized: mentions len()/a volatile name,
        with no attribute constant / bit_length rounding in sight."""
        if self._stabilized(expr):
            return False
        for sub in ast.walk(expr):
            if _is_len_call(sub):
                return True
            if isinstance(sub, ast.Name) and sub.id in self.volatile:
                return True
        return False

    def _constructor_shape_volatile(self, call: ast.Call) -> bool:
        name = _terminal_name(call.func)
        root = _root_name(call.func)
        if name not in _CONSTRUCTORS or root not in _ARRAY_MODULES:
            return False
        if not call.args:
            return False
        return self.expr_volatile(call.args[0])

    def _joiner_width_volatile(self, call: ast.Call) -> bool:
        """np.concatenate/hstack/vstack over a dynamic member list: the
        joined leading dim is the members' summed width — len() of the
        joined list — unless the operand is padded to a config constant /
        bit_length size class."""
        name = _terminal_name(call.func)
        root = _root_name(call.func)
        if name not in _JOINERS or root not in _ARRAY_MODULES:
            return False
        if not call.args:
            return False
        op = call.args[0]
        if self._stabilized(op):
            return False
        if self.expr_volatile(op):
            return True
        for sub in ast.walk(op):
            # A comprehension / *splat member list, or a member drawn from
            # an already-volatile array: width is data-dependent by
            # construction.
            if isinstance(sub, (ast.ListComp, ast.GeneratorExp,
                                ast.Starred)):
                return True
            if isinstance(sub, ast.Name) and sub.id in self.volatile_arrays:
                return True
        return False

    def value_builds_volatile_array(self, value: ast.AST) -> bool:
        for sub in ast.walk(value):
            if isinstance(sub, ast.Call) and (
                    self._constructor_shape_volatile(sub)
                    or self._joiner_width_volatile(sub)):
                return True
            if isinstance(sub, ast.Name) and sub.id in self.volatile_arrays:
                return True
        return False

    def _bind(self, target: ast.AST, volatile: bool, varray: bool) -> None:
        if isinstance(target, ast.Name):
            (self.volatile.add if volatile
             else self.volatile.discard)(target.id)
            (self.volatile_arrays.add if varray
             else self.volatile_arrays.discard)(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind(elt, volatile, varray)

    def _walk(self, body) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            if isinstance(stmt, ast.Assign):
                vol = self.expr_volatile(stmt.value)
                varr = self.value_builds_volatile_array(stmt.value)
                for t in stmt.targets:
                    self._bind(t, vol, varr)
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                self._bind(stmt.target, self.expr_volatile(stmt.value),
                           self.value_builds_volatile_array(stmt.value))
            elif isinstance(stmt, ast.AugAssign):
                if self.expr_volatile(stmt.value):
                    self._bind(stmt.target, True, False)
            elif isinstance(stmt, (ast.If, ast.While, ast.For)):
                if isinstance(stmt, ast.For):
                    self._bind(stmt.target, False, False)
                self._walk(stmt.body)
                self._walk(stmt.orelse)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                self._walk(stmt.body)
            elif isinstance(stmt, ast.Try):
                self._walk(stmt.body)
                for h in stmt.handlers:
                    self._walk(h.body)
                self._walk(stmt.orelse)
                self._walk(stmt.finalbody)


@register
class SizeClassRule(Rule):
    id = "size-class"
    summary = ("jit input shape (or static arg) keyed on a data-dependent "
               "length instead of a padded size class")
    rationale = (
        "A jitted program is keyed on input shapes and static-arg values: "
        "a run-length- or batch-fill-derived dimension compiles a fresh "
        "XLA program per distinct value, mid-serving (the PR 10 merkle "
        "recompile bug, found after the fact in bench p99).  Pad to "
        "batch_lanes/GROUP_K or round with bit_length() size classes."
    )

    def applies(self, ctx: FileContext) -> bool:
        return ctx.is_py and (
            ctx.in_hot_scope() or "parallel" in ctx.parts
        )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        wrappers = module_wrappers(ctx)
        if not wrappers:
            return ()
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            vol: Optional[_Volatility] = None
            for sub in ast.walk(node):
                if not (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Name)):
                    continue
                info = wrappers.get(sub.func.id)
                if info is None:
                    continue
                if vol is None:
                    vol = _Volatility(node)
                seen_lines = set()  # one finding per call line, not per arg
                for arg in list(sub.args) + [kw.value for kw in sub.keywords]:
                    if arg.lineno in seen_lines:
                        continue
                    if vol.value_builds_volatile_array(arg):
                        seen_lines.add(arg.lineno)
                        out.append(Finding(
                            self.id, ctx.display_path,
                            arg.lineno, arg.col_offset,
                            f"argument to jitted {sub.func.id}() has a "
                            "data-dependent shape (derived from len()/run "
                            "length): each distinct length compiles a "
                            "fresh program — pad to a size class",
                        ))
                for pname, arg in info.static_args(sub):
                    if vol.expr_volatile(arg):
                        out.append(Finding(
                            self.id, ctx.display_path,
                            arg.lineno, arg.col_offset,
                            f"static arg {pname}= of jitted "
                            f"{sub.func.id}() receives a data-dependent "
                            "length: every distinct value is a recompile "
                            "— pad/round to a size class",
                        ))
        return out
