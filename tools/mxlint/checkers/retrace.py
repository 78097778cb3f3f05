"""Checker: Python ``if`` on traced-array arguments in jitted functions.

The recompile-elimination discipline (bucket ladders, pad-to-bucket
canonicalization, the `num_traces` regression tests) dies quietly at one
construct: a Python ``if`` whose condition reads a traced argument
inside a function handed to ``jax.jit``. Under tracing the condition
must concretize an abstract value — either it raises
(``TracerBoolConversionError``) or, when the value happens to be
concrete at trace time, it silently bakes one branch into the
executable and every new value mints a fresh trace.
Both failure modes are invisible in small tests and catastrophic on a
serving hot path.

Enforced (narrow first cut): inside a function passed to ``jit``
(first positional argument, or a ``jit`` decorator),
an ``if`` STATEMENT whose test uses a parameter of that function is a
finding, unless the use is trace-safe:

- ``x is None`` / ``x is not None`` (pytree-structure dispatch — the
  structure is part of the trace signature, not a traced value);
- ``isinstance``/``len``/``hasattr``/``getattr``/``callable``/``type``
  calls (static-shape/structure predicates);
- static metadata attributes: ``.shape``/``.ndim``/``.dtype``/
  ``.size``/``.weak_type`` (trace-time constants under jit).

Parameters named in ``static_argnames`` (or positioned by
``static_argnums``) of the jit call are exempt — they are hashed into
the trace signature by contract, branching on them is the point.
Conditional EXPRESSIONS (``a if c else b``) and ``while`` loops are out
of scope for this cut; the statement form is where the repo's past
retrace bugs lived.
"""
from __future__ import annotations

import ast

from ..astutil import dotted
from ..core import Checker, Finding

_SAFE_CALLS = {"isinstance", "len", "hasattr", "getattr", "callable",
               "type"}
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "weak_type"}


def _all_defs(tree):
    """name -> [def nodes], INCLUDING nested defs (the dominant repo
    idiom wraps the pure fn in a closure before handing it to jit)."""
    defs = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    return defs


def _static_params(call):
    """Parameter names/positions the jit call itself marks static."""
    names, nums = set(), set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and \
                        isinstance(el.value, str):
                    names.add(el.value)
        elif kw.arg == "static_argnums":
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and \
                        isinstance(el.value, int):
                    nums.add(el.value)
    return names, nums


def _traced_params(fn, static_names=(), static_nums=()):
    """Positional parameter names of `fn` that jit will trace."""
    args = list(fn.args.posonlyargs) + list(fn.args.args)
    out = set()
    for i, a in enumerate(args):
        if a.arg in ("self", "cls") and i == 0:
            continue
        if a.arg in static_names or i in static_nums:
            continue
        out.add(a.arg)
    if fn.args.vararg is not None:
        out.add(fn.args.vararg.arg)
    return out


def _dynamic_uses(test, params):
    """Names from `params` used dynamically (not via a trace-safe
    predicate) anywhere in the `if` test expression."""
    hits = set()

    def visit(node, exempt):
        if isinstance(node, ast.Name):
            if node.id in params and not exempt:
                hits.add(node.id)
            return
        if isinstance(node, ast.Compare):
            ops_static = node.ops and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops)
            for child in [node.left] + node.comparators:
                visit(child, exempt or ops_static)
            return
        if isinstance(node, ast.Call):
            callee = (dotted(node.func) or "").split(".")[-1]
            safe = callee in _SAFE_CALLS
            # The callee expression itself is never exempt: x.sum() is
            # a dynamic read even though it is syntactically a Call.
            visit(node.func, exempt)
            for child in list(node.args) + [kw.value
                                            for kw in node.keywords]:
                visit(child, exempt or safe)
            return
        if isinstance(node, ast.Attribute):
            static = node.attr in _STATIC_ATTRS
            visit(node.value, exempt or static)
            return
        for child in ast.iter_child_nodes(node):
            visit(child, exempt)

    visit(test, False)
    return hits


class RetraceHazardChecker(Checker):
    name = "retrace-hazard"
    description = ("no Python `if` on traced-array arguments inside "
                   "functions passed to jax.jit — "
                   "branch with jnp.where/lax.cond or mark the arg "
                   "static")

    def check_module(self, mod):
        defs = _all_defs(mod.tree)
        # (fn node, traced param names) for every jit target we can
        # resolve statically. A dict keyed by id() dedups a fn reached
        # through several jit sites; traced sets intersect (a param
        # static at EVERY site is safe).
        targets = {}

        def note(fn, traced):
            prev = targets.get(id(fn))
            if prev is None:
                targets[id(fn)] = (fn, set(traced))
            else:
                prev[1].intersection_update(traced)

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                callee = (dotted(node.func) or "").split(".")[-1]
                if callee != "jit" or not node.args:
                    continue
                snames, snums = _static_params(node)
                first = node.args[0]
                if isinstance(first, ast.Lambda):
                    continue        # a lambda body has no `if` statements
                if isinstance(first, ast.Name):
                    for fn in defs.get(first.id, ()):
                        note(fn, _traced_params(fn, snames, snums))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    d = dec
                    snames, snums = set(), set()
                    if isinstance(d, ast.Call):
                        inner = (dotted(d.func) or "").split(".")[-1]
                        if inner == "partial" and d.args and (
                                (dotted(d.args[0]) or "")
                                .split(".")[-1] == "jit"):
                            snames, snums = _static_params(d)
                            note(node, _traced_params(node, snames,
                                                      snums))
                            continue
                        if inner == "jit":
                            snames, snums = _static_params(d)
                            note(node, _traced_params(node, snames,
                                                      snums))
                            continue
                    if (dotted(d) or "").split(".")[-1] == "jit":
                        note(node, _traced_params(node))

        findings = []
        for fn, traced in targets.values():
            if not traced:
                continue
            for stmt in ast.walk(fn):
                if not isinstance(stmt, ast.If):
                    continue
                used = _dynamic_uses(stmt.test, traced)
                if used:
                    findings.append(Finding(
                        mod.relpath, stmt.lineno, self.name,
                        "`if` on traced argument%s %s of jitted "
                        "function '%s' — evaluated at TRACE time, so "
                        "it either raises on abstract values or mints "
                        "a fresh executable per value; use jnp.where/"
                        "lax.cond, branch on static metadata (.shape/"
                        ".ndim), or mark the arg static_argnames"
                        % ("s" if len(used) > 1 else "",
                           ", ".join("'%s'" % u for u in sorted(used)),
                           fn.name)))
        return findings
