"""GL003 — donation-safety: never read a variable after donating it.

``jax.jit(..., donate_argnums=...)`` hands the argument buffers to XLA for
in-place reuse: the caller's arrays are invalid afterwards — reading one
raises on a deleted buffer at best, and an older XLA:CPU corrupted the heap
outright (the tier-1 suite's historical wandering segfaults).  The rule
tracks, per function scope:

1. names bound to ``jax.jit(fn, donate_argnums=<positions>)`` or
   ``jax.jit(fn, donate_argnames=<names>)`` (constant tuples/ints/strs,
   ``name = <const>`` indirection, and either arm of a conditional
   expression are resolved; argNAMES map to positions when the jitted
   callable is a lambda whose parameter list is visible);
2. calls through those names — positional args at donated positions and
   keyword args matching donated argnames become tainted at the call line;
   a ``*args`` splat covering a donated position taints the splatted
   sequence name itself (its elements were donated through it);
3. any later ``Load`` of a tainted name in the same scope is a finding,
   until an assignment rebinds it (the ``x = donating_fn(x)`` idiom is the
   correct pattern and stays clean).

Attribute targets remain out of static reach and are skipped — the rule is
deliberately precise-over-complete so every finding is actionable.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import Iterable, Optional

from ..engine import Finding, ModuleInfo, Rule, dotted_name


def _const_positions(node: ast.AST, env: dict[str, ast.AST], depth: int = 0) -> Optional[set[int]]:
    """Evaluate a donate_argnums expression to a set of argument positions."""
    if depth > 4:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: set[int] = set()
        for elt in node.elts:
            got = _const_positions(elt, env, depth + 1)
            if got is None:
                return None
            out |= got
        return out
    if isinstance(node, ast.IfExp):  # e.g. () if cpu else (0, 1, 2)
        a = _const_positions(node.body, env, depth + 1) or set()
        b = _const_positions(node.orelse, env, depth + 1) or set()
        return a | b  # conservative union: donated on SOME path = donated
    if isinstance(node, ast.Name) and node.id in env:
        return _const_positions(env[node.id], env, depth + 1)
    return None


def _const_names(node: ast.AST, env: dict[str, ast.AST], depth: int = 0) -> set[str]:
    """Evaluate a donate_argnames expression to a set of parameter names."""
    if depth > 4:
        return set()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: set[str] = set()
        for elt in node.elts:
            out |= _const_names(elt, env, depth + 1)
        return out
    if isinstance(node, ast.IfExp):
        return _const_names(node.body, env, depth + 1) | \
            _const_names(node.orelse, env, depth + 1)
    if isinstance(node, ast.Name) and node.id in env:
        return _const_names(env[node.id], env, depth + 1)
    return set()


def _callable_params(node: ast.AST) -> Optional[list[str]]:
    """Positional parameter names of an inline lambda target (the one form
    whose signature is visible at the jit() call itself)."""
    if isinstance(node, ast.Lambda):
        return [a.arg for a in node.args.args]
    return None


@dataclass(frozen=True)
class _Donation:
    """What a jitted name donates: argument positions and/or argnames."""

    positions: frozenset
    names: frozenset

    def __bool__(self) -> bool:
        return bool(self.positions) or bool(self.names)


def _jit_donations(call: ast.Call, env: dict[str, ast.AST]) -> Optional[_Donation]:
    """The donation set when ``call`` is a jax.jit/pjit with donate_arg*."""
    chain = dotted_name(call.func)
    if chain.rsplit(".", 1)[-1] not in ("jit", "pjit"):
        return None
    positions: set[int] = set()
    names: set[str] = set()
    seen = False
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            seen = True
            positions |= _const_positions(kw.value, env) or set()
        elif kw.arg == "donate_argnames":
            seen = True
            got = _const_names(kw.value, env)
            names |= got
            # map names to positions when the callable's signature is visible
            params = _callable_params(call.args[0]) if call.args else None
            if params is not None:
                positions |= {params.index(n) for n in got if n in params}
    return _Donation(frozenset(positions), frozenset(names)) if seen else None


class DonationSafetyRule(Rule):
    id = "GL003"
    title = "variable read after being donated to a jitted call"

    def check_module(self, mod: ModuleInfo) -> Iterable[Finding]:
        findings: list[Finding] = []

        def scan_scope(body: list[ast.stmt]) -> None:
            env: dict[str, ast.AST] = {}          # simple name -> last value expr
            donating: dict[str, _Donation] = {}   # jitted-fn name -> donations
            tainted: dict[str, int] = {}          # var -> donation line

            class ScopeVisitor(ast.NodeVisitor):
                def visit_FunctionDef(self, node):  # new scope: recurse separately
                    scan_scope(node.body)

                visit_AsyncFunctionDef = visit_FunctionDef

                def visit_ClassDef(self, node):
                    scan_scope(node.body)

                def visit_Lambda(self, node):
                    pass  # separate (expression) scope; nothing donated inside

                def visit_Assign(self, node):
                    self.visit(node.value)
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            env[t.id] = node.value
                            tainted.pop(t.id, None)  # rebinding un-taints
                            donated = (_jit_donations(node.value, env)
                                       if isinstance(node.value, ast.Call) else None)
                            if donated:
                                donating[t.id] = donated

                def visit_Call(self, node):
                    # direct jax.jit(f, donate_argnums=...)(a, b) application
                    donated: Optional[_Donation] = None
                    if isinstance(node.func, ast.Call):
                        donated = _jit_donations(node.func, env)
                    elif isinstance(node.func, ast.Name) and node.func.id in donating:
                        donated = donating[node.func.id]
                    if donated:
                        for pos, arg in enumerate(node.args):
                            if isinstance(arg, ast.Starred):
                                # the splat covers every remaining position:
                                # if any of them is donated, the splatted
                                # sequence's buffers went with the call
                                if isinstance(arg.value, ast.Name) and any(
                                        p >= pos for p in donated.positions):
                                    tainted.setdefault(arg.value.id, node.lineno)
                                break
                            if pos in donated.positions and isinstance(arg, ast.Name):
                                tainted.setdefault(arg.id, node.lineno)
                        # args themselves are reads AT the call — fine; visit
                        # keywords/func only so the donated args don't self-flag
                        for kw in node.keywords:
                            if kw.arg in donated.names and isinstance(kw.value, ast.Name):
                                tainted.setdefault(kw.value.id, node.lineno)
                            self.visit(kw.value)
                        return
                    self.generic_visit(node)

                def visit_Name(self, node):
                    if isinstance(node.ctx, ast.Load) and node.id in tainted \
                            and node.lineno > tainted[node.id]:
                        findings.append(Finding(
                            DonationSafetyRule.id, mod.relpath, node.lineno,
                            f"{node.id!r} was donated to a jitted call at line "
                            f"{tainted[node.id]} (donate_argnums) and read again "
                            "here — donated buffers are invalid after the call "
                            "(and corrupt the heap on XLA:CPU)",
                            symbol=f"{node.id}:L{node.lineno}"))
                    elif isinstance(node.ctx, ast.Store):
                        tainted.pop(node.id, None)

            v = ScopeVisitor()
            for stmt in body:
                v.visit(stmt)

        scan_scope(mod.tree.body)
        return findings
