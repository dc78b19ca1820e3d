"""``fedml-tpu lint --fix`` — mechanical migration of legacy ``extra.get``
idioms to ``cfg_extra(cfg, name, default)``.

GL001 flags three legacy read idioms; this module REWRITES the one that has a
semantics-preserving mechanical form — the ``.get`` call::

    cfg.extra.get("aot_programs")                     -> cfg_extra(cfg, 'aot_programs', None)
    (getattr(cfg, "extra", {}) or {}).get("k", 3)     -> cfg_extra(cfg, 'k', 3)
    extra = cfg.extra; ... extra.get("silo_dp", True) -> cfg_extra(cfg, 'silo_dp', True)
    x = extra.setdefault("k", 3)                      -> x = cfg_extra(cfg, 'k', 3)
    x = cfg.extra["k"]                                -> x = cfg_extra(cfg, 'k', None)
    if "k" in cfg.extra: ...                          -> if cfg_extra_present(cfg, 'k'): ...
    if "k" not in extra: ...                          -> if (not cfg_extra_present(cfg, 'k')): ...
    cfg.extra["k"] = v                                -> set_cfg_extra(cfg, 'k', v)

The original default expression is carried verbatim (``.get`` with no default
becomes an explicit ``None``), so the rewrite never swaps in the registry
default where the old code returned ``None`` — behavior is identical, the
read just becomes registry-checked.

``setdefault`` in VALUE position is rewritten too (the ROADMAP carried
item): the read half is exactly ``cfg_extra`` with the same default, and
the dict-seeding side effect is what the registry replaces — every other
registry-backed read supplies its own declared default, so the seed is
dead weight.  A *statement*-position ``extra.setdefault(k, v)`` exists ONLY
for that side effect (someone downstream reads the dict raw); it is
rewritten to an EXPLICIT seed through the registry-checked write::

    cfg.extra.setdefault("k", 3)   ->   set_cfg_extra(cfg, 'k', cfg_extra(cfg, 'k', 3))

which preserves the seeded dict for every raw downstream reader (present
key keeps its value via the ``cfg_extra`` resolution order, missing key
lands the same default) while the flag name becomes declared and
GL001-checked on BOTH halves.

Value-position ``extra["k"]`` subscript READS are rewritten to
``cfg_extra(cfg, 'k', None)`` (ISSUE 12 satellite).  This is the one rewrite
that intentionally changes missing-key behavior: the subscript raised
``KeyError`` where ``cfg_extra`` returns ``None`` — but a flag read that
crashes on an unset flag is exactly the misconfiguration failure mode the
registry exists to kill, and every rewritten name becomes a declared,
GL001-checked read.  Set keys behave identically (proven by test).

``"k" in extra`` / ``"k" not in extra`` membership tests are rewritten to
``cfg_extra_present(cfg, 'k')`` (ISSUE 20 satellite) — the dedicated
membership probe keeps present-but-``None`` distinct from absent, so the
rewrite is semantics-preserving wherever the attribute-vs-dict resolution
order agrees (the same alignment every other rewrite already accepts).
The ``not in`` form is paren-wrapped so operator precedence survives any
surrounding expression.  Single-target ``extra["k"] = value`` STORES
become ``set_cfg_extra(cfg, 'k', value)`` — the one blessed write idiom,
registry-checked like the reads.

Sites the fixer cannot prove out — statement-position subscript reads,
Del/augmented targets, non-literal flag names, and receivers whose owning
config expression cannot be recovered — are reported for manual
migration, never guessed at.

``fix_source`` loops to a fixpoint (a ``.get`` nested inside another's
default argument is rewritten on the next pass), which is also what makes
``--fix`` idempotent: a second run over fixed sources reports zero rewrites.
The inserted import is the absolute ``from fedml_tpu.core.flags import
<helpers actually used>`` — the package itself migrated in PR 5, so the
fixer's targets are out-of-tree recipes/plugins where a relative import
would not resolve.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field as dc_field
from pathlib import Path
from typing import Callable, Optional

from .engine import ModuleInfo, dotted_name, str_const
from .rules.gl001_flags import _is_extra_expr

__all__ = ["fix_source", "fix_file", "fix_tree", "FixResult"]

IMPORT_MODULE = "fedml_tpu.core.flags"
#: canonical order for the inserted import's name list (and the detection
#: of what an existing import already provides)
HELPER_NAMES = ("cfg_extra", "cfg_extra_present", "set_cfg_extra")
IMPORT_LINE = f"from {IMPORT_MODULE} import cfg_extra"  # the common single-helper form


@dataclass
class FixResult:
    files_changed: list[str] = dc_field(default_factory=list)
    rewrites: int = 0
    skipped: list[str] = dc_field(default_factory=list)  # manual-migration notes

    def render(self) -> str:
        lines = [f"fixed {self.rewrites} legacy extra read(s) in "
                 f"{len(self.files_changed)} file(s)"]
        lines += [f"  rewrote: {p}" for p in self.files_changed]
        lines += [f"  manual:  {s}" for s in self.skipped]
        return "\n".join(lines)


def _cfg_expr_of(node: ast.AST, assigned: dict[str, Optional[str]]) -> Optional[str]:
    """Recover the source of the config object that owns this extra-like
    expression (``cfg.extra`` -> ``cfg``); None when it cannot be proven."""
    if isinstance(node, ast.Attribute) and node.attr == "extra":
        try:
            return ast.unparse(node.value)
        except Exception:
            return None
    if isinstance(node, ast.Call):
        fn = dotted_name(node.func)
        if fn == "getattr" and len(node.args) >= 2 and str_const(node.args[1]) == "extra":
            try:
                return ast.unparse(node.args[0])
            except Exception:
                return None
        if fn == "dict" and node.args:
            return _cfg_expr_of(node.args[0], assigned)
        return None
    if isinstance(node, ast.BoolOp):
        for v in node.values:
            out = _cfg_expr_of(v, assigned)
            if out is not None:
                return out
        return None
    if isinstance(node, ast.Name):
        return assigned.get(node.id)
    return None


def _line_offsets(source: str) -> list[int]:
    offsets, total = [0], 0
    for line in source.splitlines(keepends=True):
        total += len(line)
        offsets.append(total)
    return offsets


def _span(node: ast.AST, offsets: list[int]) -> tuple[int, int]:
    return (offsets[node.lineno - 1] + node.col_offset,
            offsets[node.end_lineno - 1] + node.end_col_offset)


def _one_pass(source: str, relpath: str,
              suppressed: Callable[[int], bool]) -> tuple[str, int, list[str]]:
    """One rewrite sweep: outermost ``.get`` candidates only (nested ones are
    caught by the fixpoint loop in :func:`fix_source`)."""
    tree = ast.parse(source)
    offsets = _line_offsets(source)
    # expressions whose value is discarded (bare expression statements): a
    # setdefault here exists only for its dict-seeding side effect, and a
    # bare subscript read has no value consumer to migrate
    stmt_position = {
        id(stmt.value) for stmt in ast.walk(tree)
        if isinstance(stmt, ast.Expr)
        and isinstance(stmt.value, (ast.Call, ast.Subscript))
    }
    extra_vars: set[str] = set()
    assigned: dict[str, Optional[str]] = {}
    # (span, replacement, helpers the replacement calls)
    candidates: list[tuple[tuple[int, int], str, tuple[str, ...]]] = []
    skipped: list[str] = []
    imported = {
        a.name
        for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)
        for a in n.names if a.name in HELPER_NAMES
    }

    def skip(node: ast.AST, why: str) -> None:
        if not suppressed(node.lineno):
            skipped.append(f"{relpath}:{node.lineno}: {why}")

    for node in ast.walk(tree):
        if getattr(node, "lineno", None) is not None and suppressed(node.lineno):
            # an annotated `# graftlint: disable=GL001(...)` site is a
            # deliberate exception — neither rewritten nor nagged about
            continue
        # mirror GL001's tracking of `extra = <extra-like>` locals, keeping
        # the recovered cfg expression alongside
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and _is_extra_expr(node.value, extra_vars):
            extra_vars.add(node.targets[0].id)
            assigned[node.targets[0].id] = _cfg_expr_of(node.value, assigned)
            continue
        # single-target subscript STORE on an extra-like receiver: the whole
        # statement becomes the registry-checked write (ISSUE 20 satellite)
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Subscript) \
                and _is_extra_expr(node.targets[0].value, extra_vars):
            sub = node.targets[0]
            name = str_const(sub.slice)
            if name is None:
                skip(node, "extra[<non-literal name>] = ... store — GL001 needs "
                           "a literal flag name; migrate by hand")
                continue
            cfg_src = _cfg_expr_of(sub.value, assigned)
            if cfg_src is None:
                skip(node, f"extra[{name!r}] = ... store: owning config object "
                           "not recoverable — migrate by hand")
                continue
            value_src = ast.unparse(node.value)
            candidates.append((_span(node, offsets),
                               f"set_cfg_extra({cfg_src}, {name!r}, {value_src})",
                               ("set_cfg_extra",)))
            continue
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
                and node.args and _is_extra_expr(node.func.value, extra_vars):
            if node.func.attr == "setdefault" and id(node) in stmt_position:
                # statement-position seed: rewrite to an explicit seed through
                # the registry-checked write — the seeded dict stays seeded
                # for raw downstream readers, the name becomes a declared
                # GL001-checked flag on both the read and write halves
                name = str_const(node.args[0])
                cfg_src = _cfg_expr_of(node.func.value, assigned)
                if (name is None or cfg_src is None
                        or len(node.args) > 2 or node.keywords):
                    skip(node, "statement-position extra.setdefault(...) with a "
                               "non-literal name / unrecoverable config / odd "
                               "call shape — migrate by hand")
                    continue
                default_src = (ast.unparse(node.args[1])
                               if len(node.args) == 2 else "None")
                candidates.append((_span(node, offsets),
                                   f"set_cfg_extra({cfg_src}, {name!r}, "
                                   f"cfg_extra({cfg_src}, {name!r}, {default_src}))",
                                   ("cfg_extra", "set_cfg_extra")))
                continue
            if node.func.attr not in ("get", "setdefault"):
                continue
            verb = node.func.attr
            name = str_const(node.args[0])
            if name is None:
                skip(node, f"extra.{verb}(<non-literal name>) — GL001 needs a "
                           "literal flag name; migrate by hand")
                continue
            cfg_src = _cfg_expr_of(node.func.value, assigned)
            if cfg_src is None:
                skip(node, f"extra.{verb}({name!r}): owning config object not "
                           "recoverable — migrate by hand")
                continue
            if len(node.args) > 2 or node.keywords:
                skip(node, f"extra.{verb}({name!r}, ...): unexpected call shape — "
                           "migrate by hand")
                continue
            default_src = ast.unparse(node.args[1]) if len(node.args) == 2 else "None"
            replacement = f"cfg_extra({cfg_src}, {name!r}, {default_src})"
            candidates.append((_span(node, offsets), replacement, ("cfg_extra",)))
        elif isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load) \
                and _is_extra_expr(node.value, extra_vars):
            if id(node) in stmt_position:
                skip(node, "statement-position extra[...] has no value use — "
                           "migrate (or delete) the site by hand")
                continue
            name = str_const(node.slice)
            if name is None:
                skip(node, f"extra[{ast.unparse(node.slice)}] — GL001 needs a "
                           "literal flag name; migrate by hand")
                continue
            cfg_src = _cfg_expr_of(node.value, assigned)
            if cfg_src is None:
                skip(node, f"extra[{name!r}]: owning config object not "
                           "recoverable — migrate by hand")
                continue
            # value-position subscript read: becomes the registry-checked
            # read with default None (missing key: KeyError -> None — the
            # deliberate semantics change documented in the module docstring)
            candidates.append(
                (_span(node, offsets), f"cfg_extra({cfg_src}, {name!r}, None)",
                 ("cfg_extra",)))
        elif isinstance(node, ast.Compare) and len(node.ops) == 1 \
                and isinstance(node.ops[0], (ast.In, ast.NotIn)) \
                and _is_extra_expr(node.comparators[0], extra_vars):
            # membership test: becomes the dedicated registry-checked probe
            # (cfg_extra_present keeps present-but-None distinct from absent,
            # so the rewrite preserves the dict-membership semantics)
            name = str_const(node.left)
            if name is None:
                skip(node, "membership test with a non-literal name — "
                           "migrate by hand")
                continue
            cfg_src = _cfg_expr_of(node.comparators[0], assigned)
            if cfg_src is None:
                skip(node, f"{name!r} in extra: owning config object not "
                           "recoverable — migrate by hand")
                continue
            repl = f"cfg_extra_present({cfg_src}, {name!r})"
            if isinstance(node.ops[0], ast.NotIn):
                # paren-wrapped so precedence survives any surrounding context
                repl = f"(not {repl})"
            candidates.append((_span(node, offsets), repl, ("cfg_extra_present",)))

    # outermost candidates only: an inner .get inside another's default arg
    # is regenerated by the outer rewrite and picked up on the next pass
    candidates.sort(key=lambda c: c[0][0])
    chosen: list[tuple[tuple[int, int], str, tuple[str, ...]]] = []
    last_end = -1
    for (start, end), repl, helpers in candidates:
        if start < last_end:
            continue
        chosen.append(((start, end), repl, helpers))
        last_end = end

    if not chosen:
        return source, 0, skipped
    out = source
    for (start, end), repl, _helpers in sorted(
            chosen, key=lambda c: c[0][0], reverse=True):
        out = out[:start] + repl + out[end:]
    used = {h for _, _, helpers in chosen for h in helpers}
    missing = [h for h in HELPER_NAMES if h in used and h not in imported]
    if missing:
        out = _insert_import(out, missing)
    return out, len(chosen), skipped


def _insert_import(source: str, names: "list[str] | None" = None) -> str:
    """Insert the flags-helper import (only the names actually needed) after
    the leading docstring/import block."""
    tree = ast.parse(source)
    insert_after = 0
    for i, stmt in enumerate(tree.body):
        if i == 0 and isinstance(stmt, ast.Expr) \
                and isinstance(stmt.value, ast.Constant) \
                and isinstance(stmt.value.value, str):
            insert_after = stmt.end_lineno or stmt.lineno
            continue
        if isinstance(stmt, (ast.Import, ast.ImportFrom)):
            insert_after = stmt.end_lineno or stmt.lineno
            continue
        break
    line = (IMPORT_LINE if not names
            else f"from {IMPORT_MODULE} import {', '.join(names)}")
    lines = source.splitlines(keepends=True)
    pos = sum(len(l) for l in lines[:insert_after])
    sep = "\n" if insert_after else ""
    return source[:pos] + sep + line + "\n" + source[pos:]


def fix_source(source: str, relpath: str = "<string>",
               max_passes: int = 10) -> tuple[str, int, list[str]]:
    """Rewrite to a fixpoint.  Returns (new_source, total_rewrites, skipped);
    re-running on the output always yields zero rewrites (idempotence).
    Lines under a ``# graftlint: disable=GL001`` suppression are left alone."""
    total, skipped = 0, []
    for _ in range(max_passes):
        mod = ModuleInfo(relpath, source)  # suppression map tracks each pass
        source, n, skipped = _one_pass(
            source, relpath, lambda line: mod.is_suppressed("GL001", line))
        total += n
        if n == 0:
            break
    return source, total, skipped


def fix_file(path: Path, result: FixResult, root: Optional[Path] = None) -> None:
    rel = path.relative_to(root).as_posix() if root else path.name
    try:
        src = path.read_text()
        new, n, skipped = fix_source(src, rel)
    except (SyntaxError, UnicodeDecodeError, OSError) as e:
        result.skipped.append(f"{rel}: unfixable ({type(e).__name__}: {e})")
        return
    result.skipped.extend(skipped)
    if n:
        path.write_text(new)
        result.files_changed.append(rel)
        result.rewrites += n


def fix_tree(root: str | Path) -> FixResult:
    """Fix every ``*.py`` under ``root`` (or the single file) in place.  The
    registry module itself is exempt — its one ``extra.get`` IS the accessor."""
    rootp = Path(root)
    result = FixResult()
    paths = [rootp] if rootp.is_file() else sorted(rootp.rglob("*.py"))
    for p in paths:
        if "__pycache__" in p.parts:
            continue
        if p.as_posix().endswith("core/flags.py"):
            continue
        fix_file(p, result, root=None if rootp.is_file() else rootp)
    return result
