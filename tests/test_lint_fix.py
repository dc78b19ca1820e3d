"""``fedml-tpu lint --fix`` (ISSUE 7 satellite, ``analysis/fix.py``).

The fixer mechanically rewrites legacy ``extra.get(...)`` reads to
``cfg_extra(cfg, name, default)`` — proven here to (1) rewrite every
recoverable idiom including nested defaults, (2) be idempotent, (3) preserve
runtime semantics exactly (the old default expression rides along), (4) leave
suppressed and non-mechanical sites alone with a manual-migration note, and
(5) silence GL001's legacy findings on the fixed sources.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

from fedml_tpu.analysis.engine import run_lint
from fedml_tpu.analysis.fix import fix_source, fix_tree

REPO_ROOT = Path(__file__).resolve().parent.parent

FLAGS_FIXTURE = """
    class FlagSpec:
        def __init__(self, name, type, default, doc):
            pass

    FLAGS = {
        "aot_programs": FlagSpec("aot_programs", "bool", False, "doc"),
        "mlp_hidden": FlagSpec("mlp_hidden", "int", 128, "doc"),
        "silo_dp": FlagSpec("silo_dp", "bool", True, "doc"),
        "comm_topk_ratio": FlagSpec("comm_topk_ratio", "float", None, "doc"),
        "comm_compress_min_size": FlagSpec("comm_compress_min_size", "float", 0.01, "doc"),
    }
"""

LEGACY_MOD = '''
    """Fixture with every rewriteable legacy idiom."""
    import os


    def f(cfg):
        a = cfg.extra.get("aot_programs")
        b = (getattr(cfg, "extra", {}) or {}).get("mlp_hidden", 64)
        extra = cfg.extra
        c = extra.get("silo_dp", True)
        nested = cfg.extra.get("comm_topk_ratio",
                               cfg.extra.get("comm_compress_min_size", 0.01))
        return a, b, c, nested
'''


def test_fix_rewrites_all_idioms_and_is_idempotent():
    src = textwrap.dedent(LEGACY_MOD)
    fixed, n, skipped = fix_source(src, "mod.py")
    assert n == 5  # 3 direct + the nested pair (outer, then inner on pass 2)
    assert skipped == []
    assert "from fedml_tpu.core.flags import cfg_extra" in fixed
    assert ".get(" not in fixed
    assert "cfg_extra(cfg, 'aot_programs', None)" in fixed
    assert "cfg_extra(cfg, 'mlp_hidden', 64)" in fixed
    assert "cfg_extra(cfg, 'silo_dp', True)" in fixed
    assert "cfg_extra(cfg, 'comm_topk_ratio', cfg_extra(cfg, 'comm_compress_min_size', 0.01))" in fixed
    again, n2, _ = fix_source(fixed, "mod.py")
    assert n2 == 0 and again == fixed  # idempotent
    compile(fixed, "mod.py", "exec")  # still valid python


def test_fix_preserves_runtime_semantics():
    """The rewrite keeps ``.get``'s default (an unset flag stays ``None``,
    never swapped for the registry default)."""
    from fedml_tpu.arguments import Config

    src = textwrap.dedent(LEGACY_MOD)
    fixed, _, _ = fix_source(src, "mod.py")
    orig_ns, fixed_ns = {}, {}
    exec(compile(src, "orig.py", "exec"), orig_ns)
    exec(compile(fixed, "fixed.py", "exec"), fixed_ns)
    for extra in ({}, {"mlp_hidden": 256, "silo_dp": False},
                  {"aot_programs": True, "comm_compress_min_size": 0.5}):
        cfg = Config(dataset="synthetic", model="lr", extra=dict(extra))
        assert fixed_ns["f"](cfg) == orig_ns["f"](cfg), extra


def test_fix_skips_manual_sites_and_suppressions(tmp_path):
    (tmp_path / "mod.py").write_text(textwrap.dedent('''
        def f(cfg, name):
            cfg.extra.setdefault(name, 3)  # non-literal name: manual
            cfg.extra["seg_base"]  # statement-position subscript: no value use
            c = name in cfg.extra  # non-literal membership: manual
            d = cfg.extra.get(name)
            e = cfg.extra[name]
            return c, d, e


        def g(cfg):  # graftlint: disable=GL001(deliberate raw read)
            return cfg.extra.get("aot_programs")
    '''))
    before = (tmp_path / "mod.py").read_text()
    res = fix_tree(tmp_path)
    assert res.rewrites == 0
    assert (tmp_path / "mod.py").read_text() == before  # untouched
    notes = "\n".join(res.skipped)
    assert "setdefault" in notes and "statement-position extra[...]" in notes
    assert "membership test with a non-literal name" in notes
    assert notes.count("literal flag name") == 2  # .get(name) + extra[name]
    assert "aot_programs" not in notes  # suppressed site: no nag either


def test_fix_rewrites_value_position_subscript(tmp_path):
    """ISSUE 12 satellite: value-position ``extra["k"]`` reads become
    ``cfg_extra(cfg, 'k', None)``.  Statement-position reads stay report-
    only; single-target stores now rewrite to ``set_cfg_extra`` (ISSUE 20
    satellite) with only the helpers actually used imported."""
    src = textwrap.dedent('''
        def f(cfg):
            a = cfg.extra["mlp_hidden"]
            extra = cfg.extra
            b = extra["silo_dp"]
            if cfg.extra["aot_programs"]:
                a += 1
            cfg.extra["comm_topk_ratio"]  # statement position: report-only
            cfg.extra["mlp_hidden"] = 3   # write target: blessed-write rewrite
            return a, b
    ''')
    fixed, n, skipped = fix_source(src, "mod.py")
    assert n == 4, fixed
    assert "cfg_extra(cfg, 'mlp_hidden', None)" in fixed
    assert "cfg_extra(cfg, 'silo_dp', None)" in fixed
    assert "cfg_extra(cfg, 'aot_programs', None)" in fixed
    assert 'cfg.extra["comm_topk_ratio"]' in fixed  # statement form survives
    assert "set_cfg_extra(cfg, 'mlp_hidden', 3)" in fixed  # store: rewritten
    assert "from fedml_tpu.core.flags import cfg_extra, set_cfg_extra" in fixed
    assert any("statement-position extra[...]" in s for s in skipped)
    compile(fixed, "mod.py", "exec")
    again, n2, _ = fix_source(fixed, "mod.py")
    assert n2 == 0 and again == fixed  # idempotent


def test_fix_subscript_semantics():
    """Set keys: identical values.  Missing key: the documented trade —
    the subscript's KeyError becomes cfg_extra's None default."""
    import pytest

    from fedml_tpu.arguments import Config

    src = "def f(cfg):\n    return cfg.extra['mlp_hidden']\n"
    fixed, n, _ = fix_source(src, "mod.py")
    assert n == 1
    orig_ns, fixed_ns = {}, {}
    exec(compile(src, "o.py", "exec"), orig_ns)
    exec(compile(fixed, "f.py", "exec"), fixed_ns)
    cfg = Config(dataset="synthetic", model="lr", extra={"mlp_hidden": 256})
    assert orig_ns["f"](cfg) == fixed_ns["f"](cfg) == 256
    empty = Config(dataset="synthetic", model="lr", extra={})
    with pytest.raises(KeyError):
        orig_ns["f"](empty)
    assert fixed_ns["f"](empty) is None


def test_fix_rewrites_value_position_setdefault(tmp_path):
    """The ROADMAP carried item: ``x = extra.setdefault(k, v)`` reads the
    flag with default ``v`` — rewritten to the registry-backed read.  The
    statement form becomes an explicit seed assignment (ISSUE 19
    satellite) — see the statement-position tests below."""
    src = textwrap.dedent('''
        def f(cfg):
            a = cfg.extra.setdefault("mlp_hidden", 64)
            extra = cfg.extra
            b = extra.setdefault("silo_dp")
            if extra.setdefault("aot_programs", False):
                a += 1
            cfg.extra.setdefault("comm_topk_ratio", 0.1)  # statement form
            return a, b
    ''')
    fixed, n, skipped = fix_source(src, "mod.py")
    assert n == 4, fixed
    assert "cfg_extra(cfg, 'mlp_hidden', 64)" in fixed
    assert "cfg_extra(cfg, 'silo_dp', None)" in fixed
    assert "cfg_extra(cfg, 'aot_programs', False)" in fixed
    # the statement-position seed becomes an explicit seed through the
    # registry-checked write (ISSUE 20: set_cfg_extra replaces the raw store)
    assert ("set_cfg_extra(cfg, 'comm_topk_ratio', "
            "cfg_extra(cfg, 'comm_topk_ratio', 0.1))") in fixed
    assert skipped == []
    compile(fixed, "mod.py", "exec")
    again, n2, _ = fix_source(fixed, "mod.py")
    assert n2 == 0 and again == fixed  # idempotent


def test_fix_rewrites_statement_position_setdefault():
    """ISSUE 19 satellite (write half upgraded by ISSUE 20): a statement-
    position ``extra.setdefault(k, v)`` (pure dict seeding for raw
    downstream readers) is rewritten to
    ``set_cfg_extra(cfg, 'k', cfg_extra(cfg, 'k', v))`` — seeded dict
    preserved, flag name declared and GL001-checked on both halves — and
    the rewrite is idempotent."""
    src = textwrap.dedent('''
        def seed(cfg):
            cfg.extra.setdefault("mlp_hidden", 64)
            extra = cfg.extra
            extra.setdefault("silo_dp")
            return cfg
    ''')
    fixed, n, skipped = fix_source(src, "mod.py")
    assert n == 2, fixed
    assert skipped == []
    assert ("set_cfg_extra(cfg, 'mlp_hidden', "
            "cfg_extra(cfg, 'mlp_hidden', 64))") in fixed
    # the no-default form seeds the explicit None that setdefault() would have
    assert ("set_cfg_extra(cfg, 'silo_dp', "
            "cfg_extra(cfg, 'silo_dp', None))") in fixed
    assert "from fedml_tpu.core.flags import cfg_extra, set_cfg_extra" in fixed
    compile(fixed, "mod.py", "exec")
    again, n2, again_skipped = fix_source(fixed, "mod.py")
    assert n2 == 0 and again == fixed and again_skipped == []  # idempotent


def test_fix_statement_setdefault_exec_semantics():
    """Exec'd before/after: a PRESENT key keeps its value and a missing key
    lands the same seed, so every raw downstream ``extra[...]`` reader sees
    an identical dict."""
    from fedml_tpu.arguments import Config

    src = textwrap.dedent('''
        def seed(cfg):
            cfg.extra.setdefault("mlp_hidden", 64)
            cfg.extra.setdefault("silo_dp", True)
            return cfg.extra
    ''')
    fixed, n, _ = fix_source(src, "mod.py")
    assert n == 2
    orig_ns, fixed_ns = {}, {}
    exec(compile(src, "o.py", "exec"), orig_ns)
    exec(compile(fixed, "f.py", "exec"), fixed_ns)
    for extra in ({}, {"mlp_hidden": 256}, {"mlp_hidden": 0, "silo_dp": False}):
        got_orig = dict(orig_ns["seed"](
            Config(dataset="synthetic", model="lr", extra=dict(extra))))
        got_fixed = dict(fixed_ns["seed"](
            Config(dataset="synthetic", model="lr", extra=dict(extra))))
        assert got_orig == got_fixed, (extra, got_orig, got_fixed)


def test_fix_setdefault_semantics_match_on_value_use():
    """For the value use itself, setdefault(k, v) and cfg_extra(cfg, k, v)
    agree whether the flag is set or unset."""
    from fedml_tpu.arguments import Config

    src = "def f(cfg):\n    return cfg.extra.setdefault('mlp_hidden', 64)\n"
    fixed, n, _ = fix_source(src, "mod.py")
    assert n == 1
    orig_ns, fixed_ns = {}, {}
    exec(compile(src, "o.py", "exec"), orig_ns)
    exec(compile(fixed, "f.py", "exec"), fixed_ns)
    for extra in ({}, {"mlp_hidden": 256}):
        assert (orig_ns["f"](Config(dataset="synthetic", model="lr", extra=dict(extra)))
                == fixed_ns["f"](Config(dataset="synthetic", model="lr", extra=dict(extra))))


def test_fix_rewrites_membership_tests():
    """ISSUE 20 satellite: value-position ``"k" in extra`` / ``not in``
    membership tests become ``cfg_extra_present(cfg, 'k')`` (the ``not in``
    form paren-wrapped), and only the helper actually used is imported."""
    src = textwrap.dedent('''
        def f(cfg):
            a = "mlp_hidden" in cfg.extra
            extra = cfg.extra
            b = "silo_dp" not in extra
            if "aot_programs" in (getattr(cfg, "extra", {}) or {}):
                a = not a
            return a, b
    ''')
    fixed, n, skipped = fix_source(src, "mod.py")
    assert n == 3, fixed
    assert skipped == []
    assert "from fedml_tpu.core.flags import cfg_extra_present" in fixed
    assert "a = cfg_extra_present(cfg, 'mlp_hidden')" in fixed
    assert "b = (not cfg_extra_present(cfg, 'silo_dp'))" in fixed
    assert "if cfg_extra_present(cfg, 'aot_programs'):" in fixed
    compile(fixed, "mod.py", "exec")
    again, n2, _ = fix_source(fixed, "mod.py")
    assert n2 == 0 and again == fixed  # idempotent


def test_fix_membership_exec_semantics():
    """Exec'd before/after: membership agrees set/unset, including the
    present-but-None key the probe exists to keep distinct from absent."""
    from fedml_tpu.arguments import Config

    src = textwrap.dedent('''
        def f(cfg):
            return "mlp_hidden" in cfg.extra, "silo_dp" not in cfg.extra
    ''')
    fixed, n, _ = fix_source(src, "mod.py")
    assert n == 2
    orig_ns, fixed_ns = {}, {}
    exec(compile(src, "o.py", "exec"), orig_ns)
    exec(compile(fixed, "f.py", "exec"), fixed_ns)
    for extra in ({}, {"mlp_hidden": 256}, {"mlp_hidden": None},
                  {"mlp_hidden": 0, "silo_dp": False}):
        cfg = Config(dataset="synthetic", model="lr", extra=dict(extra))
        assert fixed_ns["f"](cfg) == orig_ns["f"](cfg), extra


def test_fix_store_exec_semantics():
    """Exec'd before/after: the ``set_cfg_extra`` rewrite lands the same
    dict contents a raw subscript store would, and is idempotent."""
    from fedml_tpu.arguments import Config

    src = textwrap.dedent('''
        def seed(cfg, v):
            cfg.extra["mlp_hidden"] = v
            extra = cfg.extra
            extra["silo_dp"] = v * 2
            return cfg.extra
    ''')
    fixed, n, _ = fix_source(src, "mod.py")
    assert n == 2, fixed
    assert "set_cfg_extra(cfg, 'mlp_hidden', v)" in fixed
    assert "set_cfg_extra(cfg, 'silo_dp', v * 2)" in fixed
    orig_ns, fixed_ns = {}, {}
    exec(compile(src, "o.py", "exec"), orig_ns)
    exec(compile(fixed, "f.py", "exec"), fixed_ns)
    for v in (3, 0):
        got_orig = dict(orig_ns["seed"](
            Config(dataset="synthetic", model="lr", extra={}), v))
        got_fixed = dict(fixed_ns["seed"](
            Config(dataset="synthetic", model="lr", extra={}), v))
        assert got_orig == got_fixed == {"mlp_hidden": v, "silo_dp": v * 2}
    again, n2, _ = fix_source(fixed, "mod.py")
    assert n2 == 0 and again == fixed  # idempotent


def test_fixed_package_is_gl001_legacy_clean(tmp_path):
    (tmp_path / "core").mkdir()
    (tmp_path / "core" / "flags.py").write_text(textwrap.dedent(FLAGS_FIXTURE))
    (tmp_path / "mod.py").write_text(textwrap.dedent(LEGACY_MOD))
    assert any(f.symbol.startswith("legacy:") for f in run_lint(tmp_path).findings)
    res = fix_tree(tmp_path)
    assert res.rewrites == 5 and res.files_changed == ["mod.py"]
    after = run_lint(tmp_path)
    assert not any(f.symbol.startswith("legacy:") for f in after.findings), \
        [f.render() for f in after.findings]


def test_cli_lint_fix_end_to_end(tmp_path):
    (tmp_path / "core").mkdir()
    (tmp_path / "core" / "flags.py").write_text(textwrap.dedent(FLAGS_FIXTURE))
    (tmp_path / "mod.py").write_text(textwrap.dedent(LEGACY_MOD))
    cmd = [sys.executable, "-m", "fedml_tpu.cli", "lint", "--fix", str(tmp_path)]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=120,
                         cwd=str(REPO_ROOT))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "fixed 5 legacy extra read(s) in 1 file(s)" in out.stdout
    assert "cfg_extra(cfg, 'silo_dp', True)" in (tmp_path / "mod.py").read_text()
    # second invocation: nothing left to fix, lint stays clean
    out2 = subprocess.run(cmd, capture_output=True, text=True, timeout=120,
                          cwd=str(REPO_ROOT))
    assert out2.returncode == 0, out2.stdout + out2.stderr
    assert "fixed 0 legacy extra read(s)" in out2.stdout
