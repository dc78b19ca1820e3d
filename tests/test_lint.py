"""The lint engine's own tests (ISSUE 5).

Each GL rule is proven BOTH ways on fixture packages — it fires on the
violation and goes quiet under a ``# graftlint: disable=...`` — plus the
baseline round-trips, and the real ``fedml_tpu`` package lints clean with
the SHIPPED (empty) baseline: the same invariant the tier-1 gate enforces
forever after.
"""

import json
import textwrap
from pathlib import Path

import pytest

from fedml_tpu.analysis.engine import run_lint
from fedml_tpu.analysis.findings import (
    Finding, load_baseline, parse_suppressions, save_baseline,
)

PKG_ROOT = Path(__file__).resolve().parent.parent / "fedml_tpu"

#: a minimal registry module for GL001 fixtures
FLAGS_FIXTURE = """
    class FlagSpec:
        def __init__(self, name, type, default, doc):
            pass

    FLAGS = {
        "declared_flag": FlagSpec("declared_flag", "int", 1, "declared + read"),
        "dead_flag": FlagSpec("dead_flag", "bool", False, "declared, never read"),
    }
"""


def lint_files(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return run_lint(tmp_path)


def rules_fired(result):
    return {f.rule for f in result.findings}


# -- GL001: flag registry -----------------------------------------------------

def test_gl001_undeclared_read_fires(tmp_path):
    r = lint_files(tmp_path, {
        "core/flags.py": FLAGS_FIXTURE,
        "mod.py": """
            from .core.flags import cfg_extra

            def f(cfg):
                return cfg_extra(cfg, "mystery_flag")
        """,
    })
    assert any(f.rule == "GL001" and "mystery_flag" in f.message for f in r.findings)


def test_gl001_declared_cfg_extra_read_is_clean(tmp_path):
    r = lint_files(tmp_path, {
        "core/flags.py": FLAGS_FIXTURE,
        "mod.py": """
            from .core.flags import cfg_extra

            def f(cfg):
                return cfg_extra(cfg, "declared_flag", 3)
        """,
    })
    assert not any(f.symbol == "undeclared:declared_flag" for f in r.findings)
    # only the dead_flag declaration should fire
    assert [f.symbol for f in r.findings] == ["dead:dead_flag"]


def test_gl001_dead_declaration_fires_and_reads_clear_it(tmp_path):
    r = lint_files(tmp_path, {"core/flags.py": FLAGS_FIXTURE, "mod.py": "x = 1\n"})
    symbols = {f.symbol for f in r.findings if f.rule == "GL001"}
    assert symbols == {"dead:dead_flag", "dead:declared_flag"}


def test_gl001_legacy_idioms_fire(tmp_path):
    r = lint_files(tmp_path, {
        "core/flags.py": FLAGS_FIXTURE,
        "mod.py": """
            def f(cfg):
                extra = getattr(cfg, "extra", {}) or {}
                a = extra.get("declared_flag", 1)
                b = (getattr(cfg, "extra", {}) or {}).get("inline_flag")
                c = extra["declared_flag"]
                return a, b, c
        """,
    })
    syms = {f.symbol for f in r.findings if f.rule == "GL001"}
    assert "legacy:declared_flag" in syms           # .get and subscript
    assert "legacy:inline_flag" in syms             # inline chained idiom
    assert "undeclared:inline_flag" in syms         # and it is undeclared too


def test_gl001_nonliteral_name_fires_and_suppression_silences(tmp_path):
    r = lint_files(tmp_path, {
        "core/flags.py": FLAGS_FIXTURE,
        "mod.py": """
            from .core.flags import cfg_extra

            def f(cfg, name):
                bad = cfg_extra(cfg, name)
                ok = cfg_extra(cfg, name)  # graftlint: disable=GL001(fixture reason)
                return bad, ok
        """,
    })
    nonliteral = [f for f in r.findings if f.symbol.startswith("nonliteral")]
    assert len(nonliteral) == 1
    assert len(r.suppressed) == 1


def test_gl001_duck_typed_getattr_counts_as_read(tmp_path):
    # getattr(cfg, "<declared flag>", d) keeps a declaration alive but is
    # not itself flagged (Config.__getattr__ falls through to extra)
    r = lint_files(tmp_path, {
        "core/flags.py": FLAGS_FIXTURE,
        "mod.py": """
            def f(cfg):
                return getattr(cfg, "declared_flag", False)
        """,
    })
    assert [f.symbol for f in r.findings] == ["dead:dead_flag"]


# -- GL002: jit purity --------------------------------------------------------

GL002_CASES = [
    ("import time\nimport jax\n\ndef step(x):\n    t = time.time()\n    return x + t\n\njitted = jax.jit(step)\n",
     "host clock"),
    ("import numpy as np\nimport jax\n\ndef step(x):\n    return x + np.random.rand()\n\njitted = jax.jit(step)\n",
     "host randomness"),
    ("import jax\n\ndef step(x):\n    print(x)\n    return x\n\njitted = jax.jit(step)\n",
     "print"),
    ("import logging\nimport jax\nlog = logging.getLogger(__name__)\n\ndef step(x):\n    log.info('hi')\n    return x\n\njitted = jax.jit(step)\n",
     "logging"),
    ("import jax\n\ndef outer():\n    n = 0\n    def step(x):\n        nonlocal n\n        n += 1\n        return x\n    return jax.jit(step)\n",
     "nonlocal"),
]


@pytest.mark.parametrize("src,what", GL002_CASES, ids=[w for _, w in GL002_CASES])
def test_gl002_impurities_fire(tmp_path, src, what):
    r = lint_files(tmp_path, {"mod.py": src})
    assert rules_fired(r) == {"GL002"}, (what, r.render())


def test_gl002_metric_and_scan_and_decorator_forms(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import jax
        from .obs import registry as obsreg

        COUNTER = obsreg.REGISTRY.counter("fedml_fixture_total", "doc")

        @jax.jit
        def decorated(x):
            COUNTER.inc()
            return x

        def body(carry, x):
            COUNTER.inc()
            return carry, x

        def run(xs):
            return jax.lax.scan(body, 0, xs)
    """})
    gl002 = [f for f in r.findings if f.rule == "GL002"]
    assert len(gl002) == 2  # the decorated fn AND the scan body
    assert all("metric mutation" in f.message for f in gl002)


def test_gl002_pure_fn_and_suppression(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import time
        import jax

        def pure(x):
            return x * 2

        def timed(x):
            t = time.time()  # graftlint: disable=GL002(fixture: trace-time stamp is intended)
            return x + t

        a = jax.jit(pure)
        b = jax.jit(timed)
    """})
    assert not r.findings
    assert len(r.suppressed) == 1


def test_gl002_profiler_and_registry_get_allowlisted(tmp_path):
    """ISSUE 20 satellite: deliberately trace-time instrumentation —
    ``REGISTRY.get`` cost-model reads and profiler ``note_program`` /
    window hooks — is allowlisted; a mutating REGISTRY chain still fires,
    and impurities nested in an allowlisted call's arguments still fire."""
    r = lint_files(tmp_path, {"mod.py": """
        import jax
        from obs.registry import REGISTRY

        def noted(x):
            profiler.note_program("sim.step", flops=2.0)
            self_like.attributor.maybe_start(0)
            fam = REGISTRY.get("fedml_cost_flops")
            return x * 2

        clean = jax.jit(noted)
    """})
    assert not [f for f in r.findings if f.rule == "GL002"], r.render()

    r2 = lint_files(tmp_path / "fire", {"mod.py": """
        import time
        import jax
        from obs.registry import REGISTRY

        def dirty(x):
            REGISTRY.counter("c", "doc")           # registration: still impure
            profiler.note_program(time.time())     # impure ARG inside allowed call
            return x

        bad = jax.jit(dirty)
    """})
    gl002 = [f for f in r2.findings if f.rule == "GL002"]
    assert len(gl002) == 2, r2.render()
    assert any("registry call" in f.message for f in gl002)
    assert any("host clock" in f.message for f in gl002)

def test_gl003_read_after_donation_fires(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import jax

        def run(state, x):
            step = jax.jit(lambda s, v: s, donate_argnums=(0,))
            out = step(state, x)
            return state  # read after donation
    """})
    assert [f.rule for f in r.findings] == ["GL003"]
    assert "state" in r.findings[0].message


def test_gl003_rebinding_is_clean_and_conditional_donate_unions(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import jax

        def ok(state, x):
            step = jax.jit(lambda s, v: s, donate_argnums=(0,))
            state = step(state, x)   # the correct donate idiom: rebind
            return state

        def conditional(state, x, on_cpu):
            donate = () if on_cpu else (0,)
            step = jax.jit(lambda s, v: s, donate_argnums=donate)
            out = step(state, x)
            return state  # donated on SOME path -> finding
    """})
    assert len(r.findings) == 1
    assert r.findings[0].line > 0 and r.findings[0].rule == "GL003"


def test_gl003_suppression(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import jax

        def run(state, x):
            step = jax.jit(lambda s, v: s, donate_argnums=(0,))
            out = step(state, x)
            return state  # graftlint: disable=GL003(fixture: CPU-gated path)
    """})
    assert not r.findings and len(r.suppressed) == 1


def test_gl003_donate_argnames_taints_keyword_and_positional(tmp_path):
    """donate_argnames: a keyword arg matching a donated name is tainted, and
    when the jitted callable is an inline lambda the names also map to
    positions, so the positional call form is caught too."""
    r = lint_files(tmp_path, {"mod.py": """
        import jax

        def kw_form(state, x):
            step = jax.jit(lambda state, v: state, donate_argnames=("state",))
            out = step(state=state, v=x)
            return state  # read after donation via argname

        def pos_form(state, x):
            step = jax.jit(lambda state, v: state, donate_argnames=("state",))
            out = step(state, x)
            return state  # same donation, positional call

        def rebind_ok(state, x):
            step = jax.jit(lambda state, v: state, donate_argnames=("state",))
            state = step(state, x)
            return state
    """})
    assert [f.rule for f in r.findings] == ["GL003", "GL003"]
    assert all("state" in f.message for f in r.findings)


def test_gl003_splat_covering_donated_position_taints_sequence(tmp_path):
    """``step(x, *rest)`` with a donated position inside the splat taints
    ``rest`` itself; a splat past every donated position stays clean."""
    r = lint_files(tmp_path, {"mod.py": """
        import jax

        def bad(rest, x):
            step = jax.jit(lambda a, b, c: a, donate_argnums=(1, 2))
            out = step(x, *rest)
            return rest  # elements were donated through the splat

        def ok(rest, x):
            step = jax.jit(lambda a, b, c: a, donate_argnums=(0,))
            out = step(x, *rest)
            return rest  # donated position 0 was the explicit arg
    """})
    assert [f.rule for f in r.findings] == ["GL003"]
    assert "rest" in r.findings[0].message


# -- GL006: tracer branches ---------------------------------------------------

def test_gl006_branch_on_param_and_derived_value_fires(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import jax

        def step(x):
            y = x + 1
            if y > 0:
                return x
            while x > 2:
                x = x - 1
            return y

        jitted = jax.jit(step)
    """})
    gl006 = [f for f in r.findings if f.rule == "GL006"]
    assert len(gl006) == 2  # the if AND the while, both on traced values
    assert {"`if` branch" in f.message or "`while` loop" in f.message
            for f in gl006} == {True}


def test_gl006_scan_body_and_decorator_forms(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import jax

        @jax.jit
        def decorated(x):
            return x if x else -x

        def run(xs):
            def body(carry, x):
                if carry:
                    carry = carry + x
                return carry, x
            return jax.lax.scan(body, 0, xs)
    """})
    gl006 = [f for f in r.findings if f.rule == "GL006"]
    assert len(gl006) == 2  # the decorated IfExp AND the scan body's if


def test_gl006_static_predicates_stay_clean(tmp_path):
    """Structure tests on tracers are trace-time-static by design: identity
    vs None, isinstance, len(), and the static array attributes."""
    r = lint_files(tmp_path, {"mod.py": """
        import jax

        def step(x, cs):
            if cs is not None:
                x = x + 1
            if isinstance(x, tuple):
                return x[0]
            if x.ndim == 2:
                x = x.sum(-1)
            if len(x) > 3:
                x = x[:3]
            if x.shape[0] % 2 == 0:
                x = x * 2
            return x

        jitted = jax.jit(step)
    """})
    assert not [f for f in r.findings if f.rule == "GL006"], r.render()


def test_gl006_untraced_function_and_suppression(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import jax

        def host_helper(x):
            if x:  # never traced: plain python is fine
                return 1
            return 0

        def step(x):
            if x:  # graftlint: disable=GL006(fixture: concrete at trace time)
                return x
            return -x

        jitted = jax.jit(step)
    """})
    assert not r.findings
    assert len(r.suppressed) == 1


# -- GL004: lock discipline ---------------------------------------------------

GL004_SRC = """
    import threading

    class Manager:
        def __init__(self):
            self._lock = threading.Lock()
            self.counter = 0   # ctor writes are exempt

        def locked_write(self):
            with self._lock:
                self.counter += 1

        def racy_read(self):
            return self.counter

        def documented(self):  # graftlint: disable=GL004(caller holds _lock)
            return self.counter
"""


def test_gl004_fires_outside_lock_and_def_line_suppression_covers_body(tmp_path):
    r = lint_files(tmp_path, {"mod.py": GL004_SRC})
    assert [f.rule for f in r.findings] == ["GL004"]
    assert "Manager.counter" in r.findings[0].symbol
    assert len(r.suppressed) == 1  # documented() is covered by its def line


def test_gl004_lockless_class_is_ignored(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        class Plain:
            def __init__(self):
                self.counter = 0

            def bump(self):
                self.counter += 1
    """})
    assert not r.findings


# -- GL005: metric namespace --------------------------------------------------

def test_gl005_bad_name_label_and_le(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        from .obs import registry as obsreg

        BAD_NAME = obsreg.REGISTRY.counter("unnamespaced_total", "doc")
        BAD_LABEL = obsreg.REGISTRY.gauge("fedml_ok", "doc", labels=("Client",))
        RESERVED = obsreg.REGISTRY.histogram("fedml_h", "doc", labels=("le",))
        GOOD = obsreg.REGISTRY.counter("fedml_good_total", "doc", labels=("client",))
    """})
    syms = {f.symbol for f in r.findings if f.rule == "GL005"}
    assert syms == {"unnamespaced_total", "fedml_ok:Client", "fedml_h:le"}


def test_gl005_suppression(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        from .obs import registry as obsreg

        LEGACY = obsreg.REGISTRY.counter("legacy_total", "doc")  # graftlint: disable=GL005(fixture: grandfathered dashboard)
    """})
    assert not r.findings and len(r.suppressed) == 1


# -- GL007: lock order --------------------------------------------------------

def test_gl007_nested_with_cycle_fires(tmp_path):
    """A->B in one method, B->A in another: the classic ABBA deadlock."""
    r = lint_files(tmp_path, {"mod.py": """
        import threading

        class M:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def ab(self):
                with self._a:
                    with self._b:
                        pass

            def ba(self):
                with self._b:
                    with self._a:
                        pass
    """})
    cyc = [f for f in r.findings if f.rule == "GL007" and f.symbol.startswith("cycle:")]
    assert len(cyc) == 1 and "M._a" in cyc[0].message and "M._b" in cyc[0].message


def test_gl007_one_hop_cycle_and_self_deadlock(tmp_path):
    """The interprocedural hop: holding A, call a self-method that takes B
    (and the re-take of a non-reentrant lock through a helper)."""
    r = lint_files(tmp_path, {"mod.py": """
        import threading

        class M:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def outer(self):
                with self._a:
                    self.take_b()

            def take_b(self):
                with self._b:
                    pass

            def reverse(self):
                with self._b:
                    with self._a:
                        pass

            def recurse(self):
                with self._a:
                    self.take_a()

            def take_a(self):
                with self._a:
                    pass
    """})
    syms = {f.symbol for f in r.findings if f.rule == "GL007"}
    assert any(s.startswith("cycle:") for s in syms), r.render()
    assert any(s.startswith("selfdeadlock:M.recurse") for s in syms), r.render()


def test_gl007_rlock_reentry_and_ordered_nesting_stay_clean(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import threading

        class M:
            def __init__(self):
                self._a = threading.Lock()
                self._r = threading.RLock()

            def consistent_ab(self):
                with self._a:
                    with self._r:
                        pass

            def also_ab(self):
                with self._a:
                    self.take_r()

            def take_r(self):
                with self._r:
                    pass

            def reenter(self):
                with self._r:
                    self.take_r()  # RLock: reentry is the point
    """})
    assert not [f for f in r.findings if f.rule == "GL007"], r.render()


def test_gl007_blocking_ops_under_lock_fire_and_suppress(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import subprocess
        import time
        import threading

        class M:
            def __init__(self):
                self._lock = threading.Lock()
                self._queue = None

            def sleepy(self):
                with self._lock:
                    time.sleep(1.0)

            def drains(self):
                with self._lock:
                    item = self._queue.get()
                return item

            def spawns(self):
                with self._lock:
                    subprocess.run(["true"])

            def syncs(self, x):
                with self._lock:
                    x.block_until_ready()

            def documented(self):  # graftlint: disable=GL007(fixture: the lock serializes this send by design)
                with self._lock:
                    self._queue.sendall(b"x")

            def fine(self):
                time.sleep(1.0)  # no lock held
                with self._lock:
                    y = self._queue.get(timeout=1.0)  # bounded
                return y
    """})
    gl007 = [f for f in r.findings if f.rule == "GL007"]
    descs = {f.symbol for f in gl007}
    assert {"block:M.sleepy:time.sleep()",
            "block:M.drains:.get() (blocking queue read, no timeout)",
            "block:M.spawns:subprocess.run()",
            "block:M.syncs:.block_until_ready()"} <= descs, r.render()
    assert len(r.suppressed) == 1
    assert not any("fine" in f.symbol for f in gl007)


CROSS_OBJECT_CYCLE = """
    import threading

    class Ledger:
        def __init__(self, mgr):
            self._lock = threading.Lock()
            self.mgr = Manager()

        def note(self):
            with self._lock:
                pass

        def flush(self):{flush_suppress}
            with self._lock:
                self.mgr.poke()

    class Manager:
        def __init__(self):
            self._agg_lock = threading.Lock()
            self.ledger = Ledger(self)

        def poke(self):
            with self._agg_lock:
                pass

        def on_upload(self):{upload_suppress}
            with self._agg_lock:
                self.ledger.note()
"""


def test_gl007_cross_object_one_hop_cycle_fires(tmp_path):
    """The PR-9 follow-on: holding the manager lock, call a LEDGER method
    that takes the ledger lock — and a ledger method holding its lock calls
    back into the manager.  Two objects, opposite orders, one deadlock; the
    one-object-hop resolution must see it at lint time."""
    r = lint_files(tmp_path, {"mod.py": CROSS_OBJECT_CYCLE.format(
        flush_suppress="", upload_suppress="")})
    cyc = [f for f in r.findings if f.rule == "GL007" and f.symbol.startswith("cycle:")]
    assert len(cyc) == 1, r.render()
    assert "Manager._agg_lock" in cyc[0].message and "Ledger._lock" in cyc[0].message


def test_gl007_cross_object_cycle_suppresses(tmp_path):
    """def-line suppressions on both edge-recording methods silence the
    cycle (the anchor line always lands inside one of them)."""
    sup = "  # graftlint: disable=GL007(fixture: callback ordering is documented lock-free)"
    r = lint_files(tmp_path, {"mod.py": CROSS_OBJECT_CYCLE.format(
        flush_suppress=sup, upload_suppress=sup)})
    assert not [f for f in r.findings if f.rule == "GL007"
                and f.symbol.startswith("cycle:")], r.render()
    assert r.suppressed, "the cycle should be recorded as suppressed"


def test_gl007_cross_object_one_way_edge_is_clean(tmp_path):
    """manager lock -> ledger lock with NO reverse path (the real health-
    ledger shape, and the journal/recovery locks): an edge, not a cycle —
    must stay clean."""
    r = lint_files(tmp_path, {"mod.py": """
        import threading

        class Ledger:
            def __init__(self):
                self._lock = threading.Lock()

            def note(self):
                with self._lock:
                    pass

        class Manager:
            def __init__(self):
                self._agg_lock = threading.Lock()
                self.ledger = Ledger()

            def on_upload(self):
                with self._agg_lock:
                    self.ledger.note()
    """})
    assert not [f for f in r.findings if f.rule == "GL007"], r.render()


def test_gl007_cross_object_fluent_builder_attr_resolves(tmp_path):
    """``self.ledger = Ledger().attach()`` (the ClientHealthLedger idiom)
    still resolves the attr's class through the fluent chain — proven by the
    cycle FIRING through the fluent-assigned attr."""
    r = lint_files(tmp_path, {"mod.py": """
        import threading

        class Ledger:
            def __init__(self):
                self._lock = threading.Lock()
                self.mgr = Manager()

            def attach(self):
                return self

            def note(self):
                with self._lock:
                    pass

            def flush(self):
                with self._lock:
                    self.mgr.poke()

        class Manager:
            def __init__(self):
                self._agg_lock = threading.Lock()
                self.ledger = Ledger().attach()

            def poke(self):
                with self._agg_lock:
                    pass

            def on_upload(self):
                with self._agg_lock:
                    self.ledger.note()
    """})
    cyc = [f for f in r.findings if f.rule == "GL007" and f.symbol.startswith("cycle:")]
    assert len(cyc) == 1, r.render()


# -- GL008: thread-shared-state races ----------------------------------------

GL008_RACY = """
    import threading

    class Pump:
        def __init__(self):
            self._lock = threading.Lock()
            self.pending = []
            self.total = 0

        def start(self):
            threading.Thread(target=self._worker, daemon=True).start()

        def _worker(self):
            with self._lock:
                batch, self.pending = self.pending, []
            self.total += len(batch)   # RMW outside the lock

        def push(self, item):
            with self._lock:
                self.pending.append(item)

        def stats(self):
            return self.total
"""


def test_gl008_unlocked_rmw_across_threads_fires(tmp_path):
    r = lint_files(tmp_path, {"mod.py": GL008_RACY})
    gl008 = [f for f in r.findings if f.rule == "GL008"]
    assert [f.symbol for f in gl008] == ["Pump.total"], r.render()
    assert "thread" in gl008[0].message


def test_gl008_common_lock_everywhere_is_clean(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self.total = 0

            def start(self):
                threading.Thread(target=self._worker, daemon=True).start()

            def _worker(self):
                with self._lock:
                    self.total += 1

            def stats(self):
                with self._lock:
                    return self.total
    """})
    assert not [f for f in r.findings if f.rule == "GL008"], r.render()


def test_gl008_caller_holds_lock_inference(tmp_path):
    """A private helper whose every call site holds the lock analyzes as
    entered with it held — the PR-5 'caller holds the lock' methods do not
    re-fire under GL008."""
    r = lint_files(tmp_path, {"mod.py": """
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self.total = 0

            def start(self):
                threading.Thread(target=self._worker, daemon=True).start()

            def _worker(self):
                with self._lock:
                    self._bump()

            def add(self):
                with self._lock:
                    self._bump()

            def _bump(self):
                self.total += 1  # every caller holds _lock
    """})
    assert not [f for f in r.findings if f.rule == "GL008"], r.render()


def test_gl008_handler_roots_and_single_receive_loop(tmp_path):
    """Registered comm handlers share ONE receive-loop root (no false race
    between two handlers), but handler-vs-caller still fires."""
    r = lint_files(tmp_path, {"mod.py": """
        class Manager:
            def __init__(self):
                self.round_idx = 0
                self.seen = 0

            def register(self):
                self.register_message_receive_handler(1, self.handle_a)
                self.register_message_receive_handler(2, self.handle_b)

            def handle_a(self, msg):
                self.seen += 1       # only ever touched on the receive loop

            def handle_b(self, msg):
                self.seen += 1

            def poll(self):
                self.round_idx += 1  # caller thread
                return self.round_idx

            def handle_c(self, msg):
                self.round_idx += 1
    """})
    gl008 = [f for f in r.findings if f.rule == "GL008"]
    assert [f.symbol for f in gl008] == [], r.render()
    # now make handle_c a registered handler too: round_idx becomes shared
    r2 = lint_files(tmp_path / "v2", {"mod.py": """
        class Manager:
            def __init__(self):
                self.round_idx = 0

            def register(self):
                self.register_message_receive_handler(3, self.handle_c)

            def poll(self):
                self.round_idx += 1
                return self.round_idx

            def handle_c(self, msg):
                self.round_idx += 1
    """})
    assert [f.symbol for f in r2.findings if f.rule == "GL008"] == ["Manager.round_idx"]


def test_gl008_sync_objects_callbacks_and_suppression(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import queue
        import threading

        class Worker:
            def __init__(self):
                self._q = queue.Queue()
                self._done = threading.Event()
                self.count = 0
                self.latch = False

            def start(self):
                threading.Thread(target=self._loop, daemon=True).start()
                add_comm_event_sink(self._on_event)

            def _loop(self):
                while not self._done.is_set():
                    self._q.get(timeout=0.1)   # sync objects: no race

            def _on_event(self, event):
                self.count += 1                # sink runs on the comm thread

            def bump(self):
                self.count += 1                # caller thread: race

            def stop(self):  # graftlint: disable=GL008(fixture: one-way latch)
                self.latch = True

            def latched(self):
                return self.latch
    """})
    gl008 = [f for f in r.findings if f.rule == "GL008"]
    assert [f.symbol for f in gl008] == ["Worker.count"], r.render()
    assert not any(f.symbol in ("Worker._q", "Worker._done") for f in gl008)


def test_gl008_closure_thread_target_is_its_own_root(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import threading

        class Ticker:
            def __init__(self):
                self.ticks = 0

            def start(self):
                def loop():
                    self.ticks += 1   # runs on the spawned thread
                threading.Thread(target=loop, daemon=True).start()

            def read_modify(self):
                self.ticks += 1       # caller thread
    """})
    assert [f.symbol for f in r.findings if f.rule == "GL008"] == ["Ticker.ticks"]


def test_gl008_unthreaded_class_and_ctor_only_writes_are_clean(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import threading

        class Config:
            def __init__(self):
                self.value = 1

            def read(self):
                return self.value

            def write(self):
                self.value = 2   # no thread ever starts: not concurrency

        class Threaded:
            def __init__(self):
                self.limit = 10   # written ONLY here

            def start(self):
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                return self.limit

            def read(self):
                return self.limit
    """})
    assert not [f for f in r.findings if f.rule == "GL008"], r.render()


# -- GL009: handler conformance -----------------------------------------------

def test_gl009_unhandled_send_fires_and_registration_clears(tmp_path):
    r = lint_files(tmp_path, {
        "defs.py": "MSG_TYPE_PING = 1\nMSG_TYPE_PONG = 2\n",
        "node.py": """
            from .defs import MSG_TYPE_PING, MSG_TYPE_PONG

            class Node:
                def register(self):
                    self.register_message_receive_handler(MSG_TYPE_PING, self.on_ping)

                def on_ping(self, msg):
                    self.send_message(Message(MSG_TYPE_PONG, 0, 1))

                def start(self):
                    self.send_message(Message(MSG_TYPE_PING, 0, 1))
        """,
    })
    gl009 = [f for f in r.findings if f.rule == "GL009"]
    assert [f.symbol for f in gl009] == ["unhandled:MSG_TYPE_PONG"], r.render()


def test_gl009_dead_handler_fires_and_wildcard_send_exempts(tmp_path):
    r = lint_files(tmp_path, {
        "node.py": """
            MSG_TYPE_A = 1
            MSG_TYPE_B = 2

            class Node:
                def register(self):
                    self.register_message_receive_handler(MSG_TYPE_A, self.on_a)
                    self.register_message_receive_handler(MSG_TYPE_B, self.on_b)

                def start(self):
                    self.send_message(Message(MSG_TYPE_A, 0, 1))
        """,
        "generic.py": """
            MSG_TYPE_C = 3

            class Generic:
                def register(self):
                    self.register_message_receive_handler(MSG_TYPE_C, self.on_c)

                def send_any(self, msg_type):
                    self.send_message(Message(msg_type, 0, 1))  # wildcard
        """,
    })
    gl009 = [f for f in r.findings if f.rule == "GL009"]
    # MSG_TYPE_B is provably dead; MSG_TYPE_C's module routes dynamic types
    assert [f.symbol for f in gl009] == ["dead:MSG_TYPE_B"], r.render()


def test_gl009_value_matching_ifexp_and_suppression(tmp_path):
    r = lint_files(tmp_path, {
        "a.py": """
            MSG_TYPE_INIT = 1
            MSG_TYPE_SYNC = 2

            class Server:
                def dispatch(self, first):
                    self.send_message(Message(MSG_TYPE_INIT if first else MSG_TYPE_SYNC, 0, 1))

                def external(self):
                    self.send_message(Message(MSG_TYPE_EXTERNAL, 0, 1))  # graftlint: disable=GL009(fixture: handled by an out-of-repo peer)
        """,
        "b.py": """
            class Client:
                def register(self):
                    self.register_message_receive_handler(1, self.on_init)
                    self.register_message_receive_handler(2, self.on_sync)
        """,
    })
    gl009 = [f for f in r.findings if f.rule == "GL009"]
    assert not gl009, r.render()
    assert len(r.suppressed) == 1


# -- GL010: hot-path host sync ------------------------------------------------

def test_gl010_hot_path_syncs_fire_and_reachability_extends(tmp_path):
    r = lint_files(tmp_path, {"sim/engine.py": """
        import jax
        import jax.numpy as jnp

        class MeshSimulator:
            def run_rounds(self, n):
                metrics = self._round_fn(n)
                loss = float(metrics)
                host = jax.device_get(metrics)
                if metrics > 0:
                    loss += 1
                return host

            def evaluate(self):
                return self._finish()

            def _finish(self):
                acc = jnp.mean([1.0])
                return acc.item()
    """})
    gl010 = [f for f in r.findings if f.rule == "GL010"]
    whats = "\n".join(f.message for f in gl010)
    assert len(gl010) == 4, r.render()
    assert "implicit device->host sync float()" in whats
    assert "explicit host sync jax.device_get()" in whats
    assert "branching/comparing on a device value" in whats
    # reachability: _finish is hit only through the `evaluate` root
    assert any("'MeshSimulator._finish'" in f.message and ".item()" in f.message
               for f in gl010)


def test_gl010_suppression_and_cold_modules_stay_clean(tmp_path):
    r = lint_files(tmp_path, {
        "sim/engine.py": """
            import jax

            class MeshSimulator:
                def run_round(self, r):
                    out = self._round_fn(r)
                    if jax.tree_util.tree_structure(out) == self._treedef:
                        r += 1  # treedef comparison is host metadata: clean
                    host = jax.device_get(out)  # graftlint: disable=GL010(the one chunk-end sync)
                    return {k: float(v) for k, v in host.items()}
        """,
        # same syncs in a module that is NOT a hot-path root: out of scope
        "tools/report.py": """
            import jax
            import jax.numpy as jnp

            def summarize(xs):
                acc = jnp.mean(xs)
                return float(jax.device_get(acc))
        """,
    })
    assert not [f for f in r.findings if f.rule == "GL010"], r.render()
    assert len(r.suppressed) == 1
    # device_get UNTAINTS: the post-sync float() unpacking raised no finding


# -- GL011: recompile hazards -------------------------------------------------

def test_gl011_loop_rewrap_and_varying_scalar_fire(tmp_path):
    r = lint_files(tmp_path, {"mod.py": """
        import jax

        step = jax.jit(lambda s: s)

        def loop(xs):
            total = 0
            for i, x in enumerate(xs):
                fresh = jax.jit(lambda s: s)
                total = step(i)
            return total
    """})
    gl011 = [f for f in r.findings if f.rule == "GL011"]
    whats = "\n".join(f.message for f in gl011)
    assert len(gl011) == 2, r.render()
    assert "evaluated inside a loop body" in whats
    assert "per-call-varying Python scalar `i`" in whats


def test_gl011_disciplined_forms_are_clean_and_suppression_silences(tmp_path):
    r = lint_files(tmp_path, {
        "ok.py": """
            import jax
            import jax.numpy as jnp

            stepped = jax.jit(lambda s: s, static_argnums=(0,))

            def ok(xs):
                prog = jax.jit(lambda s: s)
                for i in range(3):
                    stepped(i)
                    prog(jnp.int32(i))
                return prog
        """,
        "memoized.py": """
            import jax

            def cohort(sizes):
                for n in sizes:
                    fn = jax.jit(lambda s: s)  # graftlint: disable=GL011(memoized one line below in real code)
                    fn(None)
        """,
    })
    assert not [f for f in r.findings if f.rule == "GL011"], r.render()
    assert len(r.suppressed) == 1


# -- GL012: atomic durability -------------------------------------------------

def test_gl012_direct_write_and_unfsynced_replace_fire(tmp_path):
    r = lint_files(tmp_path, {"store.py": """
        import os
        import tempfile

        def save(payload, out_dir):
            path = os.path.join(out_dir, "state.json")
            with open(path, "w") as f:
                f.write(payload)

        def commit(payload, out_dir):
            fd, tmp = tempfile.mkstemp(dir=out_dir)
            with os.fdopen(fd, "w") as f:
                f.write(payload)
            os.replace(tmp, os.path.join(out_dir, "state.json"))

        class Journal:
            def __init__(self, journal_dir):
                self.base = journal_dir

            def append(self, rec):
                with open(os.path.join(self.base, "log"), "a") as f:
                    f.write(rec)
    """})
    gl012 = [f for f in r.findings if f.rule == "GL012"]
    whats = "\n".join(f.message for f in gl012)
    assert len(gl012) == 3, r.render()
    assert "direct write under a durability directory" in whats
    assert "os.replace in 'commit' with no preceding os.fsync" in whats
    # ctor-assigned self.<attr> dir taint reaches the method's write
    assert any("'Journal.append'" in f.message for f in gl012)


def test_gl012_envelope_is_clean_and_append_log_suppresses(tmp_path):
    r = lint_files(tmp_path, {"store.py": """
        import os
        import tempfile

        def commit(payload, out_dir):
            fd, tmp = tempfile.mkstemp(dir=out_dir)
            with os.fdopen(fd, "w") as f:
                f.write(payload)
                f.flush()
                os.fsync(fd)
            os.replace(tmp, os.path.join(out_dir, "state.json"))

        def append_log(rec, log_dir):
            path = os.path.join(log_dir, "events.ndjson")
            with open(path, "a") as f:  # graftlint: disable=GL012(append-only; recovery drops a torn tail)
                f.write(rec)
    """})
    assert not [f for f in r.findings if f.rule == "GL012"], r.render()
    assert len(r.suppressed) == 1


# -- suppressions / baseline machinery ---------------------------------------

def test_parse_suppressions_multiple_ids_and_reasons():
    sup = parse_suppressions(
        "x = 1  # graftlint: disable=GL001(why),GL004\n"
        "y = 2\n"
        "z = 3  # graftlint: disable=GL005\n"
    )
    assert sup == {1: {"GL001", "GL004"}, 3: {"GL005"}}


def test_baseline_round_trip(tmp_path):
    files = {
        "core/flags.py": FLAGS_FIXTURE,
        "mod.py": "def f(cfg):\n    extra = getattr(cfg, 'extra', {}) or {}\n    return extra.get(\"rogue\")\n",
    }
    r = lint_files(tmp_path, files)
    assert r.findings
    baseline = tmp_path / "baseline.json"
    save_baseline(baseline, r.findings)
    assert load_baseline(baseline) == {f.key for f in r.findings}
    r2 = run_lint(tmp_path, baseline=baseline)
    assert r2.ok and len(r2.baselined) == len(r.findings)


def test_baseline_keys_are_line_independent():
    a = Finding("GL001", "m.py", 10, "msg", symbol="undeclared:x")
    b = Finding("GL001", "m.py", 99, "msg", symbol="undeclared:x")
    assert a.key == b.key


def test_unparseable_file_is_reported_not_crashed(tmp_path):
    r = lint_files(tmp_path, {"broken.py": "def f(:\n"})
    assert not r.ok and r.errors and "broken.py" in r.errors[0]


# -- the real package ---------------------------------------------------------

def test_fedml_tpu_package_lints_clean_with_shipped_baseline():
    """The tier-1 gate: every rule active over the real package, zero
    unsuppressed findings, and the SHIPPED baseline stays empty."""
    baseline_path = PKG_ROOT / "analysis" / "baseline.json"
    assert load_baseline(baseline_path) == set(), (
        "the shipped baseline must stay EMPTY — fix or inline-suppress new "
        "findings instead of baselining them")
    result = run_lint(PKG_ROOT, baseline=baseline_path)
    assert result.ok, "\n" + result.render()


def test_cli_lint_json_over_package():
    from fedml_tpu.cli import main

    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(["lint", "--format", "json"])
    doc = json.loads(buf.getvalue())
    assert rc == 0 and doc["ok"] and doc["findings"] == []


def _cli(args):
    """Run the lint CLI in-process, capturing (rc, stdout)."""
    import contextlib
    import io

    from fedml_tpu.cli import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(args)
    return rc, buf.getvalue()


def test_cli_lint_json_shape_on_findings(tmp_path):
    """The documented --format json contract on a dirty tree: every finding
    carries rule/path/line/severity/message/key, counts_by_rule aggregates,
    and suppressed findings are counted but not listed."""
    (tmp_path / "core").mkdir()
    (tmp_path / "core" / "flags.py").write_text(textwrap.dedent(FLAGS_FIXTURE))
    (tmp_path / "mod.py").write_text(textwrap.dedent("""
        import threading
        import time

        class M:
            def __init__(self):
                self._lock = threading.Lock()

            def slow(self):
                with self._lock:
                    time.sleep(1.0)

            def documented(self):  # graftlint: disable=GL007(fixture reason)
                with self._lock:
                    time.sleep(1.0)
    """))
    rc, out = _cli(["lint", str(tmp_path), "--format", "json"])
    doc = json.loads(out)
    assert rc == 1 and doc["ok"] is False
    assert doc["parse_errors"] == []
    assert doc["suppressed"] == 1 and doc["baselined"] == 0
    assert doc["counts_by_rule"].get("GL007") == 1
    # dead_flag + declared_flag declarations are dead in this fixture too
    assert doc["counts_by_rule"].get("GL001") == 2
    for f in doc["findings"]:
        assert set(f) == {"rule", "path", "line", "severity", "message", "key"}
        assert f["severity"] in ("error", "warning") and f["line"] > 0
    keys = {f["key"] for f in doc["findings"]}
    assert any(k.startswith("GL007:mod.py:block:M.slow") for k in keys), keys


def test_cli_baseline_write_and_read_round_trip(tmp_path):
    """--write-baseline grandfathers the current findings; a second CLI run
    against that baseline exits 0 with everything baselined; fixing the code
    then leaves a stale baseline that changes nothing."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "def f(cfg):\n"
        "    extra = getattr(cfg, 'extra', {}) or {}\n"
        "    return extra.get('rogue_flag')\n")
    baseline = tmp_path / "baseline.json"
    rc, out = _cli(["lint", str(pkg), "--baseline", str(baseline), "--write-baseline"])
    assert rc == 0 and "baselined" in out
    doc = json.loads(baseline.read_text())
    assert doc["version"] == 1 and doc["findings"]
    assert all({"key", "rule", "path", "line", "message"} <= set(e)
               for e in doc["findings"])
    # second run: same findings, now grandfathered -> exit 0
    rc2, out2 = _cli(["lint", str(pkg), "--baseline", str(baseline),
                      "--format", "json"])
    doc2 = json.loads(out2)
    assert rc2 == 0 and doc2["ok"] and doc2["findings"] == []
    assert doc2["baselined"] == len(doc["findings"])
    # the fixed tree stays clean against the now-stale baseline
    (pkg / "mod.py").write_text("def f(cfg):\n    return None\n")
    rc3, out3 = _cli(["lint", str(pkg), "--baseline", str(baseline),
                      "--format", "json"])
    doc3 = json.loads(out3)
    assert rc3 == 0 and doc3["ok"] and doc3["baselined"] == 0


# -- the flag registry + accessor --------------------------------------------

def test_cfg_extra_resolution_order_and_undeclared_rejection():
    from fedml_tpu.arguments import Config
    from fedml_tpu.core.flags import FLAGS, cfg_extra

    cfg = Config(extra={"gan_z_dim": 32})
    assert cfg_extra(cfg, "gan_z_dim") == 32           # extra dict
    assert cfg_extra(cfg, "seg_base") == 8             # registry default
    assert cfg_extra(cfg, "seg_base", 99) == 99        # explicit default wins
    assert cfg_extra(None, "seg_base") == 8            # cfg=None short-circuit
    cfg.silo_dp = False
    assert cfg_extra(cfg, "silo_dp") is False          # direct attr wins (default True)
    with pytest.raises(KeyError):
        cfg_extra(cfg, "not_a_flag")
    assert all(s.name == n for n, s in FLAGS.items())


def test_flag_reference_renders_every_flag():
    from fedml_tpu.core.flags import FLAGS, render_flag_reference

    doc = render_flag_reference()
    for name in FLAGS:
        assert f"`{name}`" in doc
