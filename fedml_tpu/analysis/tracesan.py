"""Runtime trace sanitizer — the dynamic half of the GL010/GL011 contract.

The static rules flag *syntactic* host-sync and recompile hazards; they
cannot prove the steady-state round loop is actually clean, nor catch a
transfer smuggled through a code path the reachability walk missed.  This
module turns jax's own instrumentation into a gate:

- :func:`round_guard` scopes ``jax.transfer_guard("disallow")`` around a
  steady-state round (rounds past the warmup count, default 1), so any
  IMPLICIT device<->host transfer inside the round body raises instead of
  silently serializing the pipeline.  Explicit syncs (``jax.device_get``)
  stay legal — the contract is "every host boundary is deliberate", not
  "no host boundaries".
- :func:`allow` re-opens the guard for an annotated legitimate boundary
  (wire encode, checkpoint save, streamed fold ingest, round-boundary
  metric export) and counts each crossing per site, so the report shows
  exactly where the round loop touches the host and how often.
- every XLA program build (a backend compile or a load from the persistent
  cache; the process's one ``jax.monitoring`` listener in ``obs/trace.py``
  reports them here) is counted and attributed to the first ``fedml_tpu``
  frame on the calling stack; builds witnessed INSIDE a steady-state guard
  are recompile hazards (the GL011 failure mode, observed rather than
  inferred).

Gating is absolute: unless ``FEDML_TPU_TRACESAN=1`` is set,
:func:`maybe_install_from_env` does nothing, :func:`round_guard` /
:func:`allow` return null context managers, and jax is never imported
from here — zero overhead, zero behavior change (the tier-1 suite pins
the default path bitwise).  When enabled, a JSON report dumps at
interpreter exit to ``FEDML_TPU_TRACESAN_REPORT`` or a summary to
stderr, and the tracesan gate in ``tests/test_tracesan.py`` fails if a
steady-state round ever witnesses a disallowed transfer or a compile.

Counter families (registered at import, like every obs module):
``fedml_tracesan_guarded_rounds_total``,
``fedml_tracesan_allowed_transfers_total{site}``,
``fedml_tracesan_compiles_total{phase}``,
``fedml_tracesan_violations_total{kind}``.
"""

from __future__ import annotations

import atexit
import contextlib
import json
import os
import sys
import threading
import traceback

from ..obs.registry import REGISTRY

ENV_FLAG = "FEDML_TPU_TRACESAN"
ENV_REPORT = "FEDML_TPU_TRACESAN_REPORT"
ENV_WARMUP = "FEDML_TPU_TRACESAN_WARMUP"

#: bound on stored per-event records so a pathological run cannot grow the
#: report without bound (mirrors sanitizer._MAX_LONG_HOLDS)
_MAX_EVENTS = 200

GUARDED_ROUNDS = REGISTRY.counter(
    "fedml_tracesan_guarded_rounds_total",
    "steady-state rounds executed under jax.transfer_guard('disallow')")
ALLOWED_TRANSFERS = REGISTRY.counter(
    "fedml_tracesan_allowed_transfers_total",
    "annotated host-boundary crossings while the sanitizer is active",
    labels=("site",))
COMPILES = REGISTRY.counter(
    "fedml_tracesan_compiles_total",
    "XLA backend compiles witnessed, by round phase",
    labels=("phase",))
VIOLATIONS = REGISTRY.counter(
    "fedml_tracesan_violations_total",
    "trace-hygiene violations: disallowed transfers / steady-state compiles",
    labels=("kind",))

_ACTIVE: "TraceSanitizer | None" = None


def _attribute_site(limit: int = 8) -> tuple[str, list[str]]:
    """('pkg/module.py:123:fn', short stack) of the innermost ``fedml_tpu``
    frame below this module — where package code triggered the event."""
    frames = traceback.extract_stack()[:-2]
    site = "<outside-package>"
    for frame in reversed(frames):
        path = frame.filename.replace("\\", "/")
        if ("fedml_tpu/" in path and "analysis/tracesan" not in path
                and "obs/trace.py" not in path):  # the listener's own frames
            parts = path.split("/")
            site = f"{'/'.join(parts[-2:])}:{frame.lineno}:{frame.name}"
            break
    out = []
    for frame in frames[-limit:]:
        parts = frame.filename.replace("\\", "/").split("/")
        out.append(f"{'/'.join(parts[-2:])}:{frame.lineno}:{frame.name}")
    return site, out


class TraceSanitizer:
    """Shared state behind the process's transfer/compile guard."""

    def __init__(self, warmup_rounds: int = 1):
        self.warmup_rounds = int(warmup_rounds)
        self._mu = threading.Lock()
        #: guard phase is per-thread: the compile listener fires on the
        #: thread running the dispatch, so attribution follows the caller
        self._tls = threading.local()
        self.guarded_rounds = 0
        self.allowed_sites: dict[str, int] = {}
        self.compiles: dict[str, int] = {}      # phase -> count
        self.compile_events: list[dict] = []
        self.violations: list[dict] = []

    # -- per-thread phase ------------------------------------------------------
    def _phase(self) -> str:
        if getattr(self._tls, "allowed", 0):
            # inside an annotated host boundary: exempt from the steady-
            # compile hazard the same way it is from the transfer guard
            return "allowed"
        if getattr(self._tls, "steady", 0):
            return "steady"
        if getattr(self._tls, "warmup", 0):
            return "warmup"
        return "unguarded"

    def _round(self) -> "int | None":
        return getattr(self._tls, "round_idx", None)

    # -- context managers ------------------------------------------------------
    @contextlib.contextmanager
    def round_guard(self, round_idx: int, rounds: int = 1):
        import jax

        steady = round_idx >= self.warmup_rounds
        attr = "steady" if steady else "warmup"
        prev_round = getattr(self._tls, "round_idx", None)
        setattr(self._tls, attr, getattr(self._tls, attr, 0) + 1)
        self._tls.round_idx = round_idx
        if steady:
            with self._mu:
                self.guarded_rounds += rounds
            GUARDED_ROUNDS.inc(rounds)
        try:
            if steady:
                with jax.transfer_guard("disallow"):
                    yield
            else:
                yield
        except jax.errors.JaxRuntimeError as e:
            # the transfer guard raises from inside the traced/dispatched
            # computation; record the witness before the gate re-raises
            if "transfer" in str(e).lower():
                site, stack = _attribute_site()
                VIOLATIONS.inc(kind="disallowed_transfer")
                with self._mu:
                    if len(self.violations) < _MAX_EVENTS:
                        self.violations.append({
                            "kind": "disallowed_transfer", "round": round_idx,
                            "site": site, "error": str(e).split("\n")[0],
                            "stack": stack,
                        })
            raise
        finally:
            setattr(self._tls, attr, getattr(self._tls, attr, 1) - 1)
            self._tls.round_idx = prev_round

    @contextlib.contextmanager
    def allow(self, site: str):
        import jax

        with self._mu:
            self.allowed_sites[site] = self.allowed_sites.get(site, 0) + 1
        ALLOWED_TRANSFERS.inc(site=site)
        self._tls.allowed = getattr(self._tls, "allowed", 0) + 1
        try:
            with jax.transfer_guard("allow"):
                yield
        finally:
            self._tls.allowed -= 1

    # -- compile listener ------------------------------------------------------
    def on_compile(self, duration_s: float) -> None:
        phase = self._phase()
        site, stack = _attribute_site()
        COMPILES.inc(phase=phase)
        record = {"phase": phase, "round": self._round(), "site": site,
                  "duration_s": round(float(duration_s), 4), "stack": stack}
        with self._mu:
            self.compiles[phase] = self.compiles.get(phase, 0) + 1
            if len(self.compile_events) < _MAX_EVENTS:
                self.compile_events.append(record)
            if phase == "steady" and len(self.violations) < _MAX_EVENTS:
                self.violations.append(dict(record, kind="steady_compile"))
        if phase == "steady":
            VIOLATIONS.inc(kind="steady_compile")

    # -- reporting -------------------------------------------------------------
    def report(self) -> dict:
        with self._mu:
            return {
                "warmup_rounds": self.warmup_rounds,
                "guarded_rounds": self.guarded_rounds,
                "allowed_sites": dict(sorted(self.allowed_sites.items())),
                "compiles": dict(sorted(self.compiles.items())),
                "compile_events": list(self.compile_events),
                "violations": list(self.violations),
            }


def _on_program_build(duration_s: float) -> None:
    san = _ACTIVE
    if san is not None:
        san.on_compile(duration_s)


def install(warmup_rounds: int | None = None) -> TraceSanitizer:
    """Activate the sanitizer (imports jax; subscribes to the process's one
    XLA listener, which ``obs/trace.py`` owns).  Idempotent."""
    global _ACTIVE
    if _ACTIVE is not None:
        return _ACTIVE
    if warmup_rounds is None:
        warmup_rounds = int(os.environ.get(ENV_WARMUP, "1"))
    from ..obs import trace as obstrace

    obstrace.install_xla_listener(on_build=_on_program_build)
    _ACTIVE = TraceSanitizer(warmup_rounds=warmup_rounds)
    atexit.register(_dump_on_exit)
    return _ACTIVE


def uninstall() -> None:
    """Deactivate (the subscription to the XLA listener stays — jax has no
    unregister API — but dispatches to nothing)."""
    global _ACTIVE
    _ACTIVE = None


def active() -> "TraceSanitizer | None":
    return _ACTIVE


def maybe_install_from_env() -> "TraceSanitizer | None":
    """The one public entry point for harness code: a strict no-op unless
    ``FEDML_TPU_TRACESAN=1``."""
    if os.environ.get(ENV_FLAG) == "1":
        return install()
    return None


def round_guard(round_idx: int, rounds: int = 1):
    """Guard one round of the hot loop.  Null context when inactive; a
    warmup round (``round_idx < warmup_rounds``) tracks phase only; a
    steady round runs under ``jax.transfer_guard("disallow")``."""
    san = _ACTIVE
    if san is None:
        return contextlib.nullcontext()
    return san.round_guard(round_idx, rounds)


def allow(site: str):
    """Annotate a legitimate host boundary.  Null context when inactive;
    active, it re-opens the transfer guard and counts the crossing."""
    san = _ACTIVE
    if san is None:
        return contextlib.nullcontext()
    return san.allow(site)


def _dump_on_exit() -> None:
    san = _ACTIVE
    if san is None:
        return
    rep = san.report()
    path = os.environ.get(ENV_REPORT)
    if path:
        try:
            with open(path, "w") as f:
                json.dump(rep, f, indent=1)
                f.write("\n")
        except OSError:
            path = None
    if not path:
        summary = {k: rep[k] for k in ("guarded_rounds", "allowed_sites", "compiles")}
        summary["violations"] = len(rep["violations"])
        print(f"FEDML_TPU_TRACESAN report: {json.dumps(summary)}", file=sys.stderr)
        for v in rep["violations"]:
            print(f"TRACESAN VIOLATION: {v['kind']} at {v['site']} "
                  f"(round {v['round']})", file=sys.stderr)
