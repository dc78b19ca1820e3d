"""Ahead-of-time program store — kill recurring compilation (ISSUE 7).

The instrumented multichip dryrun attributed the historical rc=124 driver
timeout to ~30 minutes of *recurring* XLA work per run (hierarchical_round
1236 s + ring_gossip 587 s): every process restart re-traced and re-lowered
the same round programs from Python even though nothing about the run had
changed.  Production FL servers restart constantly (deploys, preemptions,
cohort reshapes), so cold-start-to-first-round is a first-class cost — the
communication-perspective survey (2405.20431) and the cross-silo backend
study (2604.10859) both call out server startup/dispatch latency at fleet
scale.

This module is the fix: a persistent **program store** of
``jax.export``-serialized programs, keyed by a stable fingerprint of
everything that affects tracing —

    (site, topology/config, mesh shape + axis names, the argument pytree's
     structure/shapes/dtypes [which subsumes the model variable tree],
     hparams, chunk size / donation gating / codec flags,
     jax + jaxlib version, backend + device kind + device count)

A warm process **deserializes the lowered StableHLO instead of re-tracing**,
and the one remaining XLA compile of the deserialized module goes through the
ordinary ``jax.jit`` dispatch path — which consults the shared persistent
compilation cache (``core/cache.py``), so across processes the executable
itself is also reused.  Measured on CPU: deserialize ~5 ms + cached compile
~0.05 s vs multi-second (sim) to multi-minute (hierarchical) re-trace +
re-compile.

Design constraints honored here:

- **Never a crash.**  Corrupt, truncated, or version-mismatched entries are
  discarded and rebuilt; an unexportable program (unsupported primitive,
  foreign custom call) falls back to the plain jitted function.  The store
  can only ever cost a rebuild, not a run.
- **Cross-process safe.**  Entries are written to a temp file and
  ``os.replace``d into place (readers see an old or a complete new entry,
  never a torn one); builders serialize on an advisory ``flock`` per entry so
  N restarting processes produce ONE export, and the waiters load it.
- **Default path bit-identical.**  Everything is gated on the registered
  ``extra.aot_programs`` flag; unset means :func:`store_from_config` returns
  ``None`` and every call site runs the exact pre-existing ``jax.jit`` code.
- **Observable.**  ``fedml_aot_{hits,misses,exports}_total`` counters and
  ``fedml_aot_{load,build}_seconds`` histograms land in the global registry,
  and each load/build emits an obs-trail record through the caller's sink.

Entries live under whichever directory ``core/cache.py`` resolved for the XLA
persistent cache: ``<cache_dir>/aot_programs``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import logging
import os
import re
import tempfile
import time
from typing import Any, Callable, Iterable, Optional

from ..obs import registry as obsreg
from . import cache as cachelib
from .flags import cfg_extra

log = logging.getLogger("fedml_tpu")

__all__ = [
    "ProgramStore", "StoredProgram", "store_from_config", "default_store_dir",
    "program_key", "tree_signature", "mesh_signature", "config_signature",
    "export_program",
]

#: on-disk entry format: MAGIC + one json meta line + the serialized Exported.
#: Bump the magic when the envelope changes — old entries are then discarded
#: as corrupt and rebuilt, never misread.
_MAGIC = b"FMLAOT1\n"

AOT_HITS = obsreg.REGISTRY.counter(
    "fedml_aot_hits_total",
    "AOT program-store lookups served from a persisted entry (no re-trace).",
)
AOT_MISSES = obsreg.REGISTRY.counter(
    "fedml_aot_misses_total",
    "AOT program-store lookups that had to build (trace + export) the program.",
)
AOT_EXPORTS = obsreg.REGISTRY.counter(
    "fedml_aot_exports_total",
    "Programs export-serialized and written to the store.",
)
AOT_LOAD_TIME = obsreg.REGISTRY.histogram(
    "fedml_aot_load_seconds",
    "Wall time to read + deserialize a stored program.",
)
AOT_BUILD_TIME = obsreg.REGISTRY.histogram(
    "fedml_aot_build_seconds",
    "Wall time to build (trace + lower + export) a program on a store miss.",
)
PROGRAM_FLOPS = obsreg.REGISTRY.gauge(
    "fedml_program_flops",
    "XLA cost-model FLOPs of one compiled program (extra.cost_model_gauges).",
    labels=("program",),
)
PROGRAM_BYTES = obsreg.REGISTRY.gauge(
    "fedml_program_bytes_accessed",
    "XLA cost-model bytes accessed (HBM traffic) of one compiled program "
    "(extra.cost_model_gauges).",
    labels=("program",),
)

#: memory-address hex in default reprs would break cross-process fingerprint
#: stability; scrub it before hashing
_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


def _canon(v: Any) -> Any:
    """Canonical JSON-able form of a key component — deterministic across
    processes (sorted dicts, lists for tuples, reprs scrubbed of addresses)."""
    if isinstance(v, dict):
        return {str(k): _canon(v[k]) for k in sorted(v, key=str)}
    if isinstance(v, (list, tuple, set, frozenset)):
        items = sorted(v, key=str) if isinstance(v, (set, frozenset)) else v
        return [_canon(x) for x in items]
    if isinstance(v, (str, bool)) or v is None:
        return v
    if isinstance(v, (int, float)):
        return v
    if isinstance(v, bytes):
        return v.hex()
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return _canon(dataclasses.asdict(v))
    return f"{type(v).__module__}.{type(v).__name__}:{_ADDR_RE.sub('0x', repr(v))}"


def tree_signature(tree: Any) -> list:
    """``[(keypath, shape, dtype), ...]`` for every leaf — the structure +
    shapes + dtypes component of a program fingerprint (covers the model
    variable tree, client-state stacks, data stacks, rng keys...)."""
    if tree is None:
        return []
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in leaves:
        shape = list(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        out.append([jax.tree_util.keystr(path), shape, dtype])
    return out


def mesh_signature(mesh: Any) -> Optional[dict]:
    """Axis names + sizes (+ device platform) of a ``jax.sharding.Mesh``."""
    if mesh is None:
        return None
    try:
        devs = mesh.devices.ravel()
        return {
            "axes": list(mesh.axis_names),
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names],
            "platform": str(getattr(devs[0], "platform", "")),
        }
    except Exception:
        return {"repr": _ADDR_RE.sub("0x", repr(mesh))}


#: per-run values that do NOT affect tracing (paths, ports, endpoints, ids) —
#: excluded so a redeploy with a new run_id still hits the store.  Everything
#: else in the config rides into the fingerprint: over-inclusion can only
#: cost a rebuild, under-inclusion could serve the wrong program.
_VOLATILE_CFG_KEYS = {
    "run_id", "metrics_jsonl_path", "obs_jsonl_path", "otlp_endpoint",
    "metrics_port", "aot_programs", "aot_programs_dir", "population_store",
    "checkpoint_dir", "server_journal_dir", "client_journal_dir",
    "model_publish_dir", "global_model_file_path", "grpc_base_port",
    "tcp_base_port", "grpc_ip_config", "tcp_ip_config", "mqtt_host",
    "mqtt_port", "object_store_url", "coordinator_address", "process_id",
    "num_processes",
    # multi-tenant identity/scheduling knobs (ISSUE 14): two tenants whose
    # recipes differ only in job id / fair-share policy trace the SAME
    # programs — stripping these is what makes the shared store a cross-job
    # warm start instead of N cold ones
    "mt_job_id", "mt_weight", "mt_priority", "mt_slots", "mt_shared_aot_dir",
    # observability-only knobs (ISSUE 16): recorders, SLO watchdogs, and
    # export encodings never change what gets traced — two runs that differ
    # only in telemetry must share the same stored programs
    "otlp_protocol", "flight_recorder", "flight_dir", "flight_capacity",
    "flight_window_s", "slo_specs", "slo_interval_s", "slo_flight_dump",
    "cost_model_gauges",
}


def config_signature(cfg: Any) -> Optional[dict]:
    """The run config minus volatile per-run values, canonicalized.  Broad on
    purpose: hparams, topology knobs, codec / trust flags all
    change the traced program and must key it."""
    if cfg is None:
        return None
    d = dict(getattr(cfg, "__dict__", {}))
    extra = dict(d.get("extra") or {})
    for k in _VOLATILE_CFG_KEYS:
        d.pop(k, None)
        extra.pop(k, None)
    d["extra"] = extra
    return _canon(d)


def program_key(site: str, *, mesh: Any = None, trees: Optional[dict] = None,
                hparams: Any = None, config: Any = None,
                extra: Optional[dict] = None) -> str:
    """Stable fingerprint for one traced program at one call site.

    ``trees`` maps names to pytrees whose structure/shapes/dtypes key the
    program (pass the example argument tuple — it subsumes the model variable
    tree).  ``config`` takes the output of :func:`config_signature`.  The jax
    + jaxlib versions, backend, device kind, and device count are always
    included — a store written by one toolchain must never serve another.
    """
    import jax
    import jaxlib

    dev = jax.devices()[0]
    components = {
        "site": site,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": str(getattr(dev, "device_kind", dev.platform)),
        "n_devices": jax.device_count(),
        "mesh": mesh_signature(mesh),
        "trees": {name: tree_signature(t) for name, t in sorted((trees or {}).items())},
        "hparams": _canon(hparams),
        "config": _canon(config) if not isinstance(config, (dict, type(None))) else config,
        "extra": _canon(extra),
    }
    blob = json.dumps(components, sort_keys=True, separators=(",", ":"))
    digest = hashlib.sha256(blob.encode()).hexdigest()
    return f"{site}.{digest[:32]}"


def export_program(jitted: Callable, example_args: tuple):
    """Trace + lower ``jitted`` at ``example_args`` into a serializable
    ``jax.export.Exported``.  Retries with the TPU custom-call safety check
    waived (Pallas kernels lower to ``tpu_custom_call``, which jax.export
    refuses by default because its ABI is toolchain-pinned — exactly what the
    version-fingerprinted store already guarantees)."""
    from jax import export

    try:
        return export.export(jitted)(*example_args)
    except Exception:
        return export.export(
            jitted,
            disabled_checks=[export.DisabledSafetyCheck.custom_call("tpu_custom_call")],
        )(*example_args)


def record_program_cost(compiled, key: str) -> Optional[dict]:
    """Publish the XLA cost model's flops / bytes-accessed for one compiled
    program as ``fedml_program_flops`` / ``fedml_program_bytes_accessed``
    gauges labeled ``program=key`` (ISSUE 16 satellite: the SLO engine can
    then watch MFU-style ratios, and a perf regression shows up as a cost
    delta next to the wall-clock delta instead of a mystery).

    Returns ``{"flops", "bytes_accessed"}`` or ``None`` when the runtime
    exposes no cost analysis (interpreters, some CPU paths) — callers treat
    the gauges as best-effort."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):  # older jax: one dict per device
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0))
        bytes_accessed = float(ca.get("bytes accessed",
                                      ca.get("bytes_accessed", 0.0)))
    except Exception:
        return None
    PROGRAM_FLOPS.set(flops, program=key)
    PROGRAM_BYTES.set(bytes_accessed, program=key)
    return {"flops": flops, "bytes_accessed": bytes_accessed}


def default_store_dir() -> str:
    """``<cache_dir>/aot_programs`` — inside the XLA persistent compilation
    cache's directory (``core/cache.py``), so the two halves of a warm start
    (skip the re-trace, skip the re-compile) travel together."""
    return os.path.join(cachelib.cache_dir(), "aot_programs")


class StoredProgram:
    """One resolved store entry: the deserialized/just-built ``Exported`` plus
    where it came from.  ``call`` is the traceable entry point — wrap it in
    ``jax.jit`` (optionally with ``donate_argnums``) exactly like the original
    function; the wrapper's compile rides the persistent compilation cache."""

    __slots__ = ("exported", "key", "from_cache", "path")

    def __init__(self, exported, key: str, from_cache: bool, path: str):
        self.exported = exported
        self.key = key
        self.from_cache = from_cache
        self.path = path

    @property
    def call(self) -> Callable:
        return self.exported.call

    def bind(self, example_args: Optional[tuple] = None,
             donate_argnums: tuple = ()) -> Callable:
        """A jitted callable for this program; with ``example_args`` it is
        AOT-compiled now (compile time attributable to load, not round 1)."""
        import jax

        wrapper = jax.jit(self.exported.call, donate_argnums=tuple(donate_argnums))
        if example_args is not None:
            try:
                return wrapper.lower(*example_args).compile()
            except Exception as e:
                # the lazy wrapper re-raises on first call if the cause is
                # real; say so now rather than at a later, unrelated line
                log.warning("aot: eager compile of stored program %s failed "
                            "(%s: %s) — binding lazily", self.key,
                            type(e).__name__, e)
        return wrapper


class ProgramStore:
    """Persistent, cross-process store of exported programs.

    ``get_or_build(key, build_fn)`` is the whole contract: return the stored
    program for ``key`` if a valid entry exists, else call ``build_fn()``
    (which must return a ``jax.export.Exported``), persist it atomically, and
    return it.  Returns ``None`` only when ``build_fn`` itself fails — the
    caller then falls back to its plain jitted path.
    """

    def __init__(self, root: str, trail: Optional[Callable[[dict], None]] = None,
                 cost_gauges: bool = False):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self.trail = trail  # obs-trail sink: one record per load/build
        # extra.cost_model_gauges: publish XLA cost-model flops/bytes per
        # program at bind time (forces the AOT compile at load, so the cost
        # is attributable there — same trade as cached_jit's eager flag)
        self.cost_gauges = bool(cost_gauges)

    # -- paths ---------------------------------------------------------------
    def _path(self, key: str) -> str:
        safe = re.sub(r"[^A-Za-z0-9._-]", "_", key)
        return os.path.join(self.root, safe + ".jaxprog")

    def entries(self) -> list[str]:
        try:
            return sorted(f for f in os.listdir(self.root) if f.endswith(".jaxprog"))
        except OSError:
            return []

    # -- the contract --------------------------------------------------------
    def get_or_build(self, key: str, build_fn: Callable[[], Any]) -> Optional[StoredProgram]:
        prog = self._load(key)
        if prog is not None:
            return prog
        with self._entry_lock(key):
            # double-check under the lock: a concurrent process may have
            # finished the build while this one waited on the flock
            prog = self._load(key)
            if prog is not None:
                return prog
            AOT_MISSES.inc()
            t0 = time.perf_counter()
            try:
                exported = build_fn()
            except Exception as e:  # never a crash: fall back to plain jit
                log.warning("aot: build for %s failed (%s: %s) — falling back "
                            "to the un-stored jit path", key, type(e).__name__, e)
                return None
            build_s = time.perf_counter() - t0
            AOT_BUILD_TIME.observe(build_s)
            path = self._write(key, exported)
            self._record("build", key, build_s, hit=False)
            return StoredProgram(exported, key, from_cache=False, path=path)

    def warm(self, items: Iterable[tuple[str, Callable[[], Any]]]) -> dict:
        """Pre-resolve every (key, build_fn) a run will need before round 0.
        Returns ``{"loaded": n, "built": n, "failed": n}`` — a server calls
        this at startup so round 0 never pays a trace."""
        out = {"loaded": 0, "built": 0, "failed": 0}
        for key, build_fn in items:
            prog = self.get_or_build(key, build_fn)
            if prog is None:
                out["failed"] += 1
            elif prog.from_cache:
                out["loaded"] += 1
            else:
                out["built"] += 1
        return out

    def cached_jit(self, fn: Callable, example_args: tuple, *, key: str,
                   donate_argnums: tuple = (), eager: bool = False) -> Callable:
        """jit-through-the-store: the drop-in replacement for
        ``jax.jit(fn)`` at a traced-per-run call site.  Store hit → the
        deserialized program (re-trace skipped); miss → trace once, export,
        persist; any failure → plain ``jax.jit(fn)``.  Donation is applied to
        the wrapper, never baked into the stored artifact (the artifact stays
        valid for both the donating and non-donating caller)."""
        import jax

        prog = self.get_or_build(
            key, lambda: export_program(jax.jit(fn), example_args))
        if prog is None:
            return jax.jit(fn, donate_argnums=tuple(donate_argnums))
        bound = prog.bind(
            example_args if (eager or self.cost_gauges) else None,
            donate_argnums)
        if self.cost_gauges:
            record_program_cost(bound, key)
        return bound

    # -- on-disk format ------------------------------------------------------
    def _load(self, key: str) -> Optional[StoredProgram]:
        path = self._path(key)
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        try:
            exported = self._decode(blob)
        except Exception as e:
            # corrupt / truncated / version-mismatched: discard, rebuild
            log.warning("aot: discarding unusable entry %s (%s: %s)",
                        path, type(e).__name__, e)
            with contextlib.suppress(OSError):
                os.remove(path)
            return None
        load_s = time.perf_counter() - t0
        AOT_HITS.inc()
        AOT_LOAD_TIME.observe(load_s)
        self._record("load", key, load_s, hit=True)
        return StoredProgram(exported, key, from_cache=True, path=path)

    @staticmethod
    def _decode(blob: bytes):
        if not blob.startswith(_MAGIC):
            raise ValueError("bad magic")
        rest = blob[len(_MAGIC):]
        nl = rest.find(b"\n")
        if nl < 0:
            raise ValueError("truncated header")
        meta = json.loads(rest[:nl].decode())
        payload = rest[nl + 1:]
        if int(meta.get("payload_len", -1)) != len(payload):
            raise ValueError("truncated payload")
        import jax
        import jaxlib

        if meta.get("jax") != jax.__version__ or meta.get("jaxlib") != jaxlib.__version__:
            raise ValueError(
                f"toolchain mismatch (entry {meta.get('jax')}/{meta.get('jaxlib')}, "
                f"running {jax.__version__}/{jaxlib.__version__})")
        from jax import export

        return export.deserialize(bytearray(payload))

    def _write(self, key: str, exported) -> str:
        import jax
        import jaxlib

        payload = bytes(exported.serialize())
        meta = {
            "key": key,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "payload_len": len(payload),
            "created_unix": round(time.time(), 3),
        }
        blob = _MAGIC + json.dumps(meta, sort_keys=True).encode() + b"\n" + payload
        path = self._path(key)
        fd, tmp = tempfile.mkstemp(dir=self.root, prefix=".tmp_", suffix=".jaxprog")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)  # atomic: readers see old or complete new
        except OSError as e:
            log.warning("aot: could not persist %s (%s) — program stays "
                        "process-local", path, e)
            with contextlib.suppress(OSError):
                os.remove(tmp)
            return path
        AOT_EXPORTS.inc()
        return path

    # -- cross-process coordination ------------------------------------------
    @contextlib.contextmanager
    def _entry_lock(self, key: str):
        """Advisory per-entry flock: N restarting processes building the same
        program serialize into ONE export; the waiters load the winner's
        entry.  Reads never lock (atomic replace keeps them safe)."""
        lock_path = self._path(key) + ".lock"
        try:
            import fcntl
        except ImportError:  # non-posix: best effort, builds may duplicate
            yield
            return
        fd = os.open(lock_path, os.O_CREAT | os.O_RDWR, 0o644)
        try:
            fcntl.flock(fd, fcntl.LOCK_EX)
            yield
        finally:
            with contextlib.suppress(OSError):
                fcntl.flock(fd, fcntl.LOCK_UN)
            os.close(fd)

    # -- observability -------------------------------------------------------
    def _record(self, event: str, key: str, seconds: float, hit: bool) -> None:
        if self.trail is None:
            return
        try:
            self.trail({
                "kind": "metric", "metric": "aot_program_load", "event": event,
                "program": key, "value": round(seconds, 6), "hit": bool(hit),
            })
        except Exception:  # the trail is best-effort telemetry, never fatal
            pass


def store_from_config(cfg, trail: Optional[Callable[[dict], None]] = None
                      ) -> Optional[ProgramStore]:
    """The one gate: ``extra.aot_programs`` unset/falsy → ``None`` (every call
    site then runs its pre-existing ``jax.jit`` path, bit-identical).  Set →
    a store rooted at ``extra.aot_programs_dir`` (default: the repo-root
    cache dir's ``aot_programs/``)."""
    if cfg is None or not cfg_extra(cfg, "aot_programs"):
        return None
    root = cfg_extra(cfg, "aot_programs_dir") or default_store_dir()
    try:
        return ProgramStore(str(root), trail=trail,
                            cost_gauges=bool(cfg_extra(cfg, "cost_model_gauges")))
    except OSError as e:
        log.warning("aot: store root %s unusable (%s) — running without the "
                    "program store", root, e)
        return None
