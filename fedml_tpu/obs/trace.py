"""Distributed round tracing — trace/span identity that crosses the wire.

The reference correlates a round's client train phases and server aggregation
only through its SaaS backend's run ids; locally there is no way to line up
"client 3 trained for 1.2s" with "the server aggregated round 7".  This
module gives every phase a span (trace_id / span_id / parent_id + monotonic
and wall clocks) and propagates the (trace_id, span_id) pair over the comm
layer's ``Message`` trace header, so one round-scoped trace links the
server's round/aggregate spans with every client's train span — across
processes and transports.

Design constraints: stdlib + jax only.  ``traced`` doubles as decorator and
context manager and mirrors every span into ``jax.profiler.TraceAnnotation``
so the same names show up in XLA device profiles; the current span rides a
``contextvars.ContextVar`` so nested spans parent automatically, including
under the comm receive loop's per-message ``activate`` window.

Every finished span is also kept in a process-wide bounded ring
(:func:`recent`), so a harness in the same process can read what the program
measured without configuring a sink.  A span can note registry counters at
its start and their growth at its end (``counters=``); the XLA compile and
persistent-cache counters that the timed paths note are fed by the one
``jax.monitoring`` listener this module installs
(:func:`install_xla_listener`).
"""

from __future__ import annotations

import collections
import contextvars
import functools
import os
import random
import threading
import time
from typing import Any, Callable, Optional, Sequence, Union

import jax

from .registry import REGISTRY

__all__ = [
    "Span", "traced", "activate", "current", "start_span",
    "inject", "extract", "new_id", "recent", "clear_recent",
    "install_xla_listener", "XLA_COUNTERS", "LLM_ATTENDED_KEYS", "LLM_EXPERT_TOKENS",
    "LLM_ATTENTION_SITES", "LLM_SCAN_SITES", "LLM_KDA_SITES", "LLM_PACKED_DOCUMENTS", "LLM_LOSS_TOKENS",
]

#: finished spans, oldest first; a window of some thousand steps fits, and
#: a server that runs for weeks holds no more than this
RING_SIZE = 8192
_ring: collections.deque = collections.deque(maxlen=RING_SIZE)


def recent() -> list["Span"]:
    """The finished spans still in the ring, in the order they ended (a
    parent after its children)."""
    return list(_ring.copy())  # deque.copy is one C call: no appender can cut in


def clear_recent() -> None:
    _ring.clear()


#: ids come from a generator of this module's own, seeded from the OS once:
#: no system call per span, and ``random.seed`` (``rng.seed_everything``
#: gives every process of a run the same seed) cannot make two processes
#: draw the same ids
_ids = random.Random(os.urandom(16))


def new_id() -> str:
    """64 random bits as 16 hex chars (plenty for run-local traces)."""
    return "%016x" % _ids.getrandbits(64)


class Span:
    """One timed phase. ``trace_id`` groups spans of one logical operation
    (a federated round); ``parent_id`` is the enclosing span's ``span_id``."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id",
                 "start_wall", "start_mono", "end_wall", "end_mono", "attrs")

    def __init__(self, name: str, trace_id: Optional[str] = None,
                 parent_id: Optional[str] = None, **attrs):
        self.name = name
        self.trace_id = trace_id or new_id()
        self.span_id = new_id()
        self.parent_id = parent_id
        self.start_wall = time.time()
        self.start_mono = time.monotonic()
        self.end_wall: Optional[float] = None
        self.end_mono: Optional[float] = None
        self.attrs = attrs

    def end(self) -> "Span":
        if self.end_mono is None:
            self.end_mono = time.monotonic()
            self.end_wall = self.start_wall + (self.end_mono - self.start_mono)
        return self

    @property
    def duration_s(self) -> float:
        return (self.end_mono if self.end_mono is not None else time.monotonic()) - self.start_mono

    def header(self) -> dict:
        """The wire propagation context: what a child on the far side needs."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def to_record(self) -> dict:
        """JSONL shape the collector trail stores and ``obs.report`` reads."""
        return {
            "kind": "span",
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "ts": self.start_wall,
            "dur_s": round(self.duration_s, 9),
            **self.attrs,
        }

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, trace={self.trace_id}, span={self.span_id}, "
                f"parent={self.parent_id}, dur={self.duration_s:.6f}s)")


#: current span (a Span) or remote parent context (a header dict) — set by
#: ``traced`` locally and ``activate`` at the comm receive boundary
_current: contextvars.ContextVar[Optional[Union[Span, dict]]] = contextvars.ContextVar(
    "fedml_tpu_current_span", default=None
)


def current() -> Optional[Union[Span, dict]]:
    """The ambient span (or remote header dict) new spans will parent to."""
    return _current.get()


def start_span(name: str, parent: Any = None, **attrs) -> Span:
    """Open a span under ``parent`` (a Span, a wire header dict, or None =
    ambient context; no ambient context starts a fresh trace)."""
    if parent is None:
        parent = _current.get()
    if isinstance(parent, Span):
        return Span(name, trace_id=parent.trace_id, parent_id=parent.span_id, **attrs)
    if isinstance(parent, dict) and parent.get("trace_id"):
        return Span(name, trace_id=parent["trace_id"],
                    parent_id=parent.get("span_id"), **attrs)
    return Span(name, **attrs)


class traced:
    """Span context manager AND decorator.

    ``with traced("train", round_idx=3) as span: ...`` opens a span under the
    ambient context, makes it the ambient context for the body, mirrors it
    into ``jax.profiler.TraceAnnotation`` (TPU profile visibility), ends it
    on exit, and hands the record to ``sink`` when one is given.  ``sink``
    failures are swallowed — telemetry must never take down the traced path.
    """

    def __init__(self, name: str, parent: Any = None,
                 sink: Optional[Callable[[dict], None]] = None,
                 counters: Sequence[str] = (), **attrs):
        self.name = name
        self.parent = parent
        self.sink = sink
        self.counters = counters
        self.attrs = attrs
        self.span: Optional[Span] = None

    def __enter__(self) -> Span:
        self.span = start_span(self.name, parent=self.parent, **self.attrs)
        if self.counters:
            self._noted = [(m, m.value()) for m in map(REGISTRY.get, self.counters)]
        self._token = _current.set(self.span)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._annotation.__exit__(exc_type, exc, tb)
        _current.reset(self._token)
        self.span.end()
        if self.counters:
            # unlabelled registry counters: [value on entering, growth inside]
            self.span.attrs["counters"] = {
                m.name: [v0, m.value() - v0] for m, v0 in self._noted}
        _ring.append(self.span)
        if self.sink is not None:
            try:
                self.sink(self.span.to_record())
            except Exception:
                pass
        return False

    def __call__(self, fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with traced(self.name, parent=self.parent, sink=self.sink,
                        counters=self.counters, **self.attrs):
                return fn(*args, **kwargs)

        return wrapper


class activate:
    """Install a remote parent context (a wire header) as the ambient span
    for the duration of a message handler — the receive-side half of
    propagation.  A missing/invalid header is a no-op, so the receive loop
    can wrap every dispatch unconditionally."""

    def __init__(self, header: Optional[dict]):
        self.header = header if (isinstance(header, dict) and header.get("trace_id")) else None
        self._token = None

    def __enter__(self) -> Optional[dict]:
        if self.header is not None:
            self._token = _current.set(self.header)
        return self.header

    def __exit__(self, *exc) -> bool:
        if self._token is not None:
            _current.reset(self._token)
        return False


def inject(msg, context: Any = None) -> None:
    """Stamp a trace header onto an outgoing protocol message (send-side half
    of propagation).  ``context`` defaults to the ambient span; an existing
    header on the message is never overwritten (an explicit round stamp wins
    over the ambient context of whatever thread sends the message)."""
    if msg.get_trace() is not None:
        return
    src = context if context is not None else _current.get()
    if isinstance(src, Span):
        msg.set_trace(src.header())
    elif isinstance(src, dict) and src.get("trace_id"):
        msg.set_trace({"trace_id": src["trace_id"], "span_id": src.get("span_id")})


def extract(msg) -> Optional[dict]:
    """Read the trace header off an incoming message (None when absent)."""
    header = msg.get_trace()
    if isinstance(header, dict) and header.get("trace_id"):
        return header
    return None


# -- XLA program builds: compiles and loads from the persistent cache ---------
#: jax 0.9.0 (jax/_src/interpreters/pxla.py, jax/_src/compiler.py): the first
#: duration is recorded around ``compile_or_get_cached``, so it fires for a
#: backend compile AND for a load from the persistent cache; the second only
#: for a load, and before the first.  A jit call that finds its program in
#: memory fires neither.  Before either, a program that jit has not seen is
#: traced to a jaxpr and lowered to an MLIR module (``pjit.py``, ``pxla.py``):
#: Python time that no compile cache returns.
_BUILD_KEY = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_KEY = "/jax/compilation_cache/cache_retrieval_time_sec"
_TRACE_KEY = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_KEY = "/jax/core/compile/jaxpr_to_mlir_module_duration"

XLA_COMPILES = REGISTRY.counter(
    "fedml_xla_compiles_total",
    "XLA backend compiles (programs built by the compiler, not loaded from "
    "the persistent cache) since the listener was installed.")
XLA_COMPILE_SECONDS = REGISTRY.counter(
    "fedml_xla_compile_seconds_total",
    "Wall seconds inside those backend compiles.")
XLA_CACHE_LOADS = REGISTRY.counter(
    "fedml_xla_cache_loads_total",
    "Programs taken from the persistent compilation cache.")
XLA_CACHE_LOAD_SECONDS = REGISTRY.counter(
    "fedml_xla_cache_load_seconds_total",
    "Wall seconds retrieving and loading programs from the persistent cache.")
XLA_TRACE_SECONDS = REGISTRY.counter(
    "fedml_xla_trace_seconds_total",
    "Wall seconds jax spent tracing Python functions to jaxprs on their way "
    "to a program (a nested jit's trace is inside its caller's and counts once).")
XLA_LOWER_SECONDS = REGISTRY.counter(
    "fedml_xla_lower_seconds_total",
    "Wall seconds jax spent lowering jaxprs to MLIR modules for the compiler.")
#: what a top-level span of a timed path notes (``traced(counters=...)``)
XLA_COUNTERS = (XLA_COMPILES.name, XLA_COMPILE_SECONDS.name,
                XLA_CACHE_LOADS.name, XLA_CACHE_LOAD_SECONDS.name,
                XLA_TRACE_SECONDS.name, XLA_LOWER_SECONDS.name)

#: fed by ``LLMTrainer.fit`` from what the step program summed on the device
LLM_ATTENDED_KEYS = REGISTRY.counter(
    "fedml_llm_attended_keys_total",
    "Keys the block-sparse attention layers of an LLM step attended, summed "
    "over batch, KV heads, queries and sparse layers: kind=kept is what the "
    "per-query block selection kept (tokens at or before the query inside its "
    "kept blocks), kind=causal what plain causal attention would attend.  "
    "kept/causal is the share of the past a sparse layer reads.  A step on "
    "packed rows feeds it for its softmax layers: kept is then the keys at or "
    "before the query in its own document.",
    labels=("kind",),
)
LLM_EXPERT_TOKENS = REGISTRY.counter(
    "fedml_llm_expert_tokens_total",
    "Expert assignments of an LLM step's tokens, summed over its sparse expert "
    "layers: kind=routed is every assignment the routers made (tokens x "
    "experts per token), kind=held those that landed on experts this "
    "expert-parallel rank holds and computes.  held/routed is the share of the "
    "layer's work that is done here.",
    labels=("kind",),
)
LLM_PACKED_DOCUMENTS = REGISTRY.counter(
    "fedml_llm_packed_documents_total",
    "Documents in the packed rows of the LLM steps run so far (a document that "
    "crosses a row's end counts once in each row), summed on the device from "
    "the batch's segment ids.",
)
LLM_LOSS_TOKENS = REGISTRY.counter(
    "fedml_llm_loss_tokens_total",
    "Positions of the packed rows an LLM step trained on: kind=counted are those "
    "whose target lies in their own document (the loss is their mean), "
    "kind=masked the others (a document's last token, a row's last, padding).",
    labels=("kind",),
)
#: fed by ``ops/sparse_attention.attention_path`` while a program is traced
LLM_ATTENTION_SITES = REGISTRY.counter(
    "fedml_llm_attention_sites_total",
    "Blockwise-attention call sites of the programs traced so far, by the "
    "path each was built on: path=kernel is the fused Pallas flash kernel "
    "(plain causal attention on one TPU device at shapes it tiles), "
    "path=blockwise the lax pass (a block mask, a mesh, another backend or "
    "other shapes).  Counted at build time, once a site a trace.",
    labels=("path",),
)
#: fed by ``ops/ssd.scan_path`` while a program is traced
LLM_SCAN_SITES = REGISTRY.counter(
    "fedml_llm_scan_sites_total",
    "Selective-scan (Mamba-2 SSD) call sites of the programs traced so far, by "
    "the path each was built on: path=kernel is the fused Pallas kernel pair "
    "(one TPU device, shapes it tiles), path=scan the lax.scan over chunks (a "
    "mesh, another backend or other shapes).  Counted at build time, once a "
    "site a trace.",
    labels=("path",),
)
#: fed by ``ops/kda.kda_path`` while a program is traced
LLM_KDA_SITES = REGISTRY.counter(
    "fedml_llm_kda_sites_total",
    "Kimi Delta Attention (chunked gated delta rule) call sites of the programs "
    "traced so far, by the path each was built on: path=kernel is the fused "
    "Pallas kernel pair (one TPU device, shapes it tiles), path=scan the "
    "lax.scan over chunks (a mesh, another backend or other shapes).  Counted "
    "at build time, once a site a trace.",
    labels=("path",),
)

_listener_lock = threading.Lock()
_listener_installed = False
_build_subscribers: list[Callable[[float], None]] = []
_loading = threading.local()  # set between a cache load's two events
_traces = threading.local()   # .done: (start, seconds) of this thread's counted traces


def _own_trace_seconds(duration_s: float) -> float:
    """A finished trace's seconds less those of the traces nested in it: a
    jitted function called while another is traced reports first, and its
    seconds lie inside its caller's (the LLM step fires 700 such events)."""
    done = getattr(_traces, "done", None)
    if done is None:
        done = _traces.done = collections.deque(maxlen=1024)
    start = time.monotonic() - duration_s
    inner = 0.0
    while done and done[-1][0] >= start:
        inner += done.pop()[1]
    done.append((start, duration_s))
    return max(duration_s - inner, 0.0)


def _on_duration(key: str, duration_s: float, **_kw) -> None:
    if key == _CACHE_LOAD_KEY:
        XLA_CACHE_LOADS.inc()
        XLA_CACHE_LOAD_SECONDS.inc(max(duration_s, 0.0))
        _loading.hit = True
    elif key == _BUILD_KEY:
        if getattr(_loading, "hit", False):
            _loading.hit = False
        else:
            XLA_COMPILES.inc()
            XLA_COMPILE_SECONDS.inc(max(duration_s, 0.0))
        for fn in _build_subscribers:
            fn(duration_s)
    elif key == _TRACE_KEY:
        XLA_TRACE_SECONDS.inc(_own_trace_seconds(max(duration_s, 0.0)))
    elif key == _LOWER_KEY:
        XLA_LOWER_SECONDS.inc(max(duration_s, 0.0))


def install_xla_listener(on_build: Optional[Callable[[float], None]] = None) -> None:
    """Register THE process's ``jax.monitoring`` duration listener (jax has
    no unregister, so there is one, installed once; both entry points,
    ``fedml_tpu.init`` and ``LLMTrainer``, pass here).  ``on_build`` is
    called with the duration of every program build, compiled or loaded
    (``analysis/tracesan.py`` attributes them to round phases)."""
    global _listener_installed
    with _listener_lock:
        if on_build is not None and on_build not in _build_subscribers:
            _build_subscribers.append(on_build)
        if not _listener_installed:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listener_installed = True
