"""Per-program device-time attribution (ISSUE 18).

PERF.md round-6 left attribution as a manual escape hatch — "run
``scripts/profile_trace.py`` if fused MFU < 0.14".  This module turns that
script into a layer the engine invokes itself: a
:class:`ProgramTimeAttributor` opens a programmatic
``jax.profiler.start_trace`` window around rounds ``k..k+n`` (behind
``extra.profile_rounds`` / ``profile_dir``), parses the captured trace,
and splits the window's time into

- **compile** — host-side XLA compilation events,
- **h2d** — data movement (transfers, infeed/outfeed, device copies),
- **device-compute** — everything the chip actually executed,
- **host-gap** — window wall time not covered by any of the above (the
  dispatch/bookkeeping bubble the roofline cannot see).

The engine notes every program that ran inside the window together with
its PR-16 cost-model FLOPs (``fedml_program_flops``), so the attribution
joins analytic cost against measured device time and cross-checks the
live ``fedml_sim_mfu`` gauge: ``mfu_cost_model`` (cost-model FLOPs /
device-compute time / chip peak) landing far from ``sim_mfu_gauge``
means the wall-clock denominator is hiding host time — exactly the
signal the manual workflow existed to surface.

Everything degrades gracefully: no profiler support, an unparseable
trace, or a dead trace dir each leave a warning and a window without
attribution — never an exception into the round path.  Gating is
absolute: :func:`profiler_from_config` returns ``None`` unless
``extra.profile_rounds`` parses.
"""

from __future__ import annotations

import contextlib
import collections
import glob
import gzip
import json
import logging
import os
import tempfile
import time
from typing import Any, Optional

from ..core.flags import cfg_extra
from ..ops import flops as flopslib
from . import registry as obsreg

log = logging.getLogger("fedml_tpu.obs.profiler")

__all__ = [
    "ProgramTimeAttributor", "profiler_from_config", "parse_profile_rounds",
    "find_trace_file", "load_trace", "aggregate_device_events",
    "split_time_buckets", "bucket_rows",
]

PROFILE_WINDOWS = obsreg.REGISTRY.counter(
    "fedml_profile_windows_total",
    "Programmatic profiler trace windows completed, by outcome (attributed "
    "= trace parsed; unparsed = window closed but no readable trace).",
    labels=("outcome",),
)
PROFILE_DEVICE_SECONDS = obsreg.REGISTRY.gauge(
    "fedml_profile_device_seconds",
    "Window time split by the attributor: compile / h2d / device_compute / "
    "host_gap seconds of the last completed profile window.",
    labels=("category",),
)
PROFILE_MFU = obsreg.REGISTRY.gauge(
    "fedml_profile_mfu",
    "MFU cross-checked from the profile window: cost-model program FLOPs "
    "over measured device-compute time over chip peak (compare against "
    "fedml_sim_mfu, whose denominator is host-inclusive wall time).",
)

#: hlo categories / event-name fragments that are data movement, not compute
_H2D_CATEGORIES = ("copy", "infeed", "outfeed", "host send", "host recv")
_H2D_NAME_FRAGMENTS = ("transferto", "transferfrom", "copy")
_COMPILE_NAME_FRAGMENTS = ("compile", "xlacompile", "pjitcompil")


def parse_profile_rounds(value: Any) -> Optional[tuple[int, int]]:
    """``'n'`` -> rounds ``[0, n)``; ``'k:n'`` -> ``[k, k+n)``; ``None`` /
    unparseable / empty window -> ``None`` (the gate)."""
    if value is None:
        return None
    try:
        text = str(value).strip()
        if not text:
            return None
        if ":" in text:
            k_s, n_s = text.split(":", 1)
            k, n = int(k_s), int(n_s)
        else:
            k, n = 0, int(text)
        if n <= 0 or k < 0:
            return None
        return (k, k + n)
    except (TypeError, ValueError):
        log.warning("profiler: unparseable profile_rounds %r — disabled", value)
        return None


# ---------------------------------------------------------------------------
# trace parsing — the library `scripts/profile_trace.py` now wraps


def find_trace_file(root: str) -> Optional[str]:
    """Newest ``*.trace.json.gz`` under ``root/plugins/profile/*/`` (the
    layout ``jax.profiler`` writes); ``None`` when nothing captured."""
    runs = glob.glob(os.path.join(root, "plugins", "profile", "*", ""))
    if not runs:
        return None
    latest = max(runs, key=os.path.getmtime)
    traces = glob.glob(os.path.join(latest, "*.trace.json.gz"))
    return traces[0] if traces else None


def load_trace(path: str) -> dict:
    with gzip.open(path) as f:
        return json.load(f)


def _device_pids(trace: dict) -> set:
    pids = {e["pid"]: (e.get("args") or {}).get("name", "")
            for e in trace.get("traceEvents", [])
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    return {p for p, n in pids.items() if "TPU" in n or "device" in n.lower()}


def aggregate_device_events(trace: dict) -> dict:
    """Aggregate device-pid ``X`` events by hlo_category and source line:
    ``{key: [duration_ps, flops, bytes, n]}`` per bucket, plus host-side
    compile time — the same aggregation the round-4 script printed, now
    returned as data."""
    dev_pids = _device_pids(trace)
    cat: dict = collections.defaultdict(lambda: [0, 0, 0, 0])
    src: dict = collections.defaultdict(lambda: [0, 0, 0, 0])
    compile_ps = 0
    for e in trace.get("traceEvents", []):
        a = e.get("args") or {}
        if e.get("ph") != "X":
            continue
        if e.get("pid") in dev_pids and "hlo_category" in a:
            c = a["hlo_category"]
            if c == "while":
                continue
            d = int(a.get("device_duration_ps", 0))
            fl = int(a.get("model_flops", 0) or 0)
            by = int(a.get("raw_bytes_accessed", 0) or 0)
            for bucket, key in ((cat, c), (src, a.get("source", "?"))):
                bucket[key][0] += d
                bucket[key][1] += fl
                bucket[key][2] += by
                bucket[key][3] += 1
        elif e.get("pid") not in dev_pids:
            name = str(e.get("name", "")).lower()
            if any(f in name for f in _COMPILE_NAME_FRAGMENTS):
                # host durations are microseconds in the chrome trace format
                compile_ps += int(float(e.get("dur", 0)) * 1e6)
    return {"by_category": dict(cat), "by_source": dict(src),
            "compile_ps": compile_ps}


def split_time_buckets(aggregated: dict, wall_s: float) -> dict:
    """The four-way split: compile / h2d / device_compute / host_gap
    seconds over a window of ``wall_s`` wall seconds."""
    h2d_ps = 0
    compute_ps = 0
    for key, (d, _fl, _by, _n) in aggregated.get("by_category", {}).items():
        k = str(key).lower()
        if any(f in k for f in _H2D_CATEGORIES) or any(
                f in k for f in _H2D_NAME_FRAGMENTS):
            h2d_ps += d
        else:
            compute_ps += d
    compile_s = aggregated.get("compile_ps", 0) / 1e12
    h2d_s = h2d_ps / 1e12
    compute_s = compute_ps / 1e12
    host_gap_s = max(0.0, float(wall_s) - compile_s - h2d_s - compute_s)
    return {"compile_s": round(compile_s, 6), "h2d_s": round(h2d_s, 6),
            "device_compute_s": round(compute_s, 6),
            "host_gap_s": round(host_gap_s, 6)}


def bucket_rows(bucket: dict, top: int) -> list[dict]:
    """Render one aggregation bucket as sorted report rows (achieved
    TFLOP/s and GB/s per key) — shared by the attributor and the script."""
    out = []
    for k, (d, fl, by, n) in sorted(bucket.items(), key=lambda kv: -kv[1][0])[:top]:
        out.append({
            "key": k, "ms": round(d / 1e9, 2), "n": n,
            "tflops": round(fl / (d / 1e12) / 1e12, 2) if d else 0,
            "gbps": round(by / (d / 1e12) / 1e9, 1) if d else 0,
        })
    return out


# ---------------------------------------------------------------------------


class ProgramTimeAttributor:
    """One profile window around rounds ``[start, end)``: trace, parse,
    attribute, cross-check MFU, write the attribution JSON."""

    def __init__(self, out_dir: str, *, window: tuple[int, int],
                 name: str = "sim",
                 registry: Optional[obsreg.MetricsRegistry] = None,
                 peak_flops: Optional[float] = None):
        self.out_dir = os.path.abspath(str(out_dir))
        os.makedirs(self.out_dir, exist_ok=True)
        self.name = str(name)
        self.window = (int(window[0]), int(window[1]))
        self.registry = registry or obsreg.REGISTRY
        self.peak_flops = peak_flops
        self.attribution: Optional[dict] = None
        self.attribution_path: Optional[str] = None
        self._programs: list[dict] = []
        self._active = False
        self._done = False
        self._wall_start = 0.0

    # -- window lifecycle (the engine drives these around round chunks) ------
    def maybe_start(self, round_idx: int) -> bool:
        """Open the trace when ``round_idx`` enters the window.  Returns
        whether the window is active after the call."""
        if self._active:
            return True
        if self._done or not (self.window[0] <= int(round_idx) < self.window[1]):
            return False
        try:
            import jax

            jax.profiler.start_trace(self.out_dir)
        except Exception as e:
            log.warning("profiler: start_trace failed (%s: %s) — window "
                        "disabled", type(e).__name__, e)
            self._done = True
            return False
        self._active = True
        self._wall_start = time.time()
        return True

    def note_program(self, program: str, *, flops: Optional[float] = None,
                     rounds: Optional[int] = None) -> None:
        """Record one program execution inside the window (the join key
        against the cost-model gauges)."""
        if not self._active:
            return
        self._programs.append({
            "program": str(program),
            "flops": float(flops) if flops else None,
            "rounds": int(rounds) if rounds else None,
        })

    def maybe_stop(self, next_round_idx: int) -> Optional[dict]:
        """Close the window once the next round falls past its end;
        returns the attribution (``None`` while still open / unparsed)."""
        if not self._active or int(next_round_idx) < self.window[1]:
            return None
        return self.finalize()

    def finalize(self) -> Optional[dict]:
        """Stop the trace (if open), parse, attribute, export gauges."""
        if not self._active:
            return self.attribution
        self._active = False
        self._done = True
        wall_s = time.time() - self._wall_start
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:
            log.warning("profiler: stop_trace failed (%s: %s)",
                        type(e).__name__, e)
            PROFILE_WINDOWS.inc(outcome="unparsed")
            return None
        self.attribution = self._attribute(wall_s)
        outcome = "attributed" if self.attribution is not None else "unparsed"
        PROFILE_WINDOWS.inc(outcome=outcome)
        return self.attribution

    # -- attribution ----------------------------------------------------------
    def _attribute(self, wall_s: float) -> Optional[dict]:
        trace_file = find_trace_file(self.out_dir)
        if trace_file is None:
            log.warning("profiler: no trace captured under %s", self.out_dir)
            return None
        try:
            aggregated = aggregate_device_events(load_trace(trace_file))
        except Exception as e:
            log.warning("profiler: trace %s unparseable (%s: %s)",
                        trace_file, type(e).__name__, e)
            return None
        buckets = split_time_buckets(aggregated, wall_s)
        for category, seconds in buckets.items():
            PROFILE_DEVICE_SECONDS.set(seconds,
                                       category=category.rsplit("_s", 1)[0])
        compute_s = buckets["device_compute_s"]
        cost_flops = sum(p["flops"] for p in self._programs if p["flops"])
        programs = []
        for p in self._programs:
            row = dict(p)
            if p["flops"] and cost_flops and compute_s:
                share = p["flops"] / cost_flops
                row["share_device_s"] = round(share * compute_s, 6)
            programs.append(row)
        mfu_cost_model = None
        if cost_flops and compute_s and self.peak_flops:
            mfu_cost_model = cost_flops / compute_s / float(self.peak_flops)
            PROFILE_MFU.set(mfu_cost_model)
        trace_flops = sum(v[1] for v in aggregated["by_category"].values())
        mfu_trace = None
        if trace_flops and compute_s and self.peak_flops:
            mfu_trace = trace_flops / compute_s / float(self.peak_flops)
        sim_mfu = None
        fam = self.registry.get("fedml_sim_mfu")
        if fam is not None:
            with contextlib.suppress(Exception):
                sim_mfu = float(fam.value())
        attribution = {
            "window": {"start_round": self.window[0],
                       "end_round": self.window[1],
                       "wall_s": round(wall_s, 6)},
            "buckets": buckets,
            "by_category": bucket_rows(aggregated["by_category"], 8),
            "by_source": bucket_rows(aggregated["by_source"], 12),
            "programs": programs,
            "cost_model_flops": cost_flops or None,
            "trace_model_flops": trace_flops or None,
            "chip_peak_flops": self.peak_flops,
            "mfu_cost_model": round(mfu_cost_model, 6) if mfu_cost_model else None,
            "mfu_trace": round(mfu_trace, 6) if mfu_trace else None,
            "sim_mfu_gauge": round(sim_mfu, 6) if sim_mfu else None,
            "trace_file": trace_file,
        }
        self.attribution_path = self._write(attribution)
        return attribution

    def _write(self, attribution: dict) -> Optional[str]:
        path = os.path.join(
            self.out_dir, f"{self.name}.{os.getpid()}.attribution.json")
        try:
            fd, tmp = tempfile.mkstemp(dir=self.out_dir, prefix=".tmp_",
                                       suffix=".json")
            with os.fdopen(fd, "w") as f:
                json.dump(attribution, f, sort_keys=True, indent=1, default=str)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            return path
        except OSError as e:
            log.warning("profiler: attribution write failed (%s)", e)
            return None


def profiler_from_config(cfg, *, name: str = "sim",
                         peak_flops: Optional[float] = None
                         ) -> Optional[ProgramTimeAttributor]:
    """The one gate: ``extra.profile_rounds`` unset/unparseable ->
    ``None`` (no trace, no window, bit-identical default path)."""
    if cfg is None:
        return None
    window = parse_profile_rounds(cfg_extra(cfg, "profile_rounds"))
    if window is None:
        return None
    out_dir = cfg_extra(cfg, "profile_dir") or os.path.join(
        os.getcwd(), "profile_traces")
    if peak_flops is None:
        peak_flops = flopslib.local_peak_flops()
    try:
        return ProgramTimeAttributor(str(out_dir), window=window, name=name,
                                     peak_flops=peak_flops)
    except OSError as e:
        log.warning("profiler: dir %s unusable (%s) — running without the "
                    "attributor", out_dir, e)
        return None
