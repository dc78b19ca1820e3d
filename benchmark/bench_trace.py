"""Reduction of a profiler trace to the numbers the benchmark reports.

One reducer for every cell.  ``load_events`` turns an ``.xplane.pb`` into a
plain list of events; everything else works on that list, so the self-check
can feed it a small recorded fixture.  Device events are those of a device
plane's "XLA Ops" line; host spans are the benchmark's own
``TraceAnnotation`` names (``bench.*``) on host planes.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench."
NO_SPAN = "_no_span_"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def parse_hlo(text: str) -> tuple[str, str]:
    """An op event's name on this installation is its whole HLO line,
    ``%fusion.140 = (f32[..], ..) fusion(...), kind=kOutput, calls=...``, and
    carries no category stat.  Returns the short name (``fusion.140``) and a
    category: ``fusion:kOutput`` and the like for fusions, else the opcode
    (``copy``, ``all-gather-start``, ``convolution``)."""
    short, sep, rest = text.partition(" = ")
    short = short.lstrip("%")
    if not sep:
        return short, ""
    kind = re.search(r"kind=(k[A-Za-z]+)", rest)
    if kind:
        return short, "fusion:" + kind.group(1)
    if rest.startswith("("):  # a tuple type: skip to its closing bracket
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.partition(" ")[2]
    return short, rest.partition("(")[0].strip()


def load_events(xplane_path: str) -> list[dict]:
    """Device op events (the "XLA Ops" line, and the "Async XLA Ops" line
    for collectives in flight) and ``bench.*`` host spans as dicts with
    ``plane``, ``line``, ``name``, ``start_ns``, ``dur_ns`` and, for device
    ops, ``category`` (see ``parse_hlo``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, ASYNC_LINE):
                continue
            for ev in line.events:
                if is_device:
                    short, cat = parse_hlo(ev.name)
                    out.append({"plane": plane.name, "line": line.name, "name": short,
                                "start_ns": float(ev.start_ns), "dur_ns": float(ev.duration_ns),
                                "category": cat})
                elif ev.name.startswith(SPAN_PREFIX):
                    out.append({"plane": plane.name, "line": line.name, "name": ev.name,
                                "start_ns": float(ev.start_ns), "dur_ns": float(ev.duration_ns)})
    return out


def device_ops(events: list[dict]) -> dict[str, list[dict]]:
    by_dev: dict[str, list[dict]] = defaultdict(list)
    for e in events:
        if DEVICE_PLANE.match(e["plane"]) and e["line"] == OPS_LINE:
            by_dev[e["plane"]].append(e)
    return by_dev


def spans(events: list[dict]) -> list[dict]:
    return [e for e in events if e["name"].startswith(SPAN_PREFIX)]


def window_of(events: list[dict]) -> tuple[float, float]:
    """The traced window: the ``bench.window`` span where there is one, else
    the hull of all events."""
    for e in events:
        if e["name"] == "bench.window":
            return e["start_ns"], e["start_ns"] + e["dur_ns"]
    lo = min(e["start_ns"] for e in events)
    hi = max(e["start_ns"] + e["dur_ns"] for e in events)
    return lo, hi


def union_intervals(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for lo, hi in sorted(iv):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(a, b) for a, b in merged]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if min(b, hi) > max(a, lo)]


def busy(events: list[dict], select=None) -> dict:
    """Busy-union per device inside the window, averaged over the devices
    that ran anything.  ``select(event) -> bool`` restricts the ops."""
    lo, hi = window_of(events)
    per_dev = {}
    for dev, ops in device_ops(events).items():
        iv = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops
              if select is None or select(e)]
        per_dev[dev] = sum(b - a for a, b in union_intervals(_clip(iv, lo, hi))) / 1e9
    n = max(1, len(per_dev))
    return {"window_s": (hi - lo) / 1e9, "busy_s": sum(per_dev.values()) / n,
            "per_device_busy_s": per_dev}


# ops that only contain other ops of the same line: a scan's ``while`` spans
# all of its body's events, and would count their time twice
CONTAINERS = ("while", "conditional", "call")


def op_seconds(events: list[dict], select=None) -> dict[str, float]:
    """Summed device seconds per op name inside the window, averaged over
    devices (an op that runs on every chip counts once); container ops are
    left out."""
    lo, hi = window_of(events)
    by_dev = device_ops(events)
    sums: dict[str, float] = defaultdict(float)
    for ops in by_dev.values():
        for e in ops:
            if e.get("category") in CONTAINERS or (select is not None and not select(e)):
                continue
            a, b = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
            if b > a:
                sums[e["name"]] += (b - a) / 1e9
    n = max(1, len(by_dev))
    return {k: v / n for k, v in sums.items()}


def idle_gaps_by_span(events: list[dict]) -> dict[str, float]:
    """Idle seconds of the first device inside the window, attributed to the
    innermost ``bench.*`` span (other than ``bench.window``) that covers the
    middle of each gap."""
    lo, hi = window_of(events)
    by_dev = device_ops(events)
    if not by_dev:
        return {}
    ops = by_dev[sorted(by_dev)[0]]
    merged = union_intervals(_clip([(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops], lo, hi))
    gaps, cur = [], lo
    for a, b in merged:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    sp = sorted((s for s in spans(events) if s["name"] != "bench.window"),
                key=lambda s: s["dur_ns"])
    out: dict[str, float] = defaultdict(float)
    for a, b in gaps:
        mid = (a + b) / 2
        name = next((s["name"] for s in sp
                     if s["start_ns"] <= mid <= s["start_ns"] + s["dur_ns"]), NO_SPAN)
        out[name] += (b - a) / 1e9
    return dict(out)


def exposed_seconds(events: list[dict], is_collective) -> float:
    """Seconds inside the window in which a collective runs on a device and
    no other op does, averaged over devices."""
    lo, hi = window_of(events)
    total, n = 0.0, 0
    for dev, ops in device_ops(events).items():
        n += 1
        inflight = [e for e in events if e["plane"] == dev and e["line"] == ASYNC_LINE]
        coll = union_intervals(_clip([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                                      for e in ops + inflight if is_collective(e)], lo, hi))
        comp = union_intervals(_clip([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                                      for e in ops if not is_collective(e)], lo, hi))
        covered = 0.0
        for a, b in coll:
            for c, d in comp:
                if d <= a:
                    continue
                if c >= b:
                    break
                covered += min(b, d) - max(a, c)
        total += (sum(b - a for a, b in coll) - covered) / 1e9
    return total / max(1, n)


def sample(events: list[dict], n_ops: int = 600) -> list[dict]:
    """The first ``n_ops`` device events inside the window with the host
    spans that overlap them: small enough to keep and to look at by hand."""
    lo, hi = window_of(events)
    ops = sorted((e for e in events if "category" in e and e["start_ns"] >= lo),
                 key=lambda e: e["start_ns"])[:n_ops]
    if not ops:
        return []
    end = max(e["start_ns"] + e["dur_ns"] for e in ops)
    sp = [e for e in spans(events) if e["name"] != "bench.window"
          and e["start_ns"] < end and e["start_ns"] + e["dur_ns"] > lo]
    return ops + sp


def breakdown(events: list[dict], top: int = 10) -> dict:
    ops = sorted(op_seconds(events).items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps_by_span(events).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in gaps]}
