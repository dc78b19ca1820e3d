"""Readers of the per-layer metrics that come from the program's own spans
and counters (reader spec ``program_spans:<function>``).

The program keeps every finished span of ``fedml_tpu.obs.trace.traced`` in a
ring (``recent()``); this module reads that ring in-process, once per run, and
reduces it to the numbers of the metric files that name it.  A program without
the ring (an older commit) makes every reader here return ``None``.

Which spans are the window's: the last ``window["attempted"]`` spans of each
top name (``llm.step``; or ``sim.run_rounds`` and ``sim.eval``) with everything
the program recorded from the first of them on; what ended before is set-up.

Clock: the k-th ``bench.*`` span of the traced window (``bench.llm_step``;
``bench.run_rounds``, ``bench.evaluate``) opens a few microseconds before the
k-th top span of the program.  The least difference over those pairs is taken
as the distance between ``Span.start_mono`` and the trace's clock; after the
shift every top span has to lie inside its ``bench.*`` span (``TOLERANCE_NS``),
else the readers that need the trace's clock return ``None`` and say why.
Readers of durations and counters need no clock.

The first reader called in a run writes what it found to
``chiprun_out/bench/spans.<cell>.json``: every span on the trace's clock, each
step's (or round's) time by span, and the device's idle gaps by span, so that a
stalled step can be read by hand.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics
import sys
import traceback

import bench_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOLERANCE_NS = 100e3
NO_SPAN = bench_trace.NO_SPAN
_STATE = "_program_spans"

# per kind of timed path: the spans that are one step (or one chunk) of the
# window, the benchmark span that opens just before each, the span whose start
# marks a step's period, and the span that holds the whole window's call
KINDS = (
    {"tops": {"llm.step": "bench.llm_step"}, "period": "llm.next_batch", "root": "llm.fit"},
    {"tops": {"sim.run_rounds": "bench.run_rounds", "sim.eval": "bench.evaluate"},
     "period": None, "root": None},
)


def say(msg: str) -> None:
    print(f"[program_spans] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ the tree
def as_records(spans) -> list[dict]:
    """``obs.trace.Span`` objects as plain dicts on a nanosecond scale, in
    order of their start."""
    out = [{"name": s.name, "id": s.span_id, "parent": s.parent_id,
            "start_ns": s.start_mono * 1e9, "dur_ns": (s.end_mono - s.start_mono) * 1e9,
            "attrs": dict(s.attrs)} for s in spans if s.end_mono is not None]
    return sorted(out, key=lambda r: r["start_ns"])


def end_ns(r: dict) -> float:
    return r["start_ns"] + r["dur_ns"]


def self_ns(span: dict, spans: list[dict]) -> float:
    """A span's duration less the durations of its direct children."""
    return span["dur_ns"] - sum(c["dur_ns"] for c in spans if c["parent"] == span["id"])


def pick_window(records: list[dict], attempted: int):
    """(kind, tops by name, root, window records, set-up records) or ``None``:
    the last ``attempted`` spans of each top name are the window's pieces."""
    names = {r["name"] for r in records}
    kind = next((k for k in KINDS if all(t in names for t in k["tops"])), None)
    if kind is None or attempted < 1:
        return None
    tops = {}
    for name in kind["tops"]:
        mine = [r for r in records if r["name"] == name]
        if len(mine) < attempted:
            say(f"{len(mine)} spans named {name} for {attempted} attempted")
            return None
        tops[name] = mine[-attempted:]
    first = min((t[0] for t in tops.values()), key=lambda r: r["start_ns"])
    by_id = {r["id"]: r for r in records}
    root = by_id.get(first["parent"]) if kind["root"] else None
    if root is not None and root["name"] != kind["root"]:
        root = None
    lo = root["start_ns"] if root is not None else first["start_ns"]
    window = [r for r in records if r["start_ns"] >= lo]
    setup = [r for r in records if end_ns(r) <= lo]
    return kind, tops, root, window, setup


def pieces_of(kind: dict, tops: dict, root, window: list[dict]) -> list[dict]:
    """One entry per step (or chunk): its bounds and its time by span name
    (self times, so the entries add up to ``host_and_device_ns``)."""
    n = len(next(iter(tops.values())))
    if kind["period"]:
        marks = [r for r in window if r["name"] == kind["period"]]
        if len(marks) < n + 1:
            say(f"{len(marks)} spans named {kind['period']} for {n} steps")
            return []
        marks = marks[-(n + 1):]
        bounds = [(marks[k]["start_ns"], marks[k + 1]["start_ns"]) for k in range(n)]
    else:
        bounds = [(min(t[k]["start_ns"] for t in tops.values()),
                   max(end_ns(t[k]) for t in tops.values())) for k in range(n)]
    out = []
    for lo, hi in bounds:
        members = [r for r in window if lo <= r["start_ns"] < hi and r is not root]
        by_name: dict[str, float] = {}
        for r in members:
            key = r["name"] if not any(c["parent"] == r["id"] for c in members) else r["name"] + ".self"
            by_name[key] = by_name.get(key, 0.0) + self_ns(r, members)
        total = sum(by_name.values())
        if kind["period"]:  # the loop's own time between its spans
            by_name["loop.self"] = (hi - lo) - total
            total = hi - lo
        out.append({"start_ns": lo, "end_ns": hi, "host_and_device_ns": total, "by_span_ns": by_name})
    return out


# ----------------------------------------------------------------- the clock
def window_bench_spans(events: list[dict], name: str) -> list[dict]:
    lo, hi = bench_trace.window_of(events)
    mine = [e for e in events if e["name"] == name and "category" not in e
            and lo <= e["start_ns"] <= hi]
    return sorted(mine, key=lambda e: e["start_ns"])


def clock_shift(kind: dict, tops: dict, events: list[dict]):
    """(shift in ns to take from a program time to get a trace time, why not)
    from the pairs of each top span with the benchmark span around it."""
    pairs = []
    for name, anchor in kind["tops"].items():
        bench = window_bench_spans(events, anchor)
        if len(bench) != len(tops[name]):
            return None, f"{len(bench)} {anchor} in the trace for {len(tops[name])} {name}"
        pairs += list(zip(tops[name], bench))
    if not pairs:
        return None, "no anchor pair"
    shift = min(p["start_ns"] - b["start_ns"] for p, b in pairs)
    for p, b in pairs:
        lo, hi = p["start_ns"] - shift, end_ns(p) - shift
        if lo < b["start_ns"] - TOLERANCE_NS or hi > end_ns(b) + TOLERANCE_NS:
            return None, (f"{p['name']} at {lo:.0f}..{hi:.0f} ns is not inside {b['name']} "
                          f"at {b['start_ns']:.0f}..{end_ns(b):.0f} ns after the shift")
    return shift, None


def idle_gaps(events: list[dict]) -> list[tuple[float, float]]:
    """Idle stretches of the first device inside the traced window (the gaps
    ``bench_trace.idle_gaps_by_span`` attributes)."""
    lo, hi = bench_trace.window_of(events)
    by_dev = bench_trace.device_ops(events)
    if not by_dev:
        return []
    ops = by_dev[sorted(by_dev)[0]]
    iv = [(max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)) for e in ops]
    gaps, cur = [], lo
    for a, b in bench_trace.union_intervals([(a, b) for a, b in iv if b > a]):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def innermost_timeline(spans: list[dict], skip=()) -> tuple[list[float], list[str]]:
    """The spans flattened: cut points in time and, for each stretch between
    two of them, the name of the innermost (shortest) span that covers it,
    ``NO_SPAN`` where none does."""
    spans = sorted((s for s in spans if s["name"] not in skip), key=lambda s: s["dur_ns"])
    cuts = sorted({t for s in spans for t in (s["start_ns"], end_ns(s))})
    names = []
    for lo, hi in zip(cuts, cuts[1:]):
        mid = (lo + hi) / 2
        names.append(next((s["name"] for s in spans if s["start_ns"] <= mid <= end_ns(s)), NO_SPAN))
    return cuts, names


def split_gaps(gaps, cuts: list[float], names: list[str]) -> list[tuple[float, float, dict]]:
    """Each gap with its nanoseconds by the innermost span over each part of
    it.  (The benchmark's own ``bench.*`` spans are longer than a gap, so
    ``bench_trace.idle_gaps_by_span`` gives a whole gap to the span over its
    middle; the program's spans are shorter than the gap between two steps,
    which starts in one ``llm.sync`` and ends in the next ``llm.dispatch``.)"""
    out = []
    for a, b in gaps:
        parts: dict[str, float] = {}
        i = bisect.bisect_right(cuts, a) - 1  # the stretch that holds a
        t = a
        while t < b:
            if 0 <= i < len(names):
                name, nxt = names[i], min(b, cuts[i + 1])
            elif i < 0 and cuts:  # before the first span
                name, nxt = NO_SPAN, min(b, cuts[0])
            else:  # after the last
                name, nxt = NO_SPAN, b
            parts[name] = parts.get(name, 0.0) + (nxt - t)
            t, i = nxt, i + 1
        out.append((a, b, parts))
    return out


# ------------------------------------------------------------------- loading
def reduce(records: list[dict], window: dict, events: list[dict] | None) -> dict | None:
    """Everything the readers need, from span records, the driver's window and
    the trace's events."""
    picked = pick_window(records, int(window.get("attempted", 0)))
    if picked is None:
        return None
    kind, tops, root, in_window, setup = picked
    state = {"kind": kind, "tops": tops, "root": root, "window": in_window, "setup": setup,
             "pieces": pieces_of(kind, tops, root, in_window), "shift_ns": None,
             "idle": None, "why_no_clock": "no trace"}
    if events:
        shift, why = clock_shift(kind, tops, events)
        state["shift_ns"], state["why_no_clock"] = shift, why
        if shift is None:
            say(f"no clock: {why}")
        else:
            aligned = [{**r, "start_ns": r["start_ns"] - shift} for r in in_window]
            # the root holds the whole window's call: what only it covers is no span's
            cuts, names = innermost_timeline(aligned, skip=(kind["root"],))
            gaps = split_gaps(idle_gaps(events), cuts, names)
            by_span: dict[str, float] = {}
            for _, _, parts in gaps:
                for name, ns in parts.items():
                    by_span[name] = by_span.get(name, 0.0) + ns
            lo, hi = bench_trace.window_of(events)
            state["idle"] = {"by_span_ns": by_span, "traced_window_ns": hi - lo,
                             "long_gaps": [g for g in gaps if g[1] - g[0] > 1e6]}
    return state


def write_record(state: dict, cell: str) -> None:
    shift = state["shift_ns"] or 0.0
    idle = state["idle"] or {"by_span_ns": {}, "long_gaps": []}
    rec = {
        "cell": cell, "clock": "trace" if state["shift_ns"] is not None else "program (time.monotonic)",
        "shift_ns": state["shift_ns"], "why_no_clock": state["why_no_clock"],
        "tops": {k: len(v) for k, v in state["tops"].items()},
        "pieces": [{**p, "start_ns": p["start_ns"] - shift, "end_ns": p["end_ns"] - shift}
                   for p in state["pieces"]],
        "idle_ns_by_span": idle["by_span_ns"],
        "idle_gaps_over_1ms": [[a, b - a, parts] for a, b, parts in idle["long_gaps"]],
        "window_spans": [{**r, "start_ns": r["start_ns"] - shift} for r in state["window"]],
        "setup_spans": [{**r, "start_ns": r["start_ns"] - shift} for r in state["setup"]],
    }
    out_dir = os.path.join(ROOT, "chiprun_out", "bench")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"spans.{cell}.json"), "w") as fh:
        json.dump(rec, fh, default=str)


def load(ctx) -> dict | None:
    """The run's reduced spans, read once and kept in ``ctx``."""
    if _STATE in ctx:
        return ctx[_STATE]
    state = None
    try:
        from fedml_tpu.obs import trace as obstrace

        recent = getattr(obstrace, "recent", None)
        if recent is not None:  # an older program keeps no ring
            state = reduce(as_records(recent()), ctx["window"], ctx.get("events"))
            if state is not None:
                write_record(state, ctx["cell"]["name"])
    except Exception:  # a reader returns nothing rather than fail the run
        traceback.print_exc()
        state = None
    ctx[_STATE] = state
    return state


# ------------------------------------------------------------------- readers
def piece_host_ms(ctx, args):
    """Per step (or chunk): its time less the spans named in ``less`` (those
    in which the host only waits for the device); ``q`` is 0.5 or ``"max"``."""
    st = load(ctx)
    if not st or len(st["pieces"]) < 2:
        return None
    xs = [(p["host_and_device_ns"] - sum(p["by_span_ns"].get(n, 0.0) for n in args["less"])) / 1e6
          for p in st["pieces"]]
    return max(xs) if args["q"] == "max" else statistics.median(xs)


def idle_share(ctx, args):
    """% of the traced window in which the first device is idle and the
    innermost program span at that time is one of ``spans``; or, with
    ``other_than``, is none of those (any other span, or none at all)."""
    st = load(ctx)
    if not st or not st["idle"]:
        return None
    by_span = st["idle"]["by_span_ns"]
    if "spans" in args:
        mine = [ns for name, ns in by_span.items() if name in args["spans"]]
    else:
        mine = [ns for name, ns in by_span.items() if name not in args["other_than"]]
    return 100.0 * sum(mine) / st["idle"]["traced_window_ns"]


def _counter_spans(st) -> list[dict]:
    """The window's top-level spans that noted counters, first one first."""
    top = {t["id"] for ts in st["tops"].values() for t in ts}
    if st["root"] is not None:
        top.add(st["root"]["id"])
    return [r for r in st["window"] if r["id"] in top and "counters" in r["attrs"]]


def counter_at_window_start(ctx, args):
    """A registry counter as the window's first top-level span noted it on
    entering: what set-up had counted."""
    st = load(ctx)
    spans = _counter_spans(st) if st else []
    if not spans or args["counter"] not in spans[0]["attrs"]["counters"]:
        return None
    return spans[0]["attrs"]["counters"][args["counter"]][0]


def window_counter_growth(ctx, args):
    """Growth of the named counters inside the window's top-level spans."""
    st = load(ctx)
    spans = _counter_spans(st) if st else []
    if not spans:
        return None
    return sum(r["attrs"]["counters"].get(c, [0, 0])[1] for r in spans for c in args["counters"])


def window_attr_ratio(ctx, args):
    """100 x sum of attribute ``num`` over sum of ``den`` on the window's
    spans named ``span``."""
    st = load(ctx)
    rows = [r["attrs"] for r in (st["window"] if st else [])
            if r["name"] == args["span"] and args["num"] in r["attrs"] and args["den"] in r["attrs"]]
    den = sum(a[args["den"]] for a in rows)
    return 100.0 * sum(a[args["num"]] for a in rows) / den if den > 0 else None


def load_max_over_mean(ctx, args):
    """The busiest held expert's tokens (``moe_max_load``) over the held
    experts' mean (``moe_held`` / the configuration's ``n_routed_experts``),
    summed over the expert layers and the window's steps (1 = even)."""
    pct = window_attr_ratio(ctx, {"span": "llm.step", "num": "moe_max_load", "den": "moe_held"})
    return None if pct is None else pct / 100.0 * ctx["config"]["n_routed_experts"]


def setup_span_s(ctx, args):
    """Seconds inside set-up's spans of the given names (those the cell's
    entry points opened)."""
    st = load(ctx)
    mine = [r["dur_ns"] for r in (st["setup"] if st else []) if r["name"] in args["spans"]]
    return sum(mine) / 1e9 if mine else None
