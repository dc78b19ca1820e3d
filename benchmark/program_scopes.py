"""Reader of the per-layer metrics that give device time by the program's own
named scopes (reader spec ``program_scopes:scope_share``).

The program publishes, for each of its step, chunk and eval programs, which
scope each HLO instruction belongs to (``fedml_tpu/obs/scopes.py``:
``scope_map``, read off the executable's own text after the window and the
check); the traced window's device events carry the same instruction names.
This module joins the two.  A program without ``obs/scopes.py`` (an older
commit), one that published no map, or a map that names under half of the
program's device seconds makes the reader return ``None`` and say why.

Two programs may run in one window (the FedAvg cells' chunk and evaluation)
and their instruction names may collide: ``span`` keeps to the device ops
whose middle lies inside a ``bench.*`` host span of that name, which holds
because each call ends in a sync that drains the device.

The first reader called for a program writes what it found to
``chiprun_out/bench/scopes.<cell>.json``: per program its device seconds,
seconds by innermost scope and pass, the heaviest ops and the heaviest in no
scope with their full ``op_name``, the seconds of ops the map does not name,
and what building the map cost (the program's ``obs.scope_map`` span).
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys
import traceback

import bench_trace
import readers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIN_NAMED = 0.5   # of a program's device seconds, by ops whose name the map holds
HEAVIEST = 25  # ops kept in the record, by seconds
UNSCOPED = 10  # ... and of those in no scope
_STATE = "_program_scopes"


def say(msg: str) -> None:
    print(f"[program_scopes] {msg}", file=sys.stderr, flush=True)


def inside_spans(events: list[dict], name: str):
    """``select(event)``: the device op's middle lies inside a host span
    called ``name``."""
    iv = bench_trace.union_intervals([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                                      for e in events if e["name"] == name and "category" not in e])
    starts = [a for a, _ in iv]

    def select(e: dict) -> bool:
        mid = e["start_ns"] + e["dur_ns"] / 2
        i = bisect.bisect_right(starts, mid) - 1
        return i >= 0 and mid <= iv[i][1]
    return select


def reduce(events: list[dict], scope_map: dict, span: str | None, by) -> dict:
    """One program's part of the window: its ops' seconds (those inside
    ``span`` where one is named), joined with its map.  ``by`` is the
    program's ``device_seconds_by``."""
    secs = bench_trace.op_seconds(events, inside_spans(events, span) if span else None)
    total = sum(secs.values())
    unmapped = sum(s for op, s in secs.items() if op not in scope_map)
    passes = sorted({e["pass"] for e in scope_map.values()} | {""})
    pass_of = lambda op: scope_map[op]["pass"] if op in scope_map else ""
    ranked = sorted(secs.items(), key=lambda kv: -kv[1])
    row = lambda op, s: {"op": op, "seconds": s, **{k: scope_map.get(op, {}).get(k, "")
                                                    for k in ("scope", "pass", "op_name")}}
    return {
        "op_seconds": secs, "map": scope_map, "device_seconds": total, "unmapped_seconds": unmapped,
        "seconds_by_scope_and_pass": {
            p: by({op: s for op, s in secs.items() if pass_of(op) == p}, scope_map, "scope")
            for p in passes},
        "heaviest": [row(op, s) for op, s in ranked[:HEAVIEST]],
        "heaviest_unscoped": [row(op, s) for op, s in ranked
                              if not scope_map.get(op, {}).get("scope")][:UNSCOPED],
    }


def _map_cost(program: str) -> dict | None:
    """What the program's ``obs.scope_map`` span recorded."""
    from fedml_tpu.obs import trace as obstrace

    mine = [s for s in obstrace.recent() if s.name == "obs.scope_map" and s.attrs.get("program") == program]
    return {"seconds": mine[-1].duration_s, **mine[-1].attrs} if mine else None


def write_record(cell: str, state: dict) -> None:
    out_dir = os.path.join(ROOT, "chiprun_out", "bench")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"scopes.{cell}.json"), "w") as fh:
        json.dump({"cell": cell, "window_device_seconds": state["total"],
                   "programs": state["records"]}, fh)


def _join(ctx, state: dict, program: str, span: str | None) -> dict | None:
    try:
        from fedml_tpu.obs import scopes
    except ImportError:  # an older program has no such module
        scopes = None
    events = ctx.get("events")
    scope_map = scopes.scope_map(program) if scopes is not None and events else None
    if scope_map is None:
        say(f"{program}: the program published no scope map")
        return None
    if state["total"] is None:
        state["total"] = sum(bench_trace.op_seconds(events).values())
    st = reduce(events, scope_map, span, scopes.device_seconds_by)
    state["records"].append({"program": program, "span": span, "map_cost": _map_cost(program),
                             **{k: v for k, v in st.items() if k not in ("op_seconds", "map")}})
    write_record(ctx["cell"]["name"], state)
    named = st["device_seconds"] - st["unmapped_seconds"]
    if st["device_seconds"] <= 0 or named < MIN_NAMED * st["device_seconds"]:
        say(f"{program}: the map names {named:.4f} of {st['device_seconds']:.4f} device seconds"
            f"{' inside ' + span if span else ''}: another program's ops, or a map of another compile")
        return None
    return st


def load(ctx, program: str, span: str | None) -> dict | None:
    """The program's reduced part of the window, made once and kept in
    ``ctx``; ``None`` (and why, on stderr) where there is nothing to join."""
    state = ctx.setdefault(_STATE, {"total": None, "records": [], "loaded": {}})
    key = (program, span)
    if key not in state["loaded"]:
        try:
            state["loaded"][key] = _join(ctx, state, program, span)
        except Exception:  # a reader returns nothing rather than fail the run
            traceback.print_exc()
            state["loaded"][key] = None
    return state["loaded"][key]


def selects(entry: dict, args: dict) -> bool:
    """Whether a map entry is one the metric's ``args`` ask for: ``pass``
    (one or a list) where given, and ``match`` (a regular expression over
    ``op_name``) or ``scope`` (a prefix, at a dot, of any of the op's scopes
    that is not among ``other_than``)."""
    if "pass" in args:
        wanted = args["pass"] if isinstance(args["pass"], list) else [args["pass"]]
        if entry["pass"] not in wanted:
            return False
    if "match" in args:
        return re.search(args["match"], entry["op_name"]) is not None
    prefix, skip = args["scope"], args.get("other_than", ())
    return any((s == prefix or s.startswith(prefix + ".")) and s not in skip for s in entry["scopes"])


def selected_seconds(ctx, args) -> float | None:
    """Device seconds of the ops of ``program`` (inside ``span`` where one is
    named) that ``selects`` keeps; ``None`` where there is nothing to join."""
    st = load(ctx, args["program"], args.get("span"))
    if not st:
        return None
    return sum(s for op, s in st["op_seconds"].items() if op in st["map"] and selects(st["map"][op], args))


def scope_share(ctx, args):
    """% of the window's device op seconds (the denominator of
    ``readers:op_share``) spent in the ops that ``selected_seconds`` keeps."""
    mine = selected_seconds(ctx, args)
    total = ctx[_STATE]["total"] if mine is not None else None
    return 100.0 * mine / total if total and mine > 0 else None


def scope_roofline(ctx, args):
    """Least time the chip could take for the window's required ``work``
    (``readers.required_seconds``) over the device seconds of the ops that
    ``selected_seconds`` keeps: a part's roofline read inside the step it ran
    in, its rematerialised forward included."""
    need = readers.required_seconds(ctx, args["work"])
    took = selected_seconds(ctx, args) if need > 0 else None
    return 100.0 * need / took if took else None
