"""Readers of the per-layer metrics: one small function each, named by the
metric's file under ``benchmark/metrics/``.  A reader takes the run's context
and the ``args`` of its metric file and returns a number, or ``None`` where it
finds nothing to read (the harness then leaves the metric out of the line).
A share of a roofline or of a peak is never returned as 0.
"""

from __future__ import annotations

import re
import statistics

import bench_trace
import flops


def setup_part(ctx, args):
    return ctx["setup"].get(args["key"])


def piece_ms_quantile(ctx, args):
    """Quantile of the host-clocked pieces (steps, chunks, rounds) in ms;
    ``q`` is 0.5 or 0.9 (the ninth of ten cuts)."""
    xs = ctx["window"].get(args.get("pieces", "pieces_s"))
    if not xs or len(xs) < 2:
        return None
    if args["q"] == 0.5:
        return 1e3 * statistics.median(xs)
    return 1e3 * statistics.quantiles(xs, n=10)[8]


def mfu(ctx, args):
    """Required operations of the whole window (real samples or tokens; no
    remat, no padded lane, causal attention halved) over the window's clock
    and the peak of the chips used."""
    w = ctx["window"]
    peak = ctx["peaks"]["bf16_flops"] * ctx["device"]["count"]
    v = 100.0 * w["flops_required"] / w["clock_s"] / peak
    return v if v > 0 else None


def _selector(args):
    cat = re.compile(args["category"]) if "category" in args else None
    name = re.compile(args["name"]) if "name" in args else None
    return lambda e: bool((cat is None or cat.search(e.get("category", "")))
                          and (name is None or name.search(e["name"])))


def op_share(ctx, args):
    """% of device busy time spent in the selected ops."""
    ev = ctx.get("events")
    if not ev:
        return None
    total = sum(bench_trace.op_seconds(ev).values())
    sel = sum(bench_trace.op_seconds(ev, _selector(args)).values())
    return 100.0 * sel / total if total > 0 and sel > 0 else None


def required_seconds(ctx, work: str) -> float:
    """Least time the chip could take for the window's required ``work``
    (the driver's ``roofline_work[work]``: pieces of products, each piece with
    how many times the window ran it): per product the larger of operations
    over peak and least bytes over HBM peak; 0 where there is none."""
    pieces = ctx["window"].get("roofline_work", {}).get(work)
    if not pieces or not ctx.get("peaks"):
        return 0.0
    return sum(n * flops.roofline_seconds(per_piece, ctx["peaks"]["bf16_flops"],
                                          ctx["peaks"]["hbm_bytes_per_s"])
               for per_piece, n in pieces)


def op_roofline(ctx, args):
    """``required_seconds`` of the metric's ``work`` over the device time of
    the selected ops."""
    ev = ctx.get("events")
    need = required_seconds(ctx, args["work"])
    if not ev or need <= 0:
        return None
    took = sum(bench_trace.op_seconds(ev, _selector(args)).values())
    return 100.0 * need / took if took > 0 else None


def device_idle_share(ctx, args):
    b = ctx.get("busy")
    if not b or b["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])


def hbm_peak_gb(ctx, args):
    return ctx["memory_peak_bytes"] / 1e9 if ctx.get("memory_peak_bytes") else None


def wall_share(ctx, args):
    """% of the window's clock inside the named pieces (e.g. ``evaluate``)."""
    xs = ctx["window"].get(args["pieces"])
    if not xs:
        return None
    return 100.0 * sum(xs) / ctx["window"]["clock_s"]


def collective_exposed_share(ctx, args):
    ev = ctx.get("events")
    if not ev or ctx["device"]["count"] < 2:
        return None
    sel = _selector(args)
    if not any(sel(e) for e in ev if "category" in e):
        return None
    lo, hi = bench_trace.window_of(ev)
    return 100.0 * bench_trace.exposed_seconds(ev, sel) / ((hi - lo) / 1e9)
