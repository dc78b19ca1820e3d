"""Performance dashboard over a recorded timeline (ISSUE 18).

``fedml-tpu obs dash`` renders what :mod:`obs/timeline` recorded — no
Grafana, no dependencies: a terminal view (sparklines + tables) and a
fully self-contained HTML file (inline CSS + SVG, openable from disk).

Panels, each computed once in :func:`dash_data` so the two renderers
cannot disagree:

- **round throughput** — windowed rate of the sync round histogram's
  count and the async ``fedml_async_virtual_rounds_total`` counter,
- **comm bytes by tier** — ``fedml_hier_hop_bytes_total{hop=...}`` and
  flat-path payload counters, differenced over the timeline span,
- **convergence curve** — the tee'd ``(round, test_acc)`` series plus
  first-crossing rounds-to-target,
- **per-tenant rows** — every ``job=`` label value the ScopedRegistry
  stamped, with rounds and SLO breaches per tenant,
- **SLO-breach markers** — sample pairs where any
  ``fedml_slo_breaches_total`` series increased.
"""

from __future__ import annotations

import html as _html
import json
import re
import time
from typing import Optional, Sequence

from . import timeline as tl

__all__ = ["dash_data", "render_dash_text", "render_dash_html"]

_JOB_RE = re.compile(r"\{(?:[^}]*,)?job=([^,}]+)")
_SPARK_CHARS = "▁▂▃▄▅▆▇█"

#: scalar families summed into the comm-bytes panel when present (flat
#: path; the hier hop counter is matched by prefix, per hop label)
_COMM_FAMILIES = ("fedml_comm_payload_bytes_total",
                  "fedml_comm_payload_raw_bytes_total")


def _spark(values: Sequence[float]) -> str:
    vals = [float(v) for v in values]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _SPARK_CHARS[0] * len(vals)
    return "".join(_SPARK_CHARS[min(7, int((v - lo) / (hi - lo) * 7.999))]
                   for v in vals)


def _fmt_bytes(n: float) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.1f} GiB"


def _series_delta(samples: Sequence[dict], key: str) -> float:
    pts = tl.value_series(samples, key)
    return (pts[-1][1] - pts[0][1]) if len(pts) >= 2 else 0.0


def _hist_count_rate(samples: Sequence[dict], key: str) -> Optional[float]:
    win = [s for s in samples if key in s.get("hists", {})]
    if len(win) < 2:
        return None
    t0, t1 = float(win[0]["ts"]), float(win[-1]["ts"])
    if t1 <= t0:
        return None
    return (win[-1]["hists"][key]["count"] - win[0]["hists"][key]["count"]) / (t1 - t0)


def dash_data(timeline: dict) -> dict:
    """Every panel as plain data — the single computation both renderers
    (and tests) consume.  ``timeline`` is :func:`obs.timeline.load_timeline`
    output (or a live recorder's ``{"samples","rounds","buckets"}``)."""
    samples = list(timeline.get("samples", ()))
    rounds = list(timeline.get("rounds", ()))
    span_s = (float(samples[-1]["ts"]) - float(samples[0]["ts"])
              if len(samples) >= 2 else 0.0)
    all_keys: set[str] = set()
    for s in samples:
        all_keys.update(s.get("scalars", {}))

    # throughput
    rounds_per_s = _hist_count_rate(samples, "fedml_crosssilo_round_seconds")
    versions_per_s = tl.windowed_rate(samples, "fedml_async_virtual_rounds_total")

    # comm bytes by tier
    comm: dict[str, float] = {}
    for key in sorted(all_keys):
        if key.startswith("fedml_hier_hop_bytes_total{"):
            m = re.search(r"hop=([^,}]+)", key)
            delta = _series_delta(samples, key)
            if m and delta:
                comm[m.group(1)] = comm.get(m.group(1), 0.0) + delta
        elif key.split("{", 1)[0] in _COMM_FAMILIES:
            delta = _series_delta(samples, key)
            if delta:
                name = "flat" if "raw" not in key else "flat_raw"
                comm[name] = comm.get(name, 0.0) + delta

    # convergence
    curve = [(r.get("round_idx", r.get("server_version")), r.get("test_acc"))
             for r in rounds]
    curve = [(int(i), float(a)) for i, a in curve if i is not None and a is not None]
    targets = {k: v for k, v in tl.rounds_to_target(rounds).items()
               if v is not None}

    # per-tenant rows
    jobs: dict[str, dict] = {}
    for key in sorted(all_keys):
        m = _JOB_RE.search(key)
        if not m or not m.group(1):
            continue
        job = jobs.setdefault(m.group(1), {"rounds": None, "breaches": 0.0})
        if key.startswith("fedml_mt_job_rounds{"):
            pts = tl.value_series(samples, key)
            if pts:
                job["rounds"] = pts[-1][1]
        elif key.startswith("fedml_slo_breaches_total{"):
            pts = tl.value_series(samples, key)
            if pts:
                job["breaches"] += pts[-1][1]

    # SLO-breach markers: any breach counter increasing between samples
    markers = []
    breach_keys = [k for k in all_keys
                   if k.startswith("fedml_slo_breaches_total")]
    for key in sorted(breach_keys):
        pts = tl.value_series(samples, key)
        for (t0, v0), (t1, v1) in zip(pts, pts[1:]):
            if v1 > v0:
                markers.append({"ts": t1, "series": key, "inc": v1 - v0})
    markers.sort(key=lambda m: m["ts"])

    return {
        "n_samples": len(samples),
        "n_rounds": len(rounds),
        "span_s": round(span_s, 3),
        "skipped_segments": int(timeline.get("skipped", 0)),
        "throughput": {"rounds_per_s": rounds_per_s,
                       "versions_per_s": versions_per_s},
        "comm_bytes": comm,
        "convergence": {"curve": curve, "rounds_to_target": targets},
        "tenants": jobs,
        "slo_markers": markers,
    }


# ---------------------------------------------------------------------------
# terminal rendering


def _num(v, digits: int = 3) -> str:
    return "-" if v is None else f"{float(v):.{digits}f}"


def render_dash_text(timeline: dict) -> str:
    d = dash_data(timeline)
    lines = ["== performance timeline =="]
    lines.append(f"samples: {d['n_samples']}  rounds: {d['n_rounds']}  "
                 f"span: {d['span_s']}s  skipped segments: "
                 f"{d['skipped_segments']}")
    t = d["throughput"]
    lines.append(f"throughput: rounds/s {_num(t['rounds_per_s'])}  "
                 f"versions/s {_num(t['versions_per_s'])}")
    if d["comm_bytes"]:
        lines.append("")
        lines.append("comm bytes by tier:")
        for hop, b in sorted(d["comm_bytes"].items()):
            lines.append(f"  {hop:<12} {_fmt_bytes(b)}")
    curve = d["convergence"]["curve"]
    if curve:
        lines.append("")
        lines.append(f"convergence ({len(curve)} evals): "
                     f"{_spark([a for _, a in curve])}  "
                     f"last acc {curve[-1][1]:.4f} @ round {curve[-1][0]}")
        for target, rnd in sorted(d["convergence"]["rounds_to_target"].items()):
            lines.append(f"  target {target}: round {rnd:g}")
    if d["tenants"]:
        lines.append("")
        lines.append("tenants:")
        for job, row in sorted(d["tenants"].items()):
            lines.append(f"  job {job:<10} rounds {_num(row['rounds'], 0)}  "
                         f"slo breaches {row['breaches']:g}")
    if d["slo_markers"]:
        lines.append("")
        lines.append(f"slo breaches ({len(d['slo_markers'])}):")
        for m in d["slo_markers"][:10]:
            lines.append(f"  +{m['inc']:g} {m['series']}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# self-contained HTML


_CSS = """
body{font:14px/1.45 system-ui,sans-serif;margin:2em;background:#fafafa;color:#222}
h1{font-size:1.3em}h2{font-size:1.05em;margin-top:1.6em}
table{border-collapse:collapse;margin:.5em 0}
td,th{border:1px solid #ccc;padding:.25em .6em;text-align:right}
th{background:#eee}td:first-child,th:first-child{text-align:left}
svg{background:#fff;border:1px solid #ccc}
.mark{color:#b00;font-weight:bold}
"""


def _svg_curve(points: Sequence[tuple[float, float]], *, w: int = 560,
               h: int = 160, markers: Sequence[float] = ()) -> str:
    if not points:
        return ""
    xs = [float(p[0]) for p in points]
    ys = [float(p[1]) for p in points]
    x0, x1 = min(xs), max(xs)
    y0, y1 = min(ys), max(ys)
    xr = (x1 - x0) or 1.0
    yr = (y1 - y0) or 1.0
    pad = 8

    def px(x):
        return pad + (x - x0) / xr * (w - 2 * pad)

    def py(y):
        return h - pad - (y - y0) / yr * (h - 2 * pad)

    pts = " ".join(f"{px(x):.1f},{py(y):.1f}" for x, y in points)
    marks = "".join(
        f'<line x1="{px(m):.1f}" y1="0" x2="{px(m):.1f}" y2="{h}" '
        f'stroke="#b00" stroke-dasharray="3,3"/>'
        for m in markers if x0 <= m <= x1)
    return (f'<svg width="{w}" height="{h}" viewBox="0 0 {w} {h}">'
            f'{marks}<polyline points="{pts}" fill="none" stroke="#07c" '
            f'stroke-width="1.5"/></svg>'
            f'<div>y: [{y0:.4g}, {y1:.4g}]  x: [{x0:.4g}, {x1:.4g}]</div>')


def _table(headers: Sequence[str], rows: Sequence[Sequence]) -> str:
    head = "".join(f"<th>{_html.escape(str(hh))}</th>" for hh in headers)
    body = "".join(
        "<tr>" + "".join(f"<td>{_html.escape(str(c))}</td>" for c in row) + "</tr>"
        for row in rows)
    return f"<table><tr>{head}</tr>{body}</table>"


def render_dash_html(timeline: dict,
                     title: str = "fedml-tpu performance timeline") -> str:
    d = dash_data(timeline)
    out = [f"<!doctype html><html><head><meta charset='utf-8'>"
           f"<title>{_html.escape(title)}</title><style>{_CSS}</style></head>"
           f"<body><h1>{_html.escape(title)}</h1>"]
    out.append(
        f"<p>{d['n_samples']} samples · {d['n_rounds']} rounds · "
        f"{d['span_s']}s span · generated "
        f"{time.strftime('%Y-%m-%d %H:%M:%S')}</p>")
    t = d["throughput"]
    out.append("<h2>Throughput</h2>")
    out.append(_table(["series", "per second"], [
        ["rounds/s (sync)", _num(t["rounds_per_s"])],
        ["versions/s (async)", _num(t["versions_per_s"])]]))
    if d["comm_bytes"]:
        out.append("<h2>Comm bytes by tier</h2>")
        out.append(_table(["tier", "bytes"], [
            [hop, _fmt_bytes(b)] for hop, b in sorted(d["comm_bytes"].items())]))
    curve = d["convergence"]["curve"]
    if curve:
        out.append("<h2>Convergence</h2>")
        marker_rounds = [v for v in d["convergence"]["rounds_to_target"].values()]
        out.append(_svg_curve(curve, markers=marker_rounds))
        if d["convergence"]["rounds_to_target"]:
            out.append(_table(["accuracy target", "first round"], [
                [k, f"{v:g}"] for k, v in
                sorted(d["convergence"]["rounds_to_target"].items())]))
    if d["tenants"]:
        out.append("<h2>Tenants</h2>")
        out.append(_table(["job", "rounds", "SLO breaches"], [
            [job, _num(row["rounds"], 0), f"{row['breaches']:g}"]
            for job, row in sorted(d["tenants"].items())]))
    if d["slo_markers"]:
        out.append("<h2>SLO breaches</h2>")
        out.append(_table(["ts", "series", "increase"], [
            [f"{m['ts']:.3f}", m["series"], f"{m['inc']:g}"]
            for m in d["slo_markers"]]))
    out.append("<details><summary>raw panel data</summary><pre>"
               + _html.escape(json.dumps(d, indent=1, default=str))
               + "</pre></details>")
    out.append("</body></html>")
    return "".join(out)
