"""Generated metrics reference (ISSUE 16).

The registry is the single source of truth for every ``fedml_*`` family —
name, kind, labels, help text, histogram buckets all live at the
declaration site.  This module imports every registering module (metric
families register at import time, as module-level constants) and renders
the registry's own snapshot as markdown, so the reference CANNOT drift
from the code: regenerate with

    python -m fedml_tpu.obs.metrics_doc > docs/METRICS.md

A family missing from the doc means its module is missing from
``_REGISTERING_MODULES`` below — the generator prints import failures to
stderr and exits nonzero rather than silently documenting a subset.
"""

from __future__ import annotations

import importlib
import sys

from . import registry as obsreg

#: every module that declares ``fedml_*`` metric families at import time.
#: Keep sorted; the lint-adjacent guarantee is the generator's stderr check,
#: not this list's completeness by inspection.
_REGISTERING_MODULES = (
    "fedml_tpu.analysis.tracesan",
    "fedml_tpu.comm.base",
    "fedml_tpu.comm.chaos",
    "fedml_tpu.comm.codecs",
    "fedml_tpu.core.aot",
    "fedml_tpu.cross_silo.async_server",
    "fedml_tpu.cross_silo.client_journal",
    "fedml_tpu.cross_silo.edge",
    "fedml_tpu.cross_silo.journal",
    "fedml_tpu.cross_silo.runtime",
    "fedml_tpu.cross_silo.server",
    "fedml_tpu.obs.flight",
    "fedml_tpu.obs.health",
    "fedml_tpu.obs.otlp",
    "fedml_tpu.obs.remote",
    "fedml_tpu.obs.slo",
    "fedml_tpu.obs.timeline",
    "fedml_tpu.obs.trace",
    "fedml_tpu.ops.pallas.timing",
    "fedml_tpu.population.cohorts",
    "fedml_tpu.population.store",
    "fedml_tpu.sched.multi_tenant",
    "fedml_tpu.serving.batcher",
    "fedml_tpu.serving.gateway",
    "fedml_tpu.serving.publisher",
    "fedml_tpu.sim.engine",
)

#: section title per family prefix (the token after ``fedml_``); prefixes
#: not listed here land under their raw prefix
_SECTIONS = {
    "aot": "AOT program store",
    "async": "Buffered-async aggregation",
    "chaos": "Chaos injection",
    "client": "Client health + journals",
    "comm": "Communication layer",
    "convergence": "Convergence tracking",
    "crosssilo": "Cross-silo rounds",
    "fleet": "Fleet partition (per-job submeshes)",
    "flight": "Flight recorder",
    "gateway": "Tenant-routed serving gateway",
    "hier": "Hierarchical aggregation tree",
    "journal": "Server recovery journal",
    "mt": "Multi-tenant control plane",
    "obs": "Observability trail shipping",
    "otlp": "OTLP egress",
    "pallas": "Pallas kernels",
    "pop": "Population-scale store",
    "program": "Compiled-program cost model",
    "runtime": "Event-driven runtime",
    "serving": "Serving fleet",
    "sim": "Simulation engine",
    "slo": "SLO watchdog",
    "timeline": "Performance timeline",
    "tracesan": "Runtime trace sanitizer",
    "xla": "XLA program builds (compiles and persistent-cache loads)",
}


def _import_all() -> list[str]:
    """Import every registering module; returns the failures (module:
    error) instead of raising, so the caller can report ALL of them."""
    failures = []
    for mod in _REGISTERING_MODULES:
        try:
            importlib.import_module(mod)
        except Exception as e:  # noqa: BLE001 — the error string IS the report
            failures.append(f"{mod}: {type(e).__name__}: {e}")
    return failures


def _prefix(name: str) -> str:
    parts = name.split("_")
    return parts[1] if len(parts) > 1 and parts[0] == "fedml" else parts[0]


def render_metrics_reference(registry: obsreg.MetricsRegistry | None = None
                             ) -> str:
    """Markdown reference for every registered family, grouped by
    subsystem prefix.  Call after :func:`_import_all` (or after the
    subsystems you care about are imported)."""
    snap = (registry or obsreg.REGISTRY).snapshot()
    by_section: dict[str, list[dict]] = {}
    for fam in snap:
        if not fam["name"].startswith("fedml_"):
            continue
        by_section.setdefault(_prefix(fam["name"]), []).append(fam)
    lines = [
        "# Metrics reference",
        "",
        "Every `fedml_*` family the framework registers, rendered from the",
        "registry's own snapshot (names, kinds, labels, and help text come",
        "from the declaration sites — this file cannot drift from the code).",
        "",
        "Regenerate: `python -m fedml_tpu.obs.metrics_doc > docs/METRICS.md`",
        "",
        "Exposition: `extra.metrics_port` serves the Prometheus text format;",
        "`extra.otlp_endpoint` ships the same families over OTLP (see",
        "`docs/FLAGS.md`).  SLO specs (`extra.slo_specs`) reference these",
        "names directly.",
        "",
    ]
    for prefix in sorted(by_section):
        lines.append(f"## {_SECTIONS.get(prefix, prefix)} (`fedml_{prefix}_*`)")
        lines.append("")
        lines.append("| metric | kind | labels | help |")
        lines.append("|---|---|---|---|")
        for fam in sorted(by_section[prefix], key=lambda f: f["name"]):
            labels = ", ".join(fam.get("labels") or ()) or "—"
            help_text = " ".join(str(fam.get("help", "")).split())
            kind = fam["kind"]
            if kind == "histogram" and fam.get("buckets"):
                b = fam["buckets"]
                kind = f"histogram ({len(b)} buckets ≤ {b[-1]:g})"
            lines.append(
                f"| `{fam['name']}` | {kind} | {labels} | {help_text} |")
        lines.append("")
    return "\n".join(lines)


def main() -> int:
    failures = _import_all()
    if failures:
        for f in failures:
            print(f"metrics_doc: import failed — {f}", file=sys.stderr)
        return 1
    print(render_metrics_reference())
    return 0


if __name__ == "__main__":
    sys.exit(main())
