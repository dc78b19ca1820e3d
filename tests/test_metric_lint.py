"""Global-registry metric lint (ISSUE 3 satellite; ISSUE 5 moved the
name/label rule into the static engine as GL005).

The namespace rule itself now lives in
``fedml_tpu/analysis/rules/gl005_metrics.py`` and runs over every module in
tier-1 via ``fedml-tpu lint`` — this file DELEGATES to it (same compiled
regexes, plus a whole-package static pass) and keeps the complementary
RUNTIME checks the static rule cannot do: families registered with computed
names, and re-registration conflict behavior of the live registry.
"""

import importlib

import pytest

from fedml_tpu.analysis.rules.gl005_metrics import (
    LABEL_RE as _LABEL,
    METRIC_NAME_RE as _NAME,
    MetricNamespaceRule,
)

#: every module that registers families in the global registry — extend this
#: list when instrumenting a new layer
INSTRUMENTED_MODULES = [
    "fedml_tpu.comm.base",
    "fedml_tpu.comm.chaos",
    "fedml_tpu.comm.codecs",
    "fedml_tpu.core.aot",
    "fedml_tpu.cross_silo.async_server",
    "fedml_tpu.cross_silo.client_journal",
    "fedml_tpu.cross_silo.journal",
    "fedml_tpu.cross_silo.runtime",
    "fedml_tpu.cross_silo.server",
    "fedml_tpu.sched.multi_tenant",
    "fedml_tpu.obs.flight",
    "fedml_tpu.obs.health",
    "fedml_tpu.obs.otlp",
    "fedml_tpu.obs.trace",
    "fedml_tpu.obs.remote",
    "fedml_tpu.obs.slo",
    "fedml_tpu.obs.timeline",
    "fedml_tpu.ops.pallas.timing",
    "fedml_tpu.population.cohorts",
    "fedml_tpu.population.store",
    "fedml_tpu.serving.batcher",
    "fedml_tpu.serving.gateway",
    "fedml_tpu.serving.publisher",
    "fedml_tpu.sim.engine",
]


def test_static_gl005_pass_over_package_is_clean():
    """The engine's own rule over the real package: every literal
    REGISTRY.counter/gauge/histogram registration anywhere in fedml_tpu/
    (imported by a test or not) is fedml_-namespaced with valid labels."""
    from pathlib import Path

    from fedml_tpu.analysis.engine import run_lint

    pkg = Path(importlib.import_module("fedml_tpu").__file__).parent
    result = run_lint(pkg, rules=[MetricNamespaceRule()],
                      baseline=pkg / "analysis" / "baseline.json")
    assert result.ok, "\n" + result.render()


def test_global_registry_names_are_namespaced_and_unique():
    for mod in INSTRUMENTED_MODULES:
        importlib.import_module(mod)
    from fedml_tpu.obs.registry import REGISTRY

    families = REGISTRY.snapshot()
    assert families, "instrumented modules registered nothing?"
    names = [fam["name"] for fam in families]
    for fam in families:
        assert _NAME.fullmatch(fam["name"]), (
            f"metric {fam['name']!r} violates the fedml_[a-z0-9_]+ namespace")
        assert fam["kind"] in ("counter", "gauge", "histogram"), fam
        for label in fam["labels"]:
            assert _LABEL.fullmatch(label), (fam["name"], label)
            assert label != "le", f"{fam['name']}: 'le' is reserved for histograms"
    # one family per name — the registry's dict keying guarantees it; keep
    # the invariant asserted so a refactor can't silently lose it
    assert len(names) == len(set(names))


def test_comm_compression_families_registered():
    """ISSUE-4 families must exist under the fedml_comm_*/fedml_crosssilo_*
    namespaces (the lint above then validates their shapes)."""
    for mod in INSTRUMENTED_MODULES:
        importlib.import_module(mod)
    from fedml_tpu.obs.registry import REGISTRY

    names = {fam["name"] for fam in REGISTRY.snapshot()}
    for required in (
        "fedml_comm_payload_bytes_total",
        "fedml_comm_payload_raw_bytes_total",
        "fedml_comm_compression_ratio",
        "fedml_crosssilo_buffered_updates_peak",
    ):
        assert required in names, f"{required} not registered"


def test_conflicting_reregistration_is_refused():
    """No metric can be registered twice with a conflicting type or label
    set — same-spec re-registration returns the SAME family object."""
    for mod in INSTRUMENTED_MODULES:
        importlib.import_module(mod)
    from fedml_tpu.obs.registry import REGISTRY

    cls_for = {"counter": REGISTRY.counter, "gauge": REGISTRY.gauge,
               "histogram": REGISTRY.histogram}
    for fam in REGISTRY.snapshot():
        # same spec -> same object
        metric = REGISTRY.get(fam["name"])
        assert cls_for[fam["kind"]](fam["name"], labels=tuple(fam["labels"])) is metric
        # conflicting labels -> loud failure
        with pytest.raises(ValueError):
            cls_for[fam["kind"]](fam["name"], labels=tuple(fam["labels"]) + ("rogue",))
        # conflicting type -> loud failure
        other = REGISTRY.gauge if fam["kind"] != "gauge" else REGISTRY.counter
        with pytest.raises(ValueError):
            other(fam["name"], labels=tuple(fam["labels"]))