"""Self-check of the yardstick, run at the start of every CPU rehearsal and
by ``python3 benchmark/selfcheck.py``.

- the trace reducer, on a small recorded trace (``fixtures/trace_small.json``:
  one training step of cell ``mistral7b_d2.sft_2k`` as the v5e's profiler
  gave it), returns the busy-union, idle share and per-op sums written
  beside the events; the expected numbers were worked out by the
  brute-force sweep below, not by the reducer;
- the FLOP and byte functions give 698M / 1.57G parameters and ResNet-20's
  245.1 MFLOP a sample to within 1%;
- every name and unit of ``BENCHMARK.json`` keeps to the contract's letters;
  ``per_layer`` holds at most 128 entries under names of their own, each with
  a file whose reader resolves, every cell it lists is a cell, and no two
  entries read the same number: the same reader with the same arguments,
  moving the same end-to-end metric (a new cell joins a metric's
  ``workloads`` in ``BENCHMARK.json``, which alone holds that list).
"""

from __future__ import annotations

import importlib
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import bench_trace
import flops

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MAX_PER_LAYER = 128
# tests/test_granite.py, which a benchmark PR may not edit, pins 22 granite.*
# entries: these read what llm.* and step.* entries read, and go when a PR
# that may edit that test lists the Granite cell in those entries instead
PINNED_COPIES = frozenset(
    [f"granite.{m}" for m in ("mfu", "step_ms_p50", "step_ms_p90", "device_idle_share", "hbm_peak_gb",
                              "matmul_share", "xla_matmul_roofline", "host_ms_p50", "host_ms_max",
                              "idle_between_steps_share", "idle_h2d_share", "idle_dispatch_share",
                              "idle_sync_share", "window_compiles")]
    + ["granite.mamba_step_share", "granite.ssd_step_share", "granite.attention_step_share"])


def sweep_busy(intervals, lo, hi):
    """Brute force: sort the end points and add up the stretches covered."""
    points = sorted({lo, hi, *[p for a, b in intervals for p in (a, b) if lo < p < hi]})
    covered = 0.0
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e in intervals):
            covered += b - a
    return covered


def check_trace() -> None:
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as fh:
        fx = json.load(fh)
    ev, want = fx["events"], fx["expected"]
    got = bench_trace.busy(ev)
    lo, hi = bench_trace.window_of(ev)
    ops = [e for e in ev if e.get("line") == bench_trace.OPS_LINE]
    brute = sweep_busy([(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops], lo, hi) / 1e9
    assert abs(got["busy_s"] - brute) < 1e-9, (got["busy_s"], brute)
    assert abs(got["busy_s"] - want["busy_s"]) < 1e-9, (got, want)
    assert abs(got["window_s"] - want["window_s"]) < 1e-9, (got, want)
    idle = 100.0 * (1.0 - got["busy_s"] / got["window_s"])
    assert abs(idle - want["idle_share_pct"]) < 1e-6, (idle, want)
    sums = bench_trace.op_seconds(ev)
    for name, sec in want["op_seconds"].items():
        assert abs(sums[name] - sec) < 1e-9, (name, sums[name], sec)
    gaps = bench_trace.idle_gaps_by_span(ev)
    assert abs(sum(gaps.values()) - (got["window_s"] - got["busy_s"])) < 1e-9, gaps
    for name, sec in want["idle_gaps"].items():
        assert abs(gaps[name] - sec) < 1e-9, (name, gaps, sec)


def check_flops() -> None:
    base = {"hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
            "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32000}
    n2 = flops.transformer_param_counts({**base, "num_hidden_layers": 2})["total"]
    n6 = flops.transformer_param_counts({**base, "num_hidden_layers": 6})["total"]
    assert abs(n2 - 698e6) < 1e6 and abs(n6 - 1.571e9) < 1e6, (n2, n6)
    r = flops.resnet20_train_flops_per_sample()
    assert abs(r - 245.1e6) / 245.1e6 < 0.01, r
    mm = flops.transformer_step_matmuls({**base, "num_hidden_layers": 2}, 4, 2048)
    per_token = sum(f for f, _ in mm) / (4 * 2048)
    want = flops.transformer_train_flops_per_token({**base, "num_hidden_layers": 2}, 2048)
    assert abs(per_token - want) / want < 1e-9, (per_token, want)
    conv = sum(f for f, _ in flops.resnet20_step_convs(1))
    assert abs(conv - r) / r < 1e-9, (conv, r)


def check_names() -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in b[group]:
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]), w
        for kind, key in (("configs", "config"), ("traffic", "traffic")):
            assert os.path.exists(os.path.join(HERE, kind, w[key] + ".json")), w[key]
        assert os.path.exists(os.path.join(HERE, "limits", w["name"] + ".json")), w["name"]
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells, (m["name"], m["workloads"])
    check_per_layer(b)


def _metric_spec(name: str) -> dict:
    with open(os.path.join(HERE, "metrics", name + ".json")) as fh:
        return json.load(fh)


def check_per_layer(b: dict) -> None:
    names = [m["name"] for m in b["per_layer"]]
    assert len(names) <= MAX_PER_LAYER, len(names)
    assert len(set(names)) == len(names), sorted(n for n in set(names) if names.count(n) > 1)
    cells = {w["name"] for w in b["workloads"]}
    by_reader: dict = {}
    for m in b["per_layer"]:
        assert any(e["name"] == m["moves"] for e in b["end_to_end"]), m
        assert set(m.get("workloads", cells)) <= cells, (m["name"], m["workloads"])
        spec = _metric_spec(m["name"])
        mod, _, fn = spec["reader"].partition(":")
        assert callable(getattr(importlib.import_module(mod), fn, None)), (m["name"], spec["reader"])
        # the list is BENCHMARK.json's; a copy in the file may only repeat it
        assert spec.get("workloads", m.get("workloads")) == m.get("workloads"), m["name"]
        key = (spec["reader"], json.dumps(spec.get("args", {}), sort_keys=True), m["moves"])
        by_reader.setdefault(key, []).append(m["name"])
    copies = {k: v for k, v in by_reader.items() if len([n for n in v if n not in PINNED_COPIES]) > 1}
    assert not copies, f"entries that read the same number: {list(copies.values())}"


def run() -> None:
    check_trace()
    check_flops()
    check_names()


if __name__ == "__main__":
    run()
    print("selfcheck ok")
