"""Self-check of the yardstick, run at the start of every CPU rehearsal and
by ``python3 benchmark/selfcheck.py``.

- the trace reducer, on a small recorded trace (``fixtures/trace_small.json``:
  one training step of cell ``mistral7b_d2.sft_2k`` as the v5e's profiler
  gave it), returns the busy-union, idle share and per-op sums written
  beside the events; the expected numbers were worked out by the
  brute-force sweep below, not by the reducer;
- the FLOP and byte functions give 698M / 1.57G parameters and ResNet-20's
  245.1 MFLOP a sample to within 1%;
- every name and unit of ``BENCHMARK.json`` keeps to the contract's letters.
"""

from __future__ import annotations

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
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(HERE, "metrics", m["name"] + ".json")), m["name"]
        assert any(e["name"] == m["moves"] for e in b["end_to_end"]), m


def run() -> None:
    check_trace()
    check_flops()
    check_names()


if __name__ == "__main__":
    run()
    print("selfcheck ok")
