"""Tests of ``per_layer``'s shape and of the readers that read a part inside
the step (on the CPU, at sizes a test run can hold):

    python3 -m pytest benchmark/test_per_layer.py -q

- ``selfcheck.check_names`` passes on ``BENCHMARK.json`` as committed, and
  refuses more than 128 entries, a name twice, a reader that does not resolve,
  a cell that does not exist, and a second entry that reads the same number
  (the same reader, arguments and end-to-end metric) as another;
- ``program_scopes.scope_roofline`` on a hand-made context (the recorded trace
  ``fixtures/trace_small.json`` and a map of two of its instructions) gives
  known work over known seconds, and ``None``, never 0, where nothing is
  selected or no work was required;
- each language-model cell's driver, at its rehearsal sizes, hands the window
  every ``roofline_work`` entry that a metric listing the cell names.
"""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import selfcheck  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    BENCH = json.load(_fh)


def _spec(name: str) -> dict:
    with open(os.path.join(HERE, "metrics", name + ".json")) as fh:
        return json.load(fh)


# ------------------------------------------------------------- the shape
def test_benchmark_as_committed_passes():
    selfcheck.check_names()
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) <= selfcheck.MAX_PER_LAYER and len(set(names)) == len(names)
    # every pinned copy stands beside the one entry it copies
    key = lambda m: (_spec(m["name"])["reader"], _spec(m["name"]).get("args"), m["moves"])
    for pinned in (m for m in BENCH["per_layer"] if m["name"] in selfcheck.PINNED_COPIES):
        twins = [m["name"] for m in BENCH["per_layer"]
                 if m["name"] not in selfcheck.PINNED_COPIES and key(m) == key(pinned)]
        assert len(twins) == 1, (pinned["name"], twins)
    assert len(selfcheck.PINNED_COPIES) == sum(m["name"] in selfcheck.PINNED_COPIES for m in BENCH["per_layer"])


def _broken(how: str, monkeypatch) -> dict:
    b = copy.deepcopy(BENCH)
    llm_mfu = next(m for m in b["per_layer"] if m["name"] == "llm.mfu")
    if how == "a_copy":
        b["per_layer"].append({**llm_mfu, "name": "other.mfu"})
        monkeypatch.setattr(selfcheck, "_metric_spec", lambda n: _spec("llm.mfu" if n == "other.mfu" else n))
    elif how == "too_many":
        b["per_layer"] += [{**llm_mfu, "name": f"other.{i}"} for i in range(129 - len(b["per_layer"]))]
        monkeypatch.setattr(selfcheck, "_metric_spec", lambda n: {**_spec("llm.mfu"), "args": {"n": n}}
                            if n.startswith("other.") else _spec(n))
    elif how == "a_name_twice":
        b["per_layer"].append(dict(llm_mfu))
    elif how == "no_such_reader":
        monkeypatch.setattr(selfcheck, "_metric_spec", lambda n: {**_spec(n), "reader": "readers:no_such_reader"}
                            if n == "llm.mfu" else _spec(n))
    elif how == "no_such_cell":
        llm_mfu["workloads"] = [*llm_mfu["workloads"], "no_such.cell"]
    elif how == "a_stale_copy_of_the_list":
        monkeypatch.setattr(selfcheck, "_metric_spec", lambda n: {**_spec(n), "workloads": ["mistral7b_d2.sft_2k"]}
                            if n == "llm.mfu" else _spec(n))
    return b


@pytest.mark.parametrize("how", ["a_copy", "too_many", "a_name_twice", "no_such_reader", "no_such_cell",
                                 "a_stale_copy_of_the_list"])
def test_check_refuses(how, monkeypatch):
    b = _broken(how, monkeypatch)
    with pytest.raises(AssertionError):
        selfcheck.check_per_layer(b)


# ------------------------------------------- a part's roofline in the step
class _Text:
    """What ``obs/scopes.scope_map`` takes from a compiled program."""

    def __init__(self, *instructions):
        self.text = "ENTRY %main (p: f32[2]) -> f32[2] {\n" + "".join(
            f'  %{name} = f32[2]{{0}} add(%p, %p), metadata={{op_name="{op_name}"}}\n'
            for name, op_name in instructions) + "}\n"

    def as_text(self):
        return self.text


SSD_OP, CONV_OP = "fusion.1018", "dynamic-slice_dynamic-update-slice_fusion.10"
SSD_S, CONV_S = 0.00122096, 0.000144337          # their seconds in the fixture
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    import program_scopes
    from fedml_tpu.obs import scopes

    monkeypatch.setattr(program_scopes, "ROOT", str(tmp_path))
    scopes.note_program("t.roofline", _Text(
        (SSD_OP, "jit(step)/llm.fwd_bwd/llm.mixer.mamba/llm.mixer.mamba.ssd/mul"),
        (CONV_OP, "jit(step)/llm.fwd_bwd/llm.mixer.mamba/llm.mixer.mamba.conv/add")))
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as fh:
        events = json.load(fh)["events"]
    # 2 steps of one piece bound by its FLOPs, 1 of one bound by its bytes
    work = {"ssd": [([(0.25 * SSD_S * PEAKS["bf16_flops"], 1.0)], 2)],
            "conv": [([(1.0, 0.5 * CONV_S * PEAKS["hbm_bytes_per_s"])], 1)],
            "none": [([(0.0, 0.0)], 3)]}
    return {"events": events, "cell": {"name": "t.roofline"}, "peaks": PEAKS,
            "window": {"roofline_work": work}}


def test_scope_roofline_is_known_work_over_known_seconds(ctx):
    import program_scopes

    read = lambda **a: program_scopes.scope_roofline(ctx, {"program": "t.roofline", **a})
    assert read(scope="llm.mixer.mamba.ssd", work="ssd") == pytest.approx(50.0)
    assert read(scope="llm.mixer.mamba.conv", work="conv") == pytest.approx(50.0)
    both = 0.5 * SSD_S + 0.5 * CONV_S
    assert read(scope="llm.mixer.mamba", work="ssd") == pytest.approx(100 * 0.5 * SSD_S / (SSD_S + CONV_S))
    assert read(scope="llm.mixer.mamba", work="ssd") + read(scope="llm.mixer.mamba", work="conv") \
        == pytest.approx(100 * both / (SSD_S + CONV_S))
    # the same join as scope_share's
    share = program_scopes.scope_share(ctx, {"program": "t.roofline", "scope": "llm.mixer.mamba.ssd"})
    total = sum(program_scopes.bench_trace.op_seconds(ctx["events"]).values())
    assert share == pytest.approx(100 * SSD_S / total)


@pytest.mark.parametrize("why", ["nothing_selected", "no_work_named", "work_needs_nothing", "no_peaks",
                                 "no_map"])
def test_scope_roofline_gives_none_never_zero(ctx, why):
    import program_scopes

    args = {"program": "t.roofline", "scope": "llm.mixer.mamba.ssd", "work": "ssd"}
    if why == "nothing_selected":
        args["scope"] = "llm.mixer.attention"
    elif why == "no_work_named":
        args["work"] = "flash"
    elif why == "work_needs_nothing":
        args["work"] = "none"
    elif why == "no_peaks":
        ctx["peaks"] = None
    else:
        args["program"] = "t.nobody_noted_this"
    assert program_scopes.scope_roofline(ctx, args) is None


# --------------------------------------- the drivers hand over the work
LM_CELLS = [w["name"] for w in BENCH["workloads"] if w["config"] != "resnet20_cifar10_fedavg"]


@pytest.mark.parametrize("cell", LM_CELLS)
def test_driver_hands_the_window_every_work_its_metrics_name(cell):
    import importlib

    import jax
    import readers
    from run import load_cell

    _, entry, config, traffic, _ = load_cell(cell, rehearse_cpu=True)
    named = {_spec(m["name"]).get("args", {}).get("work") for m in BENCH["per_layer"]
             if cell in m.get("workloads", [cell])} - {None}
    driver = importlib.import_module(traffic["driver"]).Driver(entry, config, traffic, 5, jax.devices()[:1])
    driver.build()
    driver.first_steps()
    window = driver.window(0.2)
    assert named <= set(window["roofline_work"]), (named, set(window["roofline_work"]))
    ctx = {"window": window, "peaks": PEAKS}
    for work in named:
        need = readers.required_seconds(ctx, work)
        # no TPU here: every attention site takes the lax pass, so the kernel required nothing
        assert (need == 0) if work == "flash" else (need > 0), (work, need)
    driver.free()
