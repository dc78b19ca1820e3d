"""Tests of the comparison that decides ``correct``, at sizes a test run can
hold (the configurations' ``rehearsal`` sizes, on the CPU):

    python3 -m pytest benchmark/test_correct.py -q

- the control (the reference in float8 in the program's place) comes out as
  not correct under each cell's limits;
- a whole run with the harness's look for a chip skipped (``--rehearse-cpu``)
  and the timed path broken underneath (a step that returns its state
  unchanged; half of the batch left out) prints ``correct: false``, and the
  same run unbroken prints ``correct: true``.

The benchmark's own runs never run these.  On the chip, at the cells' own
sizes, ``control.py`` and ``run.py --fault`` give the readings the limits in
``benchmark/limits/`` were set from (PERF.md, section 2).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE]

with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    CELLS = [w["name"] for w in json.load(_fh)["workloads"]]


def _last_json(out: str, prefix: str = "") -> dict:
    lines = [l for l in out.splitlines() if l.startswith(prefix + "{")]
    return json.loads(lines[-1][len(prefix):])


def _run(script: str, *args: str) -> str:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, os.path.join(HERE, script), *args, "--rehearse-cpu"],
                       capture_output=True, text=True, env=env, cwd=ROOT, timeout=1500)
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
def test_run_sees_a_broken_timed_path(cell, fault):
    args = ["--workload", cell, "--seed", "7", "--seconds", "1", "--trace", "0"]
    if fault:
        args += ["--fault", fault]
    result = _last_json(_run("run.py", *args))
    assert result["correct"] is (fault is None), result["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    import compare
    from run import load_json

    row = _last_json(_run("control.py", "--workload", cell, "--seeds", "7"), "CONTROL ")
    limits = load_json(HERE, "limits", cell + ".json")["rehearsal"]
    ok, compared = compare.judge(row["control_fp8"], limits)
    assert not ok, compared
    ok, compared = compare.judge(row["fault_half_batch"], limits)
    assert not ok, compared
