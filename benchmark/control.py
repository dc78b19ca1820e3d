#!/usr/bin/env python3
"""The control and the planted faults of a cell, at the cell's own size.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--faults half_batch,state_unchanged]

For each seed the float32 reference runs once, then in the program's place
the reference in the next precision down (``fp8``), and the reference with
each planted fault (half of each batch left out; the state left unchanged);
the gaps of each against the float32 reference are printed as one JSON line
per seed.  Limits are set from these readings
(the upper ones) and from the program's own runs (the lower ones); the
benchmark's runs never call this.  ``--rehearse-cpu`` as in ``run.py``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="fp8")
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--rehearse-cpu", action="store_true")
    a = ap.parse_args()
    from run import load_cell

    _, cell, config, traffic, _ = load_cell(a.workload, a.rehearse_cpu)
    import jax

    from fedml_tpu.core import cache as progcache

    if not a.rehearse_cpu and jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    progcache.setup_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for seed in (int(s) for s in a.seeds.split(",")):
        driver = importlib.import_module(traffic["driver"]).Driver(
            cell, config, traffic, seed, jax.devices()[: cell["chips"]])
        readings = {"reference": driver.reference()}
        for control in filter(None, a.controls.split(",")):
            readings["control_" + control] = driver.reference(control=control)
        for fault in filter(None, a.faults.split(",")):
            readings["fault_" + fault] = driver.reference(fault=fault)
        row = {"workload": a.workload, "seed": seed}
        row.update({k: driver.gaps(v, readings["reference"])
                    for k, v in readings.items() if k != "reference"})
        print("CONTROL " + json.dumps(row), flush=True)
        out_dir = os.path.join(ROOT, "chiprun_out", "bench")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"control.{a.workload}.seed{seed}.json"), "w") as fh:
            json.dump(readings, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
