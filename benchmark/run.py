#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data: the cell's entry in
``BENCHMARK.json`` names a configuration (``benchmark/configs/<config>.json``)
and a traffic mix (``benchmark/traffic/<traffic>.json``); the traffic file
names the driver (a module under ``benchmark/``) and the end-to-end rate it
reports; ``benchmark/limits/<cell>.json`` holds the limits of the cell's
``correct``; each metric of ``BENCHMARK.json`` that lists the cell has a file
``benchmark/metrics/<metric>.json`` naming its reader.  A later PR adds a
cell or a metric by adding files and entries; nothing here names one.

One process.  Refuses to run off a TPU unless ``--rehearse-cpu`` is given,
which runs the configuration's tiny ``rehearsal`` sizes, reports no device
metric and says so in its line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import gc
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
if HERE not in sys.path:
    sys.path.insert(0, HERE)


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def metric_applies(m: dict, cell: str) -> bool:
    return "workloads" not in m or cell in m["workloads"]


def load_cell(workload: str, rehearse_cpu: bool):
    """``BENCHMARK.json`` and the cell's own files: its entry, configuration,
    traffic mix and limits; ``None`` where no cell has that name.  A CPU
    rehearsal takes the files' tiny ``rehearsal`` sizes and limits, and holds
    JAX to the CPU."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        return None
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    limits = load_json(HERE, "limits", cell["name"] + ".json")
    if rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = {**config, **config.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    return bench, cell, config, traffic, limits["rehearsal" if rehearse_cpu else "limits"]


def resolve(spec: str):
    """``module:function`` under ``benchmark/``."""
    mod, _, fn = spec.partition(":")
    return getattr(importlib.import_module(mod), fn)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny shapes on the CPU; no device metric is reported")
    ap.add_argument("--fault", default=None, choices=("state_unchanged", "half_batch"),
                    help="tests and limit-setting only: break the timed path underneath")
    a = ap.parse_args()

    loaded = load_cell(a.workload, a.rehearse_cpu)
    if loaded is None:
        say(f"no cell {a.workload!r} in BENCHMARK.json")
        return 2
    bench, cell, config, traffic, limits = loaded
    if a.rehearse_cpu:
        import selfcheck

        selfcheck.run()
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={cell['chips']}")

    marks = [("args_and_files", time.perf_counter() - T_PROCESS)]
    import jax

    from fedml_tpu.core import cache as progcache

    marks.append(("import_jax", time.perf_counter() - T_PROCESS))
    devs = jax.devices()
    marks.append(("devices", time.perf_counter() - T_PROCESS))
    if not a.rehearse_cpu and (devs[0].platform != "tpu" or len(devs) < cell["chips"]):
        say(f"cell needs {cell['chips']} TPU chip(s); JAX sees {len(devs)} x {devs[0].platform}")
        return 2
    devices = devs[: cell["chips"]]
    # the program's own rule places the cache: $JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache; every program is kept, however quick
    cache_dir = progcache.setup_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    import bench_trace
    import peaks as peaklib

    peaks = None if a.rehearse_cpu else peaklib.peaks(devices[0].device_kind)

    # ------------------------------------------------------------- set-up
    driver = importlib.import_module(traffic["driver"]).Driver(
        cell, config, traffic, a.seed, devices)
    driver.fault = a.fault
    t = time.perf_counter()
    driver.build()
    build_s = time.perf_counter() - t
    marks.append(("build", time.perf_counter() - T_PROCESS))
    first = driver.first_steps()
    marks.append(("first_steps", time.perf_counter() - T_PROCESS))
    out_dir = os.path.join(ROOT, "chiprun_out", "bench")
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{a.workload}.seed{a.seed}.trace{a.trace}"
    trace_dir = os.path.join(ROOT, ".bench_trace", tag)  # read, sampled and removed below
    if a.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    # set-up's garbage is collected now and its survivors are set aside, so
    # that no collection inside the window has them to walk
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_PROCESS

    # ------------------------------------------------------------- window
    window = driver.window(a.seconds)
    if a.trace:
        jax.profiler.stop_trace()
    stats = [d.memory_stats() or {} for d in devices]
    peak_stats = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    program_memory = driver.program_memory()
    memory_peak = max(peak_stats, program_memory.get("resident_and_temp", 0))

    record = {"workload": a.workload, "seed": a.seed, "seconds": a.seconds, "trace": a.trace,
              "cache_dir": cache_dir,
              "setup": {"build_s": build_s, **first, "setup_s": setup_s, "marks": marks,
                        "driver_marks": getattr(driver, "marks", [])},
              "memory_stats_peak": peak_stats, "program_memory": program_memory,
              "window": {k: v for k, v in window.items() if k != "roofline_work"}}
    with open(os.path.join(out_dir, "pieces." + tag + ".json"), "w") as fh:
        json.dump(record, fh)
    record["window"].pop("losses", None)
    print("PIECES " + json.dumps(record), flush=True)

    # -------------------------------------------------------------- check
    driver.free()
    t = time.perf_counter()
    ok, compared, gaps = driver.check(limits)
    say(f"reference took {time.perf_counter() - t:.1f} s; worst gaps at {gaps.get('_at')}")
    with open(os.path.join(out_dir, "check." + tag + ".json"), "w") as fh:
        json.dump({"program": driver.readings, "reference": driver.reference_readings,
                   "gaps": gaps}, fh)

    # ------------------------------------------------------------ metrics
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(ok), "attempted": window["attempted"], "failed": window["failed"]}
    metrics: dict = {}
    if a.rehearse_cpu:
        result["rehearsal"] = "cpu, tiny shapes: no device metric is reported"
        shutil.rmtree(trace_dir, ignore_errors=True)
    elif not a.trace:
        values = {"setup_s": setup_s, traffic["rate_metric"]: window["work"] / window["clock_s"]}
        for m in bench["end_to_end"]:
            if metric_applies(m, a.workload):
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        events = bench_trace.load_events(bench_trace.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        with open(os.path.join(out_dir, "events." + tag + ".json"), "w") as fh:
            json.dump(bench_trace.sample(events), fh)
        busy = bench_trace.busy(events)
        device.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
        ctx = {"cell": cell, "config": config, "traffic": traffic, "window": window,
               "events": events, "busy": busy, "peaks": peaks, "device": device,
               "memory_peak_bytes": memory_peak,
               "setup": {"build_s": build_s, "setup_s": setup_s,
                         "compile_s": max(first["first_call_s"] - first["steady_s"], 1e-9)}}
        for m in bench["per_layer"]:
            if not metric_applies(m, a.workload):
                continue
            spec = load_json(HERE, "metrics", m["name"] + ".json")
            value = resolve(spec["reader"])(ctx, spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = bench_trace.breakdown(events)
    result.update(metrics=metrics, device=device, compared=compared)
    for name, c in compared.items():
        say(f"compared {name}: {c['value']:.6g} (limit {c['limit']:.6g})")
    say(f"correct={ok}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
