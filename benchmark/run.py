"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never opens a JAX backend: the workers it starts hold the chips.
It reads the cell from BENCHMARK.json (the configuration's and the traffic
mix's files by their names), starts the runtime, lets the module for the
mix's kind (benchmark/<kind>_cell.py) reach the chip through the entry points
a user calls, and prints one JSON object as the last line of its output:
the cell's end-to-end metrics with --trace 0, its per-layer metrics with
--trace 1. Without a TPU, with fewer chips than the cell asks for, or on a
device kind the table of peaks does not hold, it exits non-zero and prints
no result. There is no list of cells, configurations or metrics in this
file: a new one is new files and one new entry in BENCHMARK.json.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import tempfile
import time
import traceback
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def process_start_wall() -> float:
    """When this process started, on time.time()'s clock."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))


def read_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str, rehearsal: bool) -> Dict[str, Any]:
    """The cell as data: its entry, its configuration, its traffic mix, and
    the metrics BENCHMARK.json lists for it. A rehearsal swaps in what
    benchmark/rehearsal/cells/<workload>.json says, and may bring an entry
    of its own for a mix that no cell runs yet."""
    bench = read_json(ROOT, "BENCHMARK.json")
    swap = (read_json(HERE, "rehearsal", "cells", workload + ".json")
            if rehearsal else {})
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 swap.get("entry"))
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    config = read_json(ROOT, config_entry["file"])
    mix = read_json(HERE, "traffic", entry["traffic"] + ".json")
    if rehearsal:
        config = read_json(HERE, "rehearsal", "configs",
                           swap["config"] + ".json")
        mix = _merged(mix, swap["traffic"])

    def mine(metric):
        return workload in metric.get("workloads", [workload])
    return {"name": workload, "chips": entry["chips"], "config": config,
            "traffic": mix, "platform": "cpu" if rehearsal else "tpu",
            "trace_dir": os.path.join(ROOT, ".bench_trace", workload),
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def _merged(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = (_merged(base[k], v)
                  if isinstance(v, dict) and isinstance(base.get(k), dict)
                  else v)
    return out


def per_layer_values(cell: Dict[str, Any], summary: Dict[str, Any]
                     ) -> Dict[str, float]:
    """Each per-layer metric through its own reader: benchmark/metrics/
    <metric>.json names a module under benchmark/readers/ and its
    arguments; <quantity>.<cells> (one quantity split by the end-to-end
    metric it moves) reads <quantity>.json. A reader gets the run's summary
    (counters, series, the reduced trace, the peak, the device, the
    configuration and the traffic mix). One that finds nothing returns None
    and the metric is left out."""
    values = {}
    for metric in cell["per_layer"]:
        spec = read_json(HERE, "metrics",
                         metric["name"].split(".")[0] + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(summary, spec.get("args", {}))
        if value is not None:
            values[metric["name"]] = float(value)
    return values


def run_cell(workload: str, seed: int, seconds: int, trace: bool,
             rehearsal: bool = False) -> Dict[str, Any]:
    """Run the cell, print its lines, and return the last one."""
    started = process_start_wall()
    cell = load_cell(workload, rehearsal)
    kind = importlib.import_module(
        f"benchmark.{cell['traffic']['kind']}_cell")
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)

    # the runtime's session directory (logs) goes under TMPDIR, which the
    # driver gives each side its own, not to the fixed /tmp/ray_tpu_sessions
    os.environ.setdefault("RAY_TPU_SESSION_DIR_ROOT", os.path.join(
        tempfile.gettempdir(), "ray_tpu_sessions"))
    import ray_tpu
    from benchmark import model
    from ray_tpu import serve
    if rehearsal:
        ray_tpu.init(num_cpus=4, num_tpus=cell["chips"])
    else:
        ray_tpu.init()
    try:
        have = ray_tpu.cluster_resources().get("TPU", 0)
        if have < cell["chips"]:
            raise RuntimeError(f"the runtime found {have} TPU chips, the "
                               f"cell needs {cell['chips']}")
        facts = kind.run(cell, args)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise RuntimeError("the benchmark's own process opened a JAX backend")

    device = facts["device"]
    peak = None if rehearsal else model.peak(device["kind"])
    summary = kind.summarize(cell, facts)
    setup_s = facts["window"]["wall_start"] - started
    summary["end_to_end"]["setup_s"] = setup_s
    summary["counters"].update(
        worker_open_s=device["worker_open_s"],
        setup_compile_s=facts["setup"]["compile_s"],
        setup_cache_misses=facts["setup"]["cache_misses"],
        memory_peak_bytes=facts["memory"]["memory_peak_bytes"],
        memory_limit_bytes=facts["memory"]["memory_limit_bytes"],
        chips=cell["chips"])
    summary.update(trace=facts.get("trace"), peak=peak, device=device,
                   config=cell["config"], traffic=cell["traffic"])

    facts["timeline"][0][1] -= started   # seconds after process start
    print(json.dumps({"info": summary["info"], "check": facts["check"],
                      "setup": facts["setup"], "setup_s": setup_s,
                      "timeline": facts["timeline"],
                      "memory": facts["memory"],
                      "regions": (facts.get("trace") or {}).get("regions"),
                      "errors": summary["errors"]}), flush=True)
    if trace:
        values = per_layer_values(cell, summary)
    else:
        values = {m["name"]: summary["end_to_end"].get(m["name"])
                  for m in cell["end_to_end"]}
        missing = [k for k, v in values.items() if v is None]
        if missing:
            raise RuntimeError(f"no reading for {missing}")
    units = {m["name"]: m["unit"]
             for m in cell["end_to_end"] + cell["per_layer"]}
    prefix = "rehearsal." if rehearsal else ""
    line = {"correct": summary["correct"], "attempted": summary["attempted"],
            "failed": summary["failed"],
            "metrics": {prefix + k: {"value": v, "unit": units[k]}
                        for k, v in values.items()},
            "device": {"platform": device["platform"], "kind": device["kind"],
                       "count": cell["chips"],
                       "memory_peak_bytes":
                           facts["memory"]["memory_peak_bytes"]}}
    if trace and facts.get("trace"):
        line["device"]["busy_s"] = facts["trace"]["busy_s"]
        line["device"]["window_s"] = facts["trace"]["window_s"]
        line["breakdown"] = facts["trace"]["breakdown"]
    print(json.dumps(line), flush=True)
    return line


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=int, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    a = parser.parse_args(argv)
    try:
        run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
        return 0
    except Exception:  # the program's boundary: no result line, exit 1
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
