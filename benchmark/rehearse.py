"""Rehearse every cell on the CPU at a tiny size, through run.py's own code.

    python3 benchmark/rehearse.py [workload ...] [--seconds 3] [--trace 1]

Finds wrong paths, arguments and control flow at no chip time: the tiny
configuration and the cut-down traffic of benchmark/rehearsal/, four virtual
CPU devices, a TPU resource that is only claimed. Every metric it prints is
named rehearsal.<metric> and the device says "cpu": nothing here is a
measurement. run.py itself never takes this path.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run  # noqa: E402  (the environment comes first)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("workloads", nargs="*")
    parser.add_argument("--seconds", type=int, default=3)
    parser.add_argument("--seed", type=int, default=2147483659)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=None)
    args = parser.parse_args()
    names = args.workloads or sorted(
        f[:-len(".json")]
        for f in os.listdir(os.path.join(HERE, "rehearsal", "cells")))
    failed = []
    for name in names:
        for trace in ([args.trace] if args.trace is not None else [0, 1]):
            print(f"--- rehearsal {name} --trace {trace}", flush=True)
            line = run.run_cell(name, args.seed, args.seconds, bool(trace),
                                rehearsal=True)
            if not line["correct"]:
                failed.append((name, trace))
    print("rehearsal failed:" if failed else "rehearsal passed", failed or "")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
