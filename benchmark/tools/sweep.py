"""Find an open-loop serve cell's knee once, on the chip, by hand:

    chiprun -- python3 benchmark/tools/sweep.py --workload <cell> --rates 40,80,120 --seconds 10

One deployment, then the cell's own traffic at each rate in turn through
serve_cell.measure. A rate is sustained when its backlog does not grow: the
second half of the window answers no slower than 1.5 x the first, and no
more requests are unanswered at the window's end than one second's arrivals.
The result goes to chiprun_out/sweep_<cell>.json; the builder copies it to
benchmark/sweeps/<cell>.json and writes four fifths of the knee into the
traffic file as a number. run.py never searches for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import run, serve_cell  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=int, default=10)
    parser.add_argument("--seed", type=int, default=2147483659)
    args = parser.parse_args()
    cell = run.load_cell(args.workload, rehearsal=False)

    import ray_tpu
    from ray_tpu import serve
    ray_tpu.init()
    rows = []
    try:
        port, loaded = serve_cell.deploy(cell, args.seed)
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(cell["traffic"], rate_per_s=rate)
            window = serve_cell.measure(port, mix, cell["config"]["vocab_size"],
                                        args.seed, args.seconds)
            load = window.pop("load")
            facts = {"window": window, "check": {"ok": True}}
            s = serve_cell.summarize(dict(cell, traffic=mix), facts)
            info = s["info"]
            growing = bool(
                info["p50_second_half_ms"] is None
                or info["p50_second_half_ms"] > 1.5 * info["p50_first_half_ms"]
                or info["unanswered_at_window_end"] > rate)
            forward = s["series"]["forward_ms"]
            rows.append({
                "rate_per_s": rate, "seconds": args.seconds,
                "p50_ms": s["end_to_end"]["serve_p50_ms"],
                "p95_ms": s["end_to_end"]["serve_p95_ms"],
                "p50_first_half_ms": info["p50_first_half_ms"],
                "p50_second_half_ms": info["p50_second_half_ms"],
                "unanswered_at_window_end": info["unanswered_at_window_end"],
                "failed": s["failed"], "backlog_growing": growing,
                "generator_late_p99_ms": info["generator_late_p99_ms"],
                "batch_mean": sum(s["series"]["batch_requests"]) / max(
                    len(s["series"]["batch_requests"]), 1),
                "forward_ms_median": sorted(forward)[len(forward) // 2]
                if forward else None})
            print(json.dumps(rows[-1]), flush=True)
            del load
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/sweep_{args.workload}.json", "w") as f:
        json.dump({"workload": args.workload, "device": loaded["device"]["kind"],
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
