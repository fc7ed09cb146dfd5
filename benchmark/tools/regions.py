"""Where a train cell's device time goes, by the regions the program names.
Run on the chip, by hand:

    chiprun [--chips 4] -- python3 benchmark/tools/regions.py --workload <cell> --seed <n>

One process that holds the cell's chips (this is a tool, not a cell: no
runtime, no trainer). It builds the cell's step from the cell's own
configuration and traffic files as train_cell.train_loop does, warms it up,
times TIMED_STEPS steps untraced, runs the mix's `trace_steps` steps under
ray_tpu.util.profiling.device_trace, and writes what
profiling.device_regions makes of the trace and the compiled step, with
what the tracing cost, to benchmark/regions/<cell>.json. A copy goes to
chiprun_out/regions/ beside the trace itself and the step's HLO text, which
is what a chiprun call brings back; the builder copies the table from there
and commits it.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark import model, run  # noqa: E402
from ray_tpu._private.compile_cache import (  # noqa: E402
    export_compile_cache_dir)

TIMED_STEPS = 5


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=2147483659)
    args = parser.parse_args()
    cell = run.load_cell(args.workload, rehearsal=False)
    mix = cell["traffic"]
    if mix["kind"] != "train":
        raise SystemExit(f"{args.workload} is a {mix['kind']} cell; the "
                         "regions are the train step's")

    export_compile_cache_dir()   # before jax is imported
    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding

    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import (TrainState, init_train_state,
                                          make_train_step)
    from ray_tpu.util import profiling

    device = jax.devices()[0]
    if device.platform != "tpu" or len(jax.devices()) < cell["chips"]:
        raise SystemExit(f"needs {cell['chips']} TPU chips, found "
                         f"{len(jax.devices())} x {device.platform}")

    cfg = GPTConfig(**model.gpt_config_kwargs(cell["config"]),
                    attention="flash", remat_policy="full")
    mesh = build_mesh(MeshConfig(**mix["mesh"]))
    strategy = strategy_from_name(mix["strategy"])
    act_sharding = strategy.activation_sharding(mesh)
    optimizer = optax.adamw(cell["config"]["train"]["learning_rate"])
    state = init_train_state(lambda: gpt_init(jax.random.PRNGKey(0), cfg),
                             optimizer, mesh, strategy)
    seeded_init = jax.jit(lambda key: gpt_init(key, cfg),
                          out_shardings=strategy.param_shardings(
                              mesh, state.params))
    state = TrainState(seeded_init(jax.random.PRNGKey(args.seed % (2 ** 31))),
                       state.opt_state, state.step)
    tokens = np.random.default_rng(args.seed).integers(
        0, cell["config"]["vocab_size"],
        (mix["global_batch"], mix["seq"] + 1), dtype=np.int32)
    batch = {"tokens": jax.device_put(
        tokens, NamedSharding(mesh, strategy.batch_spec))}
    step = make_train_step(
        lambda p, b: gpt_loss(p, b, cfg, mesh=mesh,
                              act_sharding=act_sharding),
        optimizer, mesh, strategy, sample_params=state.params
    ).lower(state, batch).compile()

    def one_step(state):
        with jax.profiler.TraceAnnotation("host:dispatch"):
            t0 = time.perf_counter()
            state, metrics = step(state, batch)
        with jax.profiler.TraceAnnotation("host:wait_step"):
            jax.block_until_ready(metrics)
        return state, time.perf_counter() - t0

    def steps(state, n):
        times = []
        for _ in range(n):
            state, seconds = one_step(state)
            times.append(seconds)
        return state, times

    state, _ = steps(state, mix["warmup_steps"])
    state, untraced = steps(state, TIMED_STEPS)
    out_dir = os.path.join("chiprun_out", "regions")
    log_dir = os.path.join(out_dir, args.workload)
    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir)
    with profiling.device_trace(log_dir):
        state, traced = steps(state, mix["trace_steps"])

    t0 = time.perf_counter()
    hlo_text = step.as_text()
    hlo_text_s = time.perf_counter() - t0
    with open(os.path.join(log_dir, "step.hlo.txt"), "w") as f:
        f.write(hlo_text)
    xplane = max(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                        "*.xplane.pb")),
                 key=os.path.getmtime)
    t0 = time.perf_counter()
    table = profiling.device_regions(xplane, hlo_text)
    reduce_s = time.perf_counter() - t0
    table.update(
        workload=args.workload, seed=args.seed, steps=mix["trace_steps"],
        device={"platform": device.platform, "kind": device.device_kind,
                "count": cell["chips"]},
        tracing_cost={
            "untraced_step_s": untraced,
            "untraced_step_median_s": statistics.median(untraced),
            "traced_step_s": traced,
            "hlo_text_bytes": len(hlo_text), "hlo_text_s": hlo_text_s,
            "xplane_bytes": os.path.getsize(xplane), "reduce_s": reduce_s})
    for path in (os.path.join(BENCH, "regions", args.workload + ".json"),
                 os.path.join(out_dir, args.workload + ".json")):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(table, f, indent=1, ensure_ascii=False)
            f.write("\n")
    median = table["median"]
    print(json.dumps({k: median[k] for k in ("window_s", "busy_s", "busy_pct")}
                     | table["tracing_cost"]))
    for row in median["rows"] + median["kernels"] + median["idle_gaps"]:
        print(row)
    return 0


if __name__ == "__main__":
    sys.exit(main())
