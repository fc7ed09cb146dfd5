"""Take a train cell's slow steps apart once, on the chip, by hand:

    chiprun -- python3 benchmark/tools/step_probe.py --workload <cell> --seconds 70

train_cell.train_loop's set-up and measured loop, with each step's host side
split where train_loop takes one reading (the call that dispatches the step,
the wait for it, the loss coming to the host, train.report), the loop
thread's and the process's CPU seconds, and every garbage collection by
generation (gc.callbacks). A step is slow when dispatch-to-done passes the
window's median by 2 ms. The result goes to chiprun_out/step_probe_<cell>.json
and its summary to the last line of the output. PR 27 sized the platform's
hazard with it: one step in ~200 was 3-122 ms slow, inside the wait, with
the process idle and no collection near (PERF.md, section 6). A
`--rehearsal 1` run is the tiny configuration on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

SLOW_BY_S = 0.002


def probe_loop(config: Dict[str, Any]) -> None:
    """train_cell.train_loop up to the end of its window; no trace, no
    reference."""
    import gc

    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding

    from benchmark import model, worker
    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import (TrainState, init_train_state,
                                          make_train_step)

    mix, seed = config["traffic"], config["seed"]
    worker.open_device(config["platform"], config["chips"])
    program = model.family(config["model"]).program(config["model"])
    mesh = build_mesh(MeshConfig(**mix["mesh"]))
    strategy = strategy_from_name(mix["strategy"])
    act_sharding = strategy.activation_sharding(mesh)
    optimizer = optax.adamw(config["model"]["train"]["learning_rate"])
    state = init_train_state(lambda: program.init(jax.random.PRNGKey(0)),
                             optimizer, mesh, strategy)
    seeded_init = jax.jit(program.init,
                          out_shardings=strategy.param_shardings(
                              mesh, state.params))
    state = TrainState(seeded_init(jax.random.PRNGKey(seed % (2 ** 31))),
                       state.opt_state, state.step)
    tokens = np.random.default_rng(seed).integers(
        0, config["model"]["vocab_size"],
        (mix["global_batch"], mix["seq"] + 1), dtype=np.int32)
    batch = {"tokens": jax.device_put(
        tokens, NamedSharding(mesh, strategy.batch_spec))}
    step = make_train_step(
        lambda p, b: program.loss(p, b, mesh, act_sharding),
        optimizer, mesh, strategy, sample_params=state.params
    ).lower(state, batch).compile()

    collections, began = [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            began[0] = time.perf_counter()
        else:
            collections.append((info["generation"], began[0],
                                time.perf_counter() - began[0]))
    gc.callbacks.append(on_gc)

    def one_step(state):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        t1 = time.perf_counter()
        jax.block_until_ready(metrics)
        t2 = time.perf_counter()
        loss = float(metrics["loss"])
        t3 = time.perf_counter()
        train.report({"loss": loss, "step_s": t2 - t0})
        return state, (t0, t1, t2, t3, time.perf_counter())

    for _ in range(mix["warmup_steps"]):
        state, _ = one_step(state)
    rows = []
    start = time.perf_counter()
    thread_cpu, process_cpu = time.thread_time(), time.process_time()
    while True:
        state, t = one_step(state)
        rows.append({"at_s": t[0] - start, "call_s": t[1] - t[0],
                     "wait_s": t[2] - t[1], "float_s": t[3] - t[2],
                     "report_s": t[4] - t[3],
                     "thread_cpu_s": time.thread_time() - thread_cpu,
                     "process_cpu_s": time.process_time() - process_cpu})
        thread_cpu, process_cpu = time.thread_time(), time.process_time()
        if t[2] - start >= config["seconds"]:
            break
    train.report({"bench_facts": {
        "rows": rows, "tracked_objects": len(gc.get_objects()),
        "collections": [(g, at - start, took)
                        for g, at, took in collections if at >= start]}})


def summarize(facts: Dict[str, Any]) -> Dict[str, Any]:
    rows = facts["rows"]
    done = [r["call_s"] + r["wait_s"] for r in rows]
    median = statistics.median(done)
    slow = [dict(r, step=i, over_median_s=d - median)
            for i, (r, d) in enumerate(zip(rows, done))
            if d > median + SLOW_BY_S]
    by_generation = {g: [took for gen, _, took in facts["collections"]
                         if gen == g] for g in (0, 1, 2)}
    return {"steps": len(rows), "median_step_s": median,
            "slow_steps": len(slow), "slow": slow,
            "collections": {g: {"n": len(v), "seconds": sum(v)}
                            for g, v in by_generation.items()},
            "tracked_objects": facts["tracked_objects"]}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=int, default=70)
    parser.add_argument("--seed", type=int, default=2147483659)
    parser.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    cell = run.load_cell(args.workload, rehearsal=bool(args.rehearsal))

    os.environ.setdefault("RAY_TPU_SESSION_DIR_ROOT", os.path.join(
        tempfile.gettempdir(), "ray_tpu_sessions"))
    import ray_tpu
    from ray_tpu.train import JaxTrainer, ScalingConfig
    if args.rehearsal:
        ray_tpu.init(num_cpus=4, num_tpus=cell["chips"])
    else:
        ray_tpu.init()
    try:
        result = JaxTrainer(
            probe_loop,
            train_loop_config={
                "model": cell["config"], "traffic": cell["traffic"],
                "seed": args.seed, "seconds": args.seconds,
                "platform": cell["platform"], "chips": cell["chips"]},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                tpus_per_worker=cell["chips"])).fit()
    finally:
        ray_tpu.shutdown()
    if result.error:
        raise RuntimeError(f"fit() failed: {result.error}")
    facts = result.metrics_dataframe[-1]["bench_facts"]
    summary = summarize(facts)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"step_probe_{cell['name']}.json"),
              "w") as f:
        json.dump({"summary": summary, "rows": facts["rows"],
                   "collections": facts["collections"]}, f)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
