"""A train cell: the chip is reached through JaxTrainer(...).fit(); the loop
below runs in the one worker that holds the cell's chips.

Keys of a training mix (benchmark/traffic/<name>.json):
  kind "train", global_batch, seq, strategy, mesh, warmup_steps,
  trace_steps, reference_rows (rows of the batch per reference-loss call),
  loss_rel_tol with its reason.
"""

from __future__ import annotations

import time
from typing import Any, Dict


def train_loop(config: Dict[str, Any]) -> None:
    """build_mesh -> init_train_state -> make_train_step on a fixed, seeded,
    device-resident batch; warm up, then step for `seconds` by the loop's own
    clock, reporting each step as a user's loop does. Everything measured
    leaves as host numbers in the last report. What the model is comes from
    the configuration's family (benchmark/families/). (The persistent cache's
    key of the step holds this file's and the family's line numbers down to
    the loss call, so a line added above it is one cold compile in every
    checkout: PERF.md, PR 21.)"""
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
    timeline = worker.Timeline("loop_entered_wall")
    facts: Dict[str, Any] = {"timeline": timeline.marks,
                             "device": worker.open_device(
                                 config["platform"], config["chips"])}
    watch = worker.CompileWatch()
    timeline.mark("device_open")

    family = model.family(config["model"])
    program = family.program(config["model"])
    mesh = build_mesh(MeshConfig(**mix["mesh"]))
    strategy = strategy_from_name(mix["strategy"])
    act_sharding = strategy.activation_sharding(mesh)
    optimizer = optax.adamw(config["model"]["train"]["learning_rate"])

    # init_train_state jits a function of no arguments, so a seed inside it
    # is a constant of the program and every seed would compile anew. It is
    # called with a fixed key for the state's structure, shardings and
    # optimizer state; the weights come from one jitted call that takes the
    # seed's key as an argument, into the same shardings.
    state = init_train_state(lambda: program.init(jax.random.PRNGKey(0)),
                             optimizer, mesh, strategy)
    seeded_init = jax.jit(program.init,
                          out_shardings=strategy.param_shardings(
                              mesh, state.params))
    key = jax.random.PRNGKey(seed % (2 ** 31))
    state = TrainState(seeded_init(key), state.opt_state, state.step)
    jax.block_until_ready(state)
    timeline.mark("state_ready")

    tokens = np.random.default_rng(seed).integers(
        0, config["model"]["vocab_size"],
        (mix["global_batch"], mix["seq"] + 1), dtype=np.int32)
    batch = {"tokens": jax.device_put(
        tokens, NamedSharding(mesh, strategy.batch_spec))}
    step = make_train_step(
        lambda p, b: program.loss(p, b, mesh, act_sharding),
        optimizer, mesh, strategy, sample_params=state.params
    ).lower(state, batch).compile()
    timeline.mark("step_compiled")

    def one_step(state):
        with jax.profiler.TraceAnnotation("host:dispatch"):
            t_dispatch = time.perf_counter()
            state, metrics = step(state, batch)
        with jax.profiler.TraceAnnotation("host:wait_step"):
            jax.block_until_ready(metrics)
            t_done = time.perf_counter()
        with jax.profiler.TraceAnnotation("host:report"):
            loss = float(metrics["loss"])
            train.report({"loss": loss, "step_s": t_done - t_dispatch})
        return state, (t_dispatch, t_done), loss

    warm_losses = []
    for _ in range(mix["warmup_steps"]):
        state, _times, loss = one_step(state)
        warm_losses.append(loss)
    facts["setup"] = watch.snapshot()
    timeline.mark("warm")

    # the measured window: nothing below compiles
    window_wall = time.time()
    t0 = time.perf_counter()
    steps, losses = [], []
    while True:
        state, times, loss = one_step(state)
        steps.append(times)
        losses.append(loss)
        if times[1] - t0 >= config["seconds"]:
            break
    after = watch.snapshot()
    facts["window"] = {
        "wall_start": window_wall, "start": t0, "end": t0 + config["seconds"],
        "steps": steps, "losses": losses,
        "compiles": after["compiles"] - facts["setup"]["compiles"],
        "tokens_per_step": mix["global_batch"] * mix["seq"]}
    facts["memory"] = worker.memory_peak(config["chips"], step)

    if config["trace"]:
        tracer = worker.Tracer(config["trace_dir"], config["platform"])
        tracer.start()
        for _ in range(mix["trace_steps"]):
            state, _times, _loss = one_step(state)
        facts["trace"] = tracer.stop(step)

    # correctness, outside the window: the first step's loss against the
    # family's plain reference (float32, full matmul precision) on the same
    # weights and batch, a few rows at a call
    del state
    params = seeded_init(key)
    ref_loss = jax.jit(
        lambda p, t: family.reference_loss(p, t, config["model"]))
    rows = mix["reference_rows"]
    parts = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, mix["global_batch"], rows):
            part = jax.device_put(
                tokens[i:i + rows], NamedSharding(mesh, strategy.batch_spec))
            parts.append(float(ref_loss(params, part)))
    facts["check"] = {"first_loss": warm_losses[0],
                      "reference_loss": sum(parts) / len(parts),
                      "last_loss": losses[-1]}
    train.report({"bench_facts": facts})


def run(cell: Dict[str, Any], args) -> Dict[str, Any]:
    """Driver side: fit() and return the worker's facts."""
    from ray_tpu.train import JaxTrainer, ScalingConfig
    result = JaxTrainer(
        train_loop,
        train_loop_config={
            "model": cell["config"], "traffic": cell["traffic"],
            "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
            "trace_dir": cell["trace_dir"], "platform": cell["platform"],
            "chips": cell["chips"]},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpus_per_worker=cell["chips"])).fit()
    if result.error:
        raise RuntimeError(f"fit() failed: {result.error}")
    facts = result.metrics_dataframe[-1]["bench_facts"]
    check, tol = facts["check"], cell["traffic"]["loss_rel_tol"]
    gap = abs(check["first_loss"] - check["reference_loss"])
    check["gap"] = gap
    check["gap_limit"] = tol * max(1.0, abs(check["reference_loss"]))
    check["ok"] = bool(gap <= check["gap_limit"]
                       and check["last_loss"] < check["first_loss"])
    return facts


def summarize(cell: Dict[str, Any], facts: Dict[str, Any]) -> Dict[str, Any]:
    """Readings of the window -> end-to-end metrics, and the counters and
    series the per-layer readers take theirs from."""
    from benchmark import estimators
    w = facts["window"]
    steps = [tuple(s) for s in w["steps"]]
    steps_per_s, n = estimators.step_rate(steps, w["start"], w["end"])
    rate = None if steps_per_s is None else w["tokens_per_step"] * steps_per_s
    times = estimators.step_times(steps, w["start"], w["end"])
    gap = estimators.host_gap_share(steps, w["start"], w["end"])
    inside = estimators.whole_steps(steps, w["start"], w["end"])
    slow = max(range(1, len(inside)), default=None,
               key=lambda i: inside[i][1] - inside[i - 1][1])
    return {
        "end_to_end": {"train_tokens_per_s": rate},
        "info": {"steps_counted": n,
                 "median_step_s": estimators.median(times) if times else None,
                 "slowest_step": None if slow is None else {
                     "s": times[slow - 1],
                     "host_gap_before_s": inside[slow][0] - inside[slow - 1][1],
                     "dispatch_to_done_s": inside[slow][1] - inside[slow][0]},
                 "step_times_s": times,
                 "first_loss": facts["check"]["first_loss"],
                 "reference_loss": facts["check"]["reference_loss"],
                 "last_loss": facts["check"]["last_loss"]},
        "counters": {
            "tokens_per_s": rate, "seq": cell["traffic"]["seq"],
            "host_gap_pct": None if gap is None else 100.0 * gap,
            "window_compiles": w["compiles"]},
        "series": {"step_ms": [1e3 * t for t in times]},
        "attempted": len(steps), "failed": 0, "errors": [],
        "correct": bool(facts["check"]["ok"] and w["compiles"] == 0
                        and n >= 1)}
