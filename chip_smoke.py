"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the runtime's two model paths once, through the entry points a user
calls, at GPT-2 small's published widths (768 / 12 layers / 12 heads / 3072 /
vocab 50304, bf16, seq 1024) with seeded random weights and data:

  python3 chip_smoke.py            one TPU chip:
    task   a plain @ray_tpu.remote(num_tpus=1) task opens the chip, checks
           the flash kernel's forward and backward against the reference,
           and leaves the chip (no pooled worker may keep it)
    train  JaxTrainer, one worker holding the chip: build_mesh ->
           init_train_state -> make_train_step, flash attention, a few steps
           on a fixed batch; loss finite and falling; first-step loss equal
           to the plain-attention loss; the Mosaic kernel in the program
    train  again, shorter: the step's compile comes from the compile cache
    serve  serve.run, one replica holding the chip, answers HTTP requests
           through the proxy with a jitted forward, each equal to the
           plain-attention forward computed in the replica
  python3 chip_smoke.py --chips 4  one host with four chips, and only this:
    train  one worker owning four chips, tp_fsdp on fsdp=2 x tensor=2;
           first-step loss equal to a one-device run of the same init and
           batch; parameters spread over the devices; collectives compiled

The process that runs this file never initializes a JAX backend: a chip
belongs to one process at a time, and here that is always a worker. Every
phase prints its facts as one JSON line; a failed phase raises, and the last
line of stdout is {"ok": true, "device": {...}} only if all of them passed.
Without an accelerator the script exits non-zero.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import threading
import time
import traceback
import urllib.request

# Tolerances, set beforehand from the dtype (bf16 activations, fp32 loss).
# The two loss tolerances are relative to max(1, |loss|), the form
# __graft_entry__ uses.
FLASH_LOSS_TOL = 2e-4     # flash vs plain attention, one device
SHARDED_LOSS_TOL = 2e-2   # sharded vs one device, __graft_entry__'s value
LOGIT_TOL = 0.125         # 4 bf16 ulps in the top logit binade [4, 8)
HTTP_PORT = 8177


@dataclasses.dataclass(frozen=True)
class Size:
    """What a run is sized by. REAL is what the chip runs; the CPU rehearsal
    in tests/test_chip_smoke.py passes a tiny one to the same phases."""
    model: str              # a GPTConfig preset name
    batch: int
    seq: int
    kernel_shapes: tuple    # [batch, heads, seq, head_dim] of each kernel check
    prompt_len: int         # serve pads every prompt to this
    platform: str           # what the workers' jax.devices() must report


# Batch 64 x seq 1024 is what the step was compiled for ahead of the chip
# run (6.2 GB temporaries + 2.1 GB arguments of 16 GB); not laddered.
# The kernel is checked at a small shape and at what one chip sees of the
# benchmark's two train cells: its tiles follow from the shape, so a shape
# it mis-tiles fails here before a benchmark run.
REAL = Size(model="gpt2_small", batch=64, seq=1024,
            kernel_shapes=((8, 12, 1024, 64), (64, 12, 1024, 64),
                           (16, 16, 2048, 64)),
            prompt_len=128, platform="tpu")


class SmokeFailure(Exception):
    """A phase did not meet its check."""


def log(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def process_gone(pid: int) -> bool:
    """True once the process has exited (a zombie awaiting its parent's
    wait() has already given up its devices)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except OSError:
        return True


# ---------------------------------------------------------------------------
# Code that runs inside the worker that holds the chip
# ---------------------------------------------------------------------------

def open_device(platform: str) -> dict:
    """Open this process's JAX backend and say what it found."""
    import jax
    t0 = time.perf_counter()
    devices = jax.devices()
    facts = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices),
             "pid": os.getpid(), "open_s": time.perf_counter() - t0}
    if facts["platform"] != platform:
        raise SmokeFailure(
            f"worker {os.getpid()} runs JAX on {facts['platform']!r}, "
            f"not on {platform!r}")
    return facts


def kernel_check(platform: str, shapes: tuple, seed: int) -> dict:
    """Body of the plain-task phase: at each shape, the flash kernel's
    forward and backward against mha_reference on bf16 inputs. Both are
    measured against the same reference run in fp32 at full matmul
    precision, so the tolerance is the plain bf16 path's own error: the
    kernel may be at most twice as far from the truth. The kernel runs on
    the whole shape; the references, which hold the score matrix, on slices
    of the batch."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference

    facts = open_device(platform)

    def outputs(attn, dtype, arrays, rows):
        @jax.jit
        def run(q, k, v, g):
            out, vjp = jax.vjp(lambda *a: attn(*a, causal=True), q, k, v)
            return (out,) + vjp(g)
        parts = [run(*(x[i:i + rows].astype(dtype) for x in arrays))
                 for i in range(0, arrays[0].shape[0], rows)]
        return [jnp.concatenate(p).astype(jnp.float32) for p in zip(*parts)]

    facts["errors"] = {}
    for shape in shapes:
        batch, heads, seq, _ = shape
        arrays = [jax.random.normal(key, shape, jnp.float32)
                  for key in jax.random.split(jax.random.PRNGKey(seed), 4)]
        rows = max(1, (1 << 27) // (heads * seq * seq))   # 0.5 GB of scores
        with jax.default_matmul_precision("highest"):
            truth = outputs(mha_reference, jnp.float32, arrays, rows)
        plain = outputs(mha_reference, jnp.bfloat16, arrays, rows)
        flash = outputs(flash_attention, jnp.bfloat16, arrays, batch)
        facts["errors"]["x".join(map(str, shape))] = {
            name: {"flash": float(jnp.abs(f - t).max()),
                   "plain": float(jnp.abs(p - t).max())}
            for name, t, p, f in zip(("out", "dq", "dk", "dv"), truth, plain,
                                     flash)}
    return facts


def param_bytes_per_device(params) -> dict:
    import jax
    per_device: dict = {}
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            key = str(shard.device)
            per_device[key] = per_device.get(key, 0) + shard.data.nbytes
    return {"total": total, "per_device": per_device}


def train_loop(config: dict) -> None:
    """The train worker's loop: reports its set-up facts once, then loss
    and step time (host clock around block_until_ready) for every step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, SingleDeviceSharding

    from ray_tpu import train
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import init_train_state, make_train_step

    size = Size(**config["size"])
    seed = config["seed"]
    setup = {"device": open_device(size.platform)}
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1
    jax.monitoring.register_event_listener(on_event)

    cfg = getattr(GPTConfig, size.model)()
    mesh = build_mesh(MeshConfig(**config["mesh"]))
    strategy = strategy_from_name(config["strategy"])
    act_sharding = strategy.activation_sharding(mesh)
    optimizer = optax.adamw(3e-4)

    def init():
        return gpt_init(jax.random.PRNGKey(seed), cfg)

    t0 = time.perf_counter()
    state = init_train_state(init, optimizer, mesh, strategy)
    jax.block_until_ready(state)
    setup["init_s"] = time.perf_counter() - t0
    setup["param_bytes"] = param_bytes_per_device(state.params)
    step = make_train_step(
        lambda p, b: gpt_loss(p, b, cfg, mesh=mesh,
                              act_sharding=act_sharding),
        optimizer, mesh, strategy, sample_params=state.params)
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (size.batch, size.seq + 1), dtype=np.int32)
    batch = {"tokens": jax.device_put(
        tokens, NamedSharding(mesh, strategy.batch_spec))}

    # What the first step's loss is compared with: the same parameters and
    # batch on ONE device with plain attention — no kernel, no mesh.
    plain_cfg = dataclasses.replace(cfg, attention="reference")
    one_device = SingleDeviceSharding(jax.devices()[0])
    setup["plain_loss"] = float(
        jax.jit(lambda p, b: gpt_loss(p, b, plain_cfg))(
            jax.device_put(state.params, one_device),
            {"tokens": jax.device_put(tokens, one_device)}))

    t0 = time.perf_counter()
    lowered = step.lower(state, batch)
    setup["lower_s"] = time.perf_counter() - t0
    before = dict(cache_events)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    setup["compile_s"] = time.perf_counter() - t0
    setup["compile_cache"] = {
        "dir": jax.config.jax_compilation_cache_dir,
        "hits": cache_events["hits"] - before["hits"],
        "misses": cache_events["misses"] - before["misses"]}
    text = compiled.as_text()
    setup["kernel_calls"] = text.count("tpu_custom_call")
    setup["collectives"] = {op: text.count(f" {op}(") + text.count(
        f" {op}-start(") for op in ("all-reduce", "all-gather",
                                    "reduce-scatter", "all-to-all")}
    mem = compiled.memory_analysis()
    setup["program_bytes"] = {
        "temp": mem.temp_size_in_bytes,
        "arguments": mem.argument_size_in_bytes}
    train.report({"setup": setup})

    for _ in range(config["steps"]):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready(metrics)
        metrics["step_s"] = time.perf_counter() - t0
        train.report(metrics)


class GPTReplica:
    """Serve replica: holds seeded GPT parameters on the chip and answers a
    token prompt with the next token (argmax of a jitted forward), next to
    the same forward with plain attention."""

    def __init__(self, size: dict, seed: int):
        self._size = Size(**size)
        self._seed = seed
        self._loaded = None
        self._load_lock = threading.Lock()

    def _load(self) -> dict:
        """Open the chip and put the parameters on it — on the first
        request's thread, not in the constructor: the controller gives a
        replica 60 s to answer its first health check, and a cold start on
        the chip (open, compile and run the init) has taken 46 s."""
        import jax
        import jax.numpy as jnp
        from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init

        t0 = time.perf_counter()
        device = open_device(self._size.platform)
        cfg = getattr(GPTConfig, self._size.model)()
        params = jax.jit(
            lambda: gpt_init(jax.random.PRNGKey(self._seed), cfg))()
        jax.block_until_ready(params)
        device["load_s"] = time.perf_counter() - t0

        def last_logits(attention):
            att_cfg = dataclasses.replace(cfg, attention=attention)

            def fn(params, tokens, last):
                logits, _ = gpt_forward(params, tokens, att_cfg)
                return logits[0, last].astype(jnp.float32)
            return jax.jit(fn)
        return {"device": device, "params": params,
                "flash": last_logits("flash"),
                "plain": last_logits("reference")}

    async def __call__(self, request):
        # JAX work runs on a thread: the replica's loop keeps answering
        # health checks while the first request loads and compiles.
        tokens = request.json()["tokens"]
        return await asyncio.get_running_loop().run_in_executor(
            None, self._answer, tokens)

    def _answer(self, tokens: list) -> dict:
        import numpy as np
        n = len(tokens)
        if not 0 < n <= self._size.prompt_len:
            raise ValueError(f"prompt of {n} tokens, "
                             f"need 1..{self._size.prompt_len}")
        with self._load_lock:
            if self._loaded is None:
                self._loaded = self._load()
        m = self._loaded
        padded = np.zeros((1, self._size.prompt_len), np.int32)
        padded[0, :n] = tokens
        t0 = time.perf_counter()
        flash = np.asarray(m["flash"](m["params"], padded, n - 1))
        compute_s = time.perf_counter() - t0
        plain = np.asarray(m["plain"](m["params"], padded, n - 1))
        token = int(flash.argmax())
        return {"next_token": token,
                "finite": bool(np.isfinite(flash).all()),
                "vocab": int(flash.shape[0]),
                "max_abs_diff": float(np.abs(flash - plain).max()),
                # how far below the plain forward's best logit our token is
                "plain_gap": float(plain.max() - plain[token]),
                "compute_s": compute_s, "device": m["device"]}


# ---------------------------------------------------------------------------
# Phases, driven from the process that never touches JAX
# ---------------------------------------------------------------------------

def task_phase(size: Size, seed: int) -> dict:
    """A plain task that used the chip must not leave a pooled worker
    sitting on it: one_chip_phases checks the pid is gone once the next
    holder has had the chip."""
    import ray_tpu
    facts = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(kernel_check).remote(
            size.platform, size.kernel_shapes, seed), timeout=600)
    log("task", **facts)
    for shape, errors in facts["errors"].items():
        for name, err in errors.items():
            require(err["flash"] <= 2 * err["plain"] + 1e-6,
                    f"flash kernel {name} at {shape}: error {err['flash']} "
                    f"against fp32, plain bf16 attention has {err['plain']}")
    return facts


def train_phase(size: Size, *, chips: int, mesh: dict, strategy: str,
                steps: int, seed: int, loss_tol: float,
                expect_cache_hit: bool = False) -> dict:
    from ray_tpu.train import JaxTrainer, ScalingConfig
    t0 = time.perf_counter()
    result = JaxTrainer(
        train_loop,
        train_loop_config={
            "size": dataclasses.asdict(size), "seed": seed, "mesh": mesh,
            "strategy": strategy, "steps": steps},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpus_per_worker=chips)).fit()
    rows = result.metrics_dataframe
    setup, step_rows = rows[0]["setup"], rows[1:]
    losses = [r["loss"] for r in step_rows]
    log("train", fit_s=time.perf_counter() - t0, model=size.model,
        batch=size.batch, seq=size.seq, mesh=mesh, strategy=strategy,
        losses=losses, step_s=[r["step_s"] for r in step_rows],
        grad_norm=[r["grad_norm"] for r in step_rows], **setup)

    device = setup["device"]
    require(device["count"] >= chips,
            f"worker sees {device['count']} devices, needs {chips}")
    require(len(losses) == steps, f"{len(losses)} of {steps} steps reported")
    require(all(isinstance(x, float) and x == x and abs(x) != float("inf")
                for x in losses), f"losses not finite host floats: {losses}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    diff = abs(losses[0] - setup["plain_loss"])
    require(diff <= loss_tol * max(1.0, abs(setup["plain_loss"])),
            f"first-step loss {losses[0]} vs plain-attention one-device "
            f"loss {setup['plain_loss']}: off by {diff}, allowed "
            f"{loss_tol} relative")
    # The compiled Mosaic kernel on a TPU, the interpreter's plain ops
    # elsewhere — and nothing else.
    require((setup["kernel_calls"] > 0) == (device["platform"] == "tpu"),
            f"{setup['kernel_calls']} Mosaic calls in the step compiled "
            f"for {device['platform']}")
    if chips > 1:
        held = setup["param_bytes"]
        require(len(held["per_device"]) == chips
                and max(held["per_device"].values()) < held["total"] / 2,
                f"parameters not spread over {chips} devices: {held}")
        require(sum(setup["collectives"].values()) > 0,
                "no collective in the compiled sharded step")
    if expect_cache_hit:
        require(setup["compile_cache"]["hits"] >= 1,
                f"the step's compile missed the cache: "
                f"{setup['compile_cache']}")
    return device


def serve_phase(size: Size, *, seed: int, n_requests: int) -> dict:
    import numpy as np
    from ray_tpu import serve

    serve.start(http_options=serve.HTTPOptions(port=HTTP_PORT))
    app = serve.deployment(GPTReplica, name="gpt",
                           ray_actor_options={"num_tpus": 1}).bind(
        dataclasses.asdict(size), seed)
    serve.run(app, name="chip_smoke", route_prefix="/gpt")
    rng = np.random.default_rng(seed)
    answers, latencies = [], []
    for i in range(n_requests):
        n = size.prompt_len if i == 0 else int(
            rng.integers(1, size.prompt_len + 1))
        body = json.dumps({"tokens": rng.integers(0, 512, n).tolist()})
        req = urllib.request.Request(
            f"http://127.0.0.1:{HTTP_PORT}/gpt", data=body.encode(),
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        # the first request waits for the replica and compiles the forward
        with urllib.request.urlopen(req, timeout=600) as resp:
            require(resp.status == 200, f"HTTP {resp.status}")
            answers.append(json.loads(resp.read()))
        latencies.append(time.perf_counter() - t0)
    device = answers[0]["device"]
    log("serve", model=size.model, prompt_len=size.prompt_len,
        request_s=latencies, device=device,
        answers=[{k: v for k, v in a.items() if k != "device"}
                 for a in answers])
    for a in answers:
        require(a["finite"] and 0 <= a["next_token"] < a["vocab"],
                f"bad answer {a}")
        require(a["max_abs_diff"] <= LOGIT_TOL
                and a["plain_gap"] <= LOGIT_TOL,
                f"flash forward differs from plain attention: {a}")
    return device


def one_chip_phases(size: Size, seed: int) -> dict:
    """task -> train -> train (cached compile) -> serve, each a new process
    that gets the chip only after the one before has let go of it."""
    train = dict(chips=1, mesh={"data": 1}, strategy="dp", seed=seed,
                 loss_tol=FLASH_LOSS_TOL)
    holders = [
        task_phase(size, seed),
        train_phase(size, steps=6, **train),
        train_phase(size, steps=2, expect_cache_hit=True, **train),
        serve_phase(size, seed=seed, n_requests=4)]
    pids = [h["pid"] for h in holders]
    log("handover", pids=pids)
    require(len(set(pids)) == len(pids), f"a process was reused: {pids}")
    require(all(process_gone(pid) for pid in pids[:-1]),
            f"an earlier holder of the chip is still alive: {pids}")
    return holders[1]


def four_chip_phase(size: Size, seed: int) -> dict:
    return train_phase(
        size, chips=4, mesh={"data": 1, "fsdp": 2, "tensor": 2},
        strategy="tp_fsdp", steps=3, seed=seed, loss_tol=SHARDED_LOSS_TOL)


def run(chips: int, seed: int) -> dict:
    """The phases on a fresh local cluster; returns the device as the worker
    that held it reported it. Raises on the first failed phase."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private.compile_cache import export_compile_cache_dir

    ray_tpu.init()
    try:
        detected = ray_tpu.cluster_resources().get("TPU", 0)
        log("cluster", tpu_resource=detected, chips=chips,
            compile_cache_dir=export_compile_cache_dir())
        require(detected >= chips,
                f"the raylet detected {detected} TPU chips, need {chips}")
        if chips == 4:
            return four_chip_phase(REAL, seed)
        return one_chip_phases(REAL, seed)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    try:
        device = run(args.chips, args.seed)
        jax = sys.modules.get("jax")
        if jax is not None:
            from jax._src import xla_bridge
            require(not xla_bridge.backends_are_initialized(),
                    "the parent process initialized a JAX backend")
    except Exception as e:  # the script's boundary: report, exit non-zero
        traceback.print_exc()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        k: device[k] for k in ("platform", "kind", "count")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
