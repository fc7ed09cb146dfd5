"""Multi-process (multi-controller) gang correctness check.

The real multi-host path: each host runs ONE process owning its local
chips; `jax.distributed.initialize` joins them into one JAX runtime whose
global device list spans every process, and the SAME pjit-compiled SPMD
program runs in lockstep on all of them (collectives ride ICI/DCN — on
CPU test gangs, gloo). Reference analogue: torch DDP process-group
bootstrap in `python/ray/train/torch/config.py:64` +
`train/_internal/backend_executor.py:347` rank mapping; here the gang is
a JAX multi-controller mesh instead of a NCCL process group.

This module provides one FIXED dp x fsdp GPT train-step workload so that
 a) a single-process run over N devices, and
 b) an n-process gang with N/n local devices each
provably compute the SAME loss — numerical equivalence of the sharded
multi-controller step, asserted in CI (tests/test_train.py) and in the
driver-visible `__graft_entry__.dryrun_multichip`.
"""

from __future__ import annotations

import os
import re
import socket
import subprocess
import sys
from typing import List, Optional

from ray_tpu._private.compile_cache import export_compile_cache_dir

# Fixed workload: deterministic config + data seed shared by every mode.
_VOCAB, _SEQ, _BATCH, _STEPS = 512, 64, 8, 2
_DATA_SEED = 7


def step_loss(data_axis: int, fsdp_axis: int) -> float:
    """Run the fixed dp x fsdp workload on the CURRENT jax runtime
    (single- or multi-process alike) and return the step-_STEPS loss.

    In a multi-process gang every process must call this with the same
    arguments; the returned loss is fully replicated, so each process
    reads the identical value from its local shard.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding

    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import init_train_state, make_train_step

    cfg = GPTConfig(vocab_size=_VOCAB, d_model=128, n_layers=2, n_heads=4,
                    d_ff=256, max_seq=_SEQ)
    mesh = build_mesh(MeshConfig(data=data_axis, fsdp=fsdp_axis))
    opt = optax.adamw(1e-3)
    strategy = strategy_from_name("fsdp")
    state = init_train_state(lambda: gpt_init(jax.random.PRNGKey(0), cfg),
                             opt, mesh, strategy)
    step = make_train_step(lambda p, b: gpt_loss(p, b, cfg, mesh=mesh),
                           opt, mesh, strategy, sample_params=state.params)
    tokens_np = np.random.RandomState(_DATA_SEED).randint(
        0, cfg.vocab_size, (_BATCH, _SEQ + 1))
    # device_put against the GLOBAL sharding: each process materializes
    # only its addressable shards of the (identical) host array.
    tokens = jax.device_put(jnp.array(tokens_np, jnp.int32),
                            NamedSharding(mesh, strategy.batch_spec))
    m = None
    for _ in range(_STEPS):
        state, m = step(state, {"tokens": tokens})
    return float(np.asarray(jax.device_get(m["loss"])))


def init_process(rank: int, num_processes: int, coordinator: str,
                 local_devices: int, platform: str = "cpu") -> None:
    """Join this process to the gang. MUST run before any other jax use
    in the process (the platform/device-count flags bind at backend
    init). On CPU gangs the cross-process collective backend is gloo."""
    if platform == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       flags)
        os.environ["XLA_FLAGS"] = (
            flags +
            f" --xla_force_host_platform_device_count={local_devices}")
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if num_processes > 1:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=rank)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_gang_subprocesses(n_processes: int, local_devices: int,
                          data_axis: int, fsdp_axis: int,
                          timeout: float = 420.0) -> List[float]:
    """Spawn n worker processes, each `local_devices` CPU devices, run the
    fixed workload over the global mesh; return every process's loss."""
    port = free_port()
    export_compile_cache_dir()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # each worker sets its own device count
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.parallel.mp_check",
             str(rank), str(n_processes), f"127.0.0.1:{port}",
             str(local_devices), str(data_axis), str(fsdp_axis)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        for rank in range(n_processes)
    ]
    import time as _time
    losses: List[Optional[float]] = [None] * n_processes
    outputs: List[str] = [""] * n_processes
    deadline = _time.monotonic() + timeout
    try:
        # Poll ALL workers: waiting in rank order would wedge on rank 0
        # (blocked in the gang rendezvous) for the full timeout when a
        # LATER rank crashed at startup — and then discard its stderr.
        pending = set(range(n_processes))
        failed = None
        while pending and _time.monotonic() < deadline:
            for rank in list(pending):
                if procs[rank].poll() is None:
                    continue
                out, _ = procs[rank].communicate()
                outputs[rank] = out or ""
                pending.discard(rank)
                for line in outputs[rank].splitlines():
                    mo = re.match(
                        r"MP_CHECK rank=(\d+) loss=([-\d.naninf]+)", line)
                    if mo:
                        losses[rank] = float(mo.group(2))
                if procs[rank].returncode != 0 and losses[rank] is None:
                    failed = rank
            if failed is not None:
                break
            if pending:
                _time.sleep(0.2)
        if failed is not None:
            tail = "\n".join(outputs[failed].strip().splitlines()[-6:])
            raise RuntimeError(
                f"gang worker {failed} failed "
                f"rc={procs[failed].returncode}:\n{tail}")
        if pending:
            raise RuntimeError(
                f"gang workers {sorted(pending)} still running at the "
                f"{timeout:.0f}s deadline (rendezvous hang?)")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    missing = [r for r, x in enumerate(losses) if x is None]
    if missing:
        tails = "\n---\n".join("\n".join(o.strip().splitlines()[-4:])
                               for o in outputs)
        raise RuntimeError(f"gang workers {missing} produced no loss:\n"
                           f"{tails}")
    return [x for x in losses if x is not None]


def main(argv: List[str]) -> None:
    rank, nprocs, coordinator, local_devices, data_axis, fsdp_axis = (
        int(argv[0]), int(argv[1]), argv[2], int(argv[3]), int(argv[4]),
        int(argv[5]))
    init_process(rank, nprocs, coordinator, local_devices)
    loss = step_loss(data_axis, fsdp_axis)
    print(f"MP_CHECK rank={rank} loss={loss:.6f}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
