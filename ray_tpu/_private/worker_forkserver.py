"""Worker fork-server (zygote): pay the interpreter + framework import cost
once per node, then fork workers in milliseconds.

The reference hides worker startup latency by prestarting idle worker
processes in the raylet's WorkerPool (src/ray/raylet/worker_pool.h). A cold
``python`` start that imports jax and the framework costs about a second,
which serializes badly on small CI boxes — so we go further: one warm
template process per raylet that ``fork()``s a worker per request. Children
inherit the warmed import state but create their own event loop and RPC
connections; no threads or event loops exist in the template at fork time,
so the fork is safe.

Protocol (line-delimited JSON over stdin/stdout):
  raylet -> forkserver: {"spawn": {"env": {...}, "log_path": "..."}}
                        {"spawn_batch": [{"env": ..., "log_path": ...}, ...]}
  forkserver -> raylet: {"event": "ready"}
                        {"event": "spawned", "pid": N, "worker_id": "..."}
                        {"event": "exit", "pid": N, "worker_id": "...",
                         "status": N}
A `spawn_batch` line forks every requested child back to back (launch
storms pay one pipe write + one template wakeup for N workers, not N).
On stdin EOF (raylet death) or SIGTERM (the owning process exiting) the
forkserver terminates its children, reaps them and exits.
"""

from __future__ import annotations

import json
import os
import select
import signal
import sys


def _send(msg: dict) -> None:
    sys.stdout.write(json.dumps(msg) + "\n")
    sys.stdout.flush()


def _run_child(req: dict) -> None:
    """Forked child: detach, redirect output, become a worker. Never returns."""
    try:
        os.setsid()
    except OSError:
        pass
    log_path = req.get("log_path")
    if log_path:
        fd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        os.dup2(fd, 1)
        os.dup2(fd, 2)
        if fd > 2:
            os.close(fd)
    devnull = os.open(os.devnull, os.O_RDONLY)
    os.dup2(devnull, 0)
    if devnull > 2:
        os.close(devnull)
    # Reset to exactly the requested env: the template's env belongs to the
    # raylet that started the zygote and may be stale for this spawn.
    env = req.get("env", {})
    if env:
        os.environ.clear()
        os.environ.update(env)
        # os.environ alone doesn't retrofit sys.path — the zygote built its
        # path from the PYTHONPATH it was STARTED with. Prepend any request
        # PYTHONPATH entries the zygote didn't have (same staleness class
        # as the env reset above).
        for p in reversed(env.get("PYTHONPATH", "").split(os.pathsep)):
            if p and p not in sys.path:
                sys.path.insert(0, p)
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    signal.signal(signal.SIGINT, signal.SIG_IGN)
    # Every child forked from the template inherits the SAME PRNG state
    # (and object addresses — see the pool/serve tests): reseed so
    # worker-side random choices (jitter, sampling) don't march in
    # lockstep across the fleet.
    import random
    random.seed()
    try:
        import numpy as _np
        _np.random.seed()
    except Exception:  # noqa: BLE001 — numpy is optional here
        pass
    try:
        from ray_tpu._private import worker_main
        worker_main.main()
    except SystemExit:
        pass
    except BaseException:
        import traceback
        traceback.print_exc()
    finally:
        os._exit(0)


def _warm_imports() -> None:
    """Pre-import the worker's heavy module set while still
    single-threaded, so fork->register is import-free in the child.
    worker_main's own top-level imports are light (its heavy deps load
    inside main()), so name the hot ones explicitly; each is
    best-effort — a missing optional dep must not kill the zygote."""
    for mod in ("ray_tpu._private.worker_main",
                "ray_tpu._private.serialization",
                "ray_tpu._private.core_worker",
                "ray_tpu._private.rpc",
                "ray_tpu._private.config",
                "ray_tpu._private.object_store",
                "ray_tpu._private.runtime_env",
                "ray_tpu.dag.compiled",
                "ray_tpu.exceptions",
                "numpy",
                # Without the template import every forked child that
                # uses jax pays the full (~0.6s) import serially on a
                # loaded box. Import only — backend init stays lazy, so
                # no threads exist at fork time and the zygote never
                # holds the chip; each child opens it on first use.
                "jax"):
        try:
            __import__(mod)
        except Exception:  # noqa: BLE001
            pass


def _fork_one(spawn: dict, children: dict) -> None:
    pid = os.fork()
    if pid == 0:
        _run_child(spawn)  # never returns
    wid = spawn.get("env", {}).get("RAY_TPU_WORKER_ID", "")
    children[pid] = wid
    _send({"event": "spawned", "pid": pid, "worker_id": wid})


def _terminate(children: dict) -> None:
    for pid in list(children):
        try:
            os.kill(pid, signal.SIGTERM)
        except OSError:
            pass


def main() -> None:
    _warm_imports()
    # Freeze the preloaded heap before serving forks: children inherit
    # the template's object graph (jax + the worker module set, hundreds
    # of thousands of objects), and without this every gen-2 GC pass in
    # every forked worker re-traverses it — measured as a ~50-75 ms
    # stop-the-world stall that made the n:n actor-call smoke row
    # bimodal (slow mode = a burst that contained one such pass). The
    # permanent generation survives fork, so one freeze here covers the
    # whole fleet; it also keeps copy-on-write pages shared (gc touches
    # refcount-adjacent GC headers when it scans).
    import gc
    gc.collect()
    gc.freeze()

    children: dict = {}  # pid -> worker_id hex
    terminated = []      # non-empty once SIGTERM arrived
    signal.signal(signal.SIGTERM, lambda *_: terminated.append(True))
    _send({"event": "ready"})
    stdin_fd = sys.stdin.fileno()
    buf = b""
    eof = False
    while True:
        try:
            readable, _, _ = select.select([stdin_fd], [], [],
                                           0.02 if eof else 0.2)
        except InterruptedError:
            readable = []
        if terminated and not eof:
            eof = True
            _terminate(children)
        # Reap exited children and report them.
        while children:
            try:
                pid, status = os.waitpid(-1, os.WNOHANG)
            except ChildProcessError:
                break
            if pid == 0:
                break
            wid = children.pop(pid, None)
            _send({"event": "exit", "pid": pid, "worker_id": wid,
                   "status": status})
        if eof and not children:
            return
        if not readable or eof:
            continue
        chunk = os.read(stdin_fd, 1 << 16)
        if not chunk:
            # Raylet died or closed us: terminate children, drain, exit.
            eof = True
            _terminate(children)
            continue
        buf += chunk
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            if not line.strip():
                continue
            try:
                req = json.loads(line)
            except ValueError:
                continue
            batch = req.get("spawn_batch")
            if batch is None:
                spawn = req.get("spawn")
                batch = [spawn] if spawn is not None else []
            for spawn in batch:
                _fork_one(spawn, children)


if __name__ == "__main__":
    main()
