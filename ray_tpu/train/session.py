"""Per-worker training session: report/get_checkpoint/get_context.

Reference parity: python/ray/train/_internal/session.py (_TrainSession :109,
report :662, get_checkpoint :749) — the worker side of the Train control
plane. The hot loop (the jitted train step) never touches this; report() is
called once per logging interval with scalar metrics.

report() blocks until the driver consumes the result — that per-round
synchronization is what keeps N SPMD workers in lockstep with the driver's
bookkeeping, replacing the reference's queue+next_results pairing
(train/_internal/backend_executor.py:541).
"""

from __future__ import annotations

import contextlib
import queue
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import numpy as np

from ray_tpu._private import compile_cache
from ray_tpu.train.checkpoint import Checkpoint


@dataclass
class TrainContext:
    world_size: int = 1
    world_rank: int = 0
    local_rank: int = 0
    local_world_size: int = 1
    node_rank: int = 0
    experiment_name: str = ""
    storage_path: str = ""
    trial_id: str = ""

    def get_world_size(self) -> int:
        return self.world_size

    def get_world_rank(self) -> int:
        return self.world_rank

    def get_local_rank(self) -> int:
        return self.local_rank

    def get_local_world_size(self) -> int:
        return self.local_world_size

    def get_node_rank(self) -> int:
        return self.node_rank

    def get_experiment_name(self) -> str:
        return self.experiment_name

    def get_trial_id(self) -> str:
        return self.trial_id

    def get_storage_path(self) -> str:
        return self.storage_path


def _host_value(value: Any) -> Any:
    """Metrics leave the worker as host values. A jax.Array pickled to the
    driver is rebuilt there as a device array, which initializes a JAX
    backend in the driver — and on a TPU host the chip belongs to this
    worker, so the driver would fail or hang opening it."""
    jax = sys.modules.get("jax")
    if jax is not None and isinstance(value, jax.Array):
        host = np.asarray(value)
        return host.item() if host.ndim == 0 else host
    if isinstance(value, dict):
        return {k: _host_value(v) for k, v in value.items()}
    if type(value) in (list, tuple):
        return type(value)(_host_value(v) for v in value)
    return value


class _StampedQueue(queue.Queue):
    """Stamps a message `queued_at` at the instant it takes the slot, which
    for a put() that had to wait is later than the call."""

    def _put(self, item):
        item["queued_at"] = time.time()
        super()._put(item)


class _Session:
    """Lives inside the train-worker actor; bridges the user's train fn
    (running on an executor thread) and the driver's polling."""

    def __init__(self, context: TrainContext,
                 checkpoint: Optional[Checkpoint] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 started: Optional[float] = None, watched: bool = False):
        self.context = context
        self.starting_checkpoint = checkpoint
        self.datasets = datasets or {}
        self._results: "queue.Queue" = _StampedQueue(maxsize=1)
        # Seconds the last report's put() waited for the queue's one slot
        # (the driver had not taken the round before); rides the NEXT
        # message, since it is known only once the put has returned.
        self._blocked_s: Optional[float] = None
        # The last report(), entry -> return, and the loop's stretch from
        # that return to the next entry, on time.perf_counter(): they ride
        # the next message too (blocked_s lies inside call_s).
        self._call_s: Optional[float] = None
        self._returned: Optional[float] = None
        # When TrainWorker.start_run was entered, on time.time() (the clock
        # compile_cache's records are on), and whether the compile watch was
        # on from then: the first report carries both, with how long ago
        # that was and the loop's thread, and the driver lays the records
        # out between the two stamps (compile_cache.partition).
        self._started = started
        self._watched = watched
        self._stop = threading.Event()
        # Save-on-preempt: set by TrainWorker.request_save (driver push) or
        # implied by a drain notice for this worker's node; cleared when a
        # checkpoint is reported.
        self._save_requested = threading.Event()

    # -- called from the user train fn (executor thread) --

    def should_checkpoint(self) -> bool:
        """True when the training loop should save NOW: this worker's host
        received a drain/preemption notice (or the driver requested an
        immediate save). A loop that checkpoints every N steps should also
        checkpoint when this flips, so the post-preemption restart resumes
        from the current step instead of the last periodic save."""
        if self._save_requested.is_set():
            return True
        try:
            from ray_tpu._private import worker_api
            return worker_api.local_node_draining()
        except Exception:  # noqa: BLE001 — outside a worker process
            return False

    def request_save(self):
        self._save_requested.set()

    def report(self, metrics: Dict[str, Any],
               checkpoint: Optional[Checkpoint] = None):
        entered = time.perf_counter()
        if self._stop.is_set():
            raise _StopTraining()
        if checkpoint is not None:
            self._save_requested.clear()
        # Where jax is loaded the report is a span of the loop's thread in
        # the profiler's own trace, beside the device's ops.
        jax = sys.modules.get("jax")
        if jax is not None:
            compile_cache.watch()   # a loop that imported jax itself
        with (jax.profiler.TraceAnnotation("train:report") if jax is not None
              else contextlib.nullcontext()):
            message = {"type": "report", "metrics": _host_value(metrics),
                       "checkpoint": checkpoint,
                       "rank": self.context.world_rank}
            if self._started is not None:
                # this report's entry on the wall clock: now, less what
                # perf_counter says has passed since
                message.update(
                    started_at=self._started, watched=self._watched,
                    loop_thread=threading.get_ident(),
                    first_report_s=time.time() - self._started
                    - (time.perf_counter() - entered))
                self._started = None
            self._put(message, entered)
        # Block until consumed: put the *next* item only after the driver
        # drains; queue(maxsize=1) already provides that.
        self._returned = time.perf_counter()
        self._call_s = self._returned - entered

    def finish(self, value: Any = None, error: Optional[str] = None):
        self._put({"type": "error", "error": error}
                  if error else {"type": "done", "value": value},
                  time.perf_counter())

    def _put(self, message: dict, entered: float) -> None:
        message["blocked_s"] = self._blocked_s
        message["call_s"] = self._call_s
        message["loop_s"] = (None if self._returned is None
                             else entered - self._returned)
        # what this process compiled since its last message (start-up's
        # programs on the first, a recompile on a later one)
        compiles = compile_cache.drain()
        if compiles:
            message["compiles"] = compiles
        t0 = time.perf_counter()
        self._results.put(message)
        self._blocked_s = time.perf_counter() - t0

    # -- called from the actor's RPC threads --

    def next_result(self, timeout: float = 10.0) -> Optional[dict]:
        try:
            return self._results.get(timeout=timeout)
        except queue.Empty:
            return None

    def stop(self):
        self._stop.set()


class _StopTraining(Exception):
    pass


_session: Optional[_Session] = None


def _set_session(s: Optional[_Session]):
    global _session
    _session = s


def _get_session() -> Optional[_Session]:
    return _session


def get_context() -> TrainContext:
    if _session is None:
        return TrainContext()
    return _session.context


def report(metrics: Dict[str, Any],
           checkpoint: Optional[Checkpoint] = None) -> None:
    """Report metrics (+ optional checkpoint) for this round; blocks until
    the driver has consumed the previous round (lockstep backpressure)."""
    if _session is None:
        raise RuntimeError("train.report() called outside a train worker")
    _session.report(metrics, checkpoint)


def get_checkpoint() -> Optional[Checkpoint]:
    if _session is None:
        return None
    return _session.starting_checkpoint


def should_checkpoint() -> bool:
    """Save-on-preempt hook: True when this worker's node is being drained
    (spot reclaim / downscale) and the loop should checkpoint immediately.
    Always False outside a train worker."""
    if _session is None:
        return False
    return _session.should_checkpoint()


def get_dataset_shard(name: str = "train"):
    if _session is None:
        raise RuntimeError("get_dataset_shard() outside a train worker")
    ds = _session.datasets.get(name)
    if ds is None:
        raise KeyError(f"no dataset shard named '{name}'")
    return ds
