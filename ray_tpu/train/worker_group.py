"""WorkerGroup: a gang of train-worker actors on a placement group.

Reference parity: python/ray/train/_internal/worker_group.py +
backend_executor.py:197 (PG creation) / :347 (rank mapping).

TPU-first: bundles are per-host gangs (a worker owns every chip of its
host), placed STRICT_PACK onto one slice when the resources fit — the ICI
domain is the scheduling unit (SURVEY.md §7).
"""

from __future__ import annotations

import socket
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

import cloudpickle

import ray_tpu
from ray_tpu._private import compile_cache
from ray_tpu.train import session as _session_mod
from ray_tpu.train.session import TrainContext, _Session
from ray_tpu.util.placement_group import placement_group, \
    remove_placement_group
from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy


class TrainWorker:
    """Actor hosting one training process (one host's worth of chips)."""

    def __init__(self):
        self._session: Optional[_Session] = None
        self._thread: Optional[threading.Thread] = None

    def node_info(self) -> Dict[str, Any]:
        import os
        return {"hostname": socket.gethostname(), "pid": os.getpid(),
                "ip": "127.0.0.1",
                "node_id": os.environ.get("RAY_TPU_NODE_ID", "")}

    def set_env(self, env: Dict[str, str]) -> None:
        import os
        os.environ.update(env)

    def start_run(self, fn_bytes: bytes, config: Optional[dict],
                  context: TrainContext,
                  checkpoint=None, datasets: Optional[dict] = None) -> None:
        started = time.time()       # the clock the watch's records are on
        watched = compile_cache.watch()
        compile_cache.drain()       # an earlier run's, or the backend hook's
        fn = cloudpickle.loads(fn_bytes)
        sess = _Session(context, checkpoint=checkpoint, datasets=datasets,
                        started=started, watched=watched)
        self._session = sess
        _session_mod._set_session(sess)

        def _target():
            try:
                if config is not None:
                    out = fn(config)
                else:
                    out = fn()
                sess.finish(out)
            except _session_mod._StopTraining:
                sess.finish(None)
            except BaseException:  # noqa: BLE001
                sess.finish(None, error=traceback.format_exc())

        t = threading.Thread(target=_target, daemon=True,
                             name="train_loop")
        self._thread = t
        t.start()

    def poll(self, timeout: float = 10.0) -> Optional[dict]:
        if self._session is None:
            return {"type": "error", "error": "worker not started"}
        out = self._session.next_result(timeout)
        if out is not None:
            # this process's clock, as `queued_at`: the hand-over from the
            # loop's thread to this RPC thread
            out["taken_at"] = time.time()
            if out["type"] in ("done", "error"):
                _session_mod._set_session(None)
        return out

    def interrupt(self) -> None:
        if self._session is not None:
            self._session.stop()

    def request_save(self) -> None:
        """Driver-side save-on-preempt push: the next report should carry
        a checkpoint (session.should_checkpoint() flips true)."""
        if self._session is not None:
            self._session.request_save()

    def execute(self, fn_bytes: bytes, *args, **kwargs):
        """Run an arbitrary fn inline on the worker (setup/teardown path)."""
        fn = cloudpickle.loads(fn_bytes)
        return fn(*args, **kwargs)


class WorkerGroup:
    def __init__(self, num_workers: int,
                 resources_per_worker: Dict[str, float],
                 placement_strategy: str = "PACK",
                 max_concurrency: int = 4):
        self.num_workers = num_workers
        self._pg = placement_group(
            [dict(resources_per_worker) for _ in range(num_workers)],
            strategy=placement_strategy)
        if not self._pg.wait(120.0):
            remove_placement_group(self._pg)
            raise TimeoutError(
                f"placement group for {num_workers} train workers "
                f"({resources_per_worker} each) not placeable")
        self.placed_at = time.time()    # BackendExecutor.start's boundary
        cls = ray_tpu.remote(TrainWorker)
        self.workers = []
        for i in range(num_workers):
            w = cls.options(
                num_cpus=resources_per_worker.get("CPU", 1),
                resources={k: v for k, v in resources_per_worker.items()
                           if k != "CPU"} or None,
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    placement_group=self._pg,
                    placement_group_bundle_index=i),
                max_concurrency=max_concurrency,
            ).remote()
            self.workers.append(w)

    def execute(self, fn: Callable, *args, timeout: Optional[float] = 60,
                **kwargs) -> List[Any]:
        """Run fn(*args) on every worker, gather results (barrier)."""
        fn_b = cloudpickle.dumps(fn)
        refs = [w.execute.remote(fn_b, *args, **kwargs) for w in self.workers]
        return ray_tpu.get(refs, timeout=timeout)

    def node_infos(self) -> List[Dict[str, Any]]:
        return ray_tpu.get([w.node_info.remote() for w in self.workers],
                           timeout=60)

    def shutdown(self):
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        try:
            remove_placement_group(self._pg)
        except Exception:
            pass
        self.workers = []
