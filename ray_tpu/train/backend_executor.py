"""BackendExecutor: drives a WorkerGroup through a training run.

Reference parity: python/ray/train/_internal/backend_executor.py
(BackendExecutor :65, PG creation :197, rank mapping :347,
get_next_results :541) and train/torch/config.py:64 (_setup_torch_process
group) — here the backend hook configures the JAX distributed runtime
(coordinator rendezvous over the GCS-backed collective layer) instead of a
NCCL/TCP process group; in-program collectives are compiled by XLA and need
no runtime object at all (SURVEY.md §2.5).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import cloudpickle

from ray_tpu._private import compile_cache, flightrec
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import ScalingConfig
from ray_tpu.train.session import TrainContext
from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.util import tracing


@dataclass
class BackendConfig:
    """Base backend config; subclass hooks run on start/shutdown."""

    def on_start(self, executor: "BackendExecutor") -> None:  # noqa: D401
        pass

    def on_shutdown(self, executor: "BackendExecutor") -> None:
        pass


@dataclass
class JaxBackendConfig(BackendConfig):
    """Sets up the JAX distributed runtime across hosts when needed.

    distributed='auto': initialize jax.distributed only when >1 node hosts
    workers. On a single host (or CPU tests) each worker keeps its private
    local backend. distributed='force': ALWAYS form the multi-controller
    gang — the real multi-host path (one process per host, global device
    list spanning every process) — even when the worker processes share a
    host, which is how CI proves multi-process correctness without
    multi-host hardware (reference: backend_executor.py:347 rank mapping +
    train/torch/config.py:64 process-group bootstrap).

    platform='cpu' (tests): each worker process binds
    `local_device_count` virtual CPU devices and cross-process
    collectives run over gloo; '' leaves the worker's platform alone
    (TPU workers own their host's chips natively).
    """

    distributed: str = "auto"  # auto | off | force
    coordinator_port: int = 0  # 0 = pick a free port on worker 0
    platform: str = ""
    local_device_count: int = 0

    def on_start(self, executor: "BackendExecutor") -> None:
        infos = executor.node_info_per_worker
        n_nodes = len({i["hostname"] for i in infos})
        if self.distributed == "off":
            return
        if self.distributed == "auto" and n_nodes <= 1:
            return
        from ray_tpu.parallel.mp_check import free_port, init_process
        port = self.coordinator_port
        if not port:
            # The coordinator binds on WORKER 0's host, so the free-port
            # probe must run there — a driver-side probe checks the wrong
            # machine on real multi-host clusters.
            w0 = executor.worker_group.workers[0]
            import ray_tpu as _rt
            port = _rt.get(w0.execute.remote(cloudpickle.dumps(free_port)),
                           timeout=60)
        coord = f"{infos[0]['ip']}:{port}"
        world = executor.world_size
        fn_b = cloudpickle.dumps(init_process)
        import ray_tpu
        refs = [
            w.execute.remote(fn_b, rank, world, coord,
                             self.local_device_count, self.platform)
            for rank, w in enumerate(executor.worker_group.workers)
        ]
        ray_tpu.get(refs, timeout=180)


# The phases of a run, in the runtime's idiom: one tree of flight-recorder
# spans a run (root `train:run`, every span under the run's id as its
# trace_id, drawn by `ray_tpu timeline`) and catalogued ray_tpu_train_*
# metrics in this process's registry. What is measured in a worker is
# stamped there, rides the message the worker sends anyway, and is folded
# and exported here.
_metrics: Optional[dict] = None
_MAX_PROGRAMS = 16              # ray_tpu_train_program_seconds' names a run
_program_rows: List[dict] = []  # its rows of the last run, to take away


def _metric_handles() -> dict:
    global _metrics
    if _metrics is None:
        from ray_tpu.util import metrics
        _metrics = {
            "start": metrics.Gauge(
                "ray_tpu_train_start_seconds",
                "wall time of the last run's start-up. On the driver: "
                "Phase=workers (BackendExecutor.start) and inside it "
                "Phase=placement (placement group asked for -> placed), "
                "Phase=actors (first train-worker actor asked for -> "
                "every node_info back), Phase=hook (backend.on_start); "
                "Phase=training (loop shipped, every worker's "
                "start_run back); Phase=before (this process's last "
                "init() returned -> BackendExecutor.start entered; not set "
                "where it called none). In the slowest worker, carried by "
                "its first message: Phase=first_report (start_run -> the "
                "loop's first report() entered) and, inside it, what it "
                "compiled: Phase=trace, lower, cache_load (loads from the "
                "persistent cache), compile (backend compiles less those "
                "loads), of them Phase=off_thread on other threads than the "
                "loop's; and the loop's thread between its compiles: "
                "Phase=head (start_run -> the first compile record), "
                "between (the gaps from one record to the next), "
                "first_step (the last record -> the first report()), so "
                "that first_report = head + trace + lower + cache_load + "
                "compile - off_thread + between + first_step. A worker "
                "whose compiles were not watched from start_run sets "
                "first_report alone",
                tag_keys=("Phase",)),
            "program": metrics.Gauge(
                "ray_tpu_train_program_seconds",
                "the slowest worker's start-up by program (Program= the "
                "compile records' fun_name, the 16 largest and the rest "
                "under `other`): Phase=trace, lower, cache_load, compile, "
                "their sum Phase=total, and Phase=after (the loop's thread "
                "from a record of the program to the next record, or to "
                "the first report() after the last one)",
                tag_keys=("Program", "Phase")),
            "recompiles": metrics.Counter(
                "ray_tpu_train_recompiles_total",
                "programs a train worker compiled after its first "
                "message: a shape or a static argument that changed "
                "mid-run (the compile:* spans name the function)"),
            "report": metrics.Histogram(
                "ray_tpu_train_report_seconds",
                "what a train.report() round costs. In the worker, one "
                "process's clock each: Phase=call (report() entry -> "
                "return), inside it Phase=blocked (the loop's put waited "
                "for the driver to take the round before), Phase=wake "
                "(result queued by the loop's thread -> taken by the "
                "RPC thread that serves poll). Across processes: "
                "Phase=poll (result queued on the worker -> held by the "
                "driver; worker's and driver's wall clocks)",
                boundaries=[0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                            0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 5.0],
                tag_keys=("Phase",)),
        }
    return _metrics


class TrainingFailedError(RuntimeError):
    """A training attempt failed. ``preempted`` marks attempts lost to a
    planned node drain / spot reclaim: JaxTrainer retries those without
    charging FailureConfig.max_failures (unless fail_on_preemption)."""

    def __init__(self, *args, preempted: bool = False):
        self.preempted = preempted
        super().__init__(*args)


class BackendExecutor:
    def __init__(self, scaling: ScalingConfig,
                 backend: Optional[BackendConfig] = None,
                 experiment_name: str = "", storage_path: str = "",
                 trial_id: str = ""):
        self.scaling = scaling
        self.backend = backend or JaxBackendConfig()
        self.experiment_name = experiment_name
        self.storage_path = storage_path
        self.trial_id = trial_id
        self.worker_group: Optional[WorkerGroup] = None
        self.node_info_per_worker: List[dict] = []
        self.world_size = scaling.num_workers

    def start(self):
        entered = flightrec.stamp()
        self._started_at = started = entered[0]
        self._save_pushed = False
        # the run's trace: every span of the run carries run_id, and
        # train:run (exported by shutdown) is the root of its tree
        self.run_id = flightrec.new_trace_id()
        self._run_span = flightrec.new_trace_id()
        workers_span = flightrec.new_trace_id()
        self._record_before(entered)
        self._last_taken: Dict[int, tuple] = {}
        self.worker_group = WorkerGroup(
            self.scaling.num_workers, self.scaling.worker_resources(),
            self.scaling.placement_strategy)
        placed = self.worker_group.placed_at
        self.node_info_per_worker = self.worker_group.node_infos()
        answered = time.time()
        self.backend.on_start(self)
        hooked = time.time()
        self._start_preempt_watcher()
        now = time.time()
        gauge = _metric_handles()["start"]
        for phase, name, a, b in (
                ("placement", "train:placement", started, placed),
                ("actors", "train:actors", placed, answered),
                ("hook", "train:backend_hook", answered, hooked)):
            gauge.set(b - a, {"Phase": phase})
            self._span(name, a, b, workers_span)
        gauge.set(now - started, {"Phase": "workers"})
        self._span("train:start_workers", started, now, self._run_span,
                   span_id=workers_span)

    def _record_before(self, entered: tuple) -> None:
        """The driver's stretch between the two calls: this process's last
        init() returned -> start() entered (`entered`, a flightrec.stamp()),
        as a gauge and, with tracing enabled, a span of the run's trace
        with `jax_loaded` at its two edges (a root beside train:run, which
        starts where it ends: the timeline clamps a child into its parent's
        slice). Nothing where this process called no init()."""
        from ray_tpu._private import worker_api
        returned = worker_api.init_returned()
        if returned is None:
            return
        _metric_handles()["start"].set(entered[0] - returned[0],
                                       {"Phase": "before"})
        if tracing.is_enabled():
            self._span("train:before_start", returned[0], entered[0], "",
                       jax_loaded=[returned[1], entered[1]])

    def _span(self, name: str, start: float, end: float, parent_id: str,
              **extra) -> None:
        """Export a span of this run's tree."""
        try:
            tracing.export_span(flightrec.span_event(
                name, self.run_id, start, end, parent_id=parent_id, **extra))
        except Exception:  # noqa: BLE001 — observability never blocks
            pass

    # ---- driver-side preemption watcher ----

    def _start_preempt_watcher(self):
        """Event-driven watch of the driver's drain-event log so
        save-on-preempt fires even when only the DRIVER sees the notice
        (e.g. the gang workers' pubsub frames were lost with their node,
        or the notice landed between report rounds). Worker-side
        should_checkpoint() and the get_next_results() check remain the
        other two braces.

        The core worker's nodes-channel pubsub pushes a wakeup the
        instant a notice lands (worker_api.add_drain_event_listener), so
        steady state costs zero polls; a slow poll remains as the
        fallback for a dropped subscription (no core, or the GCS channel
        lost mid-run). Without a subscription the legacy 0.25 s poll
        cadence is kept."""
        self._stop_preempt_watcher()  # restart attempts re-arm cleanly
        self._watch_stop = threading.Event()
        kick = self._watch_kick = threading.Event()
        from ray_tpu._private import worker_api

        def _listener():
            kick.set()

        self._watch_listener = _listener
        try:
            subscribed = worker_api.add_drain_event_listener(_listener)
        except Exception:  # noqa: BLE001 — not connected (unit tests)
            subscribed = False
        poll_s = 5.0 if subscribed else 0.25

        def _loop():
            while not self._watch_stop.is_set():
                kick.wait(poll_s)  # push wakeup; timeout = poll fallback
                kick.clear()
                if self._watch_stop.is_set() or self._save_pushed:
                    return
                try:
                    if self._preempted_since_start():
                        self._save_pushed = True
                        self.request_save()
                        return
                except Exception:  # noqa: BLE001 — watcher must not die
                    pass

        self._watcher = threading.Thread(
            target=_loop, daemon=True, name="train-preempt-watcher")
        self._watcher.start()

    def _stop_preempt_watcher(self):
        stop = getattr(self, "_watch_stop", None)
        if stop is not None:
            stop.set()
        kick = getattr(self, "_watch_kick", None)
        if kick is not None:
            kick.set()  # unblock the wait so the thread exits promptly
        listener = getattr(self, "_watch_listener", None)
        if listener is not None:
            from ray_tpu._private import worker_api
            try:
                worker_api.remove_drain_event_listener(listener)
            except Exception:  # noqa: BLE001
                pass
            self._watch_listener = None
        watcher = getattr(self, "_watcher", None)
        if watcher is not None:
            watcher.join(timeout=2.0)
            self._watcher = None

    def _preempted_since_start(self) -> bool:
        """Did a node HOSTING THIS GANG receive a drain/preemption notice
        after this attempt started? Gang failures observed afterwards
        classify as planned loss (the SPMD gang co-fails with its slowest
        host, so a single drained host explains the whole restart).
        Events for unrelated nodes (routine downscales elsewhere) must
        not launder genuine crashes into uncharged retries."""
        from ray_tpu._private import worker_api
        try:
            events = worker_api.drain_events()
        except Exception:  # noqa: BLE001 — not connected (unit tests)
            return False
        start = getattr(self, "_started_at", 0.0)
        gang_nodes = {i.get("node_id", "") for i in self.node_info_per_worker}
        gang_nodes.discard("")
        def _hexes(ev) -> list:
            ids = ev.get("node_ids") or [ev.get("node_id")]
            return [nid.hex() if hasattr(nid, "hex") else str(nid or "")
                    for nid in ids]

        for ev in events:
            if ev.get("time", 0.0) < start:
                continue
            # Unknown gang placement (old workers without node_id): keep
            # the permissive classification rather than charging a
            # possibly-planned loss. Slice gang_draining events carry
            # every member id — any overlap with the training gang's
            # hosts classifies the restart as planned.
            if not gang_nodes or gang_nodes & set(_hexes(ev)):
                return True
        return False

    def request_save(self):
        """Best-effort save-on-preempt push to every gang worker."""
        for w in self.worker_group.workers if self.worker_group else []:
            try:
                w.request_save.remote()
            except Exception:  # noqa: BLE001 — worker may be mid-restart
                pass

    def _contexts(self) -> List[TrainContext]:
        """Global rank = position; local rank = index within its node
        (reference rank mapping: backend_executor.py:347)."""
        by_node: Dict[str, List[int]] = {}
        for i, info in enumerate(self.node_info_per_worker):
            by_node.setdefault(info["hostname"], []).append(i)
        node_order = sorted(by_node)
        ctxs = []
        for rank, info in enumerate(self.node_info_per_worker):
            host = info["hostname"]
            ctxs.append(TrainContext(
                world_size=self.world_size, world_rank=rank,
                local_rank=by_node[host].index(rank),
                local_world_size=len(by_node[host]),
                node_rank=node_order.index(host),
                experiment_name=self.experiment_name,
                storage_path=self.storage_path, trial_id=self.trial_id))
        return ctxs

    def start_training(self, train_fn: Callable, config: Optional[dict],
                       checkpoint: Optional[Checkpoint] = None,
                       datasets_per_worker: Optional[List[dict]] = None):
        started = time.time()
        self._first_round = True
        fn_b = cloudpickle.dumps(train_fn)
        refs = []
        for i, (w, ctx) in enumerate(zip(self.worker_group.workers,
                                         self._contexts())):
            ds = datasets_per_worker[i] if datasets_per_worker else None
            refs.append(w.start_run.remote(fn_b, config, ctx,
                                           checkpoint, ds))
        import ray_tpu
        ray_tpu.get(refs, timeout=60)
        now = time.time()
        _metric_handles()["start"].set(now - started, {"Phase": "training"})
        self._span("train:start_training", started, now, self._run_span)

    def get_next_results(self, timeout: float = 600.0) -> Optional[List[dict]]:
        """One result per worker for this round, or None when all done.

        Raises TrainingFailedError if any worker errored.
        """
        import ray_tpu
        started = time.time()
        report_seconds = _metric_handles()["report"]
        deadline = time.monotonic() + timeout
        results: List[Optional[dict]] = [None] * len(self.worker_group.workers)
        pending = set(range(len(results)))
        finished: Dict[int, dict] = {}
        # Driver-side save-on-preempt push: if a gang node's drain notice
        # reached the driver (it may land here before the workers see
        # their own pubsub), tell every worker to checkpoint on its next
        # report. Belt to the worker-side should_checkpoint() braces.
        if not self._save_pushed and self._preempted_since_start():
            self._save_pushed = True
            self.request_save()
        while pending:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError("timed out waiting for train results")
            refs = {i: self.worker_group.workers[i].poll.remote(
                min(5.0, remaining)) for i in pending}
            for i, ref in refs.items():
                try:
                    out = ray_tpu.get(ref, timeout=30)
                except Exception as e:  # noqa: BLE001 — gang worker lost
                    self._interrupt()
                    raise TrainingFailedError(
                        f"{type(e).__name__}: {e}",
                        preempted=(getattr(e, "preempted", False)
                                   or self._preempted_since_start()))
                if out is None:
                    continue
                if out.get("queued_at") is not None:
                    report_seconds.observe(
                        max(0.0, time.time() - out["queued_at"]),
                        {"Phase": "poll"})
                    if out.get("taken_at") is not None:
                        report_seconds.observe(
                            max(0.0, out["taken_at"] - out["queued_at"]),
                            {"Phase": "wake"})
                for phase in ("blocked", "call"):
                    if out.get(phase + "_s") is not None:
                        report_seconds.observe(out[phase + "_s"],
                                               {"Phase": phase})
                if out["type"] == "error":
                    self._interrupt()
                    raise TrainingFailedError(
                        out["error"],
                        preempted=self._preempted_since_start())
                if out["type"] == "done":
                    finished[i] = out
                    pending.discard(i)
                else:
                    results[i] = out
                    pending.discard(i)
        messages = {i: finished.get(i) or out
                    for i, out in enumerate(results)}
        # per-round spans only where tracing is on; a compile's span hangs
        # under the round whose message carried it, or under the run
        parent = self._run_span
        if tracing.is_enabled():
            parent = flightrec.new_trace_id()
            self._span("train:round", started, time.time(), self._run_span,
                       span_id=parent)
            self._export_report_spans(messages, parent)
        self._fold_compiles(messages, parent)
        if finished and len(finished) == len(results):
            return None
        if finished:
            # Mixed done/report: treat stragglers' reports as the last round.
            return [r for r in results if r is not None] or None
        return results

    def _export_report_spans(self, messages: Dict[int, dict],
                             round_span: str) -> None:
        """The worker's side of a round, on the worker's lane, from the
        stamps its messages carry. A message says when it took the queue's
        slot (`queued_at`, the worker's wall clock: microseconds before its
        report() returned) and, on the worker's perf_counter, how long the
        report BEFORE it took (`call_s`) and how long the loop then ran
        until it entered this one (`loop_s`). So the message before dates
        both: train:report ends where it was queued, under the round that
        took it, and train:loop starts there, under this round."""
        for i, out in messages.items():
            pid = self.node_info_per_worker[i].get("pid")
            before = self._last_taken.get(i)
            if before is not None and out.get("call_s") is not None:
                queued_at, parent = before
                self._span("train:report", queued_at - out["call_s"],
                           queued_at, parent, pid=pid, call_s=out["call_s"],
                           blocked_s=out.get("blocked_s"))
                self._span("train:loop", queued_at,
                           queued_at + out["loop_s"], round_span, pid=pid,
                           loop_s=out["loop_s"])
            self._last_taken[i] = (out.get("queued_at"), round_span)

    def _fold_compiles(self, messages: Dict[int, dict],
                       parent: str) -> None:
        """What the workers compiled since their last message
        (_private/compile_cache.py's records, carried by this round's
        messages): every record a compile:<phase> span on its worker's
        lane, under `parent`; the first round's are the run's start-up, in
        the gauges, and any later one is a recompile, in the counter."""
        handles = _metric_handles()
        for i, out in messages.items():
            pid = self.node_info_per_worker[i].get("pid")
            for fun_name, phase, start, end, load_s, _thread in out.get(
                    "compiles", ()):
                cache = ({"cache": "miss" if load_s is None else "hit"}
                         if phase == "compile" else {})
                self._span("compile:" + phase, start, end, parent, pid=pid,
                           fun_name=fun_name, **cache)
        if not self._first_round:
            recompiles = sum(record[1] == "compile"
                             for out in messages.values()
                             for record in out.get("compiles", ()))
            if recompiles:
                handles["recompiles"].inc(recompiles)
            return
        self._first_round = False
        slowest = max(messages.values(),
                      key=lambda out: out.get("first_report_s", -1.0))
        traced = tracing.is_enabled()
        for i, out in messages.items():
            # a loop that never reports carries no stamps, and one whose
            # compiles were not watched from start_run has no partition
            if not out.get("watched") or not (traced or out is slowest):
                continue
            laid = compile_cache.partition(
                out.get("compiles", ()), out["started_at"],
                out["started_at"] + out["first_report_s"],
                out["loop_thread"])
            if traced:
                self._export_start_up_spans(
                    laid.gaps, self.node_info_per_worker[i].get("pid"))
            if out is slowest:
                self._set_start_up(laid)
        if not slowest.get("watched"):
            self._set_start_up(None)
        if "first_report_s" in slowest:
            handles["start"].set(slowest["first_report_s"],
                                 {"Phase": "first_report"})

    def _export_start_up_spans(self, gaps, pid) -> None:
        """A worker's lane from start_run to its first report without a
        hole: what lies between the compile:* spans, under train:run."""
        for name, start, end, after in gaps:
            if name != "between":
                self._span("train:" + name, start, end, self._run_span,
                           pid=pid)
            elif end - start >= 0.001:
                self._span("train:between", start, end, self._run_span,
                           pid=pid, after=after)

    def _set_start_up(self, laid: Optional[compile_cache.Partition]) -> None:
        """The slowest worker's start-up into the gauges: its phases and its
        programs. None (its compiles were not watched from start_run) takes
        the last run's rows away and sets nothing, so that a reader finds no
        row where nothing was measured."""
        from ray_tpu.util import metrics
        global _program_rows
        handles = _metric_handles()
        for tags in _program_rows:
            metrics.remove("ray_tpu_train_program_seconds", tags)
        _program_rows = []
        if laid is None:
            for phase in compile_cache.STRETCHES:
                metrics.remove("ray_tpu_train_start_seconds",
                               {"Phase": phase})
            return
        for phase, value in laid.seconds.items():
            handles["start"].set(value, {"Phase": phase})
        by_size = sorted(laid.programs.items(),
                         key=lambda item: -sum(item[1].values()))
        rows: Dict[str, Dict[str, float]] = dict(by_size[:_MAX_PROGRAMS])
        for _name, own in by_size[_MAX_PROGRAMS:]:
            other = rows.setdefault("other", dict.fromkeys(own, 0.0))
            for phase, value in own.items():
                other[phase] += value
        for program, own in rows.items():
            own = dict(own, total=sum(own[p] for p in compile_cache.PHASES))
            for phase, value in own.items():
                tags = {"Program": program, "Phase": phase}
                handles["program"].set(value, tags)
                _program_rows.append(tags)

    def _interrupt(self):
        for w in self.worker_group.workers:
            try:
                w.interrupt.remote()
            except Exception:
                pass

    def shutdown(self):
        self._stop_preempt_watcher()
        if self.worker_group is not None:
            self.backend.on_shutdown(self)
            self.worker_group.shutdown()
            self.worker_group = None
            self._span("train:run", self._started_at, time.time(), "",
                       span_id=self._run_span)
