"""Driver-side global state + the init/get/put/wait API core.

Reference parity: python/ray/_private/worker.py (ray.init :1219, get :2547,
put :2679, wait :2744, shutdown :1796, get_actor :2890).
"""

from __future__ import annotations

import asyncio
import atexit
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from ray_tpu import exceptions as exc
from ray_tpu._private import flightrec
from ray_tpu._private.config import Config, set_config
from ray_tpu._private.core_worker import CoreWorker
from ray_tpu._private.node import HeadNode, detect_node_resources
from ray_tpu._private.object_ref import ObjectRef

logger = logging.getLogger(__name__)


class _GlobalState:
    def __init__(self):
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self.loop_thread: Optional[threading.Thread] = None
        self.head: Optional[HeadNode] = None
        self.core: Optional[CoreWorker] = None
        self.initialized = False
        self.namespace = ""
        self.gcs_address = ""
        self.exported_functions: Dict[str, bool] = {}
        # Job-level default runtime env (init(runtime_env=...)); merged
        # under per-task/actor envs by resolve_runtime_env.
        self.job_runtime_env: Optional[dict] = None
        # Ray-client mode (init(address="ray_tpu://...")): every API call
        # proxies through this context instead of a local CoreWorker.
        self.client = None
        # flightrec.stamp() at the return of this process's last init():
        # where BackendExecutor.start's `before` stretch begins
        self.init_returned: Optional[tuple] = None

    def run(self, coro, timeout: Optional[float] = None):
        fut = asyncio.run_coroutine_threadsafe(coro, self.loop)
        return fut.result(timeout)


_state = _GlobalState()


def _ensure_loop():
    if _state.loop is not None:
        return
    ready = threading.Event()

    def _run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        _state.loop = loop
        ready.set()
        loop.run_forever()

    t = threading.Thread(target=_run, daemon=True, name="ray_tpu-loop")
    t.start()
    _state.loop_thread = t
    ready.wait(10)


def is_initialized() -> bool:
    return _state.initialized


def get_core() -> CoreWorker:
    # Worker-process context: the executing CoreWorker registers itself here
    # so user code inside tasks can call the public API.
    if _worker_core.core is not None:
        return _worker_core.core
    if not _state.initialized:
        init()
    return _state.core


def peek_core() -> Optional[CoreWorker]:
    """The live CoreWorker, or None — NEVER auto-initializes. For
    observability paths (span export, serve request events) that must
    degrade to buffering instead of spinning up a cluster as a side
    effect."""
    if _worker_core.core is not None:
        return _worker_core.core
    return _state.core if _state.initialized else None


class _WorkerCore:
    """Set inside worker processes (see worker_main) for API reentrancy."""
    def __init__(self):
        self.core: Optional[CoreWorker] = None


_worker_core = _WorkerCore()


def init(address: Optional[str] = None, *,
         num_cpus: Optional[float] = None,
         num_tpus: Optional[float] = None,
         resources: Optional[Dict[str, float]] = None,
         labels: Optional[Dict[str, str]] = None,
         object_store_memory: Optional[int] = None,
         namespace: str = "",
         runtime_env: Optional[dict] = None,
         system_config: Optional[dict] = None,
         ignore_reinit_error: bool = True,
         log_level: int = logging.WARNING):
    """Start (or connect to) a cluster and connect this driver."""
    if _state.initialized:
        if ignore_reinit_error:
            return _state
        raise RuntimeError("ray_tpu already initialized")
    entered = flightrec.stamp()
    if isinstance(address, str) and (address.startswith("ray_tpu://")
                                     or address.startswith("ray://")):
        # Client mode (reference: ray.init("ray://...")): the process
        # never joins the cluster network; the whole API proxies through
        # the head's ClientServer. runtime_env packages are zipped locally
        # and shipped with the first submission that references them.
        from ray_tpu.util.client import ClientContext
        endpoint = address.split("://", 1)[1]
        _state.client = ClientContext(endpoint, namespace=namespace,
                                      runtime_env=runtime_env)
        _state.namespace = namespace
        _state.initialized = True
        atexit.register(shutdown)
        return _state
    from ray_tpu._private import runtime_env as _re
    _state.job_runtime_env = _re.validate(runtime_env)
    if address in (None, "auto"):
        # Job entrypoints / CLI children inherit the cluster address
        # (reference: RAY_ADDRESS handling in ray.init).
        address = os.environ.get("RAY_TPU_ADDRESS") or None
    logging.basicConfig(level=log_level)
    config = Config.load(system_config)
    set_config(config)
    _ensure_loop()
    _state.namespace = namespace

    # phase -> its two edges, each a flightrec.stamp() at a boundary below
    phases: Dict[str, tuple] = {}

    async def _boot():
        if address is None:
            res = detect_node_resources(num_cpus, num_tpus, resources, config)
            head = HeadNode(config, resources=res, labels=labels,
                            object_store_memory=object_store_memory)
            gcs_address = await head.start()
            raylet_address = head.raylet.address
            _state.head = head
            phases.update(head.boot_phases)
        else:
            gcs_address = address
            from ray_tpu._private import rpc
            conn = await rpc.connect(gcs_address)
            nodes = await conn.request("get_all_nodes", {})
            await conn.close()
            alive = [n for n in nodes if n.alive]
            if not alive:
                raise exc.RayTpuSystemError("no alive nodes in cluster")
            heads = [n for n in alive if n.is_head]
            raylet_address = (heads[0] if heads else alive[0]).address
        from ray_tpu._private import rpc
        at_connect = flightrec.stamp()
        conn = await rpc.connect(gcs_address)
        job_id = await conn.request("register_job",
                                    {"driver_address": "", "entrypoint": ""})
        await conn.close()
        core = CoreWorker("driver", gcs_address, raylet_address, config,
                          job_id=job_id)
        await core.start_async()
        phases["connect"] = (at_connect, flightrec.stamp())
        _state.core = core
        _state.gcs_address = gcs_address
        return gcs_address

    _state.run(_boot(), timeout=60)
    _state.initialized = True
    atexit.register(shutdown)
    _state.init_returned = flightrec.stamp()
    _record_init(entered, _state.init_returned, phases)
    return _state


def init_returned() -> Optional[tuple]:
    """flightrec.stamp() at the return of this process's last init(), None
    where it called none (a worker, a client)."""
    return _state.init_returned


def _process_start_wall() -> Optional[float]:
    """When this process started, on time.time()'s clock: its start time
    from /proc (clock ticks after boot) against the uptime. None where
    /proc does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return None


_INIT_PHASE_SPANS = {"before": "runtime:before_init",
                     "gcs": "runtime:gcs_start",
                     "raylet": "runtime:raylet_start",
                     "connect": "runtime:connect"}


def _record_init(entered: tuple, returned: tuple,
                 phases: Dict[str, tuple]) -> None:
    """init() as the caller saw it, from flightrec.stamp()s at its edges:
    ray_tpu_init_seconds and, by phase, ray_tpu_init_phase_seconds in this
    process's registry (gcs and raylet read 0.0 where init joined a cluster
    that was there; before, the process's start -> init() entered, 0.0
    where /proc does not say when that was) and, where tracing is enabled (a
    driver with tracing off records no span at all), the `runtime:init`
    flight-recorder span with one child a phase that ran and, ahead of it
    in the same trace, `runtime:before_init`, each with `jax_loaded` at its
    two edges."""
    try:
        from ray_tpu.util import metrics, tracing
        (start, _), (end, _) = entered, returned
        metrics.Gauge(
            "ray_tpu_init_seconds",
            "wall time of this process's last ray_tpu.init(): head or "
            "connection up and the driver's core worker started"
        ).set(end - start)
        by_phase = metrics.Gauge(
            "ray_tpu_init_phase_seconds",
            "inside ray_tpu_init_seconds: Phase=gcs (GcsServer.start), "
            "Phase=raylet (the head Raylet.start), both 0.0 where init() "
            "joined a running cluster, and Phase=connect (register_job and "
            "the driver's CoreWorker.start_async); ahead of it: "
            "Phase=before (this process's start, from /proc -> init() "
            "entered; 0.0 where /proc does not say)",
            tag_keys=("Phase",))
        born = _process_start_wall()
        if born is not None:    # a process starts with nothing loaded
            phases = dict(phases, before=((born, False), entered))
        for phase in _INIT_PHASE_SPANS:
            (a, _), (b, _) = phases.get(phase, ((0.0, None), (0.0, None)))
            by_phase.set(b - a, {"Phase": phase})
        if tracing.is_enabled():
            root = flightrec.span_event(
                "runtime:init", flightrec.new_trace_id(), start, end,
                jax_loaded=[entered[1], returned[1]])
            tracing.export_span(root)
            for phase, ((a, a_loaded), (b, b_loaded)) in phases.items():
                # `before` lies ahead of runtime:init, so it is a second
                # root of the trace: the timeline clamps a child into its
                # parent's slice
                tracing.export_span(flightrec.span_event(
                    _INIT_PHASE_SPANS[phase], root["trace_id"], a, b,
                    parent_id="" if phase == "before" else root["span_id"],
                    jax_loaded=[a_loaded, b_loaded]))
    except Exception:  # noqa: BLE001 — observability never blocks init
        logger.debug("init span/metric not recorded", exc_info=True)


def client_mode():
    return _state.client


def shutdown():
    if not _state.initialized:
        return
    if _state.client is not None:
        try:
            _state.client.disconnect()
        except Exception:
            pass
        _state.client = None
        _state.initialized = False
        return
    try:
        if _state.core is not None:
            _state.run(_state.core.shutdown_async(), timeout=10)
    except Exception:
        pass
    try:
        if _state.head is not None:
            _state.run(_state.head.stop(), timeout=10)
    except Exception:
        pass
    _state.core = None
    _state.head = None
    _state.initialized = False
    _state.exported_functions.clear()
    _state.job_runtime_env = None


def resolve_runtime_env(env: Optional[dict]) -> Optional[dict]:
    """Merge a per-task/actor env over the job default and validate."""
    from ray_tpu._private import runtime_env as _re
    merged = _re.merge(_state.job_runtime_env, _re.validate(env))
    return merged


def put(value: Any) -> ObjectRef:
    if _state.client is not None:
        return _state.client.put(value)
    core = get_core()
    # put_sync is thread-safe: inline-size values never cross threads; large
    # values only hop to the loop for the store RPCs.
    return core.put_sync(value)


def get(refs, timeout: Optional[float] = None):
    if _state.client is not None:
        return _state.client.get(refs, timeout)
    core = get_core()
    if isinstance(refs, (list, tuple)):
        bad = [r for r in refs if not isinstance(r, ObjectRef)]
        if bad:
            raise TypeError(
                f"get() expects ObjectRefs; got {type(bad[0]).__name__}")
        refs = list(refs)
    elif not isinstance(refs, ObjectRef):
        raise TypeError(
            f"get() expects an ObjectRef or a list of them; got "
            f"{type(refs).__name__}")
    coro = core.get_async(refs, timeout)
    return _call_on_core_loop(core, coro, timeout)


def get_local(ref: ObjectRef, timeout: Optional[float] = None):
    """Node-local object-plane get: `(value,)` when this node's store
    holds the object (pinned zero-copy view), None when it does not.
    Never crosses the network — callers fall back to `get()` for the
    cross-node transfer path."""
    if _state.client is not None:
        return None  # client mode has no node-local store
    core = get_core()
    if not isinstance(ref, ObjectRef):
        raise TypeError(f"get_local() expects an ObjectRef; got "
                        f"{type(ref).__name__}")
    return _call_on_core_loop(core, core.get_local_async(ref, timeout),
                              timeout)


def wait(refs: List[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True):
    if _state.client is not None:
        return _state.client.wait(list(refs), num_returns=num_returns,
                                  timeout=timeout)
    core = get_core()
    refs = list(refs)
    if any(not isinstance(r, ObjectRef) for r in refs):
        raise TypeError("wait() expects a list of ObjectRefs")
    coro = core.wait_async(refs, num_returns, timeout, fetch_local)
    return _call_on_core_loop(core, coro, None)


def _on_core_loop(core: CoreWorker) -> bool:
    """True when the caller is executing on the core event loop thread
    (async actor methods, serve replicas/controller)."""
    try:
        return asyncio.get_running_loop() is core.loop
    except RuntimeError:
        return False


def _call_on_core_loop(core: CoreWorker, coro, timeout):
    """Run coro on the core loop from whatever thread we're on."""
    if _on_core_loop(core):
        coro.close()
        raise RuntimeError(
            "blocking API called from the core event loop; use await/async "
            "variants inside async actors")
    fut = asyncio.run_coroutine_threadsafe(coro, core.loop)
    return fut.result(None if timeout is None else timeout + 10)


def kill(actor, *, no_restart: bool = True):
    if _state.client is not None:
        return _state.client.kill(actor, no_restart)
    from ray_tpu.actor import ActorHandle
    if not isinstance(actor, ActorHandle):
        raise TypeError("kill() expects an ActorHandle")
    core = get_core()
    if _on_core_loop(core):
        # Async-actor context: fire and forget (kill is idempotent).
        asyncio.ensure_future(core.kill_actor(actor._actor_id, no_restart))
        return
    _call_on_core_loop(core, core.kill_actor(actor._actor_id, no_restart), 10)


def cancel(ref: ObjectRef, *, force: bool = False):
    if _state.client is not None:
        return _state.client.cancel(ref, force)
    core = get_core()
    _call_on_core_loop(core, core.cancel_task(ref, force), 10)


def get_actor(name: str, namespace: Optional[str] = None):
    if _state.client is not None:
        return _state.client.get_actor(name, namespace)
    from ray_tpu.actor import ActorHandle
    core = get_core()
    ns = namespace if namespace is not None else _state.namespace
    info = _call_on_core_loop(core, core.get_named_actor(name, ns), 10)
    return ActorHandle._from_actor_info(info)


def nodes() -> List[dict]:
    if _state.client is not None:
        return _state.client.nodes()
    core = get_core()
    infos = _call_on_core_loop(core, core.gcs.request("get_all_nodes", {}), 10)
    return [{
        "NodeID": n.node_id.hex(), "Alive": n.alive, "Address": n.address,
        "Resources": n.resources_total, "Labels": n.labels,
        "IsHead": n.is_head, "Draining": getattr(n, "draining", False),
        "SliceId": getattr(n, "slice_id", ""),
    } for n in infos]


async def prestart_workers_async(core, count: int,
                                 runtime_env: Optional[dict] = None) -> int:
    """Core-loop half of prestart_workers — the ONE place that prepares
    the env and shapes the hint RPC (the serve controller calls this
    directly; keep the payload in sync with raylet rpc_prestart_workers
    by editing here, not at call sites)."""
    env = resolve_runtime_env(runtime_env)
    env_hash = ""
    if env:
        if env.get("container"):
            # Container workers need dedicated spawns (WarmPools.pop is
            # exact-only for them — a generic process can never enter
            # the container retroactively): a hint would fork generic
            # workers no container create can use, and pin the fresh
            # pool floor doing it. Same skip the GCS's own hint path
            # (_send_prestart_hints) applies.
            return 0
        # Same packaging + hash stamping the actor spec will get, so
        # the hint keys the SAME pool the creates will ask for (and
        # the package upload itself is pre-warmed).
        prepared = await core.prepare_runtime_env(dict(env))
        env_hash = prepared.get("_hash", "")
    return await core.gcs.request(
        "prestart_workers", {"count": int(count), "env_hash": env_hash})


def prestart_workers(count: int, runtime_env: Optional[dict] = None) -> int:
    """Warm the cluster's worker pools ahead of a launch storm: `count`
    actor/task creations for `runtime_env` are about to be submitted.
    The GCS fans the hint across schedulable raylets (env-keyed pool
    floors + immediate multi-spawn through the forkserver), so the storm
    finds forked workers instead of paying cold process boots. Best
    effort; returns the number of nodes hinted."""
    core = get_core()
    return _call_on_core_loop(
        core, prestart_workers_async(core, count, runtime_env), 30)


def drain_events() -> List[dict]:
    """Drain/preemption notices observed by this process's core worker
    ({"time", "node_id", "address", "deadline"} per event). Train uses
    this to classify gang failures as planned (uncharged) losses."""
    core = _worker_core.core or _state.core
    return list(core.drain_events) if core is not None else []


def add_drain_event_listener(cb) -> bool:
    """Register a push wakeup fired (from the core loop) whenever a
    drain/preemption notice lands in this process's drain-event log.
    Returns False when no core worker is connected — the caller should
    fall back to polling drain_events(). The callback must be cheap and
    thread-agnostic (typically threading.Event.set)."""
    core = _worker_core.core or _state.core
    if core is None:
        return False
    core.drain_listeners.append(cb)
    return True


def remove_drain_event_listener(cb) -> None:
    core = _worker_core.core or _state.core
    if core is not None:
        try:
            core.drain_listeners.remove(cb)
        except ValueError:
            pass


def local_node_draining() -> bool:
    """True inside a process whose hosting node received a drain notice
    (spot reclaim / downscale). The save-on-preempt hook: a training loop
    should checkpoint now — this host is going away."""
    core = _worker_core.core or _state.core
    return bool(core is not None and core.local_node_draining)


def cluster_resources() -> Dict[str, float]:
    if _state.client is not None:
        return _state.client.cluster_resources()
    core = get_core()
    view = _call_on_core_loop(core,
                              core.gcs.request("get_cluster_resources", {}), 10)
    out: Dict[str, float] = {}
    for info in view.values():
        if not info["alive"]:
            continue
        for k, v in info["total"].items():
            out[k] = out.get(k, 0) + v
    return out


def available_resources() -> Dict[str, float]:
    core = get_core()
    view = _call_on_core_loop(core,
                              core.gcs.request("get_cluster_resources", {}), 10)
    out: Dict[str, float] = {}
    for info in view.values():
        if not info["alive"]:
            continue
        for k, v in info["available"].items():
            out[k] = out.get(k, 0) + v
    return out


def internal_kv_put(key: bytes, value: bytes, namespace: str = "kv",
                    overwrite: bool = True) -> bool:
    """Cluster-wide KV (reference: ray.experimental.internal_kv)."""
    core = get_core()
    return _call_on_core_loop(core, core.gcs.request("kv_put", {
        "namespace": namespace, "key": key, "value": value,
        "overwrite": overwrite}), 30)


def internal_kv_get(key: bytes, namespace: str = "kv") -> Optional[bytes]:
    core = get_core()
    return _call_on_core_loop(core, core.gcs.request("kv_get", {
        "namespace": namespace, "key": key}), 30)


def internal_kv_del(key: bytes, namespace: str = "kv") -> bool:
    core = get_core()
    return _call_on_core_loop(core, core.gcs.request("kv_del", {
        "namespace": namespace, "key": key}), 30)


def internal_kv_keys(prefix: bytes = b"", namespace: str = "kv") -> List[bytes]:
    core = get_core()
    return _call_on_core_loop(core, core.gcs.request("kv_keys", {
        "namespace": namespace, "prefix": prefix}), 30)


# Awaitable internal-KV variants for ON-LOOP callers (async actors — the
# serve controller's write-ahead store is the main one): the sync
# wrappers above block on the core loop and would deadlock there.

async def internal_kv_put_async(core, key: bytes, value: bytes,
                                namespace: str = "kv",
                                overwrite: bool = True) -> bool:
    return await core.gcs.request("kv_put", {
        "namespace": namespace, "key": key, "value": value,
        "overwrite": overwrite})


async def internal_kv_get_async(core, key: bytes,
                                namespace: str = "kv") -> Optional[bytes]:
    return await core.gcs.request("kv_get", {
        "namespace": namespace, "key": key})


async def internal_kv_del_async(core, key: bytes,
                                namespace: str = "kv") -> bool:
    return await core.gcs.request("kv_del", {
        "namespace": namespace, "key": key})


async def internal_kv_keys_async(core, prefix: bytes = b"",
                                 namespace: str = "kv") -> List[bytes]:
    return await core.gcs.request("kv_keys", {
        "namespace": namespace, "prefix": prefix})


def timeline(job_id=None, device_trace: Optional[str] = None) -> List[dict]:
    """Chrome-trace-format task timeline (reference: ray.timeline).

    Flight-recorder upgrade: besides one "X" slice per completed task,
    the export carries per-phase sub-slices (args_resolve / exec /
    result_put on the executing worker's lane, submit->dispatch on the
    owner's) and `ph:"s"/"f"` flow events that connect a submission on
    the driver to its execution on the worker across pids — load the
    file in chrome://tracing or Perfetto to follow a task hop by hop.
    Every exported span is a slice too (a train run's tree, the raylet's
    actor launches, traced tasks). `device_trace`: a directory that
    util/profiling.device_trace wrote, with the compiled step's HLO text
    beside the trace (profiling.trace_files): adds a lane a chip with the
    device's ops by region, on the spans' clock."""
    from ray_tpu._private import flightrec
    core = get_core()
    events = _call_on_core_loop(
        core, core.gcs.request("get_task_events",
                               {"job_id": job_id, "limit": 100000}), 30)
    trace = flightrec.build_trace(events)
    if device_trace:
        from ray_tpu.util import profiling
        trace.extend(profiling.device_slices(
            *profiling.trace_files(device_trace)))
    return trace
