"""Raylet: the per-node daemon.

Capability parity with the reference raylet (src/ray/raylet/node_manager.h,
worker_pool.h, local_task_manager.h, scheduling/): worker lifecycle management,
the worker-lease protocol with distributed scheduling + spillback (each raylet
decides locally against a synced cluster resource view, forwarding the lease to
a better node when it has no capacity — hybrid pack/spread policy per
hybrid_scheduling_policy.h), placement-group bundle reservation
(bundle_scheduling_policy.h), the in-process shared-memory object store
(plasma runs inside the raylet in the reference too), node-to-node object
transfer (object_manager.h pull/push in chunks), and worker-death detection
feeding actor failover.
"""

from __future__ import annotations

import asyncio
import atexit
import logging
import os
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ray_tpu._private import compile_cache, rpc
from ray_tpu._private.common import NodeInfo, TaskSpec
from ray_tpu._private.config import Config
from ray_tpu._private.ids import NodeID, ObjectID, PlacementGroupID, WorkerID
from ray_tpu._private.object_store import ObjectStoreHost

logger = logging.getLogger(__name__)


class _SharedForkServer:
    """Process-wide zygote client (worker_forkserver.py).

    One warm template process serves every raylet in this OS process (the
    fake cluster runs many raylets per process) and survives across
    cluster setups, so only the first cluster in a test run pays the
    template's import cost. Spawn requests carry the per-worker env, so
    the template is raylet-agnostic.
    """

    _inst: Optional["_SharedForkServer"] = None

    def __init__(self):
        self.proc: Optional[asyncio.subprocess.Process] = None
        self.ready = False
        self.dead = False
        self.handlers: Dict[str, "Raylet"] = {}   # worker_id hex -> raylet
        self._starting = False
        self._ready_callbacks: List = []
        # Buffered before proc is up: (env, log_path, raylet) records —
        # kept structured (not pre-encoded bytes) so spawns that outlive
        # a dead zygote can fail over to Popen as a batch.
        self._pending_spawns: List[tuple] = []
        self._base_env: Optional[Dict[str, str]] = None
        # time.time() when the zygote was asked for and at its `ready`
        # event: ray_tpu_worker_zygote_ready_seconds, and how much of a
        # spawn waited for it (waited_in)
        self.asked_at: Optional[float] = None
        self.ready_at: Optional[float] = None

    @classmethod
    def get(cls) -> "_SharedForkServer":
        if cls._inst is None or cls._inst.dead:
            prev = cls._inst
            cls._inst = cls()
            if prev is not None:
                cls._inst._base_env = prev._base_env
        return cls._inst

    async def ensure_started(self, env: Dict[str, str]):
        if self.proc is not None or self._starting or self.dead:
            return
        self._base_env = dict(env)
        self._starting = True
        self.asked_at = time.time()
        try:
            self.proc = await asyncio.create_subprocess_exec(
                sys.executable, "-m", "ray_tpu._private.worker_forkserver",
                env=env,
                stdin=asyncio.subprocess.PIPE,
                stdout=asyncio.subprocess.PIPE,
                stderr=subprocess.DEVNULL,
                start_new_session=True)
        except Exception:
            self.dead = True
            self._fail_pending()
            return
        finally:
            self._starting = False
        atexit.register(self._stop_at_exit)
        if self._pending_spawns:
            pending, self._pending_spawns = self._pending_spawns, []
            if not self._write_batch([(e, lp) for e, lp, _r in pending]):
                # The pipe died before the buffered spawns ever reached
                # the zygote: fail them over (as a batch) via Popen.
                self._pending_spawns = pending
                self._fail_pending()
                return
        asyncio.ensure_future(self._reader())

    def _stop_at_exit(self):
        """Interpreter exit: the zygote and the workers it forked are this
        process's to stop, and none may outlive it — not even for the
        moment the zygote would need to notice its stdin closing. SIGTERM
        makes the zygote end and reap its children first."""
        if self.dead or self.proc.returncode is not None:
            return
        pid = self.proc.pid
        try:
            os.kill(pid, signal.SIGTERM)
        except ProcessLookupError:
            return
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            try:
                if os.waitpid(pid, os.WNOHANG)[0]:
                    return
            except ChildProcessError:   # reaped by asyncio's child watcher
                return
            time.sleep(0.02)
        os.kill(pid, signal.SIGKILL)

    def _write_batch(self, jobs: List[tuple]) -> bool:
        """One spawn_batch line for N workers; False if the pipe is gone."""
        import json
        line = (json.dumps({"spawn_batch": [
            {"env": env, "log_path": lp} for env, lp in jobs]}) + "\n"
        ).encode()
        try:
            self.proc.stdin.write(line)
        except Exception:
            self.dead = True
            return False
        return True

    async def _reader(self):
        import json
        proc = self.proc
        try:
            while True:
                line = await proc.stdout.readline()
                if not line:
                    break
                try:
                    msg = json.loads(line)
                except ValueError:
                    continue
                event = msg.get("event")
                if event == "ready":
                    self.ready = True
                    self.ready_at = time.time()
                    for cb in self._ready_callbacks:
                        try:
                            cb()
                        except Exception:
                            pass
                    self._ready_callbacks.clear()
                elif event in ("spawned", "exit"):
                    raylet = self.handlers.get(msg.get("worker_id", ""))
                    if raylet is not None:
                        raylet._on_forkserver_event(event, msg)
                    if event == "exit":
                        self.handlers.pop(msg.get("worker_id", ""), None)
        finally:
            self.dead = True
            self.ready = False
            self._fail_pending()

    def _fail_pending(self):
        """Zygote died (or could not start). Spawns still BUFFERED here
        never reached it — their workers can still start, just without
        the warm fork: hand them back to their raylets as one batched
        Popen failover (one-by-one fallback was the old behavior; a
        launch storm buffered behind a dead zygote paid N serial
        round trips through the create timeout). Workers the zygote
        actually tracked are gone (or unknowable): report exits so
        supply accounting doesn't leak phantom handles."""
        pending, self._pending_spawns = self._pending_spawns, []
        by_raylet: Dict[int, tuple] = {}
        for env, log_path, raylet in pending:
            self.handlers.pop(env.get("RAY_TPU_WORKER_ID", ""), None)
            by_raylet.setdefault(id(raylet), (raylet, []))[1].append(
                (env, log_path))
        for raylet, jobs in by_raylet.values():
            try:
                raylet._popen_failover_batch(jobs)
            except Exception:
                logger.exception("batched Popen failover failed")
        for wid, raylet in list(self.handlers.items()):
            try:
                raylet._on_forkserver_event(
                    "exit", {"worker_id": wid, "pid": -1, "status": -1})
            except Exception:
                pass
        self.handlers.clear()

    def waited_in(self, start: float, end: float) -> float:
        """Seconds of [start, end] that lay before the zygote's `ready`
        event: what a spawn asked for at `start` waited for it (0.0 for one
        asked for after it, or where it never came)."""
        if self.ready_at is None:
            return 0.0
        return max(0.0, min(self.ready_at, end) - start)

    def record_ready(self) -> None:
        """How long this process's zygote took, into the registry of the
        raylet's process: every raylet that joins it says so again."""
        from ray_tpu.util import metrics
        metrics.Gauge(
            "ray_tpu_worker_zygote_ready_seconds",
            "the worker fork server (zygote) of this process asked for -> "
            "its `ready` event: its interpreter up and its imports (jax "
            "among them) done; a cold spawn asked for meanwhile waits for "
            "it (actor:spawn's zygote_wait_s)").set(
                self.ready_at - self.asked_at)

    def on_ready(self, cb):
        if self.ready:
            cb()
        else:
            self._ready_callbacks.append(cb)

    def spawn_many(self, jobs: List[tuple], raylet: "Raylet") -> bool:
        """Fork N workers with ONE request line (and one pipe write):
        `jobs` is [(env, log_path), ...]. All-or-nothing: False means no
        job was submitted and the caller should Popen-spawn instead."""
        if self.dead or not jobs:
            return not self.dead and not jobs
        if self.proc is None or self.proc.stdin is None:
            # Buffer (flushed on start). If no start is in flight — e.g.
            # this is a fresh instance replacing a dead zygote — kick one
            # off so buffered spawns don't sit forever.
            if not self._starting:
                if self._base_env is None:
                    return False  # nothing can start it: use Popen fallback
                asyncio.ensure_future(self.ensure_started(self._base_env))
            self._pending_spawns.extend(
                (env, log_path, raylet) for env, log_path in jobs)
        else:
            if not self._write_batch(jobs):
                return False
        for env, _log_path in jobs:
            self.handlers[env["RAY_TPU_WORKER_ID"]] = raylet
        return True


class PendingLease:
    """One queued worker-lease request with its per-spec scheduling keys
    resolved ONCE at enqueue. _try_dispatch / _ensure_worker_supply scan
    the pending list on every tick (and per grant); re-deriving
    env_hash / container-env / scheduling_class from the spec each scan
    was measurable overhead under a multi-client lease storm."""

    __slots__ = ("spec", "pg_key", "fut", "conn", "count", "env_hash",
                 "container_env", "sched_class", "demand_recorded")

    def __init__(self, spec, pg_key, fut, conn, count):
        self.spec = spec
        self.pg_key = pg_key
        self.fut = fut
        self.conn = conn
        self.count = count
        # Pool demand/miss accounting happens on the FIRST idle-worker
        # scan for this lease only; dispatch re-scans don't re-count.
        self.demand_recorded = False
        self.env_hash = spec.env_hash()
        env = getattr(spec, "runtime_env", None) or {}
        self.container_env = env if env.get("container") else None
        self.sched_class = spec.scheduling_class()


class WarmPools:
    """Env-hash-keyed idle worker pools with demand-sized floors.

    Replaces the flat idle list: a launch storm for one runtime env can
    no longer drain (or be starved by) another env's warm capacity, the
    reaper keeps a per-env floor sized by recent demand (EWMA of worker
    requests/s), and explicit `prestart_workers` hints — sent by the GCS
    ahead of gang restarts, serve scale-ups, and creation-batch fan-outs
    — pin a temporary floor so the pool is warm BEFORE the storm lands
    (reference: worker_pool.h PrestartWorkers + dedicated-worker pools
    per runtime env).
    """

    EWMA_HALFLIFE_S = 30.0
    # The demand floor holds enough warm workers to absorb this many
    # seconds of the recent request rate.
    DEMAND_WINDOW_S = 5.0
    # Demand-derived floors are a smoothing signal, not a license to hold
    # the node: they never exceed this per env (hints may).
    MAX_DEMAND_FLOOR = 16

    def __init__(self):
        self.pools: Dict[str, List["WorkerHandle"]] = {}
        self._rates: Dict[str, tuple] = {}   # env -> (EWMA req/s, stamp)
        # env -> (count, expires_at, fresh_alias). fresh_alias hints also
        # count toward the FRESH pool's floor (the generic workers they
        # prestart idle there until first lease).
        self._hints: Dict[str, tuple] = {}
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return sum(len(p) for p in self.pools.values())

    def sizes(self) -> Dict[str, int]:
        return {h: len(p) for h, p in self.pools.items() if p}

    def hash_list(self) -> List[str]:
        out: List[str] = []
        for h, p in self.pools.items():
            out.extend([h] * len(p))
        return out

    def put(self, handle: "WorkerHandle"):
        pool = self.pools.setdefault(handle.env_hash, [])
        if handle not in pool:
            pool.append(handle)

    def remove(self, handle: "WorkerHandle") -> bool:
        pool = self.pools.get(handle.env_hash)
        if pool and handle in pool:
            pool.remove(handle)
            return True
        # The handle may have been re-tagged after it went idle.
        for p in self.pools.values():
            if handle in p:
                p.remove(handle)
                return True
        return False

    def note_demand(self, env_hash: str, n: int = 1):
        """One worker-acquisition attempt for this env (feeds the EWMA
        floor the reaper respects)."""
        now = time.time()
        rate, ts = self._rates.get(env_hash, (0.0, now))
        if now > ts:
            rate *= 0.5 ** ((now - ts) / self.EWMA_HALFLIFE_S)
        self._rates[env_hash] = (rate + float(n), now)

    def hint(self, env_hash: str, count: int, ttl_s: float = 30.0,
             merge: bool = False, fresh_alias: bool = False):
        """Explicit prestart hint: hold at least `count` warm workers for
        `env_hash` until the hint expires (storms are announced, not
        inferred). merge=True keeps the max of this and any live hint —
        per-env max keeps a replayed hint RPC idempotent. fresh_alias
        hints ALSO count (summed across envs) toward the fresh pool's
        floor: the generic workers they prestart idle there until first
        lease, and two envs' batches must BOTH survive the reaper — a
        max would let it eat the second batch."""
        now = time.time()
        count = max(0, int(count))
        expires = now + ttl_s
        if merge:
            prev_count, prev_exp, prev_alias = self._hints.get(
                env_hash, (0, 0.0, False))
            if prev_exp > now:
                count = max(count, prev_count)
                expires = max(expires, prev_exp)
                fresh_alias = fresh_alias or prev_alias
        self._hints[env_hash] = (count, expires, fresh_alias)

    def floor(self, env_hash: str, fresh_floor: int = 0) -> int:
        """Reap-protection floor for one env pool: the fresh pool keeps
        the node's base prestart floor plus the sum of live fresh_alias
        hints; every pool keeps max(EWMA demand, live hint)."""
        now = time.time()
        hint_count, expires, _alias = self._hints.get(
            env_hash, (0, 0.0, False))
        if now >= expires:
            hint_count = 0
        if env_hash == "":
            hint_count += sum(
                c for h, (c, exp, alias) in self._hints.items()
                if h != "" and alias and exp > now)
        acc, ts = self._rates.get(env_hash, (0.0, now))
        acc *= 0.5 ** (max(0.0, now - ts) / self.EWMA_HALFLIFE_S)
        # `acc` is a decayed cumulative COUNT whose steady state is
        # rate * halflife/ln2 — convert to req/s, then hold enough warm
        # workers to absorb ~DEMAND_WINDOW_S of that rate. (Treating the
        # raw accumulator as a rate saturated the cap at <1 req/s and
        # pinned 16 jax-preloaded workers per env on light traffic.)
        est_rate = acc * 0.6931 / self.EWMA_HALFLIFE_S
        demand_floor = min(self.MAX_DEMAND_FLOOR,
                           int(est_rate * self.DEMAND_WINDOW_S + 0.5))
        base = fresh_floor if env_hash == "" else 0
        return max(base, demand_floor, hint_count)

    def prune(self):
        """Drop empty pools, expired hints, and fully decayed demand
        accumulators — a long-lived node serving many distinct runtime
        envs must not grow these dicts (and downstream per-env metric
        rows) forever."""
        now = time.time()
        for h in [h for h, p in self.pools.items() if not p and h != ""]:
            del self.pools[h]
        for h in [h for h, (_c, exp, _a) in self._hints.items()
                  if exp <= now]:
            del self._hints[h]
        for h in [h for h, (acc, ts) in self._rates.items()
                  if acc * 0.5 ** ((now - ts) / self.EWMA_HALFLIFE_S) < 0.05]:
            del self._rates[h]

    def pop(self, env_hash: str, exact: bool, alive,
            count_miss: bool = True) -> Optional["WorkerHandle"]:
        """Newest-first pop: exact env pool, then the fresh pool (a fresh
        worker can still apply the env). exact=True (container envs)
        never falls back — a generic process cannot retroactively enter
        the container. `alive(handle)` prunes dead entries mid-scan.
        count_miss=False for re-scans of an already-counted request."""
        for key in ((env_hash,) if exact or env_hash == ""
                    else (env_hash, "")):
            pool = self.pools.get(key)
            while pool:
                handle = pool.pop()
                if alive(handle):
                    self.hits += 1
                    return handle
        if count_miss:
            self.misses += 1
        return None


@dataclass
class _ActorWorkerWaiter:
    """One actor creation waiting for a worker. The spec rides along so
    rpc_register_worker can hand the newly registered worker its actor
    assignment IN THE REGISTRATION REPLY (no register→idle→re-offer→
    instantiate round trip)."""
    env_hash: str
    exact: bool
    fut: asyncio.Future
    spec: Optional[TaskSpec] = None
    epoch: int = 0
    pg_key: Optional[tuple] = None
    function_blob: Optional[bytes] = None


@dataclass
class WorkerHandle:
    worker_id: WorkerID
    pid: int
    address: str = ""            # worker RPC endpoint once registered
    proc: Optional[subprocess.Popen] = None
    registered: bool = False
    # Lease state
    leased: bool = False
    lease_class: Optional[tuple] = None
    lease_resources: Dict[str, float] = field(default_factory=dict)
    lease_pg: Optional[tuple] = None     # (pg_id, bundle_index)
    is_actor_worker: bool = False
    actor_id: Optional[object] = None
    # Restart epoch of the hosted actor: create-by-actor-id dedupe keys
    # on (actor_id, epoch) so a re-driven create for the SAME epoch joins
    # this instance while a genuine restart (epoch+1) re-instantiates.
    actor_epoch: int = -1
    idle_since: float = field(default_factory=time.time)
    conn: Optional[rpc.Connection] = None
    # Runtime env this worker has applied ("" = fresh). A tagged worker is
    # dedicated: it only serves tasks with the same env hash (reference:
    # worker_pool.h dedicated workers per runtime env).
    env_hash: str = ""
    # Owner (submitter) of the current lease; OOM victim grouping key.
    lease_owner: str = ""
    # The raylet connection the lease was granted over: when it closes
    # (driver exited), the lease is reclaimed.
    lease_conn: Optional[rpc.Connection] = None
    # Launch-storm debugging: when/how the process was spawned
    # (fork | popen | container).
    spawned_at: float = 0.0
    spawn_mode: str = ""
    # The assignment dispatched in this worker's registration reply,
    # kept until its instantiate_result arrives so an idempotent
    # register_worker REPLAY re-sends the same assignment instead of
    # stranding both sides (the first reply being lost is exactly the
    # case replays exist for).
    pending_assignment: Optional[dict] = None
    # Compiled-DAG pins (dag ids): while non-empty this worker's lease
    # is load-bearing pipeline state — excluded from OOM victim
    # selection and the idle reaper until every DAG releases it.
    dag_pins: set = field(default_factory=set)


# The resource that stands for a physical device a process holds from the
# moment it opens it until it exits (one process per chip).
CHIP_RESOURCE = "TPU"


class ResourcePool:
    """Vector resource accounting: node pool + per-bundle sub-pools."""

    def __init__(self, total: Dict[str, float]):
        self.total = dict(total)
        self.available = dict(total)
        # (pg_id_bytes, bundle_index) -> {resource: amount}
        self.bundles: Dict[tuple, Dict[str, float]] = {}
        self.bundle_available: Dict[tuple, Dict[str, float]] = {}
        # returned bundle -> chips its still-live workers lease
        self._held_chips: Dict[tuple, float] = {}

    def fits(self, request: Dict[str, float], pg_key: Optional[tuple] = None) -> bool:
        pool = self.bundle_available.get(pg_key) if pg_key else self.available
        if pool is None:
            return False
        return all(pool.get(k, 0.0) + 1e-9 >= v for k, v in request.items() if v > 0)

    def feasible(self, request: Dict[str, float]) -> bool:
        return all(self.total.get(k, 0.0) >= v for k, v in request.items() if v > 0)

    def acquire(self, request: Dict[str, float], pg_key: Optional[tuple] = None) -> bool:
        if not self.fits(request, pg_key):
            return False
        pool = self.bundle_available[pg_key] if pg_key else self.available
        for k, v in request.items():
            if v > 0:
                pool[k] = pool.get(k, 0.0) - v
        return True

    def release(self, request: Dict[str, float], pg_key: Optional[tuple] = None):
        if pg_key is not None:
            pool = self.bundle_available.get(pg_key)
            if pool is None:
                # Bundle already returned: only the chips return_bundle
                # held back for this lease are still owed to the node.
                held = self._held_chips.pop(pg_key, 0.0)
                back = min(held, request.get(CHIP_RESOURCE, 0.0))
                if back > 0:
                    self.available[CHIP_RESOURCE] = \
                        self.available.get(CHIP_RESOURCE, 0.0) + back
                if held > back:
                    self._held_chips[pg_key] = held - back
                return
        else:
            pool = self.available
        for k, v in request.items():
            if v > 0:
                pool[k] = pool.get(k, 0.0) + v

    def reserve_bundle(self, key: tuple, resources: Dict[str, float]) -> bool:
        if key in self.bundles:
            return True
        if not self.fits(resources):
            return False
        for k, v in resources.items():
            if v > 0:
                self.available[k] = self.available.get(k, 0.0) - v
        self.bundles[key] = dict(resources)
        self.bundle_available[key] = dict(resources)
        return True

    def return_bundle(self, key: tuple):
        """Give a removed bundle's resources back to the node — except the
        chips a live worker still leases from it: a process keeps an opened
        chip until it exits, so those come back through release() when the
        worker has gone, never while the chip is still held."""
        resources = self.bundles.pop(key, None)
        unleased = self.bundle_available.pop(key, None) or {}
        if resources:
            for k, v in resources.items():
                if k == CHIP_RESOURCE:
                    held = v - unleased.get(k, 0.0)
                    if held > 0:
                        self._held_chips[key] = held
                    v -= held
                if v > 0:
                    self.available[k] = self.available.get(k, 0.0) + v


class Raylet:
    def __init__(self, config: Config, gcs_address: str, session_dir: str,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 is_head: bool = False,
                 object_store_memory: Optional[int] = None,
                 node_name: str = "", slice_id: str = "", zone: str = ""):
        self.config = config
        self.gcs_address = gcs_address
        self.session_dir = session_dir
        self.node_id = NodeID.from_random()
        self.node_name = node_name or self.node_id.hex()[:8]
        self.is_head = is_head
        self.resources = resources or self._default_resources()
        self.labels = dict(labels or {})
        # TPU slice fault domain: every host of one ICI domain registers
        # the same slice_id so the GCS drains/recovers them as one gang.
        from ray_tpu.parallel.mesh import (SLICE_LABEL, ZONE_LABEL,
                                           detect_slice_id, detect_zone)
        self.slice_id = slice_id or detect_slice_id(self.labels)
        if self.slice_id:
            self.labels.setdefault(SLICE_LABEL, self.slice_id)
        # DCN locality (pod/zone): drives same-zone replacement-domain
        # preference when gangs / compiled DAGs migrate off this host.
        self.zone = zone or detect_zone(self.labels)
        if self.zone:
            self.labels.setdefault(ZONE_LABEL, self.zone)
        self.pool = ResourcePool(self.resources)
        self.server = rpc.RpcServer(f"raylet-{self.node_name}")
        self.store = ObjectStoreHost(
            object_store_memory or config.object_store_memory,
            os.path.join(session_dir, f"spill_{self.node_name}"),
        )
        self.clients = rpc.ClientPool()
        self.workers: Dict[WorkerID, WorkerHandle] = {}
        # Env-hash-keyed warm pools (was a flat idle list).
        self._pools = WarmPools()
        # Actor creates waiting for a worker (_ActorWorkerWaiter records),
        # FIFO-served by rpc_register_worker — which dispatches the actor
        # assignment in the registration reply when the waiter carries a
        # spec.
        self._actor_worker_waiters: List[_ActorWorkerWaiter] = []
        # worker_id -> future resolved by rpc_instantiate_result (the
        # constructor outcome of a register-reply-dispatched create).
        self._instantiate_results: Dict[WorkerID, asyncio.Future] = {}
        # Counters for tests / observability (exported as deltas by the
        # metrics loop).
        self.register_reply_dispatches = 0
        self.prestart_hints_received = 0
        self._exported_pool_hits = 0
        self._exported_zero_copy_gets = 0
        self._exported_pool_misses = 0
        self._pool_gauge_envs: set = set()
        # actor:spawn/register/ctor flightrec spans, flushed to the GCS
        # task-event buffer by the heartbeat loop.
        self._pending_spans: List[dict] = []
        # Content-addressed class blobs (function_id -> pickled class),
        # prefetched ONCE per node and shipped inside the instantiate
        # payload: a 1k-actor storm costs 1 GCS KV fetch here instead of
        # 1k worker-side fetches through a saturated GCS loop.
        self._function_blobs: Dict[str, bytes] = {}
        # In-flight create_actor dedupe keyed (actor_id, num_restarts):
        # a GCS-restore re-drive (or RPC replay) for an actor whose
        # original create is STILL RUNNING here must join that create,
        # not double-instantiate the actor.
        self._creating_actors: Dict[tuple, asyncio.Future] = {}
        self._pending_leases: List[PendingLease] = []
        # Compiled-DAG lease accounting: dag_id -> worker hexes pinned on
        # this node (rpc_dag_pin_workers / rpc_dag_release_workers).
        self._dag_pins: Dict[str, set] = {}
        # Driver conns that have been granted leases: on close, their
        # leased workers are reclaimed (reference: leased workers of an
        # exited job are destroyed, worker_pool.cc DisconnectClient).
        self._lease_conns: set = set()
        self._conn_owner: Dict[Any, str] = {}   # conn -> owner address
        self._autoscaler_active = False
        # Drain protocol (planned removal): a draining raylet grants no new
        # leases, lets running work finish until the deadline, and pushes
        # its primary object copies to live peers.
        self._draining = False
        self._drain_deadline = 0.0
        self._spawned_worker_prefixes: set = set()
        self._starting_workers = 0
        self.gcs_conn: Optional[rpc.Connection] = None
        # Cluster resource view: node_id -> {available, total, address}
        self.cluster_view: Dict[NodeID, dict] = {}
        self.address = ""
        self._tasks: List[asyncio.Task] = []
        compile_cache.export_compile_cache_dir()
        self._worker_env = dict(os.environ)
        self._stopped = False
        self._resources_dirty = False
        # Fork-server (zygote) for fast worker spawn; Popen is the fallback
        # if it is unavailable (worker_forkserver.py).
        self._workers_by_hex: Dict[str, WorkerHandle] = {}

    def _default_resources(self) -> Dict[str, float]:
        cpus = os.cpu_count() or 1
        res = {"CPU": float(cpus), "memory": 4 * 1024**3}
        res["object_store_memory"] = float(self.config.object_store_memory) \
            if hasattr(self, "config") else 2 * 1024**3
        return res

    # ------------------------------------------------------------------

    async def start(self, host: str = "127.0.0.1", port: int = 0) -> str:
        self.server.register_all(self)
        actual = await self.server.start(host, port)
        self.address = f"{host}:{actual}"
        # Register with GCS and subscribe to cluster events.
        self.gcs_conn = await rpc.connect(self.gcs_address, self._on_gcs_push)
        await self._register_with_gcs()
        self._tasks.append(asyncio.ensure_future(self._heartbeat_loop()))
        self._tasks.append(asyncio.ensure_future(self._idle_worker_reaper()))
        self._tasks.append(asyncio.ensure_future(self._start_forkserver()))
        self._tasks.append(asyncio.ensure_future(self._report_metrics_loop()))
        from ray_tpu.util import metrics as _metrics
        self._tasks.append(_metrics.start_loop_lag_probe("raylet"))
        # Worker stdout/stderr -> GCS "logs" pubsub -> driver echo
        # (reference: log_monitor.py LogMonitor).
        from ray_tpu._private.log_monitor import LogMonitor

        async def _publish_logs(message):
            await self.gcs_conn.request(
                "publish", {"channel": "logs", "message": message})

        def _pid_of(worker_hex12: str) -> int:
            for full, h in self._workers_by_hex.items():
                if full.startswith(worker_hex12):
                    return h.pid
            return -1

        self.log_monitor = LogMonitor(
            self.session_dir, self.node_name, _publish_logs, pid_of=_pid_of,
            owns=lambda h: h in self._spawned_worker_prefixes)
        self.log_monitor.start()
        # OOM defense (reference: memory_monitor.h + worker killing
        # policies): above the threshold, kill the newest leased worker of
        # the owner with the most leases.
        from ray_tpu._private.memory_monitor import MemoryMonitor
        if self.config.memory_monitor_interval_s > 0:
            self.memory_monitor = MemoryMonitor(
                self.config.memory_usage_threshold,
                self.config.memory_monitor_interval_s,
                self._on_memory_pressure)
            self.memory_monitor.start()
        logger.info("raylet %s started at %s", self.node_name, self.address)
        return self.address

    def _on_memory_pressure(self, usage: float):
        from ray_tpu._private.memory_monitor import pick_victim
        victim = pick_victim(list(self.workers.values()))
        if victim is None:
            return
        logger.warning(
            "node memory usage %.0f%% above threshold; OOM-killing worker "
            "pid=%s (owner %s) — the task will retry per its budget "
            "(reference: task_oom_retries)", usage * 100, victim.pid,
            victim.lease_owner)
        try:
            if victim.pid > 0:
                os.kill(victim.pid, 9)
        except OSError:
            pass

    async def stop(self):
        self._stopped = True
        from ray_tpu.util import metrics as _metrics
        _metrics.release_reporter(self)
        for gname in ("ray_tpu_raylet_pending_leases",
                      "ray_tpu_raylet_idle_workers",
                      "ray_tpu_raylet_leased_workers",
                      "ray_tpu_raylet_dag_pinned_workers",
                      "ray_tpu_worker_pool_hits_total",
                      "ray_tpu_worker_pool_misses_total"):
            _metrics.remove(gname, {"Node": self.node_name})
        for env_hash in self._pool_gauge_envs:
            _metrics.remove("ray_tpu_worker_pool_size",
                            {"Node": self.node_name,
                             "Env": env_hash or "fresh"})
        if getattr(self, "log_monitor", None) is not None:
            self.log_monitor.stop()
        if getattr(self, "memory_monitor", None) is not None:
            self.memory_monitor.stop()
        for t in self._tasks:
            t.cancel()
        for w in self.workers.values():
            if w.proc is not None:
                try:
                    w.proc.terminate()
                except Exception:
                    pass
            elif w.pid > 0:
                try:
                    os.kill(w.pid, 15)
                except OSError:
                    pass
        for w in self.workers.values():
            if w.proc is not None:
                try:
                    w.proc.wait(timeout=2)
                except Exception:
                    try:
                        w.proc.kill()
                    except Exception:
                        pass
        await self.server.stop()
        await self.clients.close_all()
        self.store.destroy()

    async def _register_with_gcs(self):
        info = NodeInfo(
            node_id=self.node_id, address=self.address,
            resources_total=dict(self.pool.total),
            resources_available=dict(self.pool.available),
            labels=self.labels, is_head=self.is_head,
            slice_id=self.slice_id, zone=self.zone,
        )
        reply = await self.gcs_conn.request("register_node", {
            "node_info": info,
            # Actor-liveness reconcile on (re)registration: a restarted
            # GCS restored from a snapshot may believe actors are ALIVE
            # on workers that died during its outage (their one-shot
            # death reports were lost) — the live set lets it drive
            # those through the failure path immediately.
            "live_worker_ids": [h.worker_id for h in self.workers.values()
                                if h.pid > 0],
        })
        for node_id, view in reply.get("cluster_view", {}).items():
            if node_id != self.node_id:
                self.cluster_view[node_id] = view
        await self.gcs_conn.request(
            "subscribe", {"channels": ["resources", "nodes", "actors"]})

    async def _report_metrics_loop(self):
        """Node-side flight-recorder gauges (worker pool + lease queue
        depth) plus the registry push for processes where the raylet is
        the only daemon (`ray_tpu start` worker nodes). When the GCS or a
        driver core shares this process, the per-process reporter claim
        leaves the push to whoever claimed first — the gauges still
        update in the shared registry either way."""
        from ray_tpu.util import metrics as _metrics
        agent = _metrics.MetricsAgent(f"raylet:{self.node_name}",
                                      self.gcs_conn.request)
        while not self._stopped:
            await asyncio.sleep(self.config.metrics_report_interval_s)
            tags = {"Node": self.node_name}

            def g(name, desc):
                return _metrics.Gauge(name, desc, tag_keys=("Node",))

            g("ray_tpu_raylet_pending_leases",
              "lease requests queued at the raylet").set(
                float(len(self._pending_leases)), tags=tags)
            g("ray_tpu_raylet_idle_workers",
              "registered workers idle in the pool").set(
                float(len(self._pools)), tags=tags)
            g("ray_tpu_raylet_leased_workers",
              "workers currently leased out").set(
                float(sum(1 for w in self.workers.values() if w.leased)),
                tags=tags)
            g("ray_tpu_raylet_dag_pinned_workers",
              "workers whose lease a compiled DAG holds pinned").set(
                float(sum(1 for w in self.workers.values()
                          if w.dag_pins)), tags=tags)
            # Object-plane health: occupancy/pinned/spill gauges plus the
            # zero-copy get counter (delta-exported like pool hits).
            st = self.store.stats()
            g("ray_tpu_store_occupancy_bytes",
              "bytes allocated to objects in the shm segment pool").set(
                float(st["used"]), tags=tags)
            g("ray_tpu_store_pinned_bytes",
              "bytes pinned by outstanding zero-copy views").set(
                float(st["pinned_bytes"]), tags=tags)
            g("ray_tpu_store_spilled_bytes",
              "cumulative bytes spilled to external storage").set(
                float(st["bytes_spilled"]), tags=tags)
            lookups = st["num_hits"] + st["num_misses"]
            g("ray_tpu_store_hit_ratio",
              "fraction of store lookups served from shm").set(
                (st["num_hits"] / lookups) if lookups else 1.0, tags=tags)
            zc = st["num_zero_copy_gets"]
            if zc > self._exported_zero_copy_gets:
                _metrics.Counter(
                    "ray_tpu_store_zero_copy_gets_total",
                    "same-node gets served as pinned zero-copy shm views",
                    tag_keys=("Node",)).inc(
                    zc - self._exported_zero_copy_gets, tags=tags)
                self._exported_zero_copy_gets = zc
            # Warm-pool health: per-env pool depth + cumulative hit/miss.
            # Rows for envs whose pool emptied AND whose floor expired
            # are removed (not left at 0 forever): a long-lived node
            # serving many per-job env hashes must not grow metric
            # cardinality without bound.
            sizes = self._pools.sizes()
            for env_hash in set(self._pool_gauge_envs) | set(sizes):
                depth = sizes.get(env_hash, 0)
                if (depth == 0 and env_hash not in sizes
                        and self._pools.floor(env_hash) == 0):
                    _metrics.remove("ray_tpu_worker_pool_size",
                                    {"Node": self.node_name,
                                     "Env": env_hash or "fresh"})
                    self._pool_gauge_envs.discard(env_hash)
                    continue
                self._pool_gauge_envs.add(env_hash)
                _metrics.Gauge(
                    "ray_tpu_worker_pool_size",
                    "idle workers per runtime-env warm pool",
                    tag_keys=("Node", "Env")).set(
                    float(depth),
                    tags={"Node": self.node_name,
                          "Env": env_hash or "fresh"})
            hits, misses = self._pools.hits, self._pools.misses
            if hits > self._exported_pool_hits:
                _metrics.Counter(
                    "ray_tpu_worker_pool_hits_total",
                    "worker requests served from a warm pool",
                    tag_keys=("Node",)).inc(
                    hits - self._exported_pool_hits, tags=tags)
                self._exported_pool_hits = hits
            if misses > self._exported_pool_misses:
                _metrics.Counter(
                    "ray_tpu_worker_pool_misses_total",
                    "worker requests that found no warm worker (cold "
                    "spawn or wait)", tag_keys=("Node",)).inc(
                    misses - self._exported_pool_misses, tags=tags)
                self._exported_pool_misses = misses
            if not self.config.metrics_agent_enabled:
                continue
            if not _metrics.claim_reporter(self):
                continue
            rpc.export_transport_metrics()
            snap = _metrics.snapshot()
            if not snap:
                continue
            try:
                await agent.ship(snap)
            except rpc.RpcError:
                pass

    async def _heartbeat_loop(self):
        while not self._stopped:
            await asyncio.sleep(self.config.heartbeat_interval_s)
            try:
                reply = await self.gcs_conn.request("heartbeat", {
                    "node_id": self.node_id,
                    "resources_available": dict(self.pool.available),
                    # Queued lease shapes feed the autoscaler's demand
                    # bin-packing (reference: resource_demand_scheduler.py).
                    "pending_demand": self._pending_demand_shapes(64),
                    # Warm-pool depth per env: the GCS creation pipeline
                    # routes storms toward live warm capacity.
                    "idle_workers": self._pools.sizes(),
                })
                if reply.get("reregister"):
                    # GCS restarted without our node in its (restored) table.
                    await self._register_with_gcs()
                if reply.get("report_actors"):
                    # Post-restore handshake: tell the (restarted) GCS
                    # which workers actually live here so it can restart
                    # ALIVE actors whose death reports it never received.
                    await self.gcs_conn.request("reconcile_actors", {
                        "node_id": self.node_id,
                        "live_worker_ids": [
                            h.worker_id for h in self.workers.values()
                            if h.pid > 0],
                    })
                self._autoscaler_active = bool(
                    reply.get("autoscaler_active"))
                self._check_worker_deaths()
                await self._flush_spans()
                if self._resources_dirty:
                    self._resources_dirty = False
                    await self._report_resources()
            except rpc.RpcError:
                # Head fault tolerance: keep dialing until the GCS (or its
                # restarted replacement on the same address) answers.
                logger.warning("raylet %s lost GCS connection; reconnecting",
                               self.node_name)
                await self._reconnect_gcs()

    def _pending_demand_shapes(self, cap: int) -> list:
        """Queued lease demand for the autoscaler, one shape per needed
        GRANT (a multi-grant request with count=n is n workers of demand)."""
        shapes: list = []
        for req in self._pending_leases:
            if req.fut.done():
                continue
            for _ in range(min(req.count, cap - len(shapes))):
                shapes.append(dict(req.spec.resources))
            if len(shapes) >= cap:
                break
        return shapes

    def _record_span(self, trace_id: str, name: str, start: float,
                     end: float, **extra):
        """Launch-path flight-recorder span (actor:spawn / actor:register
        / actor:ctor): buffered here, flushed to the GCS task-event ring
        by the heartbeat loop so `ray_tpu timeline` shows, on this node's
        lane (`node_id`: the record carries no pid), where a slow actor
        launch spent its time."""
        if not self.config.task_events_enabled:
            return
        self._pending_spans.append({
            "kind": "span", "trace_id": trace_id,
            "span_id": os.urandom(8).hex(), "parent_id": "",
            "name": name, "task_id": trace_id,
            "start": start, "end": end, "node_id": self.node_id.hex(),
            **extra})

    async def _flush_spans(self):
        if not self._pending_spans:
            return
        spans, self._pending_spans = self._pending_spans, []
        try:
            await self.gcs_conn.request("report_task_events",
                                        {"events": spans})
        except rpc.RpcError:
            pass

    async def _reconnect_gcs(self):
        while not self._stopped:
            try:
                self.gcs_conn = await rpc.connect(self.gcs_address,
                                                  self._on_gcs_push)
                await self._register_with_gcs()
                logger.info("raylet %s re-registered with GCS",
                            self.node_name)
                return
            except Exception:
                await asyncio.sleep(
                    min(1.0, self.config.heartbeat_interval_s))

    async def _report_resources(self):
        try:
            await self.gcs_conn.request("report_resources", {
                "node_id": self.node_id,
                "available": dict(self.pool.available),
            })
        except rpc.RpcError:
            pass

    def _mark_resources_dirty(self):
        """Push the new resource view to the GCS now (coalesced), so
        available_resources() reads don't race the heartbeat period."""
        if self._resources_dirty:
            return
        self._resources_dirty = True

        async def _flush():
            await asyncio.sleep(0)  # coalesce a burst of acquire/release
            if self._resources_dirty and not self._stopped:
                self._resources_dirty = False
                await self._report_resources()

        asyncio.ensure_future(_flush())

    def _on_gcs_push(self, method: str, payload):
        if method != "pub":
            return
        channel = payload["channel"]
        msg = payload["message"]
        if channel == "resources":
            if msg.get("draining"):
                # A draining peer must stop being a spillback/migration
                # target.
                self.cluster_view.pop(msg["node_id"], None)
            elif msg["node_id"] != self.node_id:
                self.cluster_view[msg["node_id"]] = {
                    "available": msg["available"], "total": msg["total"],
                    "address": msg.get("address", ""),
                    "labels": msg.get("labels", {})}
                # A peer freeing resources may unblock queued lease
                # requests via spillback.
                self._try_dispatch()
        elif channel == "nodes":
            if msg["event"] in ("dead", "draining"):
                self.cluster_view.pop(msg.get("node_id"), None)

    # ------------------------------------------------------------------
    # Worker pool

    def _worker_env_for(self, worker_id: WorkerID) -> Dict[str, str]:
        env = dict(self._worker_env)
        # Workers must import ray_tpu regardless of the driver's cwd/sys.path.
        import ray_tpu
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(
            ray_tpu.__file__)))
        existing = env.get("PYTHONPATH", "")
        if pkg_root not in existing.split(os.pathsep):
            env["PYTHONPATH"] = (pkg_root + os.pathsep + existing).rstrip(os.pathsep)
        env["RAY_TPU_RAYLET_ADDRESS"] = self.address
        env["RAY_TPU_GCS_ADDRESS"] = self.gcs_address
        env["RAY_TPU_NODE_ID"] = self.node_id.hex()
        env["RAY_TPU_SESSION_DIR"] = self.session_dir
        env["RAY_TPU_WORKER_ID"] = worker_id.hex()
        return env

    def _worker_log_path(self, worker_id: WorkerID) -> str:
        log_dir = os.path.join(self.session_dir, "logs")
        os.makedirs(log_dir, exist_ok=True)
        return os.path.join(log_dir, f"worker-{worker_id.hex()[:12]}.log")

    async def _start_forkserver(self):
        """Bring up (or join) the process-wide zygote and prestart workers."""
        fs = _SharedForkServer.get()
        await fs.ensure_started(self._worker_env_for(WorkerID.from_random()))
        if not fs.dead and not self._stopped:
            fs.on_ready(self._prestart_workers)
            fs.on_ready(fs.record_ready)

    def _on_forkserver_event(self, event: str, msg: dict):
        if event == "spawned":
            if self._stopped:
                # Forked after our stop(): nothing will ever lease it.
                try:
                    os.kill(msg["pid"], 15)
                except OSError:
                    pass
                return
            handle = self._workers_by_hex.get(msg.get("worker_id"))
            if handle is not None:
                handle.pid = msg["pid"]
            return
        if self._stopped:
            return
        # exit
        handle = self._workers_by_hex.pop(msg.get("worker_id"), None)
        if handle is not None and handle.worker_id in self.workers:
            if handle.registered and handle.conn is not None \
                    and not handle.conn.closed:
                handle.conn.abort(rpc.ConnectionLost("process exited"))
            else:
                asyncio.ensure_future(
                    self._on_worker_disconnect(handle.worker_id))

    def _spawn_worker(self, container_env: Optional[dict] = None
                      ) -> WorkerHandle:
        return self._spawn_workers(1, container_env)[0]

    def _spawn_workers(self, n: int,
                       container_env: Optional[dict] = None
                       ) -> List[WorkerHandle]:
        """Start `n` workers. Generic workers ride ONE multi-spawn
        request through the zygote (one pipe write forks N children);
        container workers stay per-process (each is its own podman/docker
        invocation)."""
        if n <= 0:
            return []
        if container_env is not None:
            return [self._spawn_container_worker(container_env)
                    for _ in range(n)]
        jobs: List[tuple] = []
        for _ in range(n):
            worker_id = WorkerID.from_random()
            env = self._worker_env_for(worker_id)
            log_path = self._worker_log_path(worker_id)
            self._spawned_worker_prefixes.add(worker_id.hex()[:12])
            jobs.append((worker_id, env, log_path))
        fs = _SharedForkServer.get()
        # Fast path: ask the zygote to fork the workers (~ms each, vs
        # seconds for a cold python+jax start). Requests written before
        # the zygote finishes importing are buffered. The FULL worker env
        # ships with each request (the child resets os.environ to it) —
        # the zygote is a long-lived singleton whose template env can be
        # stale.
        if fs.spawn_many([(env, lp) for _wid, env, lp in jobs], self):
            handles = []
            now = time.time()
            for worker_id, _env, _lp in jobs:
                handle = WorkerHandle(worker_id=worker_id, pid=-1,
                                      proc=None)
                handle.spawn_mode = "fork"
                handle.spawned_at = now
                self.workers[worker_id] = handle
                self._workers_by_hex[worker_id.hex()] = handle
                self._starting_workers += 1
                handles.append(handle)
            return handles
        return [self._popen_spawn(worker_id, env, lp)
                for worker_id, env, lp in jobs]

    @staticmethod
    def _start_worker_proc(env: Dict[str, str],
                           log_path: str) -> subprocess.Popen:
        """The one place a generic worker process is exec'd (normal
        Popen path AND zygote-death failover)."""
        # ray-tpu: noqa(ASYNC-BLOCK): cold-path spawn fallback; one append-mode open of the worker log (forkserver covers the hot path)
        out = open(log_path, "ab")
        return subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.worker_main"],
            env=env, stdout=out, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def _popen_spawn(self, worker_id: WorkerID, env: Dict[str, str],
                     log_path: str) -> WorkerHandle:
        proc = self._start_worker_proc(env, log_path)
        handle = WorkerHandle(worker_id=worker_id, pid=proc.pid, proc=proc)
        handle.spawn_mode = "popen"
        handle.spawned_at = time.time()
        self.workers[worker_id] = handle
        self._workers_by_hex[worker_id.hex()] = handle
        self._starting_workers += 1
        return handle

    def _spawn_container_worker(self, container_env: dict) -> WorkerHandle:
        # Containerized worker (runtime_env={"container": ...}): start
        # the worker inside the image via podman/docker (or the test
        # hook), pre-dedicated to this env's hash so only matching
        # leases ever use it (reference: runtime_env/container.py).
        worker_id = WorkerID.from_random()
        env = self._worker_env_for(worker_id)
        log_path = self._worker_log_path(worker_id)
        self._spawned_worker_prefixes.add(worker_id.hex()[:12])
        from ray_tpu._private import runtime_env_container as rec
        from ray_tpu._private.runtime_env import env_hash as _ehash
        argv = rec.build_worker_command(
            container_env["container"], env=env,
            session_dir=self.session_dir)
        # ray-tpu: noqa(ASYNC-BLOCK): container spawn is explicitly a slow path (podman/docker exec); one log-file open alongside
        out = open(log_path, "ab")
        proc = subprocess.Popen(argv, env=env, stdout=out,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        handle = WorkerHandle(worker_id=worker_id, pid=proc.pid,
                              proc=proc)
        handle.env_hash = (container_env.get("_hash")
                           or _ehash(container_env))
        handle.spawn_mode = "container"
        handle.spawned_at = time.time()
        self.workers[worker_id] = handle
        self._workers_by_hex[worker_id.hex()] = handle
        self._starting_workers += 1
        return handle

    def _popen_failover_batch(self, jobs: List[tuple]):
        """Spawns that were buffered at a zygote that died before forking
        them: start each via Popen, reusing the handle already tracked
        for the spawn (supply accounting and any actor-create waiter keep
        working; only the warm fork is lost)."""
        for env, log_path in jobs:
            handle = self._workers_by_hex.get(
                env.get("RAY_TPU_WORKER_ID", ""))
            if (handle is None or handle.registered or handle.proc
                    is not None or self._stopped):
                continue
            try:
                proc = self._start_worker_proc(env, log_path)
            except Exception:
                asyncio.ensure_future(
                    self._on_worker_disconnect(handle.worker_id))
                continue
            handle.proc = proc
            handle.pid = proc.pid
            handle.spawn_mode = "popen"

    @rpc.idempotent
    async def rpc_register_worker(self, conn, payload):
        """Called by a worker process once its RPC server is up.

        The reply can carry the worker's FIRST assignment: when an actor
        creation is already waiting for a worker of this env, the lease
        happens here and the instantiate payload rides the registration
        reply — the worker starts constructing immediately instead of
        going idle, being re-offered, and waiting for a separate
        instantiate dial (the register→idle→re-offer→dispatch round trip
        a launch storm pays per actor)."""
        worker_id = payload["worker_id"]
        handle = self.workers.get(worker_id)
        if handle is None:
            handle = WorkerHandle(worker_id=worker_id, pid=payload["pid"])
            self.workers[worker_id] = handle
        handle.address = payload["address"]
        handle.registered = True
        handle.conn = conn
        handle.idle_since = time.time()
        self._starting_workers = max(0, self._starting_workers - 1)
        # (Boot latency itself is visible through the Mode=cold rows of
        # ray_tpu_worker_spawn_seconds, observed ONCE per actor create
        # in _create_actor — observing it here too double-counted every
        # cold create and emitted rows for prestarts nobody waited on.)
        conn.peer_info["worker_id"] = worker_id
        prev = conn.on_close
        def _on_close(c, _prev=prev):
            asyncio.ensure_future(self._on_worker_disconnect(worker_id))
            if _prev:
                _prev(c)
        conn.on_close = _on_close
        reply = {"node_id": self.node_id, "config": self.config.to_dict()}
        if not handle.leased:
            assignment = self._try_register_assignment(handle)
            if assignment is not None:
                handle.pending_assignment = assignment
                reply["assignment"] = assignment
            else:
                self._offer_idle_worker(handle)
        elif handle.pending_assignment is not None:
            # Replayed registration whose original reply (carrying the
            # assignment) may have been lost: re-send the SAME
            # assignment. The worker applies it once; the create's
            # result future is still waiting on instantiate_result.
            reply["assignment"] = handle.pending_assignment
        self._try_dispatch()
        return reply

    def _try_register_assignment(self, handle: WorkerHandle
                                 ) -> Optional[dict]:
        """Serve the oldest compatible actor-create waiter by leasing the
        registering worker NOW and returning the instantiate payload for
        the registration reply. The waiter's future resolves to the
        result future rpc_instantiate_result will complete."""
        for waiter in list(self._actor_worker_waiters):
            if waiter.fut.done():
                self._actor_worker_waiters.remove(waiter)
                continue
            if waiter.spec is None:
                continue
            if not (handle.env_hash == waiter.env_hash
                    or (handle.env_hash == "" and not waiter.exact)):
                continue
            self._actor_worker_waiters.remove(waiter)
            self._lease_worker_for_actor(handle, waiter.spec,
                                         waiter.pg_key)
            result_fut = asyncio.get_event_loop().create_future()
            self._instantiate_results[handle.worker_id] = result_fut
            self.register_reply_dispatches += 1
            waiter.fut.set_result(("dispatched", handle, result_fut))
            assignment = {"spec": waiter.spec,
                          "num_restarts": waiter.epoch}
            if waiter.function_blob is not None:
                assignment["function_blob"] = waiter.function_blob
            return assignment
        return None

    async def _prefetch_function(self, function_id: str
                                 ) -> Optional[bytes]:
        """Fetch (once per node) the content-addressed class blob so the
        instantiate payload can carry it — the id is a content hash, so
        the cache never goes stale. Best-effort: None just means the
        worker falls back to its own KV fetch."""
        blob = self._function_blobs.get(function_id)
        if blob is not None:
            return blob
        try:
            blob = await self.gcs_conn.request("kv_get", {
                "namespace": "funcs", "key": function_id.encode()})
        except Exception:  # noqa: BLE001 — prefetch is an optimization
            return None
        if blob is None:
            return None
        if len(self._function_blobs) >= 128:
            self._function_blobs.pop(next(iter(self._function_blobs)))
        self._function_blobs[function_id] = blob
        return blob

    def _lease_worker_for_actor(self, worker: WorkerHandle, spec: TaskSpec,
                                pg_key: Optional[tuple]):
        """Stamp the lease fields for an actor create (resources were
        acquired by _create_actor before the spawn)."""
        worker.leased = True
        worker.lease_owner = spec.owner_address
        if spec.env_hash():
            worker.env_hash = spec.env_hash()
        worker.is_actor_worker = True
        worker.actor_id = spec.actor_id
        worker.lease_resources = dict(spec.resources)
        worker.lease_pg = pg_key
        self._mark_resources_dirty()

    @rpc.idempotent
    async def rpc_instantiate_result(self, conn, payload):
        """Constructor outcome of a register-reply-dispatched create,
        reported by the worker over its raylet connection."""
        handle = self.workers.get(payload["worker_id"])
        if handle is not None:
            handle.pending_assignment = None
        fut = self._instantiate_results.pop(payload["worker_id"], None)
        if fut is not None and not fut.done():
            result = payload.get("result")
            if isinstance(result, dict) and "_infra_error" in result:
                # The worker's dispatch plumbing (not the constructor)
                # failed: re-raise into the create path so the GCS
                # retries, exactly like the old request/reply dispatch.
                fut.set_exception(RuntimeError(result["_infra_error"]))
            else:
                fut.set_result(result)
        return True

    # ---- compiled-DAG lease pinning -----------------------------------

    @rpc.idempotent
    async def rpc_dag_pin_workers(self, conn, payload):
        """Pin the leases of the workers hosting `actor_ids` for a
        compiled DAG's lifetime: pinned workers are excluded from OOM
        victim selection and the idle reaper, and stay visible in
        rpc_dag_lease_accounting until rpc_dag_release_workers (or
        worker death) drops them. Set-based, so replays are no-ops."""
        dag_id = payload["dag_id"]
        by_actor = {h.actor_id: h for h in self.workers.values()
                    if h.is_actor_worker and h.actor_id is not None}
        # Validate-then-pin (atomic per raylet): a missing actor midway
        # through the loop must not leave the earlier ones half-pinned.
        handles = []
        for actor_id in payload["actor_ids"]:
            handle = by_actor.get(actor_id)
            if handle is None:
                raise rpc.RpcError(
                    f"no live worker hosts actor {actor_id.hex()[:12]} "
                    f"on node {self.node_name}")
            handles.append((actor_id, handle))
        pinned = {}
        for actor_id, handle in handles:
            handle.dag_pins.add(dag_id)
            self._dag_pins.setdefault(dag_id, set()).add(
                handle.worker_id.hex())
            pinned[actor_id.hex()] = handle.worker_id.hex()
        return pinned

    @rpc.idempotent
    async def rpc_dag_release_workers(self, conn, payload):
        """Release every lease `dag_id` pinned on this node. (Recovery's
        partial release is per-RAYLET — a dead participant's pin is
        already dropped by _on_worker_disconnect, and a migrating DAG
        releases whole draining raylets — so no worker-level subset is
        needed here.)"""
        dag_id = payload["dag_id"]
        released = sorted(self._dag_pins.pop(dag_id, set()))
        for handle in self.workers.values():
            handle.dag_pins.discard(dag_id)
        return released

    @rpc.idempotent
    async def rpc_dag_lease_accounting(self, conn, payload):
        """{dag_id: [worker hexes]} of live pinned leases on this node."""
        return {dag_id: sorted(ws)
                for dag_id, ws in self._dag_pins.items() if ws}

    async def _on_worker_disconnect(self, worker_id: WorkerID):
        handle = self.workers.pop(worker_id, None)
        self._workers_by_hex.pop(worker_id.hex(), None)
        fut = self._instantiate_results.pop(worker_id, None)
        if fut is not None and not fut.done():
            fut.set_exception(RuntimeError(
                "worker died during actor construction"))
        if handle is None:
            return
        if handle.dag_pins:
            # The DAG's failure watcher surfaces the death to the driver;
            # here the lease accounting must not leak a dead worker.
            whex = handle.worker_id.hex()
            for dag_id in list(handle.dag_pins):
                pins = self._dag_pins.get(dag_id)
                if pins is not None:
                    pins.discard(whex)
                    if not pins:
                        self._dag_pins.pop(dag_id, None)
            handle.dag_pins.clear()
        if not handle.registered:
            # Died during startup: it still counts against supply.
            self._starting_workers = max(0, self._starting_workers - 1)
        self._pools.remove(handle)
        if handle.leased:
            # Clear the flag with the release: the create path that our
            # instantiate-future exception wakes runs
            # _unlease_failed_create, which must not release AGAIN (an
            # unclamped double release makes available exceed total and
            # the node over-schedules forever).
            handle.leased = False
            self.pool.release(handle.lease_resources, handle.lease_pg)
            self._mark_resources_dirty()
        if handle.is_actor_worker and handle.actor_id is not None:
            try:
                await self.gcs_conn.request("report_actor_failure", {
                    "actor_id": handle.actor_id,
                    # The dying worker's id lets the GCS drop stale reports
                    # about an instance it already replaced (migration can
                    # recreate the actor faster than the old process exit
                    # is detected).
                    "worker_id": handle.worker_id,
                    "reason": f"worker process {handle.pid} died"})
            except rpc.RpcError:
                pass
        self._try_dispatch()

    def _check_worker_deaths(self):
        for worker_id, handle in list(self.workers.items()):
            if handle.proc is not None and handle.proc.poll() is not None:
                if handle.registered and handle.conn is not None \
                        and not handle.conn.closed:
                    handle.conn.abort(rpc.ConnectionLost("process exited"))
                else:
                    asyncio.ensure_future(self._on_worker_disconnect(worker_id))

    async def _idle_worker_reaper(self):
        """Kill surplus idle workers beyond each pool's floor.

        Per-env floors (not one global count): the fresh pool keeps the
        node's prestart floor, and every env pool keeps its demand/hint
        floor — the reaper can no longer eat a warm pool another env just
        paid to populate (the old single global floor did exactly that:
        any env's idles counted against the one shared number)."""
        while True:
            await asyncio.sleep(5.0)
            self._pools.prune()
            fresh_floor = max(2, int(self.pool.total.get("CPU", 1)))
            for env_hash, pool in list(self._pools.pools.items()):
                floor = self._pools.floor(env_hash, fresh_floor)
                surplus = len(pool) - floor
                if surplus <= 0:
                    continue
                # DAG-pinned workers are load-bearing pipeline state even
                # if they ever land back in a pool: never reap them.
                for handle in [h for h in list(pool)
                               if not h.dag_pins][:surplus]:
                    pool.remove(handle)
                    try:
                        if handle.conn:
                            await handle.conn.push("shutdown", {})
                    except Exception:
                        pass

    def _offer_idle_worker(self, handle: "WorkerHandle"):
        """A worker became available: serve the oldest compatible waiting
        actor-create (FIFO — see rpc_create_actor) or return it to its
        env's warm pool. Every idle-return path goes through here so a
        freed worker can rescue a waiting create whose own spawn died."""
        for waiter in list(self._actor_worker_waiters):
            if waiter.fut.done():
                self._actor_worker_waiters.remove(waiter)
                continue
            if handle.env_hash == waiter.env_hash or \
                    (handle.env_hash == "" and not waiter.exact):
                self._actor_worker_waiters.remove(waiter)
                waiter.fut.set_result(("worker", handle, None))
                return
        self._pools.put(handle)

    def _get_idle_worker(self, env_hash: str = "", exact: bool = False,
                         record: bool = True,
                         demand_n: int = 1) -> Optional[WorkerHandle]:
        """Pop a live idle worker compatible with `env_hash`: exact-match
        tagged workers preferred, fresh ("") workers serve any env.
        exact=True (container envs) never falls back to a fresh worker —
        a generic process cannot retroactively enter the container.

        record=False for RE-scans of a request that was already counted
        (dispatch-loop passes over a queued lease, a create's last-chance
        retry): counting each pass would inflate the EWMA demand floor
        and the miss counter with phantom requests. demand_n: workers of
        demand this request represents (a count=N multi-grant lease is N,
        not 1 — undersizing the EWMA floor ~Nx starves warm pools for
        multi-worker workloads)."""
        if record:
            self._pools.note_demand(env_hash, demand_n)
        return self._pools.pop(
            env_hash, exact,
            lambda h: (h.registered and h.worker_id in self.workers
                       and not (h.conn and h.conn.closed)),
            count_miss=record)

    @staticmethod
    def _container_env(spec) -> Optional[dict]:
        env = getattr(spec, "runtime_env", None) or {}
        return env if env.get("container") else None

    def _ensure_worker_supply(self):
        if self._draining:
            return
        # Count only leases the pool could actually serve concurrently:
        # spawning workers for requests that can't get resources just burns
        # CPU on process startup (round-1 regression on small boxes).
        avail = dict(self.pool.available)
        free_hashes = self._pools.hash_list()
        demand = 0
        container_demand: list = []
        # Container workers still starting (spawned, not yet registered):
        # their env hash is pre-set at spawn.
        starting_hashes = [h.env_hash for h in self.workers.values()
                           if not h.registered and h.env_hash]
        n_starting_container = len(starting_hashes)
        for req in self._pending_leases:
            if req.fut.done():
                continue
            spec = req.spec
            # A multi-grant request is `count` workers of demand, each
            # gated on the resources its grant would consume.
            for _ in range(req.count):
                if not all(avail.get(k, 0) >= v
                           for k, v in spec.resources.items() if v > 0):
                    break
                for k, v in spec.resources.items():
                    avail[k] = avail.get(k, 0) - v
                eh = req.env_hash
                cenv = req.container_env
                if cenv is not None:
                    # Containerized lease: only an exact-hash worker (idle
                    # or already starting) can serve it.
                    if eh in free_hashes:
                        free_hashes.remove(eh)
                    elif eh in starting_hashes:
                        starting_hashes.remove(eh)
                    else:
                        container_demand.append(cenv)
                    continue
                if eh in free_hashes:
                    free_hashes.remove(eh)
                elif "" in free_hashes:
                    free_hashes.remove("")
                else:
                    demand += 1
        spawned_container = 0
        for cenv in container_demand:
            if self.config.max_workers_per_node - len(self.workers) <= 0:
                break
            try:
                self._spawn_worker(container_env=cenv)
                spawned_container += 1
            except Exception:
                logger.exception("containerized worker spawn failed")
                break
        # Container spawns count in _starting_workers but serve only their
        # own env hash — exclude them from the generic supply.
        supply = max(0, self._starting_workers - n_starting_container
                     - spawned_container)
        can_start = self.config.max_workers_per_node - len(self.workers)
        if demand > supply and can_start <= 0:
            # The worker cap is consumed but pending leases can't use what's
            # idle: evict env-dedicated idle workers (oldest first) to make
            # room — otherwise distinct runtime envs permanently pin worker
            # slots and scheduling deadlocks (reference: worker_pool.cc
            # kills idle dedicated workers under pressure).
            tagged = [h for pool_hash, pool in self._pools.pools.items()
                      if pool_hash != "" for h in pool]
            for handle in sorted(tagged,
                                 key=lambda h: h.idle_since
                                 )[:demand - supply]:
                self._pools.remove(handle)
                self.workers.pop(handle.worker_id, None)
                self._workers_by_hex.pop(handle.worker_id.hex(), None)
                if handle.conn:
                    asyncio.ensure_future(self._push_shutdown(handle))
                can_start += 1
        self._spawn_workers(min(max(0, demand - supply), max(0, can_start)))

    async def _push_shutdown(self, handle: WorkerHandle):
        try:
            await handle.conn.push("shutdown", {})
        except Exception:
            pass

    # ------------------------------------------------------------------
    # Drain protocol (planned removal)

    @rpc.idempotent
    async def rpc_drain(self, conn, payload):
        """GCS -> raylet drain notice: stop granting leases, finish running
        work up to the deadline, push primary object copies to live peers,
        and report drain_complete once idle.

        `gang_addresses` lists fellow hosts of this node's slice draining
        in the same gang: they are pruned from the cluster view up front
        (gang-coherent rejection) so neither a lease spillback nor an
        object push-off can route work INTO the dying slice before the
        per-peer pubsub notices land."""
        gang = set(payload.get("gang_addresses") or [])
        if gang:
            for nid, view in list(self.cluster_view.items()):
                if view.get("address") in gang:
                    self.cluster_view.pop(nid, None)
        if self._draining:
            return True
        self._draining = True
        self._drain_deadline = time.time() + float(
            payload.get("deadline_s", 30.0))
        logger.info("raylet %s draining (deadline in %.1fs)",
                    self.node_name, self._drain_deadline - time.time())
        # Bounce queued lease requests: the submitter re-requests and the
        # draining guard spills it to a live peer.
        for req in self._pending_leases:
            if not req.fut.done():
                req.fut.set_result({"retry": True})
        self._pending_leases.clear()
        self._tasks.append(asyncio.ensure_future(self._drain_to_idle()))
        return True

    def _drain_spill_target(self, resources: Dict[str, float]):
        """Best live peer for a lease rejected by the drain: available
        capacity preferred, feasible-by-totals accepted."""
        fallback = None
        for _nid, view in self.cluster_view.items():
            if not view.get("address"):
                continue
            if all(view.get("available", {}).get(k, 0) >= v
                   for k, v in resources.items() if v > 0):
                return view["address"]
            if fallback is None and all(
                    view.get("total", {}).get(k, 0) >= v
                    for k, v in resources.items() if v > 0):
                fallback = view["address"]
        return fallback

    async def _drain_to_idle(self):
        """Background drain worker: migrate objects, wait for running work,
        then tell the GCS this node is safe to kill.

        Compiled-DAG pins are counted EXPLICITLY: pinned workers are
        excluded from the idle reaper, so without intervention a DAG
        whose driver never migrates would hold its leases to the bitter
        end and wedge drain_complete at the deadline. A migrating DAG
        releases its pins itself (dag_release hand-off on the drain
        notice); whatever pins remain once every ordinary lease has
        drained are SHED near the deadline — the pinned workers are shut
        down (they would die at the deadline anyway), the owning DAG's
        settled-ref watcher sees the death, and replayable DAGs recover
        while non-replayable ones fail typed exactly as a kill would."""
        try:
            await self._drain_push_objects()
        except Exception:  # noqa: BLE001 — migration is best-effort
            logger.exception("raylet %s object migration failed",
                             self.node_name)
        window = max(0.0, self._drain_deadline - time.time())
        shed_at = self._drain_deadline - min(2.0, 0.25 * window)
        shed_done = False
        last_log = 0.0
        while not self._stopped and time.time() < self._drain_deadline:
            leased = [h for h in self.workers.values() if h.leased]
            if not leased:
                break
            pinned = [h for h in leased if h.dag_pins]
            if time.time() - last_log > 1.0:
                last_log = time.time()
                logger.info(
                    "raylet %s draining: %d leased worker(s), %d of them "
                    "DAG-pinned (%s)", self.node_name, len(leased),
                    len(pinned),
                    sorted({d for h in pinned for d in h.dag_pins}))
            if pinned and len(pinned) == len(leased) and not shed_done \
                    and time.time() >= shed_at:
                # Only DAG pins stand between this node and
                # drain_complete: shed them instead of wedging until the
                # deadline. Dropping the accounting first keeps
                # rpc_dag_lease_accounting truthful while the shutdowns
                # land.
                shed_done = True
                logger.warning(
                    "raylet %s draining: shedding %d DAG-pinned "
                    "worker(s) whose owning DAG did not migrate",
                    self.node_name, len(pinned))
                for h in pinned:
                    for dag_id in list(h.dag_pins):
                        pins = self._dag_pins.get(dag_id)
                        if pins is not None:
                            pins.discard(h.worker_id.hex())
                            if not pins:
                                self._dag_pins.pop(dag_id, None)
                    h.dag_pins.clear()
                    asyncio.ensure_future(self._push_shutdown(h))
            await asyncio.sleep(0.1)
        if self._stopped:
            return
        try:
            await self.gcs_conn.request("drain_complete",
                                        {"node_id": self.node_id})
        except rpc.RpcError:
            pass

    async def _drain_push_objects(self):
        """Push sealed copies this node is the SOLE live holder of to a
        live peer and register the new location with the object's owner,
        so no owner ever needs lineage reconstruction for this
        (about-to-die) node. Copies another live node already holds are
        skipped — under a tight preemption deadline, re-copying cached
        secondaries would crowd out the sole-copy primaries that actually
        need saving."""
        peers = [v["address"] for v in self.cluster_view.values()
                 if v.get("address")]
        if not peers:
            return
        peer_set = set(peers)
        moved = 0
        for oid in list(self.store.objects):
            ent = self.store.objects.get(oid)
            if ent is None or not self.store.contains(oid):
                continue
            if ent.owner_address:
                try:
                    info = await self.clients.request(
                        ent.owner_address, "owner_locate",
                        {"object_id": ObjectID(oid), "timeout": 0.5},
                        timeout=2.0)
                except (rpc.RpcError, OSError):
                    info = None  # owner unreachable: assume sole copy
                if isinstance(info, dict):
                    if info.get("inline") is not None:
                        continue  # owner holds the value inline: safe
                    if any(loc in peer_set
                           for loc in info.get("locations", [])):
                        continue  # a live peer already has a copy
            remaining = self._drain_deadline - time.time()
            if remaining <= 0:
                # Deadline exhausted: anything left unsaved is lost to
                # lineage reconstruction — stop burning the grace window.
                logger.warning("raylet %s drain deadline hit mid-migration",
                               self.node_name)
                break
            target = peers[moved % len(peers)]
            ent2 = self.store.objects.get(oid)
            size = ent2.size if ent2 is not None else 0
            try:
                if size > self.config.object_transfer_chunk_bytes:
                    # Large object: have the peer PULL it through the
                    # object-manager chunked transfer path (bounded
                    # frames — _MAX_MSG no longer caps drainable object
                    # size), rate-limited against the drain deadline.
                    ok = await self.clients.request(
                        target, "store_fetch_remote", {
                            "object_id": oid, "locations": [self.address],
                            "owner_address": ent.owner_address},
                        timeout=max(1.0, remaining))
                    if not ok:
                        continue
                else:
                    desc = self.store.pin(oid)
                    if desc is None:
                        continue
                    try:
                        seg, offset, sz, metadata = desc
                        data = bytes(self.store.view(seg, offset, sz))
                    finally:
                        self.store.unpin(oid)
                    await self.clients.request(target, "store_put_bytes", {
                        "object_id": oid, "data": data,
                        "metadata": metadata,
                        "owner_address": ent.owner_address},
                        timeout=max(1.0, min(30.0, remaining)))
            except (rpc.RpcError, OSError):
                continue
            moved += 1
            if ent.owner_address:
                try:
                    conn = await self.clients.get(ent.owner_address)
                    await conn.notify("owner_add_location", {
                        "object_id": ObjectID(oid), "location": target})
                except Exception:  # noqa: BLE001 — owner may be gone
                    pass
        if moved:
            logger.info("raylet %s migrated %d primary copies before drain",
                        self.node_name, moved)

    # ------------------------------------------------------------------
    # Lease protocol (normal tasks)

    @rpc.non_idempotent
    async def rpc_request_worker_lease(self, conn, payload):
        """Grant local worker(s), queue, or spill to another node.

        `count` is the client's backlog hint (queued tasks of this sched
        class): the reply carries up to `count` grants in ONE round trip
        (reference: direct_task_transport.h lease pipelining), so N needed
        workers cost ~1 RPC instead of N.

        Reply: {"granted": {...}, "grants": [{...}, ...]}
             | {"spillback": address} | {"infeasible": True} | {"retry": True}
        """
        spec: TaskSpec = payload["spec"]
        count = max(1, int(payload.get("count", 1)))
        if self._draining:
            # Drain phase 1: no new grants here. Spill to a live peer when
            # one could take the shape; otherwise ask the client to retry
            # (it re-routes once the cluster view catches up). Past the
            # deadline this node is as good as dead — fail fast so clients
            # stop dialing it.
            target = self._drain_spill_target(spec.resources)
            if target is not None:
                return {"spillback": target}
            if time.time() > self._drain_deadline:
                return {"infeasible": True, "drained": True,
                        "why": (f"node {self.node_name} was drained and "
                                "no live peer can take the lease")}
            return {"retry": True, "draining": True}
        if self._container_env(spec) is not None:
            from ray_tpu._private import runtime_env_container as _rec
            if not _rec.runner_available():
                return {"infeasible": True,
                        "why": ("container runtime env needs podman or "
                                "docker on the node (or a "
                                "RAY_TPU_CONTAINER_RUNNER hook); none "
                                "found")}
        pg_key = None
        if spec.scheduling.placement_group_id is not None:
            idx = spec.scheduling.bundle_index
            if idx < 0:
                # any bundle of the PG on this node
                for key in self.pool.bundles:
                    if key[0] == spec.scheduling.placement_group_id.binary():
                        pg_key = key
                        break
                if pg_key is None:
                    return {"infeasible": True}
            else:
                pg_key = (spec.scheduling.placement_group_id.binary(), idx)
                if pg_key not in self.pool.bundles:
                    return {"infeasible": True}

        if pg_key is None and spec.scheduling.kind == "DEFAULT":
            # Distributed decision: pick best node from the synced view.
            best = self._pick_best_node(spec.resources)
            if best is not None and best != self.node_id:
                view = self.cluster_view.get(best)
                if view and view.get("address"):
                    return {"spillback": view["address"]}
                # fall through to local queue if address unknown
            if best is None and not self.pool.feasible(spec.resources):
                # Nothing available anywhere; spill to a node where the
                # request is at least feasible by its total resources.
                for node_id, view in self.cluster_view.items():
                    total = view.get("total", {})
                    if view.get("address") and all(
                            total.get(k, 0) >= v
                            for k, v in spec.resources.items() if v > 0):
                        return {"spillback": view["address"]}
                if not self._autoscaler_active:
                    return {"infeasible": True}
                # Autoscaler live: queue the request so the heartbeat
                # reports it as demand and a new node can absorb it
                # (reference: infeasible tasks wait + warn, they don't
                # fail, cluster_task_manager.cc).
        elif pg_key is None and spec.scheduling.kind == "SPREAD":
            best = self._pick_spread_node(spec.resources)
            if best is not None and best != self.node_id:
                view = self.cluster_view.get(best)
                if view and "address" in view:
                    return {"spillback": view["address"]}
        elif pg_key is None and spec.scheduling.kind == "NODE_AFFINITY":
            if spec.scheduling.node_id != self.node_id:
                view = self.cluster_view.get(spec.scheduling.node_id)
                if view and "address" in view:
                    return {"spillback": view["address"]}
                if not spec.scheduling.soft:
                    return {"infeasible": True}
        elif pg_key is None and spec.scheduling.kind == "NODE_LABEL":
            # Label-constrained placement (reference:
            # NodeLabelSchedulingStrategy): hard must match the executing
            # node; soft prefers matching nodes among the eligible;
            # availability outranks soft preference (a preference must
            # not route onto a saturated node past an idle eligible one).
            hard = spec.scheduling.labels_hard or {}
            soft = spec.scheduling.labels_soft or {}
            local_ok = (_labels_match(self.labels, hard)
                        and self.pool.feasible(spec.resources))
            local_soft = local_ok and (not soft
                                       or _labels_match(self.labels, soft))
            if not local_soft:
                target = self._label_spill_target(
                    spec.resources, hard, soft,
                    # a feasible local node only yields to a peer that is
                    # BOTH soft-matching and immediately available
                    need_beat_local=local_ok)
                if target is not None:
                    return {"spillback": target}
            if not local_ok:
                if self._autoscaler_active:
                    pass  # queue: demand heartbeat lets a labeled node spawn
                else:
                    return {"infeasible": True,
                            "why": (f"no node satisfies label constraints "
                                    f"hard={hard} (and resources "
                                    f"{spec.resources})")}

        fut = asyncio.get_running_loop().create_future()
        req = PendingLease(spec, pg_key, fut, conn, count)
        self._pending_leases.append(req)
        self._watch_lease_client(conn)
        self._try_dispatch()
        self._ensure_worker_supply()
        try:
            return await asyncio.wait_for(fut, self.config.worker_lease_timeout_s)
        except asyncio.TimeoutError:
            try:
                self._pending_leases.remove(req)
            except ValueError:
                pass
            return {"retry": True}

    def _label_spill_target(self, resources: dict, hard: dict, soft: dict,
                            need_beat_local: bool = False):
        """Best peer for a label-constrained request, or None.

        Ranking (higher wins): soft-matching AND available(4) >
        hard-only available(3) > soft-matching feasible-by-totals(2) >
        hard-only feasible(1). With need_beat_local (the local node can
        already run it), only rank-4 peers justify a hop."""
        def fits(view, key):
            caps = view.get(key, {})
            return all(caps.get(k, 0) >= v
                       for k, v in resources.items() if v > 0)

        best_rank, best_addr = 0, None
        for _nid, view in self.cluster_view.items():
            if not view.get("address"):
                continue
            labels = view.get("labels", {})
            if not _labels_match(labels, hard):
                continue
            soft_ok = bool(soft) and _labels_match(labels, soft)
            if fits(view, "available"):
                rank = 4 if soft_ok else 3
            elif fits(view, "total"):
                rank = 2 if soft_ok else 1
            else:
                continue
            if rank > best_rank:
                best_rank, best_addr = rank, view["address"]
        if need_beat_local and best_rank < 4:
            return None
        return best_addr

    @rpc.idempotent
    async def rpc_announce_client(self, conn, payload):
        """Core workers identify themselves right after connecting so a
        later disconnect maps back to their owner address (driver OR
        worker: nested-task submitters get the same reclamation)."""
        self._conn_owner[conn] = payload.get("owner_address", "")
        self._watch_lease_client(conn)
        return True

    def _watch_lease_client(self, conn):
        """Reclaim a client's leases when its raylet connection closes
        (clean shutdown or crash). Leased non-actor workers are killed —
        any task still running on them is orphaned (reference: job exit
        destroys its leased workers, worker_pool.cc DisconnectClient);
        the client's non-detached ACTORS are killed via the GCS
        owner-death notification (detached actors survive)."""
        if conn is None or conn in self._lease_conns:
            return
        if getattr(conn, "closed", False):
            # Lost the race: the conn died before we could watch it.
            asyncio.ensure_future(self._reclaim_client_leases(conn))
            return
        self._lease_conns.add(conn)
        prev = conn.on_close

        def _on_close(c, _prev=prev):
            self._lease_conns.discard(conn)
            asyncio.ensure_future(self._reclaim_client_leases(conn))
            if _prev:
                _prev(c)

        conn.on_close = _on_close

    async def _reclaim_client_leases(self, conn):
        # Pending (ungranted) requests from the dead client must not be
        # granted to nobody: cancel their futures.
        for req in self._pending_leases:
            if req.conn is conn and not req.fut.done():
                req.fut.cancel()
        self._pending_leases = [
            e for e in self._pending_leases if not e.fut.done()]
        for handle in list(self.workers.values()):
            if not (handle.leased and handle.lease_conn is conn):
                continue
            if handle.is_actor_worker:
                continue
            if handle.lease_resources.get(CHIP_RESOURCE, 0) > 0:
                self._retire_chip_worker(handle)
                continue
            handle.leased = False
            handle.lease_conn = None
            self.pool.release(handle.lease_resources, handle.lease_pg)
            self._mark_resources_dirty()
            handle.lease_resources = {}
            handle.lease_pg = None
            try:
                if handle.conn:
                    await handle.conn.push("shutdown", {})
            except Exception:
                pass
        owner = self._conn_owner.pop(conn, "")
        if owner:
            # Non-detached actors owned by the departed client die with
            # it (reference: gcs_actor_manager OnWorkerDead).
            try:
                await self.gcs_conn.request("owner_disconnected",
                                            {"owners": [owner]})
            except rpc.RpcError:
                pass
        self._try_dispatch()

    def _try_dispatch(self):
        if self._draining:
            # No grants during drain; bounce anything still queued.
            for req in self._pending_leases:
                if not req.fut.done():
                    req.fut.set_result({"retry": True})
            self._pending_leases.clear()
            return
        if not self._pending_leases:
            return
        remaining = []
        n_waiting = sum(1 for e in self._pending_leases
                        if not e.fut.done())
        idle0 = len(self._pools)
        for req in self._pending_leases:
            fut = req.fut
            if fut.done():
                continue
            spec, pg_key, count = req.spec, req.pg_key, req.count
            if not self.pool.fits(spec.resources, pg_key):
                # Re-evaluate spillback for queued requests: the entry-time
                # decision can race with concurrent grants that drained the
                # local pool (reference: each scheduling tick may spill,
                # cluster_task_manager.h). PG-pinned and affinity tasks
                # never spill.
                if pg_key is None and spec.scheduling.kind in ("DEFAULT",
                                                               "SPREAD"):
                    for node_id, view in self.cluster_view.items():
                        avail = view.get("available", {})
                        if view.get("address") and all(
                                avail.get(k, 0) >= v
                                for k, v in spec.resources.items() if v > 0):
                            # Debit our local copy of the peer's view so a
                            # burst of queued requests doesn't all spill to
                            # the same (about-to-be-full) node; the next
                            # resource pub refreshes the real numbers.
                            for k, v in spec.resources.items():
                                if v > 0:
                                    avail[k] = avail.get(k, 0) - v
                            fut.set_result(
                                {"spillback": view["address"]})
                            break
                if not fut.done():
                    remaining.append(req)
                continue
            # Fair multi-grant: one client's backlog hint must not soak
            # every idle worker while other clients' requests wait.
            cap = count
            if n_waiting > 1:
                cap = max(1, min(count, idle0 // n_waiting))
            grants = []
            while len(grants) < cap and self.pool.fits(spec.resources,
                                                       pg_key):
                worker = self._get_idle_worker(
                    req.env_hash,
                    exact=req.container_env is not None,
                    record=not req.demand_recorded,
                    demand_n=req.count)
                req.demand_recorded = True
                if worker is None:
                    break
                self.pool.acquire(spec.resources, pg_key)
                worker.leased = True
                worker.lease_owner = spec.owner_address
                if req.env_hash:
                    worker.env_hash = req.env_hash
                worker.lease_class = req.sched_class
                worker.lease_resources = dict(spec.resources)
                worker.lease_pg = pg_key
                worker.lease_conn = req.conn
                worker.idle_since = time.time()
                grants.append({
                    "worker_id": worker.worker_id,
                    "worker_address": worker.address,
                    "node_id": self.node_id,
                })
            if not grants:
                remaining.append(req)
                continue
            self._mark_resources_dirty()
            fut.set_result({"granted": grants[0], "grants": grants})
        self._pending_leases = [e for e in remaining if not e.fut.done()]
        self._ensure_worker_supply()

    @rpc.idempotent
    async def rpc_return_worker(self, conn, payload):
        """Lease released by the submitter (idle timeout or task class change)."""
        worker_id = payload["worker_id"]
        handle = self.workers.get(worker_id)
        if handle is None or not handle.leased:
            return False
        if handle.lease_resources.get(CHIP_RESOURCE, 0) > 0:
            self._retire_chip_worker(handle)
            return True
        handle.leased = False
        handle.lease_conn = None
        self.pool.release(handle.lease_resources, handle.lease_pg)
        self._mark_resources_dirty()
        handle.lease_resources = {}
        handle.lease_pg = None
        if payload.get("kill", False):
            try:
                if handle.conn:
                    await handle.conn.push("shutdown", {})
            except Exception:
                pass
        else:
            handle.idle_since = time.time()
            self._offer_idle_worker(handle)
        self._try_dispatch()
        return True

    def _retire_chip_worker(self, handle: WorkerHandle):
        """End a lease that held a chip. A process that opened a TPU chip
        keeps it until it exits, so this worker is never pooled: it is told
        to exit with its lease left in place, and _on_worker_disconnect
        releases the lease when the process has gone — the TPU unit is
        not granted again while the chip may still be held."""
        handle.lease_conn = None
        handle.is_actor_worker = False
        handle.actor_id = None
        if handle.conn is not None and not handle.conn.closed:
            handle.conn.push_nowait("shutdown", {})

    def _pick_best_node(self, resources: Dict[str, float]) -> Optional[NodeID]:
        """Hybrid pack/spread over local + synced cluster view."""
        candidates: List[tuple] = []
        if self.pool.fits(resources):
            candidates.append((self.node_id, self._utilization(
                self.pool.available, self.pool.total)))
        for node_id, view in self.cluster_view.items():
            if node_id == self.node_id:
                continue
            avail, total = view["available"], view["total"]
            if all(avail.get(k, 0) >= v for k, v in resources.items() if v > 0):
                candidates.append((node_id, self._utilization(avail, total)))
        if not candidates:
            return None
        thr = self.config.scheduler_spread_threshold
        packed = [c for c in candidates if c[1] < thr]
        # Prefer local when tied (locality, lease reuse).
        def keyfn(c):
            return (-c[1], c[0] != self.node_id)
        if packed:
            return min(packed, key=keyfn)[0]
        return min(candidates, key=lambda c: (c[1], c[0] != self.node_id))[0]

    def _pick_spread_node(self, resources) -> Optional[NodeID]:
        candidates = []
        if self.pool.fits(resources):
            candidates.append((self.node_id,
                               self._utilization(self.pool.available, self.pool.total)))
        for node_id, view in self.cluster_view.items():
            if node_id == self.node_id:
                continue
            if all(view["available"].get(k, 0) >= v
                   for k, v in resources.items() if v > 0):
                candidates.append((node_id,
                                   self._utilization(view["available"], view["total"])))
        if not candidates:
            return None
        return min(candidates, key=lambda c: c[1])[0]

    @staticmethod
    def _utilization(avail: Dict[str, float], total: Dict[str, float]) -> float:
        fracs = [1 - avail.get(k, 0) / t for k, t in total.items() if t > 0]
        return max(fracs) if fracs else 0.0

    # ------------------------------------------------------------------
    # Actor creation (GCS -> this raylet)

    @rpc.non_idempotent
    async def rpc_create_actor(self, conn, payload):
        """Create-by-actor-id dedupe in front of the real create: a GCS
        restored from a snapshot re-drives PENDING creations, and the
        original create may STILL be running on this raylet (hung
        constructor, slow worker spawn) — or may have completed with its
        reply lost to the dead GCS connection. Either way a second
        instantiation of the same (actor_id, restart-epoch) would leak a
        worker + double the actor's side effects; instead the re-drive
        joins the in-flight create or returns the already-hosted
        instance."""
        spec: TaskSpec = payload["spec"]
        epoch = payload.get("num_restarts", 0)
        key = (spec.actor_id.binary(), epoch)
        for w in self.workers.values():
            if (getattr(w, "is_actor_worker", False) and w.leased
                    and w.actor_id == spec.actor_id
                    and getattr(w, "actor_epoch", -1) == epoch):
                return {"actor_address": w.address, "worker_id": w.worker_id}
        inflight = self._creating_actors.get(key)
        if inflight is not None:
            # Shielded: the joiner's own cancellation must not cancel the
            # original create it merely observes.
            return await asyncio.shield(inflight)
        fut = asyncio.get_event_loop().create_future()
        # A joiner may never materialize; don't warn on an unretrieved
        # create failure (the original caller gets it raised directly).
        fut.add_done_callback(
            lambda f: f.exception() if not f.cancelled() else None)
        self._creating_actors[key] = fut
        try:
            result = await self._create_actor(spec, payload, epoch)
            if not fut.done():
                fut.set_result(result)
            return result
        except BaseException as e:
            if not fut.done():
                if isinstance(e, asyncio.CancelledError):
                    fut.cancel()
                else:
                    fut.set_exception(e)
            raise
        finally:
            self._creating_actors.pop(key, None)

    async def _create_actor(self, spec: TaskSpec, payload, epoch: int):
        if self._draining:
            # The GCS already excludes draining nodes from placement; this
            # covers the race where the pick happened pre-drain.
            raise RuntimeError("node is draining; actor must go elsewhere")
        cenv = self._container_env(spec)
        if cenv is not None:
            from ray_tpu._private import runtime_env_container as _rec
            if not _rec.runner_available():
                raise RuntimeError(
                    "container runtime env needs podman or docker on the "
                    "node (or a RAY_TPU_CONTAINER_RUNNER hook); none found")
        pg_key = None
        if spec.scheduling.placement_group_id is not None:
            idx = max(0, spec.scheduling.bundle_index)
            pg_key = (spec.scheduling.placement_group_id.binary(), idx)
        if not self.pool.acquire(spec.resources, pg_key):
            raise RuntimeError("resources no longer available for actor")
        from ray_tpu.util import metrics as _metrics
        trace = f"actor:{spec.actor_id.hex()}"
        # The pool charge belongs to this coroutine throughout the try
        # below (a worker lease only takes it over AFTER the try, or —
        # in the register-reply race — via the fut inspected in the
        # handler). CancelledError can land at ANY await inside — it is
        # a BaseException, so ordinary failure-branch releases never
        # see it — and without this handler a create cancelled
        # mid-prefetch or mid-spawn-wait (GCS connection death) charged
        # the node forever.
        fut: Optional[asyncio.Future] = None
        try:
            function_blob = await self._prefetch_function(spec.function_id)
            # t0 AFTER the blob prefetch: the spawn histogram/span
            # measures the wait for a worker, not the (first-create-only)
            # KV fetch.
            t0 = time.time()
            worker = self._get_idle_worker(spec.env_hash(),
                                           exact=cenv is not None)
            result_fut: Optional[asyncio.Future] = None
            mode = "warm" if worker is not None else "cold"
            if worker is None:
                self._spawn_worker(container_env=cenv)
                # FIFO hand-off: freshly registered workers go to the
                # OLDEST waiting create (rpc_register_worker serves this
                # queue). Polling here instead let N concurrent creates
                # steal each other's spawns — under a 40-actor storm on
                # one node some handlers starved to the timeout
                # (measured: 4s -> 240s). The waiter carries the SPEC so
                # registration can dispatch the assignment in its reply
                # (no idle→re-offer round trip).
                fut = asyncio.get_event_loop().create_future()
                waiter = _ActorWorkerWaiter(spec.env_hash(),
                                            cenv is not None,
                                            fut, spec, epoch, pg_key,
                                            function_blob)
                self._actor_worker_waiters.append(waiter)
                got = None
                try:
                    got = await asyncio.wait_for(
                        fut, timeout=self.config.worker_start_timeout_s)
                except asyncio.TimeoutError:
                    pass
                finally:
                    if waiter in self._actor_worker_waiters:
                        self._actor_worker_waiters.remove(waiter)
                if got is not None:
                    _kind, worker, result_fut = got
                else:
                    # Last chance: a worker freed via the idle path (the
                    # request was already counted by the first attempt).
                    worker = self._get_idle_worker(spec.env_hash(),
                                                   exact=cenv is not None,
                                                   record=False)
                if worker is None:
                    raise RuntimeError("worker failed to start for actor")
        except BaseException:
            served = None
            if fut is not None and fut.done() and \
                    not fut.cancelled() and fut.exception() is None:
                # ray-tpu: noqa(ASYNC-BLOCK): asyncio future, done() checked above — result() is a non-blocking read here
                served = fut.result()
            if served is not None and served[0] == "dispatched":
                # A registration raced the cancellation and already
                # leased the worker against this charge: undo it
                # exactly like a failed instantiate (leased flag
                # keeps the release single-shot).
                w = served[1]
                self._instantiate_results.pop(w.worker_id, None)
                self._unlease_failed_create(w, spec, pg_key)
            elif served is not None:
                # Idle rescue raced the cancellation: the worker was
                # handed over UNLEASED — give back the charge and
                # return the worker to its pool.
                self.pool.release(spec.resources, pg_key)
                self._offer_idle_worker(served[1])
            else:
                self.pool.release(spec.resources, pg_key)
            raise
        # From here the charge is (or is about to be) owned by a worker
        # lease: register-reply dispatch leased at registration, and the
        # warm path leases synchronously below before the next await —
        # every later failure releases via _unlease_failed_create's
        # leased-flag gate, never via pool_owned.
        t_worker = time.time()
        _metrics.Histogram(
            "ray_tpu_worker_spawn_seconds",
            "how long an actor create waited for its worker "
            "(Mode=warm: pool hit; Mode=cold: process boot)",
            tag_keys=("Mode",)).observe(t_worker - t0, tags={"Mode": mode})
        self._record_span(
            trace, "actor:spawn", t0, t_worker,
            zygote_wait_s=(_SharedForkServer.get().waited_in(t0, t_worker)
                           if mode == "cold" else 0.0))
        if result_fut is None:
            # Warm pool hit / idle rescue: lease here and dispatch the
            # constructor over the worker's RPC server.
            self._lease_worker_for_actor(worker, spec, pg_key)
            t_ctor = time.time()
            self._record_span(trace, "actor:register", t_worker, t_ctor)
            inst_payload = {"spec": spec,
                            "num_restarts": payload.get("num_restarts", 0)}
            if function_blob is not None:
                inst_payload["function_blob"] = function_blob
            try:
                if worker.conn is not None and not worker.conn.closed:
                    # Dispatch over the worker's registration connection
                    # (one push + one result request) — no per-create
                    # dial; a warm storm costs zero new TCP connections.
                    result_fut = asyncio.get_event_loop().create_future()
                    self._instantiate_results[worker.worker_id] = \
                        result_fut
                    await worker.conn.push("instantiate_actor",
                                           inst_payload)
                    reply = await asyncio.wait_for(
                        result_fut,
                        timeout=self.config.worker_start_timeout_s)
                else:
                    reply = await self.clients.request(
                        worker.address, "instantiate_actor", inst_payload,
                        timeout=self.config.worker_start_timeout_s)
            except BaseException:
                self._instantiate_results.pop(worker.worker_id, None)
                self._unlease_failed_create(worker, spec, pg_key)
                raise
        else:
            # Register-reply dispatch: the lease and the instantiate
            # payload rode the registration reply; await the outcome.
            t_ctor = t_worker
            self._record_span(trace, "actor:register", t_worker, t_ctor)
            try:
                reply = await asyncio.wait_for(
                    result_fut, timeout=self.config.worker_start_timeout_s)
            except BaseException:
                self._instantiate_results.pop(worker.worker_id, None)
                self._unlease_failed_create(worker, spec, pg_key)
                raise
        self._record_span(trace, "actor:ctor", t_ctor, time.time())
        if isinstance(reply, dict) and reply.get("app_error"):
            if spec.resources.get(CHIP_RESOURCE, 0) > 0:
                self._retire_chip_worker(worker)
                return {"app_error": reply["app_error"]}
            # Constructor raised: the worker is still healthy — return it
            # to the idle pool (without this it would leak, unleasable,
            # one process per attempt) and surface the error to the GCS
            # as data.
            self._unlease_failed_create(worker, spec, pg_key)
            worker.idle_since = time.time()
            self._offer_idle_worker(worker)
            self._mark_resources_dirty()
            return {"app_error": reply["app_error"]}
        # Stamp the epoch only on a COMPLETED create: the dedupe fast
        # path must never hand out the address of a worker whose
        # constructor is still running (a re-driven create joins the
        # in-flight future instead and replies post-construction).
        worker.actor_epoch = epoch
        return {"actor_address": worker.address, "worker_id": worker.worker_id}

    def _unlease_failed_create(self, worker: WorkerHandle, spec: TaskSpec,
                               pg_key: Optional[tuple]):
        if worker.leased:
            # `leased` gates the release on BOTH failure paths (here and
            # _on_worker_disconnect): whichever runs first releases, the
            # other no-ops.
            self.pool.release(spec.resources, pg_key)
        worker.leased = False
        worker.is_actor_worker = False
        worker.actor_id = None

    def _prestart_workers(self):
        """Warm the pool so first leases don't wait on worker boot
        (reference: WorkerPool prestart, worker_pool.h)."""
        if self._stopped or self._draining:
            return
        floor = min(int(self.pool.total.get("CPU", 1)), 4,
                    self.config.max_workers_per_node - len(self.workers))
        supply = len(self._pools) + self._starting_workers
        self._spawn_workers(max(0, floor - supply))

    @rpc.idempotent
    async def rpc_prestart_workers(self, conn, payload):
        """Explicit warm-up hint (GCS creation batches, gang recovery,
        serve scale-ups): `count` worker acquisitions for `env_hash` are
        about to land on this node. Pins the pool floor for the hint's
        TTL and spawns the shortfall NOW as one multi-spawn batch, so the
        storm forks before its first create arrives. Container envs are
        not generically prestartable (the spawn needs the container
        spec); their hint still pins the floor so the reaper spares any
        dedicated workers already warm."""
        if self._draining or self._stopped:
            return 0
        count = max(0, int(payload.get("count", 0)))
        env_hash = payload.get("env_hash", "") or ""
        if count <= 0:
            return 0
        self.prestart_hints_received += count
        ttl_s = float(payload.get("ttl_s",
                                  self.config.prestart_hint_ttl_s))
        # merge=True: a replayed hint RPC must stay idempotent (per-env
        # max). fresh_alias: for a non-container env the workers this
        # hint spawns are GENERIC (they apply the env at first lease) and
        # idle in the fresh pool — the alias adds this hint to that
        # pool's floor (summed across envs, so two envs' batches both
        # survive the reaper).
        self._pools.hint(env_hash, count, ttl_s=ttl_s, merge=True,
                         fresh_alias=bool(env_hash)
                         and not payload.get("container"))
        if payload.get("container"):
            return 0
        sizes = self._pools.sizes()
        supply = (sizes.get(env_hash, 0) + self._starting_workers
                  + (sizes.get("", 0) if env_hash else 0))
        can_start = self.config.max_workers_per_node - len(self.workers)
        n = min(max(0, count - supply), max(0, can_start))
        self._spawn_workers(n)
        return n

    @rpc.idempotent
    async def rpc_kill_worker(self, conn, payload):
        handle = self.workers.get(payload["worker_id"])
        if handle is None:
            return False
        if handle.proc is not None:
            try:
                handle.proc.kill()
            except Exception:
                pass
        elif handle.pid > 0:
            try:
                os.kill(handle.pid, 9)
            except OSError:
                pass
        return True

    # ------------------------------------------------------------------
    # Placement group bundles

    @rpc.idempotent
    async def rpc_reserve_bundle(self, conn, payload):
        if self._draining:
            return False
        key = (payload["pg_id"].binary(), payload["bundle_index"])
        ok = self.pool.reserve_bundle(key, payload["resources"])
        if ok:
            self._mark_resources_dirty()
        return ok

    @rpc.idempotent
    async def rpc_return_bundle(self, conn, payload):
        key = (payload["pg_id"].binary(), payload["bundle_index"])
        self.pool.return_bundle(key)
        self._mark_resources_dirty()
        return True

    # ------------------------------------------------------------------
    # Object store service (workers on this node + remote raylets)

    @rpc.non_idempotent
    async def rpc_store_create(self, conn, payload):
        oid = payload["object_id"]
        res = self.store.create(oid, payload["size"],
                                payload.get("metadata", b""),
                                payload.get("owner_address", ""))
        self._track_creating(conn, oid)
        return res

    def _track_creating(self, conn, oid):
        """Abort CREATING entries whose writer dies before sealing.

        A worker that crashes between store_create and store_seal would
        otherwise leave the entry CREATING forever: readers block in
        wait_sealed until timeout and the region never returns to the
        free list. Tie the entry to the writer's connection — on close,
        abort whatever it never sealed (abort_create is a no-op for
        entries that did seal)."""
        pending = getattr(conn, "_store_creating", None)
        if pending is None:
            pending = set()
            conn._store_creating = pending
            prev = conn.on_close

            def _abort_unsealed(c, _prev=prev):
                for o in list(pending):
                    self.store.abort_create(o)
                pending.clear()
                if _prev:
                    _prev(c)

            conn.on_close = _abort_unsealed
        pending.add(oid)

    @rpc.idempotent
    async def rpc_store_seal(self, conn, payload):
        oid = payload["object_id"]
        self.store.seal(oid)
        pending = getattr(conn, "_store_creating", None)
        if pending is not None:
            pending.discard(oid)
        return True

    @rpc.idempotent
    async def rpc_store_abort(self, conn, payload):
        """Writer-side rollback of a CREATING entry (failed local write)."""
        oid = payload["object_id"]
        self.store.abort_create(oid)
        pending = getattr(conn, "_store_creating", None)
        if pending is not None:
            pending.discard(oid)
        return True

    @rpc.non_idempotent
    async def rpc_store_get(self, conn, payload):
        oid = payload["object_id"]
        timeout = payload.get("timeout")
        if not self.store.contains(oid):
            ok = await self.store.wait_sealed(oid, timeout)
            if not ok:
                return None
        desc = self.store.pin(oid)
        if desc is not None:
            # Same-node pin descriptor = a zero-copy view handed out.
            self.store.num_zero_copy_gets += 1
        return desc

    @rpc.non_idempotent
    async def rpc_store_release(self, conn, payload):
        self.store.unpin(payload["object_id"])
        return True

    @rpc.idempotent
    async def rpc_store_contains(self, conn, payload):
        return self.store.contains(payload["object_id"])

    @rpc.idempotent
    async def rpc_store_delete(self, conn, payload):
        for oid in payload["object_ids"]:
            self.store.delete(oid)
        return True

    @rpc.idempotent
    async def rpc_store_stats(self, conn, payload):
        return self.store.stats()

    @rpc.idempotent
    async def rpc_store_list(self, conn, payload):
        """Object inventory for the state API (`ray_tpu list objects`)."""
        out = []
        for oid, ent in list(self.store.objects.items()):
            out.append({"object_id": oid.hex(), "size": ent.size,
                        "pins": ent.pins, "state": ent.state,
                        "owner": ent.owner_address})
        return out

    @rpc.idempotent
    async def rpc_store_put_bytes(self, conn, payload):
        """Put raw serialized bytes (used by small-RPC path and transfers)."""
        self.store.write_and_seal(payload["object_id"], payload["data"],
                                  payload.get("metadata", b""),
                                  payload.get("owner_address", ""))
        return True

    # ---- inter-node transfer (object manager) ----

    @rpc.idempotent
    async def rpc_store_pull_chunk(self, conn, payload):
        """Serve one chunk of a local object to a remote raylet."""
        oid = payload["object_id"]
        offset = payload["offset"]
        length = payload["length"]
        desc = self.store.pin(oid)
        if desc is None:
            return None
        try:
            seg, obj_off, size, metadata = desc
            chunk = bytes(self.store.view(seg, obj_off + offset,
                                          min(length, size - offset)))
            return {"data": chunk, "total_size": size, "metadata": metadata}
        finally:
            self.store.unpin(oid)

    @rpc.idempotent
    async def rpc_store_fetch_remote(self, conn, payload):
        """Pull an object from a remote node into the local store."""
        oid = payload["object_id"]
        if self.store.contains(oid):
            return True
        if self.store.objects.get(oid) is not None:
            # A concurrent writer holds the entry mid-transfer — e.g. a
            # REPLAYED fetch racing its still-running original (handlers
            # are not cancelled when the requesting connection dies).
            # Racing create() would crash 'already exists'; wait for the
            # first writer's seal, and only fall through to fetch if it
            # aborted (entry rolled back) or stalled out.
            if await self.store.wait_sealed(oid, timeout=60.0):
                return True
            if self.store.contains(oid):
                return True
        locations: List[str] = payload["locations"]   # raylet addresses
        chunk_size = self.config.object_transfer_chunk_bytes
        for address in locations:
            if address == self.address:
                continue
            created = False
            try:
                first = await self.clients.request(
                    address, "store_pull_chunk",
                    {"object_id": oid, "offset": 0, "length": chunk_size},
                    timeout=30.0)
                if first is None:
                    continue
                total = first["total_size"]
                name, offset = self.store.create(oid, total,
                                                 first.get("metadata", b""),
                                                 payload.get("owner_address", ""))
                created = True
                view = self.store.view(name, offset, total)
                data = first["data"]
                view[: len(data)] = data
                pos = len(data)
                while pos < total:
                    part = await self.clients.request(
                        address, "store_pull_chunk",
                        {"object_id": oid, "offset": pos, "length": chunk_size},
                        timeout=30.0)
                    if part is None:
                        raise rpc.RpcError("object disappeared mid-transfer")
                    d = part["data"]
                    view[pos : pos + len(d)] = d
                    pos += len(d)
                self.store.seal(oid)
                return True
            except (rpc.RpcError, OSError):
                # RpcError or raw socket errors (ConnectionRefused when the
                # holder node died): try the next location.
                if created:
                    # Roll back so another location (or retry) can recreate.
                    self.store.abort_create(oid)
                continue
            except MemoryError:
                raise
        return False


def _labels_match(labels: dict, constraint: dict) -> bool:
    """Every constrained label must be present with an allowed value."""
    return all(labels.get(k) in v for k, v in constraint.items())
