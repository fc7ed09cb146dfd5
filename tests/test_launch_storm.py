"""Fleet-scale actor launch: batched creation pipeline + warm pools.

Covers the launch-storm tentpole end to end plus its units:
- deterministic 100-actor storm on a 3-node fake cluster asserting
  register-reply dispatch happened and ALIVE publishes coalesced into
  far fewer pubsub frames than actors (one frame per GCS loop tick);
- WarmPools units: hit/miss accounting, env isolation, container
  exactness, demand/hint floors (the reaper must not eat a pool another
  env just paid to populate);
- forkserver multi-spawn (one request line forks N children) and the
  dead-zygote paths: batched Popen failover for buffered spawns, and
  restart-the-zygote-then-respawn.
"""

import asyncio
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# WarmPools units
# ---------------------------------------------------------------------------

def _mk_handle(env_hash=""):
    from ray_tpu._private.ids import WorkerID
    from ray_tpu._private.raylet import WorkerHandle
    h = WorkerHandle(worker_id=WorkerID.from_random(), pid=1,
                     registered=True)
    h.env_hash = env_hash
    return h


class TestWarmPools:
    def test_hit_miss_and_env_isolation(self):
        from ray_tpu._private.raylet import WarmPools
        pools = WarmPools()
        fresh = _mk_handle("")
        tagged = _mk_handle("envA")
        pools.put(fresh)
        pools.put(tagged)
        alive = lambda h: True  # noqa: E731
        # Exact env pops its own pool first, not the fresh worker.
        got = pools.pop("envA", exact=False, alive=alive)
        assert got is tagged
        assert pools.hits == 1
        # envB must NOT be served by envA's worker; falls to fresh.
        got = pools.pop("envB", exact=False, alive=alive)
        assert got is fresh
        # Nothing left: miss.
        assert pools.pop("envB", exact=False, alive=alive) is None
        assert pools.misses == 1
        # A tagged idle worker never serves the fresh ("") request.
        pools.put(_mk_handle("envA"))
        assert pools.pop("", exact=False, alive=alive) is None

    def test_container_exact_never_falls_back(self):
        from ray_tpu._private.raylet import WarmPools
        pools = WarmPools()
        pools.put(_mk_handle(""))
        assert pools.pop("cenv", exact=True, alive=lambda h: True) is None
        # The fresh worker is still there for a generic request.
        assert pools.pop("", exact=False, alive=lambda h: True) is not None

    def test_dead_entries_pruned_mid_scan(self):
        from ray_tpu._private.raylet import WarmPools
        pools = WarmPools()
        dead, live = _mk_handle(""), _mk_handle("")
        pools.put(live)
        pools.put(dead)  # newest-first pop scans the dead entry first
        got = pools.pop("", exact=False, alive=lambda h: h is live)
        assert got is live
        assert len(pools) == 0  # the dead entry was dropped, not kept

    def test_floors_demand_and_hints(self):
        from ray_tpu._private.raylet import WarmPools
        pools = WarmPools()
        # Fresh pool keeps the node's base floor.
        assert pools.floor("", fresh_floor=3) == 3
        # Env pools have no base floor...
        assert pools.floor("envA", fresh_floor=3) == 0
        # ...until demand (EWMA) or an explicit hint raises one.
        for _ in range(5):
            pools.note_demand("envA")
        assert pools.floor("envA") >= 1
        pools.hint("envB", 7, ttl_s=30.0)
        assert pools.floor("envB") == 7
        # Expired hints stop pinning the floor.
        pools.hint("envC", 9, ttl_s=-1.0)
        assert pools.floor("envC") == 0

    def test_fresh_alias_hints_sum_across_envs(self):
        """Generic workers prestarted for tagged envs idle in the fresh
        pool: concurrent hints for DIFFERENT envs must add to the fresh
        floor (a max would let the reaper eat the second env's batch),
        while a replayed hint for the SAME env stays idempotent (max)."""
        from ray_tpu._private.raylet import WarmPools
        pools = WarmPools()
        pools.hint("envA", 10, ttl_s=30.0, merge=True, fresh_alias=True)
        pools.hint("envB", 10, ttl_s=30.0, merge=True, fresh_alias=True)
        assert pools.floor("") == 20
        # RPC replay of envA's hint: per-env max, not +10.
        pools.hint("envA", 10, ttl_s=30.0, merge=True, fresh_alias=True)
        assert pools.floor("") == 20
        # Expired alias hints stop counting; prune() drops them.
        pools.hint("envA", 10, ttl_s=-1.0, merge=False, fresh_alias=True)
        assert pools.floor("") == 10
        pools.prune()
        assert "envA" not in pools._hints

    def test_reaper_respects_per_env_floors(self):
        """The old single global floor let any env's idles count against
        the shared number; per-env floors must keep a hinted pool intact
        while surplus fresh workers are reaped."""
        from ray_tpu._private.raylet import WarmPools
        pools = WarmPools()
        for _ in range(4):
            pools.put(_mk_handle("envA"))
        for _ in range(5):
            pools.put(_mk_handle(""))
        pools.hint("envA", 4, ttl_s=30.0)
        fresh_floor = 2
        reaped = {"envA": 0, "": 0}
        for env_hash, pool in list(pools.pools.items()):
            floor = pools.floor(env_hash, fresh_floor)
            while len(pool) > floor:
                pool.pop(0)
                reaped[env_hash] += 1
        assert reaped["envA"] == 0          # hinted pool untouched
        assert reaped[""] == 3              # fresh surplus beyond floor 2
        assert len(pools.pools["envA"]) == 4


# ---------------------------------------------------------------------------
# Forkserver: multi-spawn + dead-zygote paths
# ---------------------------------------------------------------------------

@pytest.mark.timeout(120)
def test_forkserver_multi_spawn_one_line():
    """One spawn_batch request line forks N children (each reported via
    its own `spawned` event, then `exit` since the bare env can't reach
    a raylet)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.worker_forkserver"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, env=env, cwd=REPO, text=True)
    try:
        ready = json.loads(proc.stdout.readline())
        assert ready["event"] == "ready"
        batch = {"spawn_batch": [
            {"env": {"RAY_TPU_WORKER_ID": f"{i:08x}"}, "log_path": ""}
            for i in range(3)]}
        proc.stdin.write(json.dumps(batch) + "\n")
        proc.stdin.flush()
        events = [json.loads(proc.stdout.readline()) for _ in range(6)]
        spawned = [e for e in events if e["event"] == "spawned"]
        exited = [e for e in events if e["event"] == "exit"]
        assert len(spawned) == 3, events
        assert sorted(e["worker_id"] for e in spawned) == \
            ["00000000", "00000001", "00000002"]
        assert len(exited) == 3, events
    finally:
        proc.stdin.close()
        proc.wait(timeout=30)


def test_buffered_spawns_fail_over_to_popen_as_batch():
    """Spawns buffered at a zygote that dies before starting must fail
    over to Popen as ONE batch per raylet (not be abandoned)."""
    from ray_tpu._private.raylet import _SharedForkServer

    class FakeRaylet:
        def __init__(self):
            self.batches = []
            self.exits = []

        def _popen_failover_batch(self, jobs):
            self.batches.append(list(jobs))

        def _on_forkserver_event(self, event, msg):
            self.exits.append((event, msg))

    fs = _SharedForkServer()
    fs._starting = True  # spawns buffer, no start kicked
    raylet = FakeRaylet()
    jobs = [({"RAY_TPU_WORKER_ID": f"{i:08x}"}, f"/tmp/w{i}.log")
            for i in range(3)]
    assert fs.spawn_many(jobs, raylet)
    assert len(fs._pending_spawns) == 3
    fs.dead = True
    fs._fail_pending()
    # All three buffered jobs arrived in ONE failover batch; none were
    # reported as phantom exits (they never forked).
    assert len(raylet.batches) == 1
    assert len(raylet.batches[0]) == 3
    assert raylet.exits == []
    assert fs._pending_spawns == []
    assert fs.handlers == {}


@pytest.mark.timeout(170)
def test_zygote_restart_then_respawn(jax_cpu):
    """Kill the zygote under a live cluster: the next actor create must
    still come up (fresh zygote or Popen failover), not hang."""
    import ray_tpu
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote(num_cpus=0.01)
        class A:
            def ping(self):
                return os.getpid()

        a = A.remote()
        ray_tpu.get(a.ping.remote(), timeout=90)
        from ray_tpu._private.raylet import _SharedForkServer
        fs = _SharedForkServer._inst
        if fs is not None and fs.proc is not None:
            import signal
            try:
                os.kill(fs.proc.pid, signal.SIGKILL)
            except OSError:
                pass
            deadline = time.time() + 30
            while not fs.dead and time.time() < deadline:
                time.sleep(0.1)
        b = A.remote()
        assert isinstance(ray_tpu.get(b.ping.remote(), timeout=90), int)
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Health-loop self-stall guard (found by the storm: a CPU-starved head
# marked live nodes dead because its OWN detector loop had stalled)
# ---------------------------------------------------------------------------

def test_health_tick_self_stall_guard():
    """A stalled health loop must credit its measured lag back to live
    nodes (their heartbeats were queued behind the same stall) — and an
    on-time tick must still detect a genuinely dead node."""
    from ray_tpu._private.common import NodeInfo
    from ray_tpu._private.config import Config
    from ray_tpu._private.gcs import GcsServer
    from ray_tpu._private.ids import NodeID

    gcs = GcsServer(Config.load({"heartbeat_interval_s": 0.5,
                                 "node_death_timeout_s": 5.0}))
    deaths = []

    async def record_death(node_id, reason, preempted=False):
        deaths.append(node_id)
        gcs.nodes[node_id].alive = False

    gcs._mark_node_dead = record_death
    nid = NodeID.from_random()
    gcs.nodes[nid] = NodeInfo(node_id=nid, address="127.0.0.1:1",
                              last_heartbeat=time.time() - 20.0)
    # Tick woke 25s late: the 20s-stale stamp measures OUR stall, not the
    # node's death. It must survive with a refreshed window.
    asyncio.run(gcs._health_tick(stall=25.0))
    assert deaths == []
    assert time.time() - gcs.nodes[nid].last_heartbeat < 5.0
    # Ticks back on time: staleness is real again; death is detected.
    gcs.nodes[nid].last_heartbeat = time.time() - 20.0
    asyncio.run(gcs._health_tick(stall=0.0))
    assert deaths == [nid]


# ---------------------------------------------------------------------------
# The launch storm itself
# ---------------------------------------------------------------------------

@pytest.mark.timeout(170)
def test_launch_storm_100_actors(jax_cpu):
    """100 actors across a 3-node fake cluster: every one comes up,
    at least part of the storm is dispatched in registration replies
    (no register→idle→re-offer→instantiate round trip), and the ALIVE
    publishes coalesce into far fewer pubsub frames than actors."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    # The storm runs ~100 worker processes on whatever cores CI gives us;
    # the shared test event loop WILL lag. Health detection is not what
    # this test measures (see test_health_tick_self_stall_guard), so give
    # heartbeats a storm-sized window instead of the 5s production one.
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2},
                      system_config={"node_death_timeout_s": 60.0})
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    try:
        cluster.connect()
        cluster.wait_for_nodes()

        @ray_tpu.remote(num_cpus=0.01)
        class Tiny:
            def ready(self):
                return 1

        # Announce the storm (the serve/gang paths send the same hint).
        from ray_tpu._private import worker_api
        worker_api.prestart_workers(40)
        frames_before = cluster.gcs.alive_frames_published
        t0 = time.time()
        actors = [Tiny.remote() for _ in range(100)]
        ray_tpu.get([a.ready.remote() for a in actors], timeout=150)
        ready_s = time.time() - t0
        # Deterministic assertions (throughput is bench territory):
        alive = [a for a in cluster.gcs.actors.values()
                 if a.state == "ALIVE"]
        assert len(alive) >= 100
        frames = cluster.gcs.alive_frames_published - frames_before
        assert frames < 100, (
            f"{frames} ALIVE frames for 100 actors: publishes did not "
            f"coalesce")
        dispatches = sum(r.register_reply_dispatches
                        for r in cluster.raylets)
        assert dispatches > 0, (
            "no create was dispatched in a registration reply")
        # Storm spread: no single node hosted the whole batch.
        per_node = [sum(1 for a in alive if a.node_id == r.node_id)
                    for r in cluster.raylets]
        assert max(per_node) < 100, per_node
        # time-to-READY, recorded for eyeballing regressions in CI logs.
        print(f"\nlaunch storm: 100 actors READY in {ready_s:.2f}s "
              f"({100 / ready_s:.0f}/s), {frames} ALIVE frames, "
              f"{dispatches} register-reply dispatches, "
              f"spread={per_node}")
    finally:
        cluster.shutdown()


@pytest.mark.timeout(120)
def test_prestart_hint_fills_pool(jax_cpu):
    """rpc_prestart_workers spawns the shortfall immediately and pins the
    pool floor for the hint TTL."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    try:
        cluster.connect()
        raylet = cluster.raylets[0]
        fut = asyncio.run_coroutine_threadsafe(
            raylet.rpc_prestart_workers(None, {"count": 6}),
            cluster._loop)
        spawned = fut.result(timeout=10)
        assert spawned >= 1
        deadline = time.time() + 60
        while time.time() < deadline and len(raylet._pools) < 6:
            time.sleep(0.2)
        assert len(raylet._pools) >= 6
        assert raylet.prestart_hints_received >= 6
        # The hint pins the reap floor for its TTL.
        assert raylet._pools.floor("", fresh_floor=2) >= 6
        # The warm path engages: a create after the hint takes a pooled
        # worker, it does not spawn one.
        hits = raylet._pools.hits

        @ray_tpu.remote(num_cpus=0.01)
        class Tiny:
            def ready(self):
                return 1

        assert ray_tpu.get(Tiny.remote().ready.remote(), timeout=60) == 1
        assert raylet._pools.hits > hits
    finally:
        cluster.shutdown()


@pytest.mark.timeout(170)
def test_serve_scaleup_sends_prestart_hints(jax_cpu):
    """The serve controller warms the worker pools before starting
    replicas: every deficit path (initial deploy, upscale) funnels
    through the reconcile loop's prestart hint, so replica time-to-READY
    is not bounded by cold worker boots (recorded for eyeballing)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    cluster.add_node(num_cpus=2)
    try:
        cluster.connect()
        cluster.wait_for_nodes()
        serve.start()
        hints_before = sum(r.prestart_hints_received
                           for r in cluster.raylets)

        @serve.deployment(num_replicas=3,
                          ray_actor_options={"num_cpus": 0.01})
        def echo(x):
            return x

        t0 = time.time()
        h = serve.run(echo.bind(), name="storm_dep",
                      route_prefix="/storm_dep")
        h.remote(1).result(timeout=90)
        ready_s = time.time() - t0
        hints = sum(r.prestart_hints_received
                    for r in cluster.raylets) - hints_before
        assert hints >= 3, (
            f"serve deploy sent {hints} prestart-hint workers; the "
            f"3-replica deficit should have warmed >= 3")
        print(f"\nserve scale-up: 3 replicas serving in {ready_s:.2f}s "
              f"({hints} prestart-hinted workers)")
        serve.shutdown()
    finally:
        cluster.shutdown()


@pytest.mark.timeout(170)
def test_gang_drain_sends_prestart_hints(jax_cpu):
    """PR 4 gang recovery warms the surviving domains' pools before
    migrating the gang's actors, and the replacements come up on the
    survivor (time-to-READY recorded)."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2})
    n1 = cluster.add_node(num_cpus=2, slice_id="sliceA")
    n2 = cluster.add_node(num_cpus=2, slice_id="sliceA")
    survivor = cluster.add_node(num_cpus=2, slice_id="sliceB")
    try:
        cluster.connect()
        cluster.wait_for_nodes()

        @ray_tpu.remote(num_cpus=0.01, max_restarts=-1)
        class Member:
            def ready(self):
                return 1

        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy)
        # Soft affinity: the members START on the doomed slice but may be
        # re-placed anywhere once it drains (a hard pin to a dead node
        # could never recover).
        members = [
            Member.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=n.node_id, soft=True)).remote()
            for n in (n1, n2) for _ in range(2)]
        ray_tpu.get([m.ready.remote() for m in members], timeout=120)
        gang_ids = {n1.node_id, n2.node_id}
        hints_before = survivor.prestart_hints_received
        t0 = time.time()
        cluster.drain_node(n1, deadline_s=8.0, grace_s=0.1, wait=False)
        deadline = time.time() + 60
        while time.time() < deadline:
            infos = list(cluster.gcs.actors.values())
            if infos and all(a.state == "ALIVE"
                             and a.node_id not in gang_ids
                             for a in infos):
                break
            time.sleep(0.1)
        ready_s = time.time() - t0
        infos = list(cluster.gcs.actors.values())
        assert all(a.state == "ALIVE" for a in infos)
        assert all(a.node_id not in gang_ids for a in infos), (
            "gang members were not migrated off the drained slice")
        assert survivor.prestart_hints_received > hints_before, (
            "gang drain did not warm the surviving domain's pool")
        print(f"\ngang failover: {len(infos)} actors READY on the "
              f"replacement domain in {ready_s:.2f}s")
    finally:
        cluster.shutdown()
