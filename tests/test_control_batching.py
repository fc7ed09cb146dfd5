"""Control-plane fan-in batching: correctness of the BATCH envelope,
lease multi-grant, and the batched submission paths.

The transport packs every frame coalesced within one loop tick into a
single BATCH envelope (rpc.py); the raylet grants multiple worker leases
per request (raylet.py); submissions/replies ride batch frames
(core_worker.py). These tests pin the load-bearing invariants: in-order
dispatch, strictly fewer writes than frames under concurrency, legacy
interop, and correctness under injected RPC delays.
"""

import asyncio
import os
import subprocess
import sys

import pytest

from ray_tpu._private import rpc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


class TestBatchEnvelope:
    def test_in_order_dispatch_fewer_writes_than_frames(self):
        """N same-tick requests arrive in submission order and ride
        strictly fewer socket writes than frames (the frames-per-write
        counter is the batching health signal)."""
        async def main():
            got = []
            srv = rpc.RpcServer("t")

            async def echo(conn, payload):
                got.append(payload)
                return payload

            srv.register("echo", echo)
            port = await srv.start()
            conn = await rpc.connect(f"127.0.0.1:{port}")
            res = await asyncio.gather(
                *[conn.request("echo", i) for i in range(64)])
            assert res == list(got) == list(range(64))
            # Client: 64 request frames coalesced into far fewer writes.
            assert conn.frames_sent >= 64
            assert conn.writes < conn.frames_sent
            assert conn.batched_frames > 0
            # Server side replies batch too.
            (sconn,) = srv.connections
            assert sconn.writes < sconn.frames_sent
            await conn.close()
            await srv.stop()

        run(main())

    def test_module_counters_and_metrics_export(self):
        before = rpc.transport_stats()

        async def main():
            srv = rpc.RpcServer("t")
            srv.register("nop", lambda conn, p: _async_none())
            port = await srv.start()
            conn = await rpc.connect(f"127.0.0.1:{port}")
            await asyncio.gather(*[conn.request("nop") for _ in range(16)])
            await conn.close()
            await srv.stop()

        run(main())
        after = rpc.transport_stats()
        assert after["frames"] - before["frames"] >= 16
        assert after["writes"] > before["writes"]
        rpc.export_transport_metrics()
        from ray_tpu.util import metrics
        names = {m["name"] for m in metrics.snapshot()}
        assert "ray_tpu_rpc_frames_total" in names
        assert "ray_tpu_rpc_writes_total" in names

    def test_legacy_peer_interop(self):
        """A peer with batching disabled (legacy per-frame envelopes)
        interoperates with a batching server in both directions."""
        async def main():
            got = []
            srv = rpc.RpcServer("t")

            async def echo(conn, payload):
                got.append(payload)
                return payload

            srv.register("echo", echo)
            port = await srv.start()
            conn = await rpc.connect(f"127.0.0.1:{port}")
            conn.batching = False  # legacy sender
            res = await asyncio.gather(
                *[conn.request("echo", i) for i in range(32)])
            assert res == got == list(range(32))
            # Legacy sender: one write per frame (after the tick's first).
            assert conn.batched_frames == 0
            # The server still batches replies; the legacy client decodes
            # them (decode always understands both framings).
            (sconn,) = srv.connections
            assert sconn.frames_sent >= 32
            # And the reverse: batching client against legacy server side.
            sconn.batching = False
            res = await asyncio.gather(
                *[conn.request("echo", i) for i in range(32)])
            assert res == list(range(32))
            await conn.close()
            await srv.stop()

        run(main())

    def test_unpicklable_frame_degrades_not_poisons(self):
        """One unpicklable reply in a batch fails only its own request;
        batch-mates complete."""
        async def main():
            srv = rpc.RpcServer("t")

            async def handler(conn, payload):
                if payload == "bad":
                    return lambda: None  # unpicklable
                return payload

            srv.register("h", handler)
            port = await srv.start()
            conn = await rpc.connect(f"127.0.0.1:{port}")
            futs = [conn.request("h", p) for p in ("a", "bad", "b")]
            res = await asyncio.gather(*futs, return_exceptions=True)
            assert res[0] == "a" and res[2] == "b"
            assert isinstance(res[1], Exception)
            await conn.close()
            await srv.stop()

        run(main())

    def test_push_nowait_coalesces(self):
        """Pubsub-style fan-out: many push_nowait frames in one tick ride
        one write and arrive in order."""
        async def main():
            srv = rpc.RpcServer("t")
            port = await srv.start()
            got = []
            done = asyncio.Event()

            def on_push(method, payload):
                got.append(payload)
                if len(got) == 50:
                    done.set()

            conn = await rpc.connect(f"127.0.0.1:{port}", on_push)
            await asyncio.sleep(0.05)
            (sconn,) = srv.connections
            w0 = sconn.writes
            for i in range(50):
                sconn.push_nowait("pub", i)
            await asyncio.wait_for(done.wait(), 10)
            assert got == list(range(50))
            assert sconn.writes - w0 <= 2  # first frame + one batch
            await conn.close()
            await srv.stop()

        run(main())


async def _async_none():
    return None


class TestLeaseMultiGrant:
    def _mk_raylet(self, tmp_path, cpus=4.0):
        from ray_tpu._private.config import Config
        from ray_tpu._private.raylet import Raylet, WorkerHandle
        from ray_tpu._private.ids import WorkerID
        cfg = Config.load({"object_store_memory": 1 << 20})
        raylet = Raylet(cfg, gcs_address="", session_dir=str(tmp_path),
                        resources={"CPU": cpus},
                        object_store_memory=1 << 20)
        raylet._stopped = True  # suppress background resource reporting
        for i in range(int(cpus)):
            h = WorkerHandle(worker_id=WorkerID.from_random(), pid=1000 + i,
                             address=f"127.0.0.1:{20000+i}", registered=True)
            raylet.workers[h.worker_id] = h
            raylet._pools.put(h)
        return raylet

    def test_multi_grant_one_round_trip(self, tmp_path):
        """A count=3 lease request gets up to 3 grants in ONE reply."""
        from ray_tpu._private.common import TaskSpec
        from ray_tpu._private.ids import JobID, TaskID

        async def main():
            raylet = self._mk_raylet(tmp_path, cpus=4.0)
            try:
                spec = TaskSpec(task_id=TaskID.of(JobID.from_int(1)),
                                job_id=JobID.from_int(1),
                                resources={"CPU": 1.0})
                reply = await raylet.rpc_request_worker_lease(
                    None, {"spec": spec, "count": 3})
                assert len(reply["grants"]) == 3
                assert reply["granted"] == reply["grants"][0]
                assert raylet.pool.available["CPU"] == 1.0
                # Legacy request shape (no count) still grants one.
                reply = await raylet.rpc_request_worker_lease(
                    None, {"spec": spec})
                assert len(reply["grants"]) == 1
            finally:
                raylet.store.destroy()

        run(main())

    def test_multi_grant_fair_share_across_clients(self, tmp_path):
        """Two greedy requests pending when workers appear split the idle
        pool instead of the first soaking it all."""
        from ray_tpu._private.common import TaskSpec
        from ray_tpu._private.ids import JobID, TaskID, WorkerID
        from ray_tpu._private.raylet import WorkerHandle

        async def main():
            raylet = self._mk_raylet(tmp_path, cpus=4.0)
            # Start with NO workers so both requests queue.
            raylet._pools.pools.clear()
            raylet.workers.clear()
            try:
                def mk_spec():
                    return TaskSpec(task_id=TaskID.of(JobID.from_int(1)),
                                    job_id=JobID.from_int(1),
                                    resources={"CPU": 1.0})
                fut_a = asyncio.ensure_future(
                    raylet.rpc_request_worker_lease(
                        None, {"spec": mk_spec(), "count": 4}))
                fut_b = asyncio.ensure_future(
                    raylet.rpc_request_worker_lease(
                        None, {"spec": mk_spec(), "count": 4}))
                await asyncio.sleep(0.05)  # both queued
                for i in range(4):
                    h = WorkerHandle(worker_id=WorkerID.from_random(),
                                     pid=2000 + i,
                                     address=f"127.0.0.1:{21000+i}",
                                     registered=True)
                    raylet.workers[h.worker_id] = h
                    raylet._pools.put(h)
                raylet._try_dispatch()
                a, b = await asyncio.gather(fut_a, fut_b)
                assert len(a["grants"]) + len(b["grants"]) == 4
                assert len(a["grants"]) >= 1 and len(b["grants"]) >= 1
            finally:
                raylet.store.destroy()

        run(main())

    def test_grant_capped_by_resources(self, tmp_path):
        """count is a hint: grants never exceed what the pool can hold."""
        from ray_tpu._private.common import TaskSpec
        from ray_tpu._private.ids import JobID, TaskID

        async def main():
            raylet = self._mk_raylet(tmp_path, cpus=2.0)
            try:
                spec = TaskSpec(task_id=TaskID.of(JobID.from_int(1)),
                                job_id=JobID.from_int(1),
                                resources={"CPU": 1.0})
                reply = await raylet.rpc_request_worker_lease(
                    None, {"spec": spec, "count": 10})
                assert len(reply["grants"]) == 2
                assert raylet.pool.available["CPU"] == 0.0
            finally:
                raylet.store.destroy()

        run(main())


class TestSpecWireFormat:
    def test_task_spec_roundtrip(self):
        """The compact wire encoding is lossless for a fully-populated
        spec (every field the control plane reads survives pickling)."""
        import pickle
        from ray_tpu._private.common import (SchedulingStrategy, TaskArg,
                                             TaskSpec)
        from ray_tpu._private.ids import (ActorID, JobID, NodeID, ObjectID,
                                          PlacementGroupID, TaskID, WorkerID)
        job = JobID.from_int(7)
        aid = ActorID.of(job)
        tid = TaskID.for_actor_task(job, aid, 5, epoch=2)
        oid = ObjectID.for_task_return(tid, 0)
        spec = TaskSpec(
            task_id=tid, job_id=job, name="m", function_id="fid",
            args=[TaskArg(0, b"inline"), TaskArg(1, object_id=oid,
                                                 owner_address="h:1")],
            num_returns=2, resources={"CPU": 0.5, "TPU": 1.0},
            scheduling=SchedulingStrategy(
                kind="PLACEMENT_GROUP",
                placement_group_id=PlacementGroupID.of(job), bundle_index=3,
                labels_hard={"zone": ["a", "b"]}),
            max_retries=4, retry_exceptions=True, owner_address="h:2",
            owner_worker_id=WorkerID.from_random(), actor_id=aid,
            method_name="m", seq_no=5, max_restarts=2, max_task_retries=1,
            max_concurrency=8, is_async_actor=True, actor_name="n",
            namespace="ns", runtime_env={"env_vars": {"A": "1"}},
            is_generator=True, kwarg_names=("k",), lifetime="detached",
            concurrency_groups={"io": 2}, concurrency_group="io",
            execute_out_of_order=True, method_options={"m": {}},
            trace_ctx=("t", "s"),
        )
        s2 = pickle.loads(pickle.dumps(spec, protocol=5))
        for f in ("task_id", "job_id", "name", "function_id", "num_returns",
                  "resources", "max_retries", "retry_exceptions",
                  "owner_address", "owner_worker_id", "actor_id",
                  "method_name", "seq_no", "max_restarts",
                  "max_task_retries", "max_concurrency", "is_async_actor",
                  "actor_name", "namespace", "runtime_env", "is_generator",
                  "kwarg_names", "lifetime", "concurrency_groups",
                  "concurrency_group", "execute_out_of_order",
                  "method_options", "trace_ctx"):
            assert getattr(s2, f) == getattr(spec, f), f
        assert s2.scheduling.kind == "PLACEMENT_GROUP"
        assert s2.scheduling.placement_group_id == \
            spec.scheduling.placement_group_id
        assert s2.scheduling.bundle_index == 3
        assert s2.scheduling.labels_hard == {"zone": ["a", "b"]}
        assert [(a.kind, a.data, a.object_id, a.owner_address)
                for a in s2.args] == \
            [(a.kind, a.data, a.object_id, a.owner_address)
             for a in spec.args]
        assert s2.scheduling_class() == spec.scheduling_class()

    def test_default_scheduling_compact(self):
        import pickle
        from ray_tpu._private.common import TaskSpec
        from ray_tpu._private.ids import JobID, TaskID
        job = JobID.from_int(1)
        spec = TaskSpec(task_id=TaskID.of(job), job_id=job)
        s2 = pickle.loads(pickle.dumps(spec, protocol=5))
        assert s2.scheduling.kind == "DEFAULT"
        assert s2.scheduling.bundle_index == -1


@pytest.fixture(scope="module")
def ray_batching(jax_cpu):
    import ray_tpu
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


class TestClusterBatching:
    def test_burst_in_order_actor_execution(self, ray_batching):
        """N concurrent submits execute in submission order. (The
        strictly-fewer-writes-than-frames counter assert lives at the
        transport level in TestBatchEnvelope and on the live cluster
        connection below — an actor burst's submissions intentionally
        merge into ONE frame app-side, so its frames/write ratio is
        already ~1 by design.)"""
        ray_tpu = ray_batching

        @ray_tpu.remote
        class Log:
            def __init__(self):
                self.seen = []

            def add(self, i):
                self.seen.append(i)
                return i

            def all(self):
                return self.seen

        a = Log.remote()
        ray_tpu.get([a.add.remote(i) for i in range(200)], timeout=120)
        assert ray_tpu.get(a.all.remote(), timeout=30) == list(range(200))

    def test_cluster_connection_batches_concurrent_requests(self,
                                                           ray_batching):
        """Concurrent requests on a live cluster connection (the driver's
        GCS channel) ride strictly fewer writes than frames."""
        import asyncio as aio
        from ray_tpu._private import worker_api
        core = worker_api.get_core()

        async def burst():
            conn = core.gcs._conn  # the live GCS Connection
            f0, w0 = conn.frames_sent, conn.writes
            await aio.gather(*[
                core.gcs.request("kv_put", {
                    "namespace": "t", "key": b"k%d" % i, "value": b"v"})
                for i in range(64)])
            return conn.frames_sent - f0, conn.writes - w0

        frames, writes = worker_api._call_on_core_loop(core, burst(), 60)
        assert frames >= 64
        assert writes < frames, (frames, writes)

    def test_task_burst_results_in_order(self, ray_batching):
        ray_tpu = ray_batching

        @ray_tpu.remote
        def sq(x):
            return x * x

        assert ray_tpu.get([sq.remote(i) for i in range(300)],
                           timeout=120) == [i * i for i in range(300)]

    def test_dependency_chain_not_deadlocked_by_batching(self, ray_batching):
        """Chained ref-args must never batch with their producer (batch
        replies are all-or-nothing; a same-batch dependency would block
        the executor on its own reply)."""
        ray_tpu = ray_batching

        @ray_tpu.remote
        def inc(x):
            return x + 1

        # Warm the lease so the pump is in batching mode.
        ray_tpu.get([inc.remote(0) for _ in range(64)], timeout=60)
        ref = inc.remote(0)
        for _ in range(8):
            ref = inc.remote(ref)
        assert ray_tpu.get(ref, timeout=60) == 9

    def test_pg_ready_push(self, ray_batching):
        """pg.ready() resolves from the commit push, and wait() works."""
        ray_tpu = ray_batching
        from ray_tpu.util.placement_group import (placement_group,
                                                  remove_placement_group)
        pg = placement_group([{"CPU": 1}], strategy="PACK")
        assert ray_tpu.get(pg.ready(), timeout=30) is True
        assert pg.wait(10) is True
        # ready() on an ALREADY-created pg resolves via the state fetch.
        assert ray_tpu.get(pg.ready(), timeout=30) is True
        remove_placement_group(pg)

    @pytest.mark.timeout(170)
    def test_three_drivers_fan_in_on_one_cluster(self, ray_batching):
        """Two more drivers join by `init(address=...)` and every one of
        the three finishes its own burst of 200 tasks on the one cluster
        while the others run theirs."""
        from ray_tpu._private import worker_api
        ray_tpu = ray_batching
        script = (
            "import os, sys\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "import ray_tpu\n"
            f"ray_tpu.init(address={worker_api._state.gcs_address!r})\n"
            "@ray_tpu.remote\n"
            "def nop():\n"
            "    return None\n"
            "done = ray_tpu.get([nop.remote() for _ in range(200)],"
            " timeout=120)\n"
            "print('DONE', len(done))\n"
            "ray_tpu.shutdown()\n")

        @ray_tpu.remote
        def nop():
            return None

        procs = [subprocess.Popen([sys.executable, "-c", script],
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True)
                 for _ in range(2)]
        try:
            counts = [len(ray_tpu.get([nop.remote() for _ in range(200)],
                                      timeout=120))]
            for p in procs:
                stdout, _ = p.communicate(timeout=150)
                assert p.returncode == 0, stdout[-3000:]
                counts += [int(ln.split()[1]) for ln in stdout.splitlines()
                           if ln.startswith("DONE ")]
        finally:
            for p in procs:
                p.kill()
        assert counts == [200, 200, 200]


class TestDelayInjectionOverBatchedPaths:
    def test_batched_dispatch_order_under_injected_delay(self):
        """RAY_TPU_TESTING_RPC_DELAY_US shuffles handler start times of a
        BATCH's sub-frames; replies still route to the right requests and
        an order-sensitive NOTIFY stream stays ordered relative to its
        barrier request (handlers are scheduled in frame order)."""
        os.environ["RAY_TPU_TESTING_RPC_DELAY_US"] = "*=0:2000"
        rpc._delay_spec = None
        try:
            async def main():
                seen = []
                srv = rpc.RpcServer("t")

                async def echo(conn, payload):
                    return payload

                async def note(conn, payload):
                    seen.append(payload)

                srv.register("echo", echo)
                srv.register("note", note)
                port = await srv.start()
                conn = await rpc.connect(f"127.0.0.1:{port}")
                res = await asyncio.gather(
                    *[conn.request("echo", i) for i in range(100)])
                assert res == list(range(100))
                for i in range(50):
                    await conn.notify("note", i)
                await conn.request("echo", "barrier")
                # Delays reorder EXECUTION, not correctness: every notify
                # was dispatched (scheduled) before the barrier returned.
                for _ in range(100):
                    if len(seen) == 50:
                        break
                    await asyncio.sleep(0.01)
                assert sorted(seen) == list(range(50))
                await conn.close()
                await srv.stop()

            run(main())
        finally:
            del os.environ["RAY_TPU_TESTING_RPC_DELAY_US"]
            rpc._delay_spec = None


class TestClientPoolRedial:
    def test_request_retries_once_after_peer_restart(self):
        """The first pooled request after a peer restart recovers by
        invalidating + re-dialing instead of surfacing ConnectionLost."""
        async def main():
            async def echo(conn, payload):
                return payload

            srv = rpc.RpcServer("t")
            srv.register("echo", echo)
            port = await srv.start()
            pool = rpc.ClientPool()
            addr = f"127.0.0.1:{port}"
            assert await pool.request(addr, "echo", 1) == 1
            await srv.stop()
            # Restart on the same port; the pooled conn is now stale.
            srv2 = rpc.RpcServer("t2")
            srv2.register("echo", echo)
            await srv2.start(port=port)
            await asyncio.sleep(0.05)
            assert await pool.request(addr, "echo", 2) == 2
            await pool.close_all()
            await srv2.stop()

        run(main())
