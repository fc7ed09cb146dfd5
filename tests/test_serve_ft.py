"""Serve under fire: queue-preserving replica failover, admission
control (bounded queues + shedding), and end-to-end request deadlines.

Reference strategy: python/ray/serve/tests (replica failure, backpressure
and request-timeout suites). Deterministic single-node tests here; the
slice-gang failover tests and chaos soak live in test_serve_gang.py.
"""

import asyncio
import collections
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.exceptions import (BackPressureError, ReplicaDiedError,
                                      ReplicaDrainingError,
                                      RequestTimeoutError)

# This file's proxy has a port of its own (as tests/test_serve_trace.py's
# has): test_serve.py keeps the default 8000, and two files' proxies on one
# port, in two xdist workers at once, lost the bind (D2's "load-sensitive"
# failures of PRs 40 and 55).
PORT = 8153


@pytest.fixture(scope="module")
def ray_mod():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture
def serve_app(ray_mod):
    yield serve
    try:
        for app in list(serve.status().keys()):
            serve.delete(app)
    except Exception:
        pass


def _replica_handles(app: str, dep: str):
    from ray_tpu.serve.api import _get_controller
    ctrl = _get_controller()
    _v, reps = ray_tpu.get(ctrl.get_replicas.remote(app, dep), timeout=30)
    return reps


def _wait_ready(app: str, dep: str, n: int, timeout: float = 90):
    from ray_tpu.serve.api import _get_controller
    ctrl = _get_controller()
    deadline = time.time() + timeout
    while time.time() < deadline:
        st = ray_tpu.get(ctrl.status.remote(), timeout=30)
        if st.get(app, {}).get(dep, {}).get("ready", 0) >= n:
            return True
        time.sleep(0.2)
    return False


# ---------------------------------------------------------------------------
# Queue-preserving failover
# ---------------------------------------------------------------------------

def test_replica_death_replayable_requests_complete(serve_app):
    """Kill a replica with dispatched-but-unfinished requests: with
    request_replay=True every retained payload re-routes to the healthy
    replica and completes — zero ReplicaDiedError for replayable
    traffic (the tentpole acceptance criterion)."""
    @serve.deployment(num_replicas=2, request_replay=True)
    class Echo:
        async def __call__(self, i):
            await asyncio.sleep(0.3)
            return i

    h = serve.run(Echo.bind(), name="ft1", route_prefix="/ft1")
    assert _wait_ready("ft1", "Echo", 2)
    # Warm the router so requests actually spread across both replicas.
    assert h.remote(-1).result(timeout=60) == -1

    resps = [h.remote(i) for i in range(8)]
    time.sleep(0.1)  # let dispatches land
    reps = _replica_handles("ft1", "Echo")
    assert len(reps) == 2
    ray_tpu.kill(reps[0])

    results = [r.result(timeout=90) for r in resps]
    assert sorted(results) == list(range(8))


def test_replica_death_not_replayable_fails_fast(serve_app):
    """Without request_replay the same failure surfaces as a typed
    ReplicaDiedError quickly — no hang, no silent re-execution of a
    possibly non-idempotent handler."""
    @serve.deployment(num_replicas=1)
    class Slow:
        async def __call__(self):
            await asyncio.sleep(30)
            return "done"

    h = serve.run(Slow.bind(), name="ft2", route_prefix="/ft2")
    assert _wait_ready("ft2", "Slow", 1)
    resp = h.remote()
    time.sleep(0.3)
    ray_tpu.kill(_replica_handles("ft2", "Slow")[0])
    t0 = time.time()
    with pytest.raises(ReplicaDiedError):
        resp.result(timeout=60)
    assert time.time() - t0 < 20, "fail-fast took too long"


def test_replica_replay_dedupes_by_request_id():
    """Replica-side half of exactly-once: a replayed request whose
    original completed on this replica returns the CACHED result
    instead of executing twice."""
    from ray_tpu.serve.replica import ReplicaActor

    async def run():
        calls = []

        async def handler(x):
            calls.append(x)
            return x * 2

        rep = ReplicaActor.__new__(ReplicaActor)
        rep._callable = handler
        rep._is_function = True
        rep._init_limits({"deployment": "d", "max_ongoing": 4,
                          "request_replay": True})
        out1 = await rep.handle_request("__call__", "", (21,), {},
                                        request_id="r1")
        out2 = await rep.handle_request("__call__", "", (21,), {},
                                        request_id="r1")   # replay
        assert out1 == out2 == 42
        assert calls == [21], "replayed request executed twice"

    asyncio.run(run())


# ---------------------------------------------------------------------------
# Admission control + load shedding
# ---------------------------------------------------------------------------

def test_overload_sheds_with_typed_backpressure(serve_app):
    """Bounded queue + drop-newest: past max_ongoing + max_queued the
    replica sheds with a typed BackPressureError, and the deployment
    stays live for later traffic."""
    @serve.deployment(num_replicas=1, max_ongoing_requests=1,
                      max_queued_requests=1)
    class Busy:
        async def __call__(self, i):
            await asyncio.sleep(0.6)
            return i

    h = serve.run(Busy.bind(), name="ft3", route_prefix="/ft3")
    assert _wait_ready("ft3", "Busy", 1)
    assert h.remote(0).result(timeout=60) == 0

    resps = [h.remote(i) for i in range(6)]
    ok, shed = 0, 0
    for r in resps:
        try:
            r.result(timeout=60)
            ok += 1
        except BackPressureError:
            shed += 1
    assert ok + shed == 6
    assert shed >= 1, "overload never shed"
    assert ok >= 2, "queued requests should still complete"
    # Deployment stays live after shedding.
    assert h.remote(99).result(timeout=60) == 99


def test_shed_surfaces_as_http_503(serve_app):
    """The HTTP proxy maps BackPressureError to a 503 with a JSON body
    carrying the gRPC-style RESOURCE_EXHAUSTED code."""
    @serve.deployment(num_replicas=1, max_ongoing_requests=1,
                      max_queued_requests=0)
    class Busy:
        # Async handler: admission control observes concurrency only
        # when handlers yield the loop (a sync handler serializes the
        # whole replica, so its queue never builds).
        async def __call__(self, request):
            await asyncio.sleep(1.2)
            return "ok"

    serve.start(http_options=serve.HTTPOptions(port=PORT))
    serve.run(Busy.bind(), name="ft4", route_prefix="/shed")
    time.sleep(1.0)

    # one (code, body) pair a request: four threads appending to two lists
    # interleave, and a request that fails outright has no body at all
    answers = []

    def hit():
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{PORT}/shed", timeout=30) as r:
                answers.append((r.status, r.read()))
        except urllib.error.HTTPError as e:
            answers.append((e.code, e.read()))
        except Exception as e:  # noqa: BLE001
            answers.append((repr(e), None))

    deadline = time.time() + 30
    while time.time() < deadline and 503 not in dict(answers):
        answers.clear()
        threads = [threading.Thread(target=hit) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(45)
    codes = [code for code, _body in answers]
    assert 503 in codes, codes
    assert 200 in codes, codes   # the admitted request succeeded
    shed_body = json.loads(dict(answers)[503])
    assert shed_body["error"] == "BackPressureError"
    assert shed_body["code"] == "RESOURCE_EXHAUSTED"


# ---------------------------------------------------------------------------
# End-to-end deadlines
# ---------------------------------------------------------------------------

def test_request_deadlines_cancel_on_replica(serve_app):
    """End-to-end deadlines, both entry points on one deployment:
    (a) handle.options(timeout_s=...) propagates an absolute deadline to
    the replica — the caller gets a typed RequestTimeoutError fast and
    the in-flight handler is CANCELLED replica-side (ongoing drops to
    zero instead of burning 30s of fake TPU time); (b) the deployment's
    request_timeout_s default applies to calls with no per-call options
    (propagated through routing metadata)."""
    @serve.deployment(num_replicas=1, request_timeout_s=0.5)
    class Slow:
        async def __call__(self):
            await asyncio.sleep(30)
            return "late"

    h = serve.run(Slow.bind(), name="ft5", route_prefix="/ft5")
    assert _wait_ready("ft5", "Slow", 1)
    t0 = time.time()
    with pytest.raises(RequestTimeoutError):
        h.options(timeout_s=0.4).remote().result(timeout=60)
    assert time.time() - t0 < 10
    # The handler was cancelled replica-side.
    rep = _replica_handles("ft5", "Slow")[0]
    deadline = time.time() + 10
    m = None
    while time.time() < deadline:
        m = ray_tpu.get(rep.get_metrics.remote(), timeout=30)
        if m["ongoing"] == 0:
            break
        time.sleep(0.2)
    assert m["ongoing"] == 0, m
    assert m["timeouts"] >= 1, m
    # (b) config-default deadline, no per-call options.
    with pytest.raises(RequestTimeoutError):
        h.remote().result(timeout=60)


# ---------------------------------------------------------------------------
# Graceful drain: rolling updates hand queued work back
# ---------------------------------------------------------------------------

def test_rolling_update_hands_queued_work_back(serve_app):
    """Queued requests on the retiring replica are handed back to the
    router during a rolling update and complete on the replacement —
    zero losses, even with request_replay=False (handed-back work never
    started executing, so it is always replay-safe)."""
    def make(version, tag):
        @serve.deployment(name="Roll", version=version, num_replicas=1,
                          max_ongoing_requests=1)
        class Roll:
            async def __call__(self, i):
                await asyncio.sleep(0.3)
                return tag

        return Roll

    serve.run(make("1", "v1").bind(), name="ft7", route_prefix="/ft7")
    assert _wait_ready("ft7", "Roll", 1)
    h = serve.get_app_handle("ft7")
    assert h.remote(0).result(timeout=60) == "v1"

    # Saturate: 1 executing + 4 queued on the v1 replica.
    resps = [h.remote(i) for i in range(5)]
    # Redeploy v2 mid-flight: replace-then-drain.
    serve.run(make("2", "v2").bind(), name="ft7", route_prefix="/ft7")

    results = [r.result(timeout=120) for r in resps]
    assert len(results) == 5
    assert set(results) <= {"v1", "v2"}, results

    # Eventually only v2 serves.
    deadline = time.time() + 60
    while time.time() < deadline:
        if h.remote(0).result(timeout=60) == "v2":
            break
        time.sleep(0.2)
    assert h.remote(0).result(timeout=60) == "v2"


def test_replica_drain_bounces_queued_admits():
    """Unit: drain() flips the gate so queued (never-started) requests
    raise ReplicaDrainingError immediately — the router's signal to
    re-route them — while the in-flight request finishes."""
    from ray_tpu.serve.replica import ReplicaActor

    async def run():
        gate = asyncio.Event()

        async def handler(x):
            await gate.wait()
            return x

        rep = ReplicaActor.__new__(ReplicaActor)
        rep._callable = handler
        rep._is_function = True
        rep._init_limits({"deployment": "d", "max_ongoing": 1,
                          "max_queued": 4})
        t1 = asyncio.ensure_future(
            rep.handle_request("__call__", "", (1,), {}))
        await asyncio.sleep(0.05)          # t1 executing
        t2 = asyncio.ensure_future(
            rep.handle_request("__call__", "", (2,), {}))
        await asyncio.sleep(0.05)          # t2 queued
        drain = asyncio.ensure_future(rep.drain(5.0))
        with pytest.raises(ReplicaDrainingError):
            await t2                       # handed back, never executed
        with pytest.raises(ReplicaDrainingError):
            # new arrivals bounce instantly while draining
            await rep.handle_request("__call__", "", (3,), {})
        gate.set()
        assert await t1 == 1               # in-flight completed
        assert await drain is True

    asyncio.run(run())


def test_replica_admission_shed_unit():
    """Unit: past max_ongoing + max_queued the replica sheds with
    BackPressureError and counts it."""
    from ray_tpu.serve.replica import ReplicaActor

    async def run():
        gate = asyncio.Event()

        async def handler(x):
            await gate.wait()
            return x

        rep = ReplicaActor.__new__(ReplicaActor)
        rep._callable = handler
        rep._is_function = True
        rep._init_limits({"deployment": "d", "max_ongoing": 1,
                          "max_queued": 1})
        t1 = asyncio.ensure_future(
            rep.handle_request("__call__", "", (1,), {}))
        await asyncio.sleep(0.05)
        t2 = asyncio.ensure_future(
            rep.handle_request("__call__", "", (2,), {}))
        await asyncio.sleep(0.05)
        with pytest.raises(BackPressureError):
            await rep.handle_request("__call__", "", (3,), {})
        assert rep.get_metrics()["shed"] == 1
        gate.set()
        assert await t1 == 1
        assert await t2 == 2

    asyncio.run(run())


# ---------------------------------------------------------------------------
# Mid-stream replay cursor
# ---------------------------------------------------------------------------

def test_stream_replays_mid_stream_with_cursor(serve_app):
    """Replica dies AFTER items were delivered: a replayable deployment
    re-routes the stream and the handle's item-offset cursor fast-
    forwards past the already-delivered items — the caller sees the full
    sequence exactly once, resumed from where it broke."""
    @serve.deployment(num_replicas=1, request_replay=True)
    class Gen:
        async def __call__(self, n):
            import os
            for i in range(n):
                await asyncio.sleep(0.25)
                yield {"i": i, "pid": os.getpid()}

    h = serve.run(Gen.bind(), name="ftc1", route_prefix="/ftc1")
    assert _wait_ready("ftc1", "Gen", 1)

    gen = h.options(stream=True).remote(6)
    items = [next(gen), next(gen)]   # two items delivered, then murder
    ray_tpu.kill(_replica_handles("ftc1", "Gen")[0])
    items.extend(gen)
    assert [it["i"] for it in items] == list(range(6)), items
    # The tail really came from the REPLACEMENT replica (a replay, not
    # a survivor): pid changed after the kill.
    assert items[-1]["pid"] != items[0]["pid"]


def test_stream_mid_stream_death_not_replayable_fails(serve_app):
    """Without request_replay a mid-stream death keeps failing fast with
    the typed error (never silently re-executes the generator)."""
    @serve.deployment(num_replicas=1)
    class Gen:
        async def __call__(self, n):
            for i in range(n):
                await asyncio.sleep(0.25)
                yield i

    h = serve.run(Gen.bind(), name="ftc2", route_prefix="/ftc2")
    assert _wait_ready("ftc2", "Gen", 1)

    gen = h.options(stream=True).remote(6)
    assert next(gen) == 0
    ray_tpu.kill(_replica_handles("ftc2", "Gen")[0])
    with pytest.raises(ReplicaDiedError):
        list(gen)


def test_stream_cursor_short_replay_raises():
    """Unit: a replayed stream that ends BEFORE the cursor (handler is
    not deterministic) surfaces a typed error instead of a divergent
    tail."""
    from ray_tpu.serve.handle import DeploymentResponseGenerator

    class _FakeRef:
        def __init__(self, v):
            self.v = v

    real_get = ray_tpu.get

    def fake_get(ref, *a, **k):
        if isinstance(ref, _FakeRef):
            return ref.v
        return real_get(ref, *a, **k)

    from ray_tpu import exceptions as exc

    first = iter([_FakeRef(0), _FakeRef(1)])

    class DieAfter:
        def __iter__(self):
            return self

        def __next__(self):
            try:
                return next(first)
            except StopIteration:
                raise exc.ActorDiedError("replica") from None

    short = iter([_FakeRef(0)])  # replay yields 1 item < cursor 2

    gen = DeploymentResponseGenerator(
        DieAfter(), recover=lambda err: short, deployment="d")
    import unittest.mock as mock
    with mock.patch.object(ray_tpu, "get", fake_get):
        assert next(gen) == 0
        assert next(gen) == 1
        with pytest.raises(ReplicaDiedError, match="not deterministic"):
            next(gen)


# ---------------------------------------------------------------------------
# Proxy failure surfaces
# ---------------------------------------------------------------------------

def test_healthz_stays_ready_during_rolling_update(serve_app):
    """/-/healthz readiness holds through a rolling update: replicas
    swap replace-then-drain and the controller never goes away."""
    def make(version):
        @serve.deployment(name="H", version=version)
        def handler(request):
            return version

        return handler

    serve.start(http_options=serve.HTTPOptions(port=PORT))
    serve.run(make("1").bind(), name="ft8", route_prefix="/ft8")
    time.sleep(1.0)

    def healthz():
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{PORT}/-/healthz", timeout=5) as r:
                return r.status
        except urllib.error.HTTPError as e:
            return e.code

    assert healthz() == 200
    done = threading.Event()

    def redeploy():
        try:
            serve.run(make("2").bind(), name="ft8", route_prefix="/ft8")
        finally:
            done.set()

    t = threading.Thread(target=redeploy)
    t.start()
    codes = []
    while not done.is_set() or len(codes) < 5:
        codes.append(healthz())
        time.sleep(0.1)
        if len(codes) > 100:
            break
    t.join(60)
    assert set(codes) == {200}, collections.Counter(codes)


def test_websocket_closes_on_replica_death(serve_app):
    """A websocket whose replica dies mid-session gets a proper CLOSE
    frame (1012 Service Restart) instead of hanging until TCP gives
    up."""
    import base64
    import os as _os

    from ray_tpu.serve import websocket as wsmod

    @serve.deployment(num_replicas=1)
    class Chat:
        async def __call__(self, request):
            yield "hello"
            while True:
                msg = await request.ws.receive(timeout=60)
                if msg is None:
                    return
                yield f"echo:{msg}"

    serve.start(http_options=serve.HTTPOptions(port=PORT))
    serve.run(Chat.bind(), name="ft9", route_prefix="/ftchat")
    time.sleep(1.0)

    async def client():
        deadline = time.time() + 30
        while True:
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", PORT)
                key = base64.b64encode(_os.urandom(16)).decode()
                writer.write(
                    f"GET /ftchat HTTP/1.1\r\nHost: x\r\n"
                    f"Upgrade: websocket\r\nConnection: Upgrade\r\n"
                    f"Sec-WebSocket-Key: {key}\r\n"
                    f"Sec-WebSocket-Version: 13\r\n\r\n".encode())
                await writer.drain()
                status = await reader.readline()
                if b"101" not in status:
                    writer.close()
                    await asyncio.sleep(0.5)
                    continue
                while (await reader.readline()) not in (b"\r\n", b""):
                    pass
                op, payload = await wsmod.read_frame(reader)
                assert (op, payload.decode()) == (wsmod.OP_TEXT, "hello")
                # Replica dies mid-session.
                ray_tpu.kill(_replica_handles("ft9", "Chat")[0])
                op, payload = await asyncio.wait_for(
                    wsmod.read_frame(reader), 30)
                writer.close()
                return op, payload
            except (ConnectionError, asyncio.IncompleteReadError, OSError):
                if time.time() > deadline:
                    raise
                await asyncio.sleep(0.5)

    op, payload = asyncio.run(asyncio.wait_for(client(), 90))
    assert op == wsmod.OP_CLOSE
    assert int.from_bytes(payload[:2], "big") == 1012
