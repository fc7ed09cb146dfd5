"""ray_tpu.serve tests (reference strategy: python/ray/serve/tests/)."""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def ray_mod():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _cleanup_apps(ray_mod):
    yield
    # Delete all apps between tests but keep the controller alive.
    try:
        for app in list(serve.status().keys()):
            serve.delete(app)
    except Exception:
        pass


def test_function_deployment_and_handle(ray_mod):
    @serve.deployment
    def double(x):
        return x * 2

    handle = serve.run(double.bind(), name="d1", route_prefix="/double")
    assert handle.remote(21).result(timeout=30) == 42


def test_class_deployment_replicas_and_routing(ray_mod):
    @serve.deployment(num_replicas=2)
    class Counter:
        def __init__(self, start):
            self.count = start

        def __call__(self, inc):
            self.count += inc
            return self.count

        def whoami(self):
            # (pid, id): replica workers fork from the same zygote
            # template, so object addresses can COLLIDE across replica
            # processes — id(self) alone no longer distinguishes them.
            import os
            return (os.getpid(), id(self))

    h = serve.run(Counter.bind(100), name="d2", route_prefix="/counter")
    results = [h.remote(1).result(timeout=30) for _ in range(6)]
    assert all(r > 100 for r in results)
    # Two distinct replicas serve requests (power-of-two-choices is
    # probabilistic and the second replica may still be starting on a
    # loaded box: sample until both appear, bounded).
    # Sample until both replicas answer. NOTE: controller status counts
    # replicas at actor-CREATION time, so it cannot gate readiness; calls
    # to a still-starting replica simply queue until its __init__ ends.
    # The budget absorbs worker-spawn latency on a loaded 1-vCPU box
    # (measured >90 s under a full-suite run).
    ids = set()
    deadline = time.time() + 150
    while len(ids) < 2 and time.time() < deadline:
        ids.add(h.whoami.remote().result(timeout=30))
    assert len(ids) == 2


def test_status_and_delete(ray_mod):
    @serve.deployment
    def f():
        return "ok"

    serve.run(f.bind(), name="d3", route_prefix="/f")
    st = serve.status()
    assert "d3" in st and st["d3"]["f"]["running"] >= 1
    serve.delete("d3")
    assert "d3" not in serve.status()


def test_composition_deployment_graph(ray_mod):
    @serve.deployment
    class Adder:
        def __init__(self, inc):
            self.inc = inc

        def __call__(self, x):
            return x + self.inc

    @serve.deployment
    class Ingress:
        def __init__(self, adder):
            self.adder = adder

        async def __call__(self, x):
            return await self.adder.remote(x)

    app = Ingress.bind(Adder.bind(10))
    h = serve.run(app, name="d4", route_prefix="/compose")
    assert h.remote(5).result(timeout=30) == 15


def test_diamond_deployment_graph(ray_mod):
    """Diamond DAG (ref deployment_graph_build: a shared leaf Application
    bound into two mid deployments must deploy ONCE and serve both):

        ingress -> {left, right} -> scale  (shared leaf)
    """
    @serve.deployment
    class Scale:
        def __init__(self, k):
            self.k = k

        def __call__(self, x):
            return x * self.k

    @serve.deployment
    class Left:
        def __init__(self, scale):
            self.scale = scale

        async def __call__(self, x):
            return await self.scale.remote(x + 1)

    @serve.deployment
    class Right:
        def __init__(self, scale):
            self.scale = scale

        async def __call__(self, x):
            return await self.scale.remote(x + 2)

    @serve.deployment
    class Fan:
        def __init__(self, left, right):
            self.left, self.right = left, right

        async def __call__(self, x):
            return (await self.left.remote(x)) + \
                   (await self.right.remote(x))

    shared = Scale.bind(10)
    app = Fan.bind(Left.bind(shared), Right.bind(shared))
    # Shared leaf appears once in the flattened graph.
    assert sorted(app.flatten().keys()) == ["Fan", "Left", "Right", "Scale"]
    h = serve.run(app, name="d4b", route_prefix="/diamond")
    # (5+1)*10 + (5+2)*10
    assert h.remote(5).result(timeout=30) == 130


def test_http_proxy(ray_mod):
    @serve.deployment
    class Echo:
        def __call__(self, request):
            data = request.json()
            return {"path": request.path, "got": data}

    serve.start(proxy=True)
    serve.run(Echo.bind(), name="d5", route_prefix="/echo")
    time.sleep(1.0)
    req = urllib.request.Request(
        "http://127.0.0.1:8000/echo/sub?a=1",
        data=json.dumps({"v": 7}).encode(),
        headers={"Content-Type": "application/json"})
    deadline = time.time() + 30
    body = None
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(req, timeout=5) as resp:
                body = json.loads(resp.read())
            break
        except Exception:
            time.sleep(0.5)
    assert body == {"path": "/sub", "got": {"v": 7}}
    with urllib.request.urlopen(
            "http://127.0.0.1:8000/-/healthz", timeout=5) as resp:
        assert resp.read() == b"success"


def test_batching(ray_mod):
    @serve.deployment
    class Batcher:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        async def handle(self, xs):
            self.batch_sizes.append(len(xs))
            return [x * 10 for x in xs]

        async def __call__(self, x):
            return await self.handle(x)

        def get_batch_sizes(self):
            return self.batch_sizes

    h = serve.run(Batcher.bind(), name="d6", route_prefix="/batch")
    resps = [h.remote(i) for i in range(8)]
    out = sorted(r.result(timeout=30) for r in resps)
    assert out == [i * 10 for i in range(8)]
    sizes = h.get_batch_sizes.remote().result(timeout=30)
    assert max(sizes) > 1  # some requests were actually batched


def test_batch_pads_to_fixed_bucket():
    """pad_batches=True: a short flush ships EXACTLY max_batch_size
    entries (pad_value fill), pad outputs are dropped — the constant
    shape a jitted batch fn needs. Unit — no cluster."""
    import asyncio

    shapes = []

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.01,
                 pad_batches=True, pad_value=0)
    async def tenx(xs):
        shapes.append(len(xs))
        return [x * 10 for x in xs]

    async def run():
        out = await asyncio.gather(*[tenx(i) for i in range(3)])
        assert list(out) == [0, 10, 20]

    asyncio.run(run())
    assert shapes == [4], shapes


def test_multiplex(ray_mod):
    @serve.deployment
    class MuxModel:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id):
            self.loads.append(model_id)
            return {"id": model_id, "scale": int(model_id[-1])}

        async def __call__(self, x):
            model_id = serve.get_multiplexed_model_id()
            model = await self.get_model(model_id)
            return x * model["scale"]

        def get_loads(self):
            return self.loads

    h = serve.run(MuxModel.bind(), name="d7", route_prefix="/mux")
    h2 = h.options(multiplexed_model_id="m2")
    h3 = h.options(multiplexed_model_id="m3")
    assert h2.remote(10).result(timeout=30) == 20
    assert h3.remote(10).result(timeout=30) == 30
    assert h2.remote(5).result(timeout=30) == 10
    loads = h.get_loads.remote().result(timeout=30)
    assert loads.count("m2") == 1  # cached on second call


def test_rolling_update_version(ray_mod):
    @serve.deployment(version="1")
    def which():
        return "v1"

    serve.run(which.bind(), name="d8", route_prefix="/which")
    h = serve.get_app_handle("d8")
    assert h.remote().result(timeout=30) == "v1"

    @serve.deployment(version="2")
    def which():  # noqa: F811
        return "v2"

    h = serve.run(which.bind(), name="d8", route_prefix="/which")
    deadline = time.time() + 30
    while time.time() < deadline:
        if h.remote().result(timeout=30) == "v2":
            break
        time.sleep(0.2)
    assert h.remote().result(timeout=30) == "v2"


def test_replica_failure_recovery(ray_mod):
    @serve.deployment(num_replicas=1)
    class Fragile:
        def __call__(self):
            return "alive"

        def crash(self):
            import os
            os._exit(1)

    h = serve.run(Fragile.bind(), name="d9", route_prefix="/fragile")
    assert h.remote().result(timeout=30) == "alive"
    try:
        h.crash.remote().result(timeout=10)
    except Exception:
        pass
    # Controller should replace the dead replica.
    deadline = time.time() + 40
    ok = False
    while time.time() < deadline:
        try:
            if h.remote().result(timeout=10) == "alive":
                ok = True
                break
        except Exception:
            time.sleep(0.5)
    assert ok


def test_user_config_reconfigure(ray_mod):
    @serve.deployment(user_config={"threshold": 5})
    class Thresh:
        def __init__(self):
            self.threshold = None

        def reconfigure(self, cfg):
            self.threshold = cfg["threshold"]

        def __call__(self):
            return self.threshold

    h = serve.run(Thresh.bind(), name="d10", route_prefix="/thresh")
    assert h.remote().result(timeout=30) == 5


def test_large_body_is_read_in_place(ray_mod):
    """A 2 MB body, wrapped as the proxy wraps one (`wrap_body`: over the
    plane's serve-body threshold it pickles out of band), comes back
    whole through a handle, and what the caller holds is a view INTO a
    store segment it attached: the body crossed as a plane reference,
    not as bytes copied into and out of the reply frame."""
    from helpers.store_segments import in_attached_segment
    from ray_tpu._private import object_plane

    @serve.deployment(max_ongoing_requests=8)
    def echo(b):
        return b

    h = serve.run(echo.bind(), name="lb", route_prefix="/lb")
    body = b"x" * (2 << 20)
    r = h.remote(object_plane.wrap_body(body)).result(timeout=60)
    assert len(r) == len(body)
    assert object_plane.body_view(r)[0] == 120
    assert isinstance(r, object_plane.SharedPayload)
    assert in_attached_segment(object_plane.body_view(r))
    assert r == body


def test_streaming_handle(ray_mod):
    """handle.options(stream=True) yields items as the replica produces
    them (reference: handle.py DeploymentResponseGenerator)."""
    @serve.deployment
    class Gen:
        def __call__(self, n):
            for i in range(n):
                yield {"i": i}

    serve.run(Gen.bind(), name="stream1", route_prefix="/stream1")
    handle = serve.get_app_handle("stream1")
    items = list(handle.options(stream=True).remote(4))
    assert items == [{"i": 0}, {"i": 1}, {"i": 2}, {"i": 3}]


def test_http_streaming_incremental(ray_mod):
    """Chunked HTTP delivery is INCREMENTAL: the first chunk arrives while
    the replica is still producing later ones (reference: proxy.py
    streaming ASGI responses)."""
    import http.client

    @serve.deployment
    class SlowGen:
        def __call__(self, request):
            import time as _t
            for i in range(3):
                yield f"chunk-{i}\n"
                _t.sleep(0.7)

    serve.start(proxy=True)
    serve.run(SlowGen.bind(), name="stream2", route_prefix="/slowgen")
    time.sleep(1.0)
    deadline = time.time() + 30
    arrival_times = []
    chunks = []
    while time.time() < deadline and not chunks:
        try:
            conn = http.client.HTTPConnection("127.0.0.1", 8000, timeout=20)
            conn.request("GET", "/slowgen")
            resp = conn.getresponse()
            if resp.status != 200:
                conn.close()
                time.sleep(0.5)
                continue
            assert resp.headers.get("Transfer-Encoding") == "chunked"
            t0 = time.monotonic()
            while True:
                piece = resp.read(16)
                if not piece:
                    break
                arrival_times.append(time.monotonic() - t0)
                chunks.append(piece)
            conn.close()
        except Exception:
            time.sleep(0.5)
    body = b"".join(chunks)
    assert body == b"chunk-0\nchunk-1\nchunk-2\n", body
    # Incremental: the first piece arrived well before the last (the
    # replica sleeps 0.7s between yields — a buffered response would
    # deliver everything at once).
    assert arrival_times[-1] - arrival_times[0] > 0.5, arrival_times


def test_grpc_ingress_unary_and_stream(ray_mod):
    """Binary-RPC ingress shares the router: unary + server streaming
    (reference: python/ray/serve/_private/proxy.py:533 gRPCProxy)."""
    from ray_tpu.serve import ServeRpcClient

    @serve.deployment
    class Svc:
        def __call__(self, x, scale=1):
            return {"y": x * scale}

        def counts(self, n):
            for i in range(n):
                yield i * 10

    serve.start(grpc_proxy=True)
    serve.run(Svc.bind(), name="rpcapp", route_prefix="/rpcapp")
    time.sleep(0.5)
    client = ServeRpcClient(serve.get_grpc_address())
    try:
        assert client.call(21, app="rpcapp", scale=2) == {"y": 42}
        got = list(client.stream(3, app="rpcapp", method="counts"))
        assert got == [0, 10, 20], got
    finally:
        client.close()


def test_websocket_echo_duplex(ray_mod):
    """RFC 6455 upgrade through the proxy, full duplex: client messages
    reach the handler via request.ws.receive(); handler yields become
    frames (reference: serve's ASGI websocket scope)."""
    import asyncio
    import base64
    import os as _os

    from ray_tpu.serve import websocket as wsmod

    @serve.deployment
    class Chat:
        async def __call__(self, request):
            assert request.method == "WEBSOCKET"
            yield "hello"                      # server-initiated push
            while True:
                msg = await request.ws.receive(timeout=30)
                if msg is None:
                    return
                if msg == "quit":
                    yield "bye"
                    return
                yield f"echo:{msg}"

    serve.start(proxy=True)
    serve.run(Chat.bind(), name="ws1", route_prefix="/chat")
    time.sleep(1.0)

    async def client():
        deadline = time.time() + 30
        while True:
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", 8000)
                key = base64.b64encode(_os.urandom(16)).decode()
                writer.write(
                    f"GET /chat HTTP/1.1\r\nHost: x\r\n"
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
                expected = wsmod.accept_key(key)
                got = []
                # first frame: server push
                op, payload = await wsmod.read_frame(reader)
                got.append((op, payload.decode()))
                # send two messages, read echoes
                for msg in ("one", "quit"):
                    writer.write(wsmod.encode_frame(
                        wsmod.OP_TEXT, msg.encode(), mask=True))
                    await writer.drain()
                    op, payload = await wsmod.read_frame(reader)
                    got.append((op, payload.decode()))
                # close frame from server after handler returns
                op, _ = await wsmod.read_frame(reader)
                got.append((op, ""))
                writer.close()
                return expected, got
            except (ConnectionError, asyncio.IncompleteReadError, OSError):
                if time.time() > deadline:
                    raise
                await asyncio.sleep(0.5)

    expected, got = asyncio.run(asyncio.wait_for(client(), 60))
    assert got[0] == (wsmod.OP_TEXT, "hello")
    assert got[1] == (wsmod.OP_TEXT, "echo:one")
    assert got[2] == (wsmod.OP_TEXT, "bye")
    assert got[3][0] == wsmod.OP_CLOSE


def test_config_deploy_and_run_import_path(ray_mod, tmp_path):
    """Declarative deployment: serve deploy config.yaml + serve run
    module:app (reference: serve/scripts.py + ServeDeploySchema)."""
    import os
    import sys
    import urllib.request

    import yaml

    helpers = os.path.join(os.path.dirname(__file__), "helpers")
    if helpers not in sys.path:
        sys.path.insert(0, helpers)

    cfg = {
        "proxy": True,
        "applications": [
            {"name": "greet", "route_prefix": "/greet",
             "import_path": "serve_apps:app",
             "deployments": [{"name": "Greeter", "num_replicas": 2}]},
            {"name": "plain", "route_prefix": "/plain",
             "import_path": "serve_apps:plain"},
        ],
    }
    path = tmp_path / "serve.yaml"
    path.write_text(yaml.safe_dump(cfg))

    deployed = serve.deploy_config(str(path))
    assert deployed == ["greet", "plain"]

    st = serve.status()
    assert "greet" in st and "plain" in st
    # override applied: two replicas for the greet app's Greeter
    h = serve.get_app_handle("greet")
    assert h.remote(type("R", (), {"path": "/x"})()).result(
        timeout=60) == "hi:/x"

    deadline = time.time() + 30
    body = None
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                    "http://127.0.0.1:8000/greet/yo", timeout=5) as r:
                body = r.read().decode()
            break
        except Exception:
            time.sleep(0.5)
    assert body == "hi:/yo", body

    serve.delete("greet")
    serve.delete("plain")

    # serve run module:app
    h2 = serve.run_import_path("serve_apps:app", name="runpath",
                               route_prefix="/rp")
    assert h2.remote(type("R", (), {"path": "/z"})()).result(
        timeout=60) == "hi:/z"
    serve.delete("runpath")


def test_config_deploy_validation(tmp_path):
    from ray_tpu.serve import load_serve_config

    with pytest.raises(ValueError, match="applications"):
        load_serve_config({})
    with pytest.raises(ValueError, match="import_path"):
        load_serve_config({"applications": [{"name": "x"}]})
    with pytest.raises(ValueError, match="duplicate"):
        load_serve_config({"applications": [
            {"name": "a", "import_path": "m:x"},
            {"name": "a", "import_path": "m:y"}]})
    cfg = load_serve_config({"applications": [
        {"import_path": "m:x"}]})
    assert cfg["applications"][0]["route_prefix"] == "/"


def test_config_overrides_do_not_leak_into_module(ray_mod, tmp_path):
    """Overrides apply to a COPY of the imported graph: redeploying the
    same import_path without overrides gets decorator defaults back."""
    import os
    import sys

    helpers = os.path.join(os.path.dirname(__file__), "helpers")
    if helpers not in sys.path:
        sys.path.insert(0, helpers)
    from ray_tpu.serve.config_deploy import (_apply_overrides,
                                             import_application)

    app1 = import_application("serve_apps:app")
    _apply_overrides(app1, [{"name": "Greeter", "num_replicas": 5}])
    assert app1.deployment.config.num_replicas == 5
    app2 = import_application("serve_apps:app")
    assert app2.deployment.config.num_replicas == 1  # default, not 5

    cfg = {"applications": [{"import_path": "m:x"}]}
    serve.load_serve_config(cfg)
    assert "name" not in cfg["applications"][0]  # caller dict untouched
