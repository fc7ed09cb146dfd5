"""Live telemetry-plane tests: delta frames land in the GCS tsdb, the
query RPC serves aligned windows, `ray_tpu top`/`traces` read them back,
and proxy-side queue wait feeds the SLO burn autoscaler.

The cluster runs with a 0.5 s tsdb resolution and report interval so
multiple slots fill within test time (production defaults are 5 s / 2 s).
"""

import subprocess
import sys
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.config import SLOConfig

# This file's proxy has a port of its own (as tests/test_serve_trace.py's
# has): test_serve.py keeps the default 8000, and two files' proxies on one
# port, in two xdist workers at once, lost the bind (D2's "load-sensitive"
# failures of PRs 40 and 55).
PORT = 8154


@pytest.fixture(scope="module")
def ray_mod():
    ray_tpu.init(num_cpus=4, num_tpus=0, system_config={
        "tsdb_resolution_s": 0.5,
        "metrics_report_interval_s": 0.5,
    })
    yield ray_tpu
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _cleanup_apps(ray_mod):
    yield
    try:
        for app in list(serve.status().keys()):
            serve.delete(app)
    except Exception:
        pass


def _gcs(method, payload, timeout=30):
    from ray_tpu._private import worker_api
    core = worker_api.get_core()
    return worker_api._call_on_core_loop(
        core, core.gcs.request(method, payload), timeout)


def _controller():
    from ray_tpu.serve.api import _get_controller
    return _get_controller()


def _wait_ready(app, dep, n, timeout=90):
    ctrl = _controller()
    deadline = time.time() + timeout
    while time.time() < deadline:
        st = ray_tpu.get(ctrl.status.remote(), timeout=30)
        if st.get(app, {}).get(dep, {}).get("ready", 0) >= n:
            return True
        time.sleep(0.2)
    return False


# ---------------------------------------------------------------------------
# acceptance: shipped frames -> aligned query windows
# ---------------------------------------------------------------------------

@pytest.mark.timeout(120)
def test_query_rpc_returns_aligned_counter_and_p99(ray_mod):
    """The headline tsdb property: after normal task traffic, the query
    RPC returns >=2 window-aligned samples both for a shipped counter
    and for a histogram-derived p99."""

    @ray_tpu.remote
    def nop(i):
        return i

    res = 0.5  # the fixture's tsdb_resolution_s

    def aligned(points):
        return all(abs(t / res - round(t / res)) < 1e-6 for t, _ in points)

    counter_pts = hist_pts = []
    deadline = time.time() + 60
    while time.time() < deadline:
        # Keep the task-phase histogram moving so p99 slots have deltas.
        ray_tpu.get([nop.remote(i) for i in range(8)], timeout=60)
        counter, hist = _gcs("metrics_query", {"queries": [
            {"name": "ray_tpu_metrics_frames_total", "fold": "value",
             "window_s": 60},
            {"name": "ray_tpu_task_phase_seconds", "fold": "p99",
             "window_s": 60},
        ]})
        counter_pts = max((s["points"] for s in counter), key=len,
                          default=[])
        hist_pts = max((s["points"] for s in hist), key=len, default=[])
        # The counter's first slots are its zero baseline, carried forward
        # until the reporter's next frame lands: wait for the state the
        # assertions below are about, not for two slots of any value.
        if (len(counter_pts) >= 2 and len(hist_pts) >= 2
                and counter_pts[-1][1] > 0):
            break
        time.sleep(0.3)

    assert len(counter_pts) >= 2, counter_pts
    assert len(hist_pts) >= 2, hist_pts
    assert aligned(counter_pts) and aligned(hist_pts)
    # Counter fold is cumulative (first slot may be the zero baseline)
    # and frames keep shipping.
    vals = [v for _, v in counter_pts]
    assert vals == sorted(vals) and vals[-1] > 0
    assert all(v >= 0 for _, v in hist_pts)
    # Series inventory RPC sees both, at the configured resolution.
    inv = _gcs("metrics_series", {})
    assert "ray_tpu_metrics_frames_total" in inv["names"]
    assert inv["resolution_s"] == pytest.approx(res)


@pytest.mark.timeout(120)
def test_top_once_renders_live_rows(ray_mod):
    """`ray_tpu top --once` (a second driver over the CLI) renders rows
    fed by the tsdb, non-tty."""
    from ray_tpu._private import worker_api

    @ray_tpu.remote
    def nop():
        return 1

    ray_tpu.get([nop.remote() for _ in range(4)], timeout=60)
    time.sleep(1.5)  # two report ticks

    addr = worker_api._state.gcs_address
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu", "top", "--once",
         "--address", addr, "--window", "60"],
        capture_output=True, text=True, timeout=90)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ray_tpu top" in out.stdout
    for section in ("serve", "object plane", "nodes"):
        assert section in out.stdout
    # Live per-node rows (cpu gauge ships from the GCS-local agent).
    assert "cpu" in out.stdout


# ---------------------------------------------------------------------------
# satellite: trace search over the task-event buffer
# ---------------------------------------------------------------------------

@pytest.mark.timeout(180)
def test_trace_search_filters(ray_mod):
    serve.start(http_options=serve.HTTPOptions(port=PORT))

    @serve.deployment
    class Mixed:
        async def __call__(self, req):
            body = getattr(req, "body", req) or b""
            if b"boom" in body:
                raise ValueError("boom")
            if b"slow" in body:
                import asyncio
                await asyncio.sleep(0.15)
            return b"ok"

    serve.run(Mixed.bind(), name="tr", route_prefix="/tr")
    assert _wait_ready("tr", "Mixed", 1)

    def post(body):
        req = urllib.request.Request(f"http://127.0.0.1:{PORT}/tr",
                                     data=body, method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.read()
        except urllib.error.HTTPError as e:
            return e.read()

    for body in (b"fast", b"fast", b"slow-one", b"boom-now"):
        post(body)

    rows = []
    deadline = time.time() + 30
    while time.time() < deadline:
        rows = _gcs("search_traces", {"deployment": "Mixed", "limit": 100})
        if len(rows) >= 4 and any(r["error"] for r in rows):
            break
        time.sleep(0.4)
    assert len(rows) >= 4, rows
    assert all(r["deployment"] == "Mixed" for r in rows)
    assert all(r["request_id"] and r["total_ms"] >= 0 for r in rows)

    slow = _gcs("search_traces", {"deployment": "Mixed", "min_ms": 100})
    assert slow and all(r["total_ms"] >= 100 for r in slow)

    errs = _gcs("search_traces", {"deployment": "Mixed", "errors_only": True})
    assert errs and all(r["error"] for r in errs)
    assert any(r["error"] == "ValueError" for r in errs)

    # The searched ids resolve in the timeline (the drill-down path of
    # `ray_tpu traces` -> `timeline --request <id>`).
    rid = errs[0]["request_id"]
    events = _gcs("get_task_events", {"limit": 100000})
    assert any(getattr(e, "request_id", None) == rid or
               (isinstance(e, dict) and e.get("request_id") == rid)
               for e in events)


# ---------------------------------------------------------------------------
# satellite: proxy-side queue wait feeds SLO burn
# ---------------------------------------------------------------------------

def _proxy_handle():
    from ray_tpu.actor import ActorHandle
    ctrl = _controller()
    actor_id = ray_tpu.get(ctrl.get_proxy_actor_id.remote(), timeout=30)
    assert actor_id
    info = _gcs("get_actor_info", {"actor_id": actor_id})
    return ActorHandle._from_actor_info(info)


@pytest.mark.timeout(240)
def test_proxy_stall_drives_slo_upscale(ray_mod):
    """Replicas are instant; only the proxy's event loop is stalled.
    Queue wait measured proxy-side must fold into the deployment's SLO
    bad fraction and drive a burn upscale — with zero replica-side
    slowness."""
    serve.start(http_options=serve.HTTPOptions(port=PORT))

    @serve.deployment(
        num_replicas=1, max_ongoing_requests=8, max_queued_requests=64,
        autoscaling_config=serve.AutoscalingConfig(
            min_replicas=1, max_replicas=3,
            # Queue-depth policy effectively disabled: only burn scales.
            target_ongoing_requests=1000.0, upscale_delay_s=999.0,
            downscale_delay_s=999.0),
        slo_config=SLOConfig(target_p99_s=0.05, slo=0.9,
                             fast_window_s=2.0, slow_window_s=6.0,
                             burn_threshold=1.5, min_samples=3,
                             upscale_cooldown_s=1.0))
    class Instant:
        async def __call__(self, req):
            return b"ok"

    serve.run(Instant.bind(), name="qslo", route_prefix="/qslo")
    assert _wait_ready("qslo", "Instant", 1)

    proxy = _proxy_handle()
    stop = threading.Event()

    def stall():
        while not stop.is_set():
            try:
                ray_tpu.get(proxy.debug_stall.remote(0.25), timeout=30)
            except Exception:
                pass

    def pump():
        while not stop.is_set():
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{PORT}/qslo", timeout=10) as r:
                    r.read()
            except Exception:
                pass

    threads = ([threading.Thread(target=stall)] +
               [threading.Thread(target=pump) for _ in range(3)])
    for th in threads:
        th.start()
    scaled = False
    burn_seen = 0.0
    try:
        ctrl = _controller()
        deadline = time.time() + 120
        while time.time() < deadline:
            st = ray_tpu.get(ctrl.status.remote(), timeout=30)
            row = st.get("qslo", {}).get("Instant", {})
            burn_seen = max(burn_seen,
                            row.get("slo", {}).get("burn_fast", 0.0))
            if row.get("target", 1) >= 2:
                scaled = True
                break
            time.sleep(0.25)
    finally:
        stop.set()
        for th in threads:
            th.join(30)
    assert scaled, f"no queue-wait upscale (max fast burn {burn_seen})"
    assert burn_seen > 1.5

    # The replicas never ran slow: every bad sample came from the proxy.
    _v, reps = ray_tpu.get(
        _controller().get_replicas.remote("qslo", "Instant"), timeout=30)
    slow = 0
    for rep in reps:
        try:
            slow += ray_tpu.get(rep.get_metrics.remote(),
                                timeout=10).get("slow", 0)
        except Exception:
            pass
    assert slow == 0
    # And the proxy's own counters made it into the tsdb.
    res = _gcs("metrics_query", {
        "name": "ray_tpu_serve_proxy_queue_slow_total",
        "tags": {"Deployment": "Instant"}, "fold": "latest"})
    assert res and res[0]["points"][0][1] > 0
