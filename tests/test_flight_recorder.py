"""Flight-recorder tests: phase stamps end to end, Chrome-trace validity
(sub-slices + flow-event pairing), per-phase metrics, server-side
task-event reduction, and the pubsub outbox cap."""

import asyncio
import json
import time
import urllib.request

import pytest


def _get_metrics_address(ray_tpu):
    from ray_tpu._private import worker_api
    core = worker_api.get_core()
    return worker_api._call_on_core_loop(
        core, core.gcs.request("get_metrics_address", {}), 10)


def _wait_for_trace(ray_tpu, name, deadline_s=10):
    deadline = time.time() + deadline_s
    trace = []
    while time.time() < deadline:
        trace = ray_tpu.timeline()
        if any(e.get("name") == name and e.get("cat") == "task"
               for e in trace):
            return trace
        time.sleep(0.3)
    return trace


# ---------------------------------------------------------------------------
# timeline validity (satellite: exported JSON is loadable Chrome trace)
# ---------------------------------------------------------------------------

def test_timeline_is_valid_chrome_trace(ray_shared):
    import ray_tpu

    @ray_tpu.remote
    def work(x):
        time.sleep(0.01)
        return x

    assert ray_tpu.get([work.remote(i) for i in range(5)],
                       timeout=60) == list(range(5))
    trace = _wait_for_trace(ray_tpu, "work")
    task_slices = [e for e in trace
                   if e.get("cat") == "task" and e["name"] == "work"]
    assert task_slices, trace

    # Loadable JSON with the required chrome-trace keys.
    loaded = json.loads(json.dumps(trace))
    assert loaded and isinstance(loaded, list)
    for e in loaded:
        for key in ("cat", "name", "ph", "ts", "pid"):
            assert key in e, e
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0, e

    # Every flow id appears exactly once as a start and once as a finish.
    starts = [e["id"] for e in loaded if e["ph"] == "s"]
    finishes = [e["id"] for e in loaded if e["ph"] == "f"]
    assert starts, "no flow events in the trace"
    assert sorted(starts) == sorted(set(starts))
    assert sorted(finishes) == sorted(set(finishes))
    assert sorted(starts) == sorted(finishes)
    for e in loaded:
        if e["ph"] == "f":
            assert e.get("bp") == "e", e

    # Phase sub-slices nest inside their task slice (same pid, tid 1).
    by_task = {e["task_id"]: e for e in task_slices}
    subs = [e for e in loaded if e.get("cat") == "phase"
            and e.get("tid") == 1 and e.get("task_id") in by_task]
    assert subs, "no phase sub-slices for completed tasks"
    names = {e["name"] for e in subs}
    assert "exec" in names, names
    for e in subs:
        parent = by_task[e["task_id"]]
        assert e["pid"] == parent["pid"]
        assert e["ts"] >= parent["ts"] - 1e-6
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-6


def test_timeline_phases_cover_lifecycle(ray_shared):
    """The merged phase record carries owner AND executor stamps in
    monotonic order (submit -> ... -> reply)."""
    import ray_tpu
    from ray_tpu._private import worker_api
    from ray_tpu._private.flightrec import PHASE_ORDER, as_dict

    @ray_tpu.remote
    def hop():
        return 1

    assert ray_tpu.get([hop.remote() for _ in range(3)],
                       timeout=60) == [1, 1, 1]
    core = worker_api.get_core()
    deadline = time.time() + 10
    phased = []
    while time.time() < deadline and not phased:
        events = worker_api._call_on_core_loop(
            core, core.gcs.request("get_task_events", {"limit": 100000}),
            30)
        phased = [e for e in events
                  if e.get("name") == "hop" and e.get("phases")]
        time.sleep(0.3)
    assert phased, "no task event carried phases"
    ph = as_dict(phased[0]["phases"])
    for must in ("submitted", "dispatched", "received", "exec_start",
                 "exec_end", "reply_handled"):
        assert must in ph, ph
    assert ph["w"], ph
    stamps = [ph[p] for p in PHASE_ORDER if p in ph]
    assert stamps == sorted(stamps), ph


def test_actor_calls_record_phases(ray_shared):
    import ray_tpu
    from ray_tpu.util.state import summarize_task_latency

    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    a = A.remote()
    assert ray_tpu.get([a.ping.remote() for _ in range(10)],
                       timeout=60) == [1] * 10
    deadline = time.time() + 10
    rows = []
    while time.time() < deadline:
        rows = [r for r in summarize_task_latency() if r["name"] == "ping"]
        if rows:
            break
        time.sleep(0.3)
    assert rows, "actor calls produced no latency rows"
    phases = {r["phase"] for r in rows}
    assert "total" in phases and "exec_end" in phases, phases
    for r in rows:
        assert r["count"] >= 1
        assert r["p95_ms"] >= r["p50_ms"] >= 0


# ---------------------------------------------------------------------------
# metrics plane
# ---------------------------------------------------------------------------

def test_phase_histograms_and_pipeline_gauges_exported(ray_shared):
    import ray_tpu

    @ray_tpu.remote
    def tick():
        return 1

    assert ray_tpu.get([tick.remote() for _ in range(20)],
                       timeout=60) == [1] * 20
    addr = _get_metrics_address(ray_tpu)
    assert addr
    deadline = time.time() + 15
    body = ""
    needed = ("ray_tpu_task_phase_seconds_bucket",
              "ray_tpu_task_queue_depth",
              "ray_tpu_lease_rpcs_inflight",
              "ray_tpu_actor_outbox_depth",
              "ray_tpu_dispatch_batch_size_bucket",
              "ray_tpu_event_loop_lag_seconds_bucket",
              "ray_tpu_pubsub_dropped_total",
              "ray_tpu_rpc_inflight_requests")
    while time.time() < deadline:
        with urllib.request.urlopen(f"http://{addr}/metrics",
                                    timeout=5) as r:
            body = r.read().decode()
        if all(n in body for n in needed):
            break
        time.sleep(0.4)
    for n in needed:
        assert n in body, f"{n} missing from /metrics"
    # Phase histograms carry the Phase tag and real observations.
    assert 'ray_tpu_task_phase_seconds_count{Phase="total"}' in body
    # Loop-lag probes run in every daemon kind of this 1-process cluster.
    for proc in ("gcs", "driver"):
        assert f'Process="{proc}"' in body, proc


def test_latency_endpoint_and_dashboard_panel(ray_shared):
    import ray_tpu

    @ray_tpu.remote
    def quick():
        return 1

    assert ray_tpu.get([quick.remote() for _ in range(5)],
                       timeout=60) == [1] * 5
    addr = _get_metrics_address(ray_tpu)
    deadline = time.time() + 10
    rows = []
    while time.time() < deadline:
        with urllib.request.urlopen(f"http://{addr}/api/latency",
                                    timeout=5) as r:
            rows = json.loads(r.read())
        if any(x["name"] == "quick" for x in rows):
            break
        time.sleep(0.3)
    mine = [x for x in rows if x["name"] == "quick"]
    assert mine, rows
    assert {"name", "phase", "count", "p50_ms", "p95_ms"} <= set(mine[0])
    with urllib.request.urlopen(f"http://{addr}/dashboard", timeout=5) as r:
        page = r.read().decode()
    assert 'id="p-latency"' in page and 'id="latency"' in page


# ---------------------------------------------------------------------------
# server-side reduction (satellite: latest-state + limit in the GCS)
# ---------------------------------------------------------------------------

def test_server_side_latest_state_reduction_and_limit():
    from ray_tpu._private.config import Config
    from ray_tpu._private.gcs import GcsServer

    gcs = GcsServer(Config())
    for tid in ("t1", "t2", "t3"):
        for state in ("PENDING", "RUNNING", "FINISHED"):
            gcs.task_events.append({
                "task_id": tid, "job_id": "j", "name": "f",
                "state": state, "time": time.time(), "worker_id": "w"})
    # A span record must not pollute the reduction.
    gcs.task_events.append({"kind": "span", "trace_id": "x", "start": 0.0})

    async def q(payload):
        return await gcs.rpc_get_task_events(None, payload)

    rows = asyncio.run(q({"latest_only": True, "limit": 100000}))
    assert len(rows) == 3
    assert all(e["state"] == "FINISHED" for e in rows)

    # State filters apply AFTER the reduction: no task is still RUNNING.
    rows = asyncio.run(q({"latest_only": True, "limit": 100000,
                          "filters": [("state", "=", "RUNNING")]}))
    assert rows == []

    # Limit applies server-side to the reduced rows.
    rows = asyncio.run(q({"latest_only": True, "limit": 2}))
    assert len(rows) == 2

    # Raw path unchanged: all events, capped by limit.
    rows = asyncio.run(q({"limit": 4}))
    assert len(rows) == 4


def test_list_tasks_server_side_limit(ray_shared):
    import ray_tpu
    from ray_tpu.util.state import list_tasks

    @ray_tpu.remote
    def nop():
        return None

    ray_tpu.get([nop.remote() for _ in range(8)], timeout=60)
    deadline = time.time() + 10
    finished = []
    while time.time() < deadline:
        finished = list_tasks(filters=[("state", "=", "FINISHED")])
        if len(finished) >= 8:
            break
        time.sleep(0.3)
    assert len(finished) >= 8
    assert all(r["state"] == "FINISHED" for r in finished)
    rows = list_tasks(limit=3)
    assert len(rows) == 3


# ---------------------------------------------------------------------------
# pubsub outbox cap (satellite: drop-oldest for stalled subscribers)
# ---------------------------------------------------------------------------

class _StalledConn:
    """Mimics the rpc.Connection surface Pubsub touches, with a socket
    that never drains."""

    def __init__(self, backed_up=True):
        self.backed_up = backed_up
        self.closed = False
        self.on_close = None
        self.pushed = []

    def write_backed_up(self):
        return self.backed_up

    def push_nowait(self, method, payload):
        self.pushed.append(payload)

    async def push(self, method, payload):
        await asyncio.sleep(3600)  # drain never completes


def test_pubsub_outbox_caps_and_drops_oldest():
    from ray_tpu._private.gcs import Pubsub

    async def run():
        pubsub = Pubsub(max_outbox=10)
        conn = _StalledConn()
        pubsub.subscribe(conn, ["nodes"])
        for i in range(35):
            pubsub.publish("nodes", {"seq": i})
        await asyncio.sleep(0)  # let the flusher start (and park)
        return pubsub, conn

    pubsub, conn = asyncio.run(run())
    # Stalled socket: nothing went through the fast path.
    assert conn.pushed == []
    depths = pubsub.outbox_depths()
    assert depths and max(depths.values()) <= 10
    # 35 published, <=10 queued, 1 may be parked in the flusher.
    assert pubsub.dropped_total >= 35 - 10 - 1
    # Newest survive; oldest dropped.
    box = next(iter(pubsub._outboxes.values()))
    assert box[-1]["message"]["seq"] == 34
    assert box[0]["message"]["seq"] >= 24

    # A healthy subscriber still takes the zero-coroutine fast path.
    async def run_fast():
        pubsub = Pubsub(max_outbox=10)
        conn = _StalledConn(backed_up=False)
        pubsub.subscribe(conn, ["nodes"])
        pubsub.publish("nodes", {"seq": 0})
        return pubsub, conn

    fast_pubsub, conn = asyncio.run(run_fast())
    assert len(conn.pushed) == 1
    assert fast_pubsub.dropped_total == 0


def test_pubsub_drop_connection_clears_outbox():
    from ray_tpu._private.gcs import Pubsub

    async def run():
        pubsub = Pubsub(max_outbox=5)
        conn = _StalledConn()
        pubsub.subscribe(conn, ["nodes"])
        for i in range(8):
            pubsub.publish("nodes", {"seq": i})
        pubsub.drop_connection(conn)
        return pubsub

    pubsub = asyncio.run(run())
    assert pubsub.outbox_depths() == {}


# ---------------------------------------------------------------------------
# spans on the timeline (build_trace on a recorded event list, no cluster)
# ---------------------------------------------------------------------------

def _recorded_events():
    """A finished task with its phase record, and a train run's spans as
    BackendExecutor and the raylet export them: driver pid 100, worker
    pid 200, a raylet span that carries its node."""
    from ray_tpu._private import flightrec
    phases = flightrec.new_record()
    for i, t in enumerate((10.0, 10.1, 10.2, 10.3, 10.4, 10.5, 10.6, 10.8,
                           10.9, 11.0)):
        phases[i] = t
    phases[flightrec.IDX_WORKER] = "feedfacecafe"
    task = {"task_id": "t1", "name": "poll", "state": "FINISHED",
            "time": 11.0, "worker_id": "0123456789ab", "phases": phases}

    def span(name, sid, parent, start, end, pid=100, **extra):
        return {"kind": "span", "trace_id": "run1", "span_id": sid,
                "parent_id": parent, "name": name, "task_id": "run1",
                "start": start, "end": end, "pid": pid, **extra}
    spans = [
        span("train:run", "a", "", 1.0, 9.0),
        span("train:start_workers", "b", "a", 1.0, 4.0),
        # stamped a hair outside its parent: clamped, as a sub-slice is
        span("train:actors", "c", "b", 1.5, 4.5),
        span("train:round", "d", "a", 5.0, 6.0),
        # the worker's clock, on the worker's lane: not clamped
        span("train:loop", "e", "d", 4.9, 5.8, pid=200, loop_s=0.9),
        span("compile:compile", "f", "d", 5.1, 5.2, pid=200,
             fun_name="step", cache="hit"),
        {"kind": "span", "trace_id": "actor:ff", "span_id": "g",
         "parent_id": "", "name": "actor:spawn", "task_id": "actor:ff",
         "start": 1.6, "end": 3.0, "node_id": "0011223344556677"},
        span("unfinished", "h", "a", 7.0, None),
    ]
    return [task], spans


def test_build_trace_draws_spans_nested_on_their_lanes():
    from ray_tpu._private import flightrec
    task, spans = _recorded_events()
    trace = flightrec.build_trace(spans[:3] + task + spans[3:])
    slices = {e["span_id"]: e for e in trace if e["cat"] == "span"}
    assert sorted(slices) == list("abcdefg")      # finished spans only
    assert all(e["ph"] == "X" for e in slices.values())
    lanes = {sid: (e["pid"], e["tid"] - flightrec.SPAN_ROW)
             for sid, e in slices.items()}
    assert lanes == {"a": ("100", 0), "b": ("100", 1), "c": ("100", 2),
                     "d": ("100", 1), "e": ("200", 2), "f": ("200", 2),
                     "g": ("node:00112233", 0)}
    for sid, e in slices.items():
        parent = slices.get(e["parent_id"])
        assert (parent is None) == (sid in "ag")
        if parent is not None and parent["pid"] == e["pid"]:
            assert parent["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"]
    assert slices["c"]["ts"] == 1.5e6 and slices["c"]["dur"] == 2.5e6
    assert slices["e"]["ts"] == 4.9e6 and slices["e"]["dur"] == pytest.approx(
        0.9e6)
    assert slices["e"]["args"] == {"loop_s": 0.9}
    assert slices["f"]["args"] == {"fun_name": "step", "cache": "hit"}
    assert slices["a"]["args"] == {} and slices["a"]["trace_id"] == "run1"


def test_build_trace_without_spans_is_what_it_was():
    """The task's slices are the same with the spans beside them, and the
    list without spans yields no span slice."""
    from ray_tpu._private import flightrec
    task, spans = _recorded_events()
    alone = flightrec.build_trace(task)
    assert [(e["cat"], e["name"], e["ph"]) for e in alone] == [
        ("task", "poll", "X"), ("phase", "args_resolve", "X"),
        ("phase", "exec", "X"), ("phase", "result_put", "X"),
        ("phase", "submit", "X"), ("flow", "task_flow", "s"),
        ("flow", "task_flow", "f")]
    together = flightrec.build_trace(spans + task)
    assert [e for e in together if e["cat"] != "span"] == alone


def test_build_trace_leaves_a_serve_requests_spans_to_its_request():
    """A span of a serve request's trace is drawn once, as a serve_span
    stamped with the request, not again as a span."""
    from ray_tpu._private import flightrec
    hop = {"kind": "serve_request", "request_id": "req1", "hop": "proxy",
           "pid": 7, "deployment": "d",
           "phases": [1.0, None, None, 1.1, None, None, None, 1.5, None]}
    span = {"kind": "span", "trace_id": "req1", "span_id": "s1",
            "parent_id": "", "name": "handler", "task_id": "x",
            "start": 1.1, "end": 1.4, "pid": 8}
    trace = flightrec.build_trace([hop, span])
    assert [e["cat"] for e in trace if e.get("span_id") == "s1"] == [
        "serve_span"]


def test_a_stamp_is_the_clock_and_whether_jax_is_loaded(monkeypatch):
    """An edge of a start-up span (runtime:*, train:before_start): when,
    and one lookup in sys.modules, which imports nothing."""
    import sys
    from ray_tpu._private import flightrec
    monkeypatch.setitem(sys.modules, "jax", object())
    before = time.time()
    at, loaded = flightrec.stamp()
    assert before <= at <= time.time() and loaded is True
    monkeypatch.delitem(sys.modules, "jax")
    assert flightrec.stamp()[1] is False and "jax" not in sys.modules


@pytest.mark.parametrize("name,extra", [
    ("runtime:before_init", {"jax_loaded": [False, True]}),
    ("train:between", {"pid": 200, "after": "_step"}),
    ("train:head", {"pid": 200})])
def test_build_trace_keeps_what_a_start_up_span_says(name, extra):
    """What the start-up spans carry beside their edges is the slice's
    args: which stretch paid the import, whose gap it is."""
    from ray_tpu._private import flightrec
    event = flightrec.span_event(name, "run1", 1.0, 1.5, **extra)
    drawn, = [e for e in flightrec.build_trace([event])
              if e["cat"] == "span"]
    assert drawn["name"] == name and drawn["dur"] == 0.5e6
    assert drawn["pid"] == str(event["pid"])
    assert drawn["args"] == {k: v for k, v in extra.items() if k != "pid"}
