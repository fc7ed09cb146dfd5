"""Train's host phases in the runtime's idiom: after one tiny JaxTrainer.fit
on the CPU, the driver's metrics registry holds ray_tpu_init_seconds,
ray_tpu_init_phase_seconds{Phase}, ray_tpu_train_start_seconds{Phase} and
ray_tpu_train_report_seconds{Phase}, the flight recorder holds one tree of
train:* spans under the run's id beside runtime:init's, `ray_tpu timeline`
draws them, and the benchmark's reader (benchmark/readers/program.py) reads
each of its fifteen metric files from that registry. A second fit of the
same driver, with tracing off, is a second trace without per-round spans. The loop jits two functions before its
first report and calls one at another shape after its third: what the worker
compiled (_private/compile_cache.py's records) rides its messages into the
same gauge, ray_tpu_train_recompiles_total and compile:<phase> spans."""

import json
import os
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPORTS = 4
SPANS = ("runtime:init", "train:start_workers", "train:start_training",
         "train:round")
INIT_SPANS = ("runtime:gcs_start", "runtime:raylet_start", "runtime:connect")
START_SPANS = ("train:placement", "train:actors", "train:backend_hook")
ROUND_SPANS = ("train:round", "train:loop", "train:report")
METRIC_FILES = ("runtime_init_s", "train_workers_start_s", "train_fn_start_s",
                "train_report_blocked_ms", "train_report_poll_ms",
                "train_first_report_s", "setup_trace_s", "setup_lower_s",
                "setup_cache_load_s", "runtime_gcs_start_s",
                "runtime_raylet_start_s", "train_placement_s",
                "train_actors_ready_s", "train_report_call_ms",
                "train_report_wake_ms")
INIT_PHASES = ("gcs", "raylet", "connect")
DRIVER_PHASES = ("workers", "placement", "actors", "hook", "training")
REPORT_PHASES = ("blocked", "call", "wake", "poll")
WORKER_PHASES = ("first_report", "trace", "lower", "cache_load", "compile")
COMPILE_SPANS = ("compile:trace", "compile:lower", "compile:compile")


@pytest.fixture(scope="module")
def fitted():
    """One fit of a loop that reports REPORTS times, with tracing enabled,
    after a fit of a loop without jax with tracing off; what the driver's
    registry and the flight recorder then hold: `spans` the traced run's
    and runtime:init's, `untraced` the other run's, `timeline` all."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, ScalingConfig, report
    from ray_tpu.util import metrics, tracing

    def loop(config):
        import jax
        import numpy as np

        def make_state(n):
            return jax.numpy.zeros(n) + 1.0

        def step(x):
            return x * 2.0

        make_state, step = jax.jit(make_state, static_argnums=0), jax.jit(step)
        step(make_state(4)).block_until_ready()
        for i in range(config["reports"]):
            if i == 3:      # a new shape mid-run: one recompile
                step(np.ones(5, np.float32)).block_until_ready()
            report({"i": i})

    def plain_loop():
        for i in range(2):
            report({"i": i})

    tracing.enable()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        tracing.disable()
        assert JaxTrainer(plain_loop, scaling_config=ScalingConfig(
            num_workers=1)).fit().error is None
        for name in ("ray_tpu_train_report_seconds",
                     "ray_tpu_train_start_seconds"):
            for phase in REPORT_PHASES + DRIVER_PHASES + WORKER_PHASES:
                metrics.remove(name, {"Phase": phase})   # another fit's
        metrics.remove("ray_tpu_train_recompiles_total")
        tracing.enable()
        result = JaxTrainer(
            loop, train_loop_config={"reports": REPORTS},
            scaling_config=ScalingConfig(num_workers=1)).fit()
        assert result.error is None
        assert len(result.metrics_dataframe) == REPORTS
        deadline = time.monotonic() + 20
        while True:     # spans reach the GCS with the next event flush
            spans = tracing.get_spans()
            runs = [s for s in spans if s["name"] == "train:run"]
            if (set(SPANS + COMPILE_SPANS) <= {s["name"] for s in spans}
                    and sum(s["name"] == "compile:compile"
                            for s in spans) >= 3 and len(runs) == 2
                    and sum(s["name"].startswith("actor:")
                            for s in spans) >= 4):
                break
            assert time.monotonic() < deadline, sorted(
                {s["name"] for s in spans})
            time.sleep(0.2)
        timeline = ray_tpu.timeline()
    finally:
        tracing.disable()
        ray_tpu.shutdown()
    rows = {(m["name"], m["tags"].get("Phase")): m
            for m in metrics.snapshot() if m["name"].startswith(
                ("ray_tpu_init_", "ray_tpu_train_"))}
    first, second = sorted(runs, key=lambda s: s["start"])
    return {"rows": rows, "timeline": timeline, "all_spans": spans,
            "spans": [s for s in spans if s["trace_id"] == second["trace_id"]
                      or s["name"].startswith("runtime:")],
            "untraced": [s for s in spans
                         if s["trace_id"] == first["trace_id"]]}


@pytest.mark.parametrize("name,phase", [
    ("ray_tpu_init_seconds", None),
    ("ray_tpu_init_phase_seconds", "gcs"),
    ("ray_tpu_init_phase_seconds", "raylet"),
    ("ray_tpu_init_phase_seconds", "connect"),
    ("ray_tpu_train_start_seconds", "workers"),
    ("ray_tpu_train_start_seconds", "placement"),
    ("ray_tpu_train_start_seconds", "actors"),
    ("ray_tpu_train_start_seconds", "hook"),
    ("ray_tpu_train_start_seconds", "training"),
    ("ray_tpu_train_start_seconds", "first_report"),
    ("ray_tpu_train_start_seconds", "trace"),
    ("ray_tpu_train_start_seconds", "lower"),
    ("ray_tpu_train_start_seconds", "compile")])
def test_start_up_gauges(fitted, name, phase):
    row = fitted["rows"][(name, phase)]
    assert row["type"] == "gauge"
    assert 0.0 < row["value"] < 120.0


def test_a_run_that_loads_nothing_reads_zero(fitted):
    """Programs this small stay under the persistent cache's threshold:
    nothing is loaded, and the row is there all the same."""
    row = fitted["rows"][("ray_tpu_train_start_seconds", "cache_load")]
    assert row["type"] == "gauge" and row["value"] == 0.0


def test_first_report_holds_what_the_worker_compiled(fitted):
    """One process's clock: start_run -> first report() is no shorter than
    the disjoint compile spans inside it."""
    seconds = {phase: fitted["rows"][("ray_tpu_train_start_seconds",
                                      phase)]["value"]
               for phase in WORKER_PHASES}
    assert seconds["first_report"] >= sum(
        seconds[p] for p in WORKER_PHASES if p != "first_report")


def test_a_new_shape_mid_run_is_one_recompile(fitted):
    row = fitted["rows"][("ray_tpu_train_recompiles_total", None)]
    assert row["type"] == "counter" and row["value"] == 1.0


@pytest.mark.parametrize("name", COMPILE_SPANS)
def test_compiles_are_spans_on_the_workers_lane(fitted, name):
    """Both programs of start-up and the recompile, each with its function's
    name, between train:start_training and the run's end, on the worker's
    pid; a backend compile says whether the persistent cache had it."""
    spans = [s for s in fitted["spans"] if s["name"] == name]
    assert [s["fun_name"] for s in sorted(spans, key=lambda s: s["start"])
            ] == ["make_state", "step", "step"]
    shipped = next(s for s in fitted["spans"]
                   if s["name"] == "train:start_training")
    driver_pids = {s["pid"] for s in fitted["spans"]
                   if s["name"].startswith("train:")
                   and s["name"] not in ("train:loop", "train:report")}
    for s in spans:
        assert shipped["start"] <= s["start"] <= s["end"]
        assert s["pid"] not in driver_pids
        assert s.get("cache") == ("miss" if name == "compile:compile"
                                  else None)


@pytest.mark.parametrize("phase,count", [
    # a report's wait is known once its put returns: it rides the next
    # message, so the `done` message brings the last one
    ("blocked", REPORTS),
    ("call", REPORTS),
    ("wake", REPORTS + 1),
    ("poll", REPORTS + 1)])
def test_report_histogram(fitted, phase, count):
    row = fitted["rows"][("ray_tpu_train_report_seconds", phase)]
    assert row["type"] == "histogram"
    assert row["count"] == count
    assert 0.0 <= row["sum"] < 60.0


def test_phases_lie_inside_what_they_split(fitted):
    """Stamps of one process's clock at boundaries inside the parent's."""
    def value(name, phase=None):
        return fitted["rows"][(name, phase)]["value"]
    assert (sum(value("ray_tpu_init_phase_seconds", p) for p in INIT_PHASES)
            <= value("ray_tpu_init_seconds"))
    assert (sum(value("ray_tpu_train_start_seconds", p)
                for p in ("placement", "actors", "hook"))
            <= value("ray_tpu_train_start_seconds", "workers"))
    report = {p: fitted["rows"][("ray_tpu_train_report_seconds", p)]["sum"]
              for p in REPORT_PHASES}
    assert report["blocked"] <= report["call"]   # the same REPORTS reports
    assert report["wake"] <= report["poll"]      # the same messages


def test_a_report_carries_the_one_before_and_the_loop_between():
    """call_s (entry -> return) and loop_s (that return -> the next entry)
    ride the next message, as blocked_s does, which lies inside call_s; the
    first message has none to carry, the closing one carries the last."""
    from ray_tpu.train.session import TrainContext, _Session
    session = _Session(TrainContext())
    messages = []
    for i in range(3):
        session.report({"i": i})
        time.sleep(0.01)
        messages.append(session.next_result(timeout=1))
    session.finish()
    messages.append(session.next_result(timeout=1))
    assert [m["type"] for m in messages] == ["report"] * 3 + ["done"]
    assert messages[0]["call_s"] is None and messages[0]["loop_s"] is None
    for m in messages[1:]:
        assert 0.0 <= m["blocked_s"] <= m["call_s"] < 0.01 <= m["loop_s"]


def test_poll_stamps_when_the_rpc_thread_took_the_message():
    from ray_tpu.train.session import TrainContext, _Session
    from ray_tpu.train.worker_group import TrainWorker
    worker = TrainWorker()
    worker._session = _Session(TrainContext())
    worker._session.report({"i": 0})
    before = time.time()
    out = worker.poll(timeout=1)
    assert out["queued_at"] <= before <= out["taken_at"] <= time.time()
    assert worker.poll(timeout=0.01) is None


@pytest.mark.parametrize("name", SPANS + INIT_SPANS + START_SPANS + (
    "train:run", "train:loop", "train:report"))
def test_span_is_in_the_flight_recorder(fitted, name):
    spans = [s for s in fitted["spans"] if s["name"] == name]
    assert spans
    assert all(s["end"] >= s["start"] for s in spans)
    if name in INIT_SPANS + START_SPANS + ("train:run",):
        assert len(spans) == 1
    if name == "train:round":       # one a round, and the closing one
        assert len(spans) == REPORTS + 1
    if name in ("train:loop", "train:report"):
        # the message after a report dates it: none for the first message
        assert len(spans) == REPORTS
        worker_pids = {s["pid"] for s in fitted["spans"]
                       if s["name"].startswith("compile:")}
        assert {s["pid"] for s in spans} == worker_pids


def _parent_names(spans):
    by_id = {s["span_id"]: s for s in spans}
    assert all(s["parent_id"] in by_id for s in spans if s["parent_id"])
    return {(s["name"], by_id[s["parent_id"]]["name"] if s["parent_id"]
             else None) for s in spans}


def test_a_run_is_one_trace_and_every_span_has_its_parent(fitted):
    run = [s for s in fitted["spans"] if not s["name"].startswith("runtime:")]
    assert len({s["trace_id"] for s in run}) == 1
    assert _parent_names(run) == {
        ("train:run", None), ("train:start_workers", "train:run"),
        ("train:placement", "train:start_workers"),
        ("train:actors", "train:start_workers"),
        ("train:backend_hook", "train:start_workers"),
        ("train:start_training", "train:run"), ("train:round", "train:run"),
        ("train:loop", "train:round"), ("train:report", "train:round"),
        ("compile:trace", "train:round"), ("compile:lower", "train:round"),
        ("compile:compile", "train:round")}
    init = [s for s in fitted["spans"] if s["name"].startswith("runtime:")]
    assert len({s["trace_id"] for s in init}) == 1
    assert _parent_names(init) == {("runtime:init", None)} | {
        (name, "runtime:init") for name in INIT_SPANS}


def test_a_round_dates_the_workers_side_from_its_messages(fitted):
    """train:report k ends where message k took the queue's slot, before the
    round that took it ends; train:loop k+1 starts there, lasts what the worker's
    clock says, and hangs under the round that waited through it."""
    spans = sorted(fitted["spans"], key=lambda s: s["start"])
    by_id = {s["span_id"]: s for s in spans}
    reports = [s for s in spans if s["name"] == "train:report"]
    loops = [s for s in spans if s["name"] == "train:loop"]
    rounds = [s for s in spans if s["name"] == "train:round"]
    for k, (report, loop) in enumerate(zip(reports, loops)):
        assert report["end"] == loop["start"]
        assert report["end"] - report["start"] == pytest.approx(
            report["call_s"], abs=1e-6)     # doubles at the epoch's size
        assert 0.0 <= report["blocked_s"] <= report["call_s"]
        assert loop["end"] - loop["start"] == pytest.approx(
            loop["loop_s"], abs=1e-6)
        assert by_id[report["parent_id"]] is rounds[k]
        assert by_id[loop["parent_id"]] is rounds[k + 1]
        # (a loop ahead of the driver queues it before the round starts)
        assert report["end"] <= rounds[k]["end"]


def test_a_second_fit_is_a_second_trace_without_per_round_spans(fitted):
    """Tracing was off for it: start-up's spans under a run id of its own,
    every parent there, nothing a round."""
    untraced = fitted["untraced"]
    assert len({s["trace_id"] for s in untraced}) == 1
    assert untraced[0]["trace_id"] != next(
        s["trace_id"] for s in fitted["spans"] if s["name"] == "train:run")
    assert _parent_names(untraced) == {
        ("train:run", None), ("train:start_workers", "train:run"),
        ("train:start_training", "train:run")} | {
        (name, "train:start_workers") for name in START_SPANS}


def test_timeline_draws_every_exported_span(fitted):
    """`ray_tpu timeline`: a slice a span, the train run's on the driver's
    lane and the worker's, the raylet's actor:* on its node's, every
    slice's parent on the page."""
    slices = [e for e in fitted["timeline"] if e["cat"] == "span"]
    exported = {s["span_id"]: s for s in fitted["all_spans"]}
    assert {e["span_id"] for e in slices} == set(exported)
    by_id = {e["span_id"]: e for e in slices}
    for e in slices:
        span = exported[e["span_id"]]
        assert e["ph"] == "X" and e["dur"] >= 0.0 and e["name"] == span["name"]
        if span.get("pid") is not None:
            assert e["pid"] == str(span["pid"])
        if e["parent_id"]:
            assert e["tid"] == by_id[e["parent_id"]]["tid"] + 1
    names = {e["name"] for e in slices}
    assert set(SPANS + INIT_SPANS + START_SPANS + ROUND_SPANS
               + COMPILE_SPANS) <= names
    launches = [e for e in slices if e["name"].startswith("actor:")]
    assert {e["name"] for e in launches} >= {"actor:spawn", "actor:ctor"}
    assert all(e["pid"].startswith("node:") and len(e["pid"]) == 13
               for e in launches)
    compiles = [e for e in slices if e["name"] == "compile:compile"]
    assert all(e["args"]["cache"] == "miss" and e["args"]["fun_name"]
               for e in compiles)


@pytest.mark.parametrize("metric", METRIC_FILES)
def test_benchmark_reader_reads_metric_file(fitted, metric):
    from benchmark.readers import program
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "program"
    value = program.read({}, spec["args"])
    row = fitted["rows"][(spec["args"]["name"],
                          spec["args"].get("tags", {}).get("Phase"))]
    if row["type"] == "histogram":
        assert value == pytest.approx(1e3 * row["sum"] / row["count"])
    else:
        assert value == row["value"]


def test_benchmark_reader_finds_nothing_in_a_program_without_the_metric():
    from benchmark.readers import program
    assert program.read({}, {"name": "ray_tpu_no_such_seconds"}) is None
    assert program.read({}, {"name": "ray_tpu_init_seconds",
                             "tags": {"Phase": "none"}}) is None


def test_report_is_a_profiler_span_only_where_jax_is_loaded(monkeypatch):
    """train:report opens a jax.profiler.TraceAnnotation on the loop's
    thread where jax is in sys.modules, and nothing where it is not: a loop
    without jax must not import it to report."""
    import sys
    from ray_tpu.train.session import TrainContext, _Session
    opened = []

    class FakeJax:
        class profiler:
            class TraceAnnotation:
                def __init__(self, name):
                    opened.append(name)

                def __enter__(self):
                    return self

                def __exit__(self, *exc):
                    return False

        Array = ()

    session = _Session(TrainContext())
    monkeypatch.setitem(sys.modules, "jax", FakeJax)
    session.report({"i": 0})
    first = session.next_result(timeout=1)
    monkeypatch.delitem(sys.modules, "jax")
    session.report({"i": 1})
    second = session.next_result(timeout=1)
    assert opened == ["train:report"]
    assert first["blocked_s"] is None and second["blocked_s"] >= 0.0
    assert second["queued_at"] >= first["queued_at"]
