"""Train's host phases in the runtime's idiom: after one tiny JaxTrainer.fit
on the CPU, the driver's metrics registry holds ray_tpu_init_seconds,
ray_tpu_init_phase_seconds{Phase}, ray_tpu_train_start_seconds{Phase} and
ray_tpu_train_report_seconds{Phase}, the flight recorder holds one tree of
train:* spans under the run's id beside runtime:init's, `ray_tpu timeline`
draws them, and the benchmark's reader (benchmark/readers/program.py) reads
each of its twenty-four metric files from that registry. A second fit of the
same driver, with tracing off, is a second trace without per-round spans. The
loop jits two functions before its first report, make_train_step's among
them, and calls one at another shape after its third: what the worker
compiled (_private/compile_cache.py's records) rides its messages into the
same gauge, ray_tpu_train_program_seconds, ray_tpu_train_recompiles_total and
compile:<phase> spans. The loop sleeps before its first jit, between its two
programs and before its first report, and says when: the records' edges lay
start_run -> the first report out as head, the compile phases, between and
first_step with nothing left over, as gauges and as spans of the worker's
lane."""

import json
import os
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPORTS = 4
SPANS = ("runtime:init", "train:start_workers", "train:start_training",
         "train:round")
INIT_SPANS = ("runtime:before_init", "runtime:gcs_start",
              "runtime:raylet_start", "runtime:connect")
START_SPANS = ("train:placement", "train:actors", "train:backend_hook")
ROUND_SPANS = ("train:round", "train:loop", "train:report")
METRIC_FILES = ("runtime_init_s", "train_workers_start_s", "train_fn_start_s",
                "train_report_blocked_ms", "train_report_poll_ms",
                "train_first_report_s", "setup_trace_s", "setup_lower_s",
                "setup_cache_load_s", "runtime_gcs_start_s",
                "runtime_raylet_start_s", "train_placement_s",
                "train_actors_ready_s", "train_report_call_ms",
                "train_report_wake_ms", "runtime_before_init_s",
                "runtime_zygote_ready_s", "train_actor_spawn_s",
                "train_before_start_s", "setup_head_s", "setup_between_s",
                "setup_first_step_s", "setup_step_program_s",
                "setup_step_after_s")
INIT_PHASES = ("gcs", "raylet", "connect")
DRIVER_PHASES = ("workers", "placement", "actors", "hook", "training",
                 "before")
REPORT_PHASES = ("blocked", "call", "wake", "poll")
COMPILE_PHASES = ("trace", "lower", "cache_load", "compile")
GAP_PHASES = ("head", "between", "first_step")
WORKER_PHASES = ("first_report", "off_thread") + COMPILE_PHASES + GAP_PHASES
COMPILE_SPANS = ("compile:trace", "compile:lower", "compile:compile")
GAP_SPANS = ("train:head", "train:between", "train:first_step")
SLEEPS = {"head": 0.2, "between": 0.1, "first_step": 0.1}


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
        stamps = [time.time()]
        import jax
        import numpy as np
        import optax
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        from ray_tpu.train.train_step import TrainState, make_train_step

        def make_state(n):
            return jax.numpy.zeros(n) + 1.0

        def loss(params, batch):
            return (params["w"] ** 2).sum() * (batch ** 2).sum()

        mesh = build_mesh(MeshConfig())     # opens the backend, as a loop's
        time.sleep(config["sleeps"]["head"])             # head does
        make_state = jax.jit(make_state, static_argnums=0)
        optimizer = optax.sgd(0.1)
        step = make_train_step(loss, optimizer, mesh, "dp")
        stamps.append(time.time())          # the first jit follows
        w = make_state(4).block_until_ready()
        stamps.append(time.time())
        time.sleep(config["sleeps"]["between"])
        stamps.append(time.time())
        state = TrainState({"w": w}, optimizer.init({"w": w}),
                           np.zeros((), np.int32))
        state, _ = step(state, np.ones(4, np.float32))
        jax.block_until_ready(state)
        stamps.append(time.time())          # start-up's last program is done
        time.sleep(config["sleeps"]["first_step"])
        stamps.append(time.time())          # the first report follows
        for i in range(config["reports"]):
            if i == 3:      # a new shape mid-run: one recompile
                state, _ = step(state, np.ones(5, np.float32))
                jax.block_until_ready(state)
            report({"i": i, "stamps": stamps})

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
            loop, train_loop_config={"reports": REPORTS, "sleeps": SLEEPS},
            scaling_config=ScalingConfig(num_workers=1)).fit()
        assert result.error is None
        assert len(result.metrics_dataframe) == REPORTS
        deadline = time.monotonic() + 20
        while True:     # spans reach the GCS with the next event flush
            spans = tracing.get_spans()
            runs = [s for s in spans if s["name"] == "train:run"]
            if (set(SPANS + COMPILE_SPANS + GAP_SPANS)
                    <= {s["name"] for s in spans}
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
    rows = {(m["name"], frozenset(m["tags"].items())): m
            for m in metrics.snapshot() if m["name"].startswith(
                ("ray_tpu_init_", "ray_tpu_train_", "ray_tpu_worker_"))}
    first, second = sorted(runs, key=lambda s: s["start"])
    return {"rows": rows, "timeline": timeline, "all_spans": spans,
            "stamps": result.metrics_dataframe[0]["stamps"],
            "spans": [s for s in spans if s["trace_id"] == second["trace_id"]
                      or s["name"].startswith("runtime:")],
            "untraced": [s for s in spans
                         if s["trace_id"] == first["trace_id"]]}


def _row(fitted, name, **tags):
    return fitted["rows"][(name, frozenset(tags.items()))]


def _start_seconds(fitted, phase):
    return _row(fitted, "ray_tpu_train_start_seconds", Phase=phase)["value"]


@pytest.mark.parametrize("name,tags", [
    ("ray_tpu_init_seconds", {}),
    ("ray_tpu_init_phase_seconds", {"Phase": "before"}),
    ("ray_tpu_init_phase_seconds", {"Phase": "gcs"}),
    ("ray_tpu_init_phase_seconds", {"Phase": "raylet"}),
    ("ray_tpu_init_phase_seconds", {"Phase": "connect"}),
    ("ray_tpu_train_start_seconds", {"Phase": "before"}),
    ("ray_tpu_train_start_seconds", {"Phase": "workers"}),
    ("ray_tpu_train_start_seconds", {"Phase": "placement"}),
    ("ray_tpu_train_start_seconds", {"Phase": "actors"}),
    ("ray_tpu_train_start_seconds", {"Phase": "hook"}),
    ("ray_tpu_train_start_seconds", {"Phase": "training"}),
    ("ray_tpu_train_start_seconds", {"Phase": "first_report"}),
    ("ray_tpu_train_start_seconds", {"Phase": "trace"}),
    ("ray_tpu_train_start_seconds", {"Phase": "lower"}),
    ("ray_tpu_train_start_seconds", {"Phase": "compile"}),
    ("ray_tpu_train_start_seconds", {"Phase": "head"}),
    ("ray_tpu_train_start_seconds", {"Phase": "between"}),
    ("ray_tpu_train_start_seconds", {"Phase": "first_step"}),
    ("ray_tpu_train_program_seconds", {"Program": "make_state",
                                       "Phase": "total"}),
    ("ray_tpu_train_program_seconds", {"Program": "_step",
                                       "Phase": "total"}),
    ("ray_tpu_train_program_seconds", {"Program": "make_state",
                                       "Phase": "after"}),
    ("ray_tpu_train_program_seconds", {"Program": "_step",
                                       "Phase": "after"}),
    ("ray_tpu_worker_zygote_ready_seconds", {})])
def test_start_up_gauges(fitted, name, tags):
    row = _row(fitted, name, **tags)
    assert row["type"] == "gauge"
    longest = 120.0
    if (name, tags.get("Phase")) == ("ray_tpu_init_phase_seconds", "before"):
        # this process's start -> init(): whatever pytest ran in it before
        # this file counts, so the bound is the process's age, not a number
        from ray_tpu._private import worker_api
        longest = time.time() - worker_api._process_start_wall()
    assert 0.0 < row["value"] < longest


@pytest.mark.parametrize("phase", ["cache_load", "off_thread"])
def test_a_run_that_loads_nothing_reads_zero(fitted, phase):
    """Programs this small stay under the persistent cache's threshold:
    nothing is loaded, and the row is there all the same. So is the row of
    the compiles on other threads than the loop's, of which there are
    none."""
    row = _row(fitted, "ray_tpu_train_start_seconds", Phase=phase)
    assert row["type"] == "gauge" and row["value"] == 0.0


def test_first_report_is_its_seven_stretches_and_nothing_else(fitted):
    """One process's clock, and a partition by construction: start_run ->
    the first report() entered is the head, the four compile phases (all
    on the loop's thread here), what lies between them and the first
    step."""
    parts = sum(_start_seconds(fitted, phase)
                for phase in COMPILE_PHASES + GAP_PHASES)
    assert parts == pytest.approx(_start_seconds(fitted, "first_report"),
                                  rel=0.01)


@pytest.mark.parametrize("phase", GAP_PHASES)
def test_a_sleep_of_the_loop_lands_in_its_stretch(fitted, phase):
    """The loop says when it was at the edges of its own stretches: from
    its entry to the first jit (the head, which holds the loop's imports and
    the backend's opening too), from the first program done to the second
    called, from the second done to the first report (which follows its
    sleep). Each gauge holds its sleep and reads within 50 ms of the loop's
    own clock."""
    (entered, first_jit, one_done, two_called, two_done,
     reporting) = fitted["stamps"]
    own = {"head": first_jit - entered, "between": two_called - one_done,
           "first_step": reporting - two_done}[phase]
    assert own >= SLEEPS[phase]
    assert own <= _start_seconds(fitted, phase) < own + 0.05


def test_a_program_has_its_rows_and_the_gaps_after_it(fitted):
    """ray_tpu_train_program_seconds: a row a program and phase, _step for
    make_train_step's; a program's `after` is the loop's thread from its
    records to the next record or to the first report, so the programs'
    sum to between + first_step, and their phases to the run's."""
    rows = {key: row["value"] for key, row in fitted["rows"].items()
            if key[0] == "ray_tpu_train_program_seconds"}
    by_program = {}
    for (_name, tags), value in rows.items():
        tags = dict(tags)
        by_program.setdefault(tags["Program"], {})[tags["Phase"]] = value
    assert set(by_program) == {"make_state", "_step"}
    for own in by_program.values():
        assert set(own) == set(COMPILE_PHASES) | {"after", "total"}
        assert own["total"] == pytest.approx(
            sum(own[p] for p in COMPILE_PHASES))
    for phase in COMPILE_PHASES:
        assert sum(own[phase] for own in by_program.values()
                   ) == pytest.approx(_start_seconds(fitted, phase))
    assert sum(own["after"] for own in by_program.values()
               ) == pytest.approx(_start_seconds(fitted, "between")
                                  + _start_seconds(fitted, "first_step"))
    assert by_program["_step"]["after"] >= SLEEPS["first_step"]
    assert by_program["make_state"]["after"] >= SLEEPS["between"]


def test_a_new_shape_mid_run_is_one_recompile(fitted):
    row = _row(fitted, "ray_tpu_train_recompiles_total")
    assert row["type"] == "counter" and row["value"] == 1.0


@pytest.mark.parametrize("name", COMPILE_SPANS)
def test_compiles_are_spans_on_the_workers_lane(fitted, name):
    """Both programs of start-up and the recompile, each with its function's
    name, between train:start_training and the run's end, on the worker's
    pid; a backend compile says whether the persistent cache had it."""
    spans = [s for s in fitted["spans"] if s["name"] == name]
    assert [s["fun_name"] for s in sorted(spans, key=lambda s: s["start"])
            ] == ["make_state", "_step", "_step"]
    shipped = next(s for s in fitted["spans"]
                   if s["name"] == "train:start_training")
    driver_pids = {s["pid"] for s in fitted["spans"]
                   if s["name"].startswith("train:") and s["name"]
                   not in ("train:loop", "train:report") + GAP_SPANS}
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
    row = _row(fitted, "ray_tpu_train_report_seconds", Phase=phase)
    assert row["type"] == "histogram"
    assert row["count"] == count
    assert 0.0 <= row["sum"] < 60.0


def test_phases_lie_inside_what_they_split(fitted):
    """Stamps of one process's clock at boundaries inside the parent's."""
    assert (sum(_row(fitted, "ray_tpu_init_phase_seconds", Phase=p)["value"]
                for p in INIT_PHASES)
            <= _row(fitted, "ray_tpu_init_seconds")["value"])
    assert (sum(_start_seconds(fitted, p)
                for p in ("placement", "actors", "hook"))
            <= _start_seconds(fitted, "workers"))
    report = {p: _row(fitted, "ray_tpu_train_report_seconds", Phase=p)["sum"]
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
    "train:run", "train:loop", "train:report", "train:before_start")
    + GAP_SPANS)
def test_span_is_in_the_flight_recorder(fitted, name):
    spans = [s for s in fitted["spans"] if s["name"] == name]
    assert spans
    assert all(s["end"] >= s["start"] for s in spans)
    if name in INIT_SPANS + START_SPANS + (
            "train:run", "train:before_start", "train:head",
            "train:first_step"):
        assert len(spans) == 1
    if name == "train:between":     # a gap of 1 ms or more, and whose it is
        assert all(s["end"] - s["start"] >= 0.001 for s in spans)
        assert {s["after"] for s in spans} <= {"make_state", "_step"}
        assert max(spans, key=lambda s: s["end"] - s["start"]
                   )["after"] == "make_state"
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
        ("compile:compile", "train:round"),
        ("train:before_start", None)} | {
        (name, "train:run") for name in GAP_SPANS}
    init = [s for s in fitted["spans"] if s["name"].startswith("runtime:")]
    assert len({s["trace_id"] for s in init}) == 1
    assert _parent_names(init) == {
        ("runtime:init", None), ("runtime:before_init", None)} | {
        (name, "runtime:init") for name in INIT_SPANS[1:]}


def test_the_drivers_spans_say_whether_jax_was_loaded(fitted):
    """Every runtime:* span and train:before_start carry `jax_loaded` at
    their two edges, so that the timeline says which stretch paid the
    driver's `import jax`: never unloaded again, and loaded (by the
    fixture's own imports at the latest) once a fit starts."""
    spans = sorted((s for s in fitted["spans"] if s["name"].startswith(
        "runtime:") or s["name"] == "train:before_start"),
        key=lambda s: (s["start"], -s["end"]))
    assert {s["name"] for s in spans} == set(INIT_SPANS) | {
        "runtime:init", "train:before_start"}
    for s in spans:
        was, is_now = s["jax_loaded"]
        assert isinstance(was, bool) and isinstance(is_now, bool)
        assert is_now or not was
    by_name = {s["name"]: s["jax_loaded"] for s in spans}
    assert by_name["runtime:before_init"][0] is False
    assert by_name["runtime:before_init"][1] == by_name["runtime:init"][0]
    assert by_name["runtime:init"][1] == by_name["train:before_start"][0]
    assert by_name["train:before_start"][1] is True


def test_before_start_runs_from_inits_return_to_starts_entry(fitted):
    """The gauge is the span: this process's last init() returned (where
    runtime:init ends) -> BackendExecutor.start entered (where train:run
    and train:start_workers start)."""
    by_name = {s["name"]: s for s in fitted["spans"]}
    before = by_name["train:before_start"]
    assert before["start"] == by_name["runtime:init"]["end"]
    assert before["end"] == by_name["train:run"]["start"]
    assert before["end"] - before["start"] == pytest.approx(
        _start_seconds(fitted, "before"), abs=1e-6)
    born = by_name["runtime:before_init"]
    assert born["end"] == by_name["runtime:init"]["start"]
    assert born["end"] - born["start"] == pytest.approx(_row(
        fitted, "ray_tpu_init_phase_seconds", Phase="before")["value"],
        abs=1e-6)
    # both lie ahead of the span they lead to, so they are roots beside it
    # and the timeline draws them whole
    drawn = {e["name"]: e["dur"] / 1e6 for e in fitted["timeline"]
             if e["cat"] == "span"}
    for span in (before, born):
        assert 0.0 < drawn[span["name"]] == pytest.approx(
            span["end"] - span["start"], abs=1e-5)


def test_the_workers_lane_has_no_hole_up_to_its_first_report(fitted):
    """`ray_tpu timeline`: on the worker's lane train:head, the compile:*
    slices, a train:between a gap of 1 ms or more and train:first_step lie
    end to end from start_run to the first report() entered, as long as the
    gauge says."""
    slices = [e for e in fitted["timeline"] if e["cat"] == "span"]
    head = next(e for e in slices if e["name"] == "train:head")
    last = next(e for e in slices if e["name"] == "train:first_step")
    edge, end = head["ts"], last["ts"] + last["dur"]
    lane = sorted((e for e in slices if e["pid"] == head["pid"]
                   and e["name"] in GAP_SPANS + COMPILE_SPANS
                   and edge <= e["ts"] < end), key=lambda e: e["ts"])
    assert {e["name"] for e in lane} == set(GAP_SPANS + COMPILE_SPANS)
    for e in lane:
        assert -1.0 <= e["ts"] - edge < 1000.0, e["name"]   # microseconds
        edge = max(edge, e["ts"] + e["dur"])
    assert edge == pytest.approx(end, abs=1.0)
    assert (end - head["ts"]) / 1e6 == pytest.approx(
        _start_seconds(fitted, "first_report"), abs=1e-4)


def test_a_cold_spawn_says_how_long_it_waited_for_the_zygote(fitted):
    """actor:spawn carries zygote_wait_s: the part of it that lay before
    the fork server's ready event, 0 for a warm pool hit."""
    spawns = [s for s in fitted["all_spans"] if s["name"] == "actor:spawn"]
    assert spawns
    for s in spawns:
        assert 0.0 <= s["zygote_wait_s"] <= s["end"] - s["start"]


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
               + COMPILE_SPANS + GAP_SPANS + ("train:before_start",)
               ) <= names
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
    row = fitted["rows"].get((spec["args"]["name"], frozenset(
        spec["args"].get("tags", {}).items())))
    if row is None:     # no create of the fits found the pool empty
        assert metric == "train_actor_spawn_s" and value is None
    elif row["type"] == "histogram":
        assert value == pytest.approx(spec["args"].get("scale", 1.0)
                                      * row["sum"] / row["count"])
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

    from ray_tpu._private import compile_cache
    monkeypatch.setattr(compile_cache, "_watching", True)   # no listener
    session = _Session(TrainContext())                      # on a fake
    monkeypatch.setitem(sys.modules, "jax", FakeJax)
    session.report({"i": 0})
    first = session.next_result(timeout=1)
    monkeypatch.delitem(sys.modules, "jax")
    session.report({"i": 1})
    second = session.next_result(timeout=1)
    assert opened == ["train:report"]
    assert first["blocked_s"] is None and second["blocked_s"] >= 0.0
    assert second["queued_at"] >= first["queued_at"]


def _first_message(loop, monkeypatch, jax_loaded_at_start_run):
    """A TrainWorker in this process runs `loop` and its first two messages
    are taken: with jax out of sys.modules at start_run, a worker that did
    not come from the zygote (which has it loaded)."""
    import sys

    import cloudpickle
    from ray_tpu._private import compile_cache
    from ray_tpu.train import session
    from ray_tpu.train.session import TrainContext
    from ray_tpu.train.worker_group import TrainWorker
    from jax._src import monitoring
    jax = sys.modules["jax"]
    if compile_cache._watching:     # an earlier test's listeners
        monitoring.unregister_scalar_listener(compile_cache._entered)
        monitoring.unregister_event_time_span_listener(compile_cache._left)
        monitoring.unregister_event_duration_listener(compile_cache._loaded)
        monkeypatch.setattr(compile_cache, "_watching", False)
    compile_cache.drain()
    if not jax_loaded_at_start_run:
        monkeypatch.delitem(sys.modules, "jax")
    worker = TrainWorker()
    cloudpickle.register_pickle_by_value(sys.modules[__name__])
    try:
        worker.start_run(cloudpickle.dumps(loop), {"jax": jax},
                         TrainContext())
    finally:
        cloudpickle.unregister_pickle_by_value(sys.modules[__name__])
    messages = [worker.poll(timeout=60), worker.poll(timeout=60)]
    worker._thread.join(timeout=60)
    session._set_session(None)
    return messages


def _jits_and_reports(config):
    """Imports jax itself (the test hands it the module: a second import
    of jax in one process is not what is under test), jits a program before
    each of its two reports."""
    import sys

    import numpy as np
    from ray_tpu.train import report
    jax = sys.modules["jax"] = config["jax"]
    for n in (3, 5):
        jax.jit(lambda x: x + 1.0)(np.ones(n, np.float32)).block_until_ready()
        report({"n": n})


START_UP_GAUGES = ("ray_tpu_init_phase_seconds",
                   "ray_tpu_train_start_seconds",
                   "ray_tpu_train_program_seconds")


@pytest.fixture
def rows_kept(fitted, monkeypatch):
    """A test that sets start-up's gauges itself leaves the fitted run's
    rows as it found them: the reader tests compare the registry with
    them."""
    from ray_tpu.train import backend_executor
    from ray_tpu.util import metrics
    monkeypatch.setattr(backend_executor, "_program_rows",
                        list(backend_executor._program_rows))
    kept = {key: row for key, row in metrics._registry.items()
            if key[0] in START_UP_GAUGES}
    yield
    with metrics._lock:
        for key in [k for k in metrics._registry if k[0] in START_UP_GAUGES]:
            del metrics._registry[key]
        metrics._registry.update(kept)


def _an_executor():
    """A BackendExecutor as start() leaves it, without a cluster."""
    from ray_tpu.train import ScalingConfig
    from ray_tpu.train.backend_executor import BackendExecutor
    executor = BackendExecutor(ScalingConfig(num_workers=1))
    executor.run_id = executor._run_span = "0" * 16
    executor.node_info_per_worker = [{"pid": 1}]
    executor._first_round = True
    return executor


def _gauge_rows():
    from ray_tpu.util import metrics
    return {(m["name"], frozenset(m["tags"].items())): m["value"]
            for m in metrics.snapshot() if m["name"] in START_UP_GAUGES}


@pytest.mark.parametrize("loaded", [True, False])
def test_a_worker_is_watched_from_start_run_or_sets_no_compile_phase(
        rows_kept, jax_cpu, monkeypatch, loaded):
    """A loop that imports jax itself, in a worker without it, is not
    watched while it compiles its first program: its first message says so,
    the fold sets first_report alone and takes an earlier run's phases and
    programs away, so that a reader finds no row where today it found 0.0.
    The watch is tried again at that first report(), so the second message
    carries the second program's records. A worker with jax loaded at
    start_run is watched from there, and its rows are a partition."""
    from benchmark.readers import program
    first, second = _first_message(_jits_and_reports, monkeypatch, loaded)
    assert first["watched"] is loaded
    assert ("compiles" in first) is loaded
    assert [r[1] for r in second["compiles"]] == ["trace", "lower", "compile"]
    assert "watched" not in second and "started_at" not in second
    executor = _an_executor()
    executor._fold_compiles({0: first}, executor._run_span)
    rows = _gauge_rows()
    start = {dict(tags)["Phase"]: value for (name, tags), value
             in rows.items() if name == "ray_tpu_train_start_seconds"}
    programs = {dict(tags)["Program"] for (name, tags) in rows
                if name == "ray_tpu_train_program_seconds"}
    args = {"name": "ray_tpu_train_start_seconds", "tags": {"Phase": "trace"}}
    assert start["first_report"] == first["first_report_s"]
    if loaded:
        assert set(WORKER_PHASES) <= set(start)
        assert sum(start[p] for p in COMPILE_PHASES + GAP_PHASES
                   # (stamps are time.time()s, 2.4e-7 s apart at today's
                   # date: a stretch of 0.08 s is not known to 1e-6 of itself)
                   ) == pytest.approx(start["first_report"], rel=1e-6,
                                      abs=1e-5)
        assert programs == {"<lambda>"}
        assert program.read({}, args) == start["trace"] > 0.0
    else:
        assert not (set(WORKER_PHASES) - {"first_report"}) & set(start)
        assert not programs
        assert program.read({}, args) is None


def test_a_run_names_sixteen_programs_and_sums_the_rest(rows_kept):
    """ray_tpu_train_program_seconds holds the 16 largest programs of a
    start-up by name and the others as `other`, and a run's rows replace
    the rows of the run before."""
    from ray_tpu._private import compile_cache
    records, at = [], 100.0
    for n in range(20):     # program n compiles for n + 1 seconds
        records.append((f"p{n}", "compile", at, at + n + 1.0, None, 1))
        at += n + 1.5
    executor = _an_executor()
    executor._set_start_up(compile_cache.partition(records, 99.0, at, 1))
    rows = {dict(tags)["Program"]: value for (name, tags), value
            in _gauge_rows().items()
            if name == "ray_tpu_train_program_seconds"
            and dict(tags)["Phase"] == "total"}
    assert rows == {"other": 1.0 + 2.0 + 3.0 + 4.0,
                    **{f"p{n}": n + 1.0 for n in range(4, 20)}}
    executor._set_start_up(compile_cache.partition(records[-1:], 99.0, at, 1))
    assert {dict(tags)["Program"] for (name, tags) in _gauge_rows()
            if name == "ray_tpu_train_program_seconds"} == {"p19"}


@pytest.mark.parametrize("gauge,case", [
    ("ray_tpu_init_phase_seconds", "no_proc"),
    ("ray_tpu_train_start_seconds", "no_init")])
def test_before_is_zero_or_absent_where_it_cannot_be_known(
        rows_kept, monkeypatch, gauge, case):
    """The two documented cases: init()'s `before` reads 0.0 where /proc
    does not say when the process started, and start()'s is not set in a
    process that called no init()."""
    from ray_tpu._private import flightrec, worker_api
    from ray_tpu.util import metrics
    metrics.remove(gauge, {"Phase": "before"})
    if case == "no_proc":
        monkeypatch.setattr(worker_api, "_process_start_wall", lambda: None)
        worker_api._record_init(flightrec.stamp(), flightrec.stamp(), {})
        assert _gauge_rows()[(gauge, frozenset({("Phase", "before")}))] == 0.0
    else:
        monkeypatch.setattr(worker_api._state, "init_returned", None)
        _an_executor()._record_before(flightrec.stamp())
        assert (gauge, frozenset({("Phase", "before")})) not in _gauge_rows()


def test_the_process_start_is_read_from_proc():
    """This process started before this module was imported, and not long
    before pytest did."""
    from ray_tpu._private import worker_api
    born = worker_api._process_start_wall()
    assert born is not None and 0.0 < time.time() - born < 7200.0
