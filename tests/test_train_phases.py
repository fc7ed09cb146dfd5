"""Train's host phases in the runtime's idiom: after one tiny JaxTrainer.fit
on the CPU, the driver's metrics registry holds ray_tpu_init_seconds,
ray_tpu_train_start_seconds{Phase} and ray_tpu_train_report_seconds{Phase},
the flight recorder holds the runtime:init / train:* spans, and the
benchmark's reader (benchmark/readers/program.py) reads each of its nine
metric files from that registry. The loop jits two functions before its
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
METRIC_FILES = ("runtime_init_s", "train_workers_start_s", "train_fn_start_s",
                "train_report_blocked_ms", "train_report_poll_ms",
                "train_first_report_s", "setup_trace_s", "setup_lower_s",
                "setup_cache_load_s")
DRIVER_PHASES = ("workers", "training")
WORKER_PHASES = ("first_report", "trace", "lower", "cache_load", "compile")
COMPILE_SPANS = ("compile:trace", "compile:lower", "compile:compile")


@pytest.fixture(scope="module")
def fitted():
    """One fit of a loop that reports REPORTS times, with tracing enabled;
    what the driver's registry and the flight recorder then hold."""
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

    for name in ("ray_tpu_train_report_seconds", "ray_tpu_train_start_seconds"):
        for phase in ("blocked", "poll") + DRIVER_PHASES + WORKER_PHASES:
            metrics.remove(name, {"Phase": phase})   # another test's fit
    metrics.remove("ray_tpu_train_recompiles_total")
    tracing.enable()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        result = JaxTrainer(
            loop, train_loop_config={"reports": REPORTS},
            scaling_config=ScalingConfig(num_workers=1)).fit()
        assert result.error is None
        assert len(result.metrics_dataframe) == REPORTS
        deadline = time.monotonic() + 20
        while True:     # spans reach the GCS with the next event flush
            spans = tracing.get_spans()
            if (set(SPANS + COMPILE_SPANS) <= {s["name"] for s in spans}
                    and sum(s["name"] == "compile:compile"
                            for s in spans) >= 3):
                break
            assert time.monotonic() < deadline, sorted(
                {s["name"] for s in spans})
            time.sleep(0.2)
    finally:
        tracing.disable()
        ray_tpu.shutdown()
    rows = {(m["name"], m["tags"].get("Phase")): m
            for m in metrics.snapshot() if m["name"].startswith(
                ("ray_tpu_init_", "ray_tpu_train_"))}
    return {"rows": rows, "spans": spans}


@pytest.mark.parametrize("name,phase", [
    ("ray_tpu_init_seconds", None),
    ("ray_tpu_train_start_seconds", "workers"),
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
                   if s["name"].startswith("train:")}
    for s in spans:
        assert shipped["start"] <= s["start"] <= s["end"]
        assert s["pid"] not in driver_pids
        assert s.get("cache") == ("miss" if name == "compile:compile"
                                  else None)


@pytest.mark.parametrize("phase,count", [
    # a report's wait is known once its put returns: it rides the next
    # message, so the `done` message brings the last one
    ("blocked", REPORTS),
    ("poll", REPORTS + 1)])
def test_report_histogram(fitted, phase, count):
    row = fitted["rows"][("ray_tpu_train_report_seconds", phase)]
    assert row["type"] == "histogram"
    assert row["count"] == count
    assert 0.0 <= row["sum"] < 60.0


@pytest.mark.parametrize("name", SPANS)
def test_span_is_in_the_flight_recorder(fitted, name):
    spans = [s for s in fitted["spans"] if s["name"] == name]
    assert spans
    assert all(s["end"] >= s["start"] for s in spans)
    if name == "train:round":       # one a round, and the closing one
        assert len(spans) == REPORTS + 1


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
