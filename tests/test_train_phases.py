"""Train's host phases in the runtime's idiom: after one tiny JaxTrainer.fit
on the CPU, the driver's metrics registry holds ray_tpu_init_seconds,
ray_tpu_train_start_seconds{Phase} and ray_tpu_train_report_seconds{Phase},
the flight recorder holds the runtime:init / train:* spans, and the
benchmark's reader (benchmark/readers/program.py) reads each of its five
metric files from that registry."""

import json
import os
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPORTS = 4
SPANS = ("runtime:init", "train:start_workers", "train:start_training",
         "train:round")
METRIC_FILES = ("runtime_init_s", "train_workers_start_s", "train_fn_start_s",
                "train_report_blocked_ms", "train_report_poll_ms")


@pytest.fixture(scope="module")
def fitted():
    """One fit of a loop that reports REPORTS times, with tracing enabled;
    what the driver's registry and the flight recorder then hold."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, ScalingConfig, report
    from ray_tpu.util import metrics, tracing

    def loop(config):
        for i in range(config["reports"]):
            report({"i": i})

    for name in ("ray_tpu_train_report_seconds", "ray_tpu_train_start_seconds"):
        for phase in ("blocked", "poll", "workers", "training"):
            metrics.remove(name, {"Phase": phase})   # another test's fit
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
            if set(SPANS) <= {s["name"] for s in spans}:
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
    ("ray_tpu_train_start_seconds", "training")])
def test_start_up_gauges(fitted, name, phase):
    row = fitted["rows"][(name, phase)]
    assert row["type"] == "gauge"
    assert 0.0 < row["value"] < 120.0


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
