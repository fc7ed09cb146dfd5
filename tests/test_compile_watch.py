"""_private/compile_cache.py's watch: every compile of a process as records
(fun_name, phase, start, end, load_s, thread), phase trace | lower | compile,
one a thread's outermost span, and as compile:<phase> spans of the profiler's
own trace; partition() lays a thread's records out between two stamps. All on
the CPU."""

import glob
import os
import threading
import time

import numpy as np
import pytest

from ray_tpu._private import compile_cache

PHASES = ("trace", "lower", "compile")


@pytest.fixture
def watched(jax_cpu, tmp_path):
    """jax with the watch on and its list empty; the persistent cache in a
    directory of the test's own, taking every program however small."""
    from jax.experimental.compilation_cache import compilation_cache
    config = jax_cpu.config
    before = (config.jax_compilation_cache_dir,
              config.jax_persistent_cache_min_compile_time_secs,
              config.jax_persistent_cache_min_entry_size_bytes)
    config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    compile_cache.watch()
    compile_cache.drain()
    yield jax_cpu
    config.update("jax_compilation_cache_dir", before[0])
    config.update("jax_persistent_cache_min_compile_time_secs", before[1])
    config.update("jax_persistent_cache_min_entry_size_bytes", before[2])
    compilation_cache.reset_cache()
    compile_cache.drain()


def _nested(jax):
    """A jitted function that calls a jitted function (and jnp's own)."""
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.where(x > 0, x, 0.0) + 1.0

    def outer(x):
        return inner(x) * 2.0 + inner(-x)

    return jax.jit(outer)


X = np.ones(7, np.float32)      # numpy: making it compiles nothing


@pytest.mark.parametrize("how", ["call", "lower_compile"])
def test_one_record_a_phase_under_the_outer_name(watched, how):
    """The inner functions' traces lie inside the outer's and are not kept:
    three records, disjoint and in order; jit(f).lower().compile() gives
    what a call of jit(f) gives."""
    outer = _nested(watched)
    if how == "call":
        outer(X).block_until_ready()
    else:
        outer.lower(X).compile()
    records = compile_cache.drain()
    assert [r[1] for r in records] == list(PHASES)
    assert {r[0] for r in records} == {"outer"}
    for (_n, _p, start, end, _l, _t), (_n2, _p2, later, _e2, _l2, _t2) in zip(
            records, records[1:]):
        assert start <= end <= later
    assert {r[5] for r in records} == {threading.get_ident()}
    assert compile_cache.drain() == []


def test_a_phase_is_the_outer_span(watched):
    """The trace record spans what jax.monitoring reports for the outer
    function itself, the inner traces inside it."""
    spans = []

    def listener(event, start, end, fun_name="", **_):
        if event.endswith("jaxpr_trace_duration"):
            spans.append((fun_name, start, end))

    watched.monitoring.register_event_time_span_listener(listener)
    try:
        _nested(watched)(X).block_until_ready()
    finally:
        watched.monitoring.unregister_event_time_span_listener(listener)
    trace = next(r for r in compile_cache.drain() if r[1] == "trace")
    assert len(spans) > 3                   # inner, where, add, multiply ..
    assert ("outer", trace[2], trace[3]) == spans[-1]
    assert all(trace[2] <= a and b <= trace[3] for _n, a, b in spans)


def test_cached_calls_add_no_record(watched):
    outer = _nested(watched)
    outer(X).block_until_ready()
    compile_cache.drain()
    for _ in range(1000):
        outer(X)
    assert compile_cache.drain() == []


def test_a_new_shape_is_three_more_records(watched):
    outer = _nested(watched)
    outer(X).block_until_ready()
    compile_cache.drain()
    outer(np.ones(9, np.float32)).block_until_ready()
    assert [r[1] for r in compile_cache.drain()] == list(PHASES)


def test_a_load_from_the_persistent_cache_rides_the_compile_record(watched):
    """A miss has no load_s; the same program compiled again in a process
    that has dropped its executable is a hit, with the retrieval's seconds
    inside the compile span."""
    _nested(watched)(X).block_until_ready()
    miss = next(r for r in compile_cache.drain() if r[1] == "compile")
    assert miss[4] is None
    watched.clear_caches()
    _nested(watched)(X).block_until_ready()
    records = compile_cache.drain()
    hit = next(r for r in records if r[1] == "compile")
    assert hit[4] is not None and 0.0 < hit[4] <= hit[3] - hit[2]
    assert all(r[4] is None for r in records if r[1] != "compile")


def test_a_second_watch_registers_nothing(watched):
    from jax._src import monitoring
    counts = (len(monitoring.get_scalar_listeners()),
              len(monitoring.get_event_time_span_listeners()),
              len(monitoring.get_event_duration_listeners()),
              len(monitoring.get_event_listeners()))
    compile_cache.watch()
    assert counts == (len(monitoring.get_scalar_listeners()),
                      len(monitoring.get_event_time_span_listeners()),
                      len(monitoring.get_event_duration_listeners()),
                      len(monitoring.get_event_listeners()))
    _nested(watched)(X).block_until_ready()
    assert len(compile_cache.drain()) == 3


def test_watch_without_jax_is_a_no_op(monkeypatch):
    """A loop without jax must not import it to be watched."""
    import sys
    monkeypatch.setattr(compile_cache, "_watching", False)
    monkeypatch.delitem(sys.modules, "jax")
    compile_cache.watch()
    assert compile_cache._watching is False
    assert "jax" not in sys.modules


def test_threads_keep_their_own_depth(watched):
    """Two threads compiling at once: each thread's outermost spans are
    kept, three a thread."""
    errors = []

    def compile_one(n):
        try:
            _nested(watched)(np.ones(n, np.float32)).block_until_ready()
        except Exception as e:  # noqa: BLE001 — shown by the assert below
            errors.append(e)

    threads = [threading.Thread(target=compile_one, args=(n,))
               for n in (11, 13)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors and not any(t.is_alive() for t in threads)
    records = compile_cache.drain()
    assert sorted(r[1] for r in records) == sorted(PHASES * 2)
    assert {r[5] for r in records} == {t.ident for t in threads}


def test_a_compile_on_a_second_thread_is_counted_apart(watched):
    """The loop's thread compiles one program while another thread compiles
    one too: the four phases hold both, `off_thread` the other thread's
    seconds, and the loop's thread is still a partition: head + its own
    records + between + first_step is the whole stretch."""
    started = time.time()
    aside = threading.Thread(
        target=lambda: _nested(watched)(
            np.ones(17, np.float32)).block_until_ready())
    aside.start()
    _nested(watched)(np.ones(19, np.float32)).block_until_ready()
    aside.join(timeout=60)
    reported = time.time()
    records = compile_cache.drain()
    laid = compile_cache.partition(records, started, reported,
                                   threading.get_ident())
    seconds = laid.seconds
    other = sum(r[3] - r[2] for r in records if r[5] == aside.ident)
    assert len(records) == 6 and other > 0.0
    assert seconds["off_thread"] == pytest.approx(other)
    assert sum(seconds[p] for p in compile_cache.PHASES) == pytest.approx(
        sum(r[3] - r[2] for r in records))
    assert (seconds["head"] + seconds["between"] + seconds["first_step"]
            + sum(seconds[p] for p in compile_cache.PHASES)
            - seconds["off_thread"]) == pytest.approx(reported - started)
    assert [name for name, _a, _b, _after in laid.gaps] == [
        "head", "between", "between", "first_step"]


def _record(name, phase, start, end, load_s=None, thread=1):
    return (name, phase, start, end, load_s, thread)


@pytest.mark.parametrize("records,seconds,programs,gaps", [
    # nothing compiled: the whole stretch is the head
    ([], {"head": 10.0}, {}, [("head", 100.0, 110.0, None)]),
    # two programs, the second loaded from the cache: every gap is named,
    # and is the `after` of the program whose record it follows
    ([_record("init", "trace", 102.0, 103.0),
      _record("init", "lower", 103.5, 104.0),
      _record("init", "compile", 104.0, 105.0),
      _record("_step", "trace", 106.0, 107.0),
      _record("_step", "lower", 107.0, 107.5),
      _record("_step", "compile", 107.5, 109.0, load_s=1.0)],
     {"head": 2.0, "trace": 2.0, "lower": 1.0, "compile": 1.5,
      "cache_load": 1.0, "between": 1.5, "first_step": 1.0},
     {"init": {"trace": 1.0, "lower": 0.5, "compile": 1.0, "after": 1.5},
      "_step": {"trace": 1.0, "lower": 0.5, "compile": 0.5,
                "cache_load": 1.0, "after": 1.0}},
     [("head", 100.0, 102.0, None), ("between", 103.0, 103.5, "init"),
      ("between", 104.0, 104.0, "init"), ("between", 105.0, 106.0, "init"),
      ("between", 107.0, 107.0, "_step"), ("between", 107.5, 107.5, "_step"),
      ("first_step", 109.0, 110.0, "_step")]),
    # a record of another thread is in the phases and in no gap
    ([_record("aside", "compile", 101.0, 108.0, thread=2),
      _record("_step", "compile", 103.0, 104.0)],
     {"head": 3.0, "compile": 8.0, "off_thread": 7.0, "first_step": 6.0},
     {"aside": {"compile": 7.0}, "_step": {"compile": 1.0, "after": 6.0}},
     [("head", 100.0, 103.0, None), ("first_step", 104.0, 110.0, "_step")]),
])
def test_partition_names_every_stretch(records, seconds, programs, gaps):
    laid = compile_cache.partition(records, 100.0, 110.0, thread=1)
    assert {k: v for k, v in laid.seconds.items() if v} == seconds
    assert {name: {k: v for k, v in own.items() if v}
            for name, own in laid.programs.items()} == programs
    assert laid.gaps == gaps
    assert (sum(laid.seconds.values()) - 2 * laid.seconds["off_thread"]
            ) == 10.0


def test_a_compile_is_a_span_of_the_profilers_trace(watched, tmp_path):
    """Under a device_trace each phase shows as a compile:<phase> span of
    the host plane, among the spans _load_xplane keeps."""
    from ray_tpu.util import profiling
    with profiling.device_trace(str(tmp_path / "trace")):
        _nested(watched)(X).block_until_ready()
    path, = glob.glob(os.path.join(str(tmp_path / "trace"), "plugins",
                                   "profile", "*", "*.xplane.pb"))
    host = profiling._load_xplane(path)["host"]
    names = [n for n, _a, _b in host]
    assert [n for n in names if n.startswith("compile:")] == [
        "compile:" + phase for phase in PHASES]
    stretch = next(h for h in host if h[0] == profiling.STRETCH_SPAN)
    assert all(stretch[1] <= a and b <= stretch[2]
               for n, a, b in host if n.startswith("compile:"))
