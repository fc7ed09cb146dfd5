"""Where JAX's persistent compilation cache lives — decided here and
nowhere else.

Where ``JAX_COMPILATION_CACHE_DIR`` is set from outside, that directory is
used. Where it is not, the cache is one fixed, git-ignored directory at the
root of the checkout: the path is part of what a cache lookup depends on,
so it never comes from ``tempfile``, a pid or the time. Either way the
answer is exported into ``os.environ``, which is how every process agrees:
JAX reads the variable when it is imported, and workers inherit the
raylet's environment (``Raylet._worker_env``).

What a compile costs is seen here too: ``watch()`` listens to the spans
``jax.monitoring`` emits around a function's trace, its lowering to MLIR and
its backend compile (with the persistent cache's load inside the last), and
``drain()`` hands them over. A call of a cached executable emits none of
them, so listening costs a step nothing. ``partition()`` lays one thread's
records end to end between two stamps of the same clock, so that a stretch
of start-up is a phase, a program, or a named gap between two of them.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Dict, List, NamedTuple, Optional, Tuple

_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def export_compile_cache_dir() -> str:
    """Export the cache directory into this process's environment (a value
    given from outside wins) and return it. Call before importing jax in a
    process that compiles; a raylet calls it before it snapshots the
    environment its workers get."""
    return os.environ.setdefault(_ENV_VAR, os.path.join(_CHECKOUT,
                                                       ".jax_cache"))


# jax.monitoring's event -> the phase of a compile it times. Each is a
# scalar at the entry and a time span (time.time() start and end, fun_name=)
# at the exit of one `dispatch.log_elapsed_time` block.
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_MAX_RECORDS = 1024

# (fun_name, phase, start, end, load_s, thread): wall-clock seconds; load_s
# is the persistent cache's retrieval inside a `compile` span (a hit), else
# None; thread is the compiling thread's threading.get_ident().
Record = Tuple[str, str, float, float, Optional[float], int]

# What a record's seconds are counted as: `compile` is a backend compile
# less the load from the persistent cache inside it, `cache_load` that load.
PHASES = ("trace", "lower", "cache_load", "compile")
# What partition() splits a thread's stretch into, in seconds: the four over
# every record, those of other threads' records apart, and the thread's own
# time around its records.
STRETCHES = PHASES + ("off_thread", "head", "between", "first_step")

_lock = threading.Lock()
_watching = False
_records: List[Record] = []
_open = threading.local()   # this thread's open spans: .depth, and the
                            # outermost one's .annotation and .load_s


def watch() -> bool:
    """Record every compile of this process from now on, and say whether
    they are. Idempotent; a no-op where jax is not loaded (a loop without
    jax must not import it), to be tried again once it is."""
    global _watching
    if _watching:
        return True
    jax = sys.modules.get("jax")
    with _lock:
        if _watching or jax is None:
            return _watching
        _watching = True
    jax.monitoring.register_scalar_listener(_entered)
    jax.monitoring.register_event_time_span_listener(_left)
    jax.monitoring.register_event_duration_secs_listener(_loaded)
    return True


def drain() -> List[Record]:
    """The records since the last call, oldest first; the list is emptied."""
    global _records
    if not _records:
        return []
    with _lock:
        out, _records = _records, []
    return out


def _entered(event: str, _value, **_kwargs) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    _open.depth = depth = getattr(_open, "depth", 0) + 1
    # A jitted function that calls jitted functions traces each of them
    # inside its own trace (hundreds for an unrolled model), and an eager op
    # inside a trace compiles there: only a thread's outermost span is kept,
    # so a phase's seconds are a sum of disjoint spans. It is also a span of
    # the profiler's own trace, beside the device's ops (free where no
    # profiler session runs).
    if depth == 1:
        _open.load_s = None
        _open.annotation = sys.modules["jax"].profiler.TraceAnnotation(
            "compile:" + phase)
        _open.annotation.__enter__()


def _loaded(event: str, seconds: float, **_kwargs) -> None:
    if event == _LOAD_EVENT and getattr(_open, "depth", 0) == 1:
        _open.load_s = seconds


def _left(event: str, start: float, end: float, fun_name: str = "",
          **_kwargs) -> None:
    phase = _PHASES.get(event)
    depth = getattr(_open, "depth", 0)
    if phase is None or not depth:      # a span that was open at watch()
        return
    _open.depth = depth - 1
    if depth > 1:
        return
    _open.annotation.__exit__(None, None, None)
    # lowering and compile name the module, `jit(f)`; the trace names `f`
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]
    with _lock:
        if len(_records) < _MAX_RECORDS:
            _records.append((fun_name, phase, start, end,
                             _open.load_s if phase == "compile" else None,
                             threading.get_ident()))


class Partition(NamedTuple):
    """One thread's stretch [started, reported] laid out by its records."""
    # STRETCHES: PHASES over every record handed in, `off_thread` (the
    # seconds of the records of other threads, which the four hold too) and
    # the thread's own `head`, `between` and `first_step`: started + head +
    # the thread's records + between + first_step == reported, by
    # construction.
    seconds: Dict[str, float]
    # fun_name -> PHASES and `after`: from each of its records' end (the
    # thread's) to the next record's start, or to `reported` after the last.
    programs: Dict[str, Dict[str, float]]
    # the stretches no record covers, in order: (head | between |
    # first_step, start, end, the fun_name of the record before it or None)
    gaps: List[Tuple[str, float, float, Optional[str]]]


def partition(records: List[Record], started: float, reported: float,
              thread: int) -> Partition:
    """Lay the records of `thread` end to end between two stamps of their
    clock, time.time(): `head` runs from `started` to the first record (all
    the way to `reported` where there is none), `between` sums the gaps from
    one record's end to the next one's start, inside a program (trace ->
    lower -> compile) and from one program to the next, and `first_step`
    runs from the last record's end to `reported`. A thread's outermost
    spans are disjoint and in order, so nothing is counted twice and nothing
    is left over."""
    seconds = dict.fromkeys(STRETCHES, 0.0)
    programs: Dict[str, Dict[str, float]] = {}
    gaps = []
    edge, before = started, None

    def gap(name: str, until: float) -> None:
        seconds[name] += until - edge
        gaps.append((name, edge, until, before))
        if before is not None:
            programs[before]["after"] += until - edge

    for fun_name, phase, start, end, load_s, ident in records:
        own = programs.setdefault(
            fun_name, dict.fromkeys(PHASES + ("after",), 0.0))
        load = (load_s or 0.0) if phase == "compile" else 0.0
        for key, value in (("cache_load", load),
                           (phase, max(0.0, end - start - load))):
            seconds[key] += value
            own[key] += value
        if ident != thread:
            seconds["off_thread"] += end - start
            continue
        gap("head" if before is None else "between", start)
        edge, before = end, fun_name
    gap("head" if before is None else "first_step", reported)
    return Partition(seconds, programs, gaps)
