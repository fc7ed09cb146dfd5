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
them, so listening costs a step nothing.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import List, Optional, Tuple

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

# (fun_name, phase, start, end, load_s): wall-clock seconds; load_s is the
# persistent cache's retrieval inside a `compile` span (a hit), else None.
Record = Tuple[str, str, float, float, Optional[float]]

_lock = threading.Lock()
_watching = False
_records: List[Record] = []
_open = threading.local()   # this thread's open spans: .depth, and the
                            # outermost one's .annotation and .load_s


def watch() -> None:
    """Record every compile of this process from now on. Idempotent; a no-op
    where jax is not loaded (a loop without jax must not import it)."""
    global _watching
    jax = sys.modules.get("jax")
    with _lock:
        if _watching or jax is None:
            return
        _watching = True
    jax.monitoring.register_scalar_listener(_entered)
    jax.monitoring.register_event_time_span_listener(_left)
    jax.monitoring.register_event_duration_secs_listener(_loaded)


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
                             _open.load_s if phase == "compile" else None))
