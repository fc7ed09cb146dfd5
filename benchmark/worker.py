"""What the benchmark's code does inside the process that holds the chip,
for a train cell and a serve cell alike: open the device, count compiles,
read the memory peak, and trace a stretch of the steady state."""

from __future__ import annotations

import os
import shutil
import time
from typing import Any, Dict

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def open_device(platform: str, chips: int) -> Dict[str, Any]:
    """Open this process's JAX backend; refuse another platform or fewer
    chips than the cell asks for."""
    import jax
    # every program goes to the persistent cache, the sub-second ones too:
    # a warm run then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    t0 = time.perf_counter()
    devices = jax.devices()
    facts = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices), "pid": os.getpid(),
             "worker_open_s": time.perf_counter() - t0}
    if facts["platform"] != platform:
        raise RuntimeError(f"worker {os.getpid()} runs JAX on "
                           f"{facts['platform']!r}, the cell needs {platform!r}")
    if facts["count"] < chips:
        raise RuntimeError(f"worker sees {facts['count']} devices, "
                           f"the cell needs {chips}")
    return facts


class Timeline:
    """Marks of set-up, in seconds after the first, which is wall time."""

    def __init__(self, label: str):
        self.entered = time.time()
        self.marks = [[label, self.entered]]

    def mark(self, what: str) -> None:
        self.marks.append([what, time.time() - self.entered])


class CompileWatch:
    """Counts what jax.monitoring says about compiles in this process:
    every backend compile (a cache load is one too) with its seconds, and
    the persistent cache's hits and misses."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.misses = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += seconds

    def _event(self, event: str, **_):
        if event == CACHE_MISS_EVENT:
            self.misses += 1
        elif event == CACHE_HIT_EVENT:
            self.hits += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_misses": self.misses, "cache_hits": self.hits}


def memory_peak(chips: int, program) -> Dict[str, Any]:
    """Peak bytes on the fullest of the cell's devices while `program` (the
    compiled step or forward) runs: what the allocator reports as its peak,
    or what is in use between two runs plus the program's temporaries and
    the outputs it does not write over its arguments, whichever is more.
    On the v5e runtime the allocator's own peak left a running program's
    temporaries out (PR 23: 3.06 GB against 2.3 GB of state + 6.6 GB of
    temporaries), so it alone would under-report."""
    import jax
    fullest: Dict[str, Any] = {}
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if stats.get("bytes_in_use", 0) >= fullest.get("bytes_in_use", -1):
            fullest = dict(stats)
    analysis = program.memory_analysis()
    temp = getattr(analysis, "temp_size_in_bytes", 0)
    fresh_output = max(getattr(analysis, "output_size_in_bytes", 0)
                       - getattr(analysis, "alias_size_in_bytes", 0), 0)
    running = fullest.get("bytes_in_use", 0) + temp + fresh_output
    return {"memory_peak_bytes": max(fullest.get("peak_bytes_in_use", 0),
                                     running if fullest else 0),
            "memory_limit_bytes": fullest.get("bytes_limit", 0),
            "allocator": fullest, "program_temp_bytes": temp,
            "program_fresh_output_bytes": fresh_output}


class Tracer:
    """start() ... stop() around a stretch of steady state; stop() reduces
    the trace in this process and removes the files."""

    def __init__(self, log_dir: str, platform: str):
        self.log_dir = log_dir
        self.platform = platform
        self._window = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # the Python tracer slows the host
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self._window = jax.profiler.TraceAnnotation("bench:window")
        self._window.__enter__()

    def stop(self, compiled=None) -> Dict[str, Any]:
        """`compiled`: the jax.stages.Compiled of the program that ran in
        the stretch. With it the reduction also joins the device's ops to
        the regions and kernels the program names (xplane.reduce_trace's
        `regions`), and says what that cost: after the window, in a traced
        run only."""
        # the window closes before anything is imported: an import inside
        # it is host time the device waits through, and reads as idle
        self._window.__exit__(None, None, None)
        import jax
        from benchmark import xplane
        from ray_tpu.util import profiling
        jax.profiler.stop_trace()
        path = xplane.newest_trace(self.log_dir)
        trace = xplane.load(path)
        shutil.rmtree(self.log_dir, ignore_errors=True)
        if self.platform != "tpu":
            # a rehearsal on the CPU: the spans are there, a device is not,
            # and no device number is made up
            if not any(n == xplane.WINDOW_SPAN for n, _a, _b in trace["host"]):
                raise RuntimeError("the trace lacks the bench:window span")
            return None
        if compiled is None:
            return xplane.reduce_trace(trace)
        t0 = time.perf_counter()
        hlo_text = compiled.as_text()
        reduced = xplane.reduce_trace(trace, hlo_text=hlo_text,
                                      regions=profiling.REGIONS,
                                      kernels=profiling.KERNELS)
        reduced["regions"].update(hlo_text_bytes=len(hlo_text),
                                  reduce_s=time.perf_counter() - t0)
        return reduced
