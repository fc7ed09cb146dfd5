"""Reduction of a profiler trace (.xplane.pb) to the numbers the benchmark
reports: device busy and idle time, the ops that took most of it, time in
collective ops, and each idle gap named by what the host was doing.

What a v5e trace holds (looked at by hand, fixtures/trace.xplane.pb): one
plane "/device:TPU:<n>" per chip whose line "XLA Ops" has one event per
executed HLO op, in order, never overlapping, named by the op's whole HLO
text ("%fusion.3 = bf16[...] fusion(...)"); and a plane "/host:CPU" with a
line per thread, on which jax.profiler.TraceAnnotation spans appear under
their own names. Device and host timestamps share one time base to within
about a millisecond (the fixture's first op starts 1 ms before the host
span that launched it), so a window is taken some seconds long.

The benchmark's processes name their spans "bench:window" (the traced
stretch) and "host:<what>" (what a host thread was doing). Only the process
that holds the chip can trace it, and it reduces its own trace.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench:window"
HOST_PREFIX = "host:"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?(\.\d+)?$")
OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
MIN_GAP_NS = 20_000   # shorter gaps are the device's own op-to-op turnaround

Interval = Tuple[float, float]


def op_name(event_name: str) -> str:
    """'%fusion.3 = bf16[..] fusion(..)' -> 'fusion.3'."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def opcode(event_name: str) -> str:
    """The HLO opcode: the first lower-case word before a '(' after the
    result's shape (layouts write their tiles as T(8,128), in capitals)."""
    m = OPCODE.search(event_name.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def is_collective(event_name: str) -> bool:
    """By opcode, or by name where the compiler fused the collective."""
    return bool(COLLECTIVE.match(opcode(event_name))
                or COLLECTIVE.match(op_name(event_name)))


def newest_trace(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> Dict[str, Any]:
    """{'devices': {n: [(name, start_ns, end_ns)]}, 'host': [(name, s, e)]}
    with host spans limited to the benchmark's own names."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name.startswith(HOST_PREFIX):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return {"devices": devices, "host": host}


def union_length(intervals: List[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def gaps_in(intervals: List[Interval], start: float, end: float
            ) -> List[Interval]:
    """The parts of [start, end] that no interval covers."""
    out, at = [], start
    for a, b in sorted(intervals):
        if a > at:
            out.append((at, min(a, end)))
        at = max(at, b)
        if at >= end:
            break
    if at < end:
        out.append((at, end))
    return [(a, b) for a, b in out if b > a]


def clip(events, start: float, end: float):
    return [(n, max(a, start), min(b, end)) for n, a, b in events
            if b > start and a < end]


def busiest_host_span(gap: Interval, host) -> str:
    """The host span that overlaps most of the gap."""
    best, best_overlap = "unannotated", 0.0
    for name, a, b in host:
        overlap = min(b, gap[1]) - max(a, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce_trace(trace: Dict[str, Any],
                 window: Optional[Interval] = None) -> Dict[str, Any]:
    """Numbers of the traced window, in seconds, averaged over the chips:
    window_s, busy_s, idle_pct, collective_s and collective_pct (median over
    chips of the time the op line spends in collective ops: the time the
    core waits on or runs them, not what is hidden under compute), and the
    breakdown: device_ops [[op, seconds]] summed over the window on the
    busiest chip, idle_gaps [[host span, seconds]] of the first chip."""
    if window is None:
        spans = [(a, b) for n, a, b in trace["host"] if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
        window = max(spans, key=lambda s: s[1] - s[0])
    start, end = window
    if not trace["devices"]:
        raise ValueError("the trace has no TPU plane: nothing ran on a chip")
    host = [(n, a, b) for n, a, b in clip(trace["host"], start, end)
            if n != WINDOW_SPAN]
    busy, collective, per_op, gap_names = [], [], {}, {}
    for index in sorted(trace["devices"]):
        events = clip(trace["devices"][index], start, end)
        spans = [(a, b) for _n, a, b in events]
        busy.append(union_length(spans))
        collective.append(sum(b - a for n, a, b in events if is_collective(n)))
        if busy[-1] == max(busy):
            per_op = {}
            for n, a, b in events:
                per_op[op_name(n)] = per_op.get(op_name(n), 0.0) + (b - a)
        if index == min(trace["devices"]):
            for gap in gaps_in(spans, start, end):
                if gap[1] - gap[0] >= MIN_GAP_NS:
                    name = busiest_host_span(gap, host)
                    gap_names[name] = gap_names.get(name, 0.0) + gap[1] - gap[0]
    window_ns = end - start
    collective.sort()
    mid = collective[len(collective) // 2] if len(collective) % 2 else 0.5 * (
        collective[len(collective) // 2 - 1] + collective[len(collective) // 2])
    busy_ns = sum(busy) / len(busy)

    def top(table):
        return [[k, v / 1e9] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": window_ns / 1e9, "busy_s": busy_ns / 1e9,
            "idle_pct": 100.0 * (1.0 - busy_ns / window_ns),
            "collective_s": mid / 1e9,
            "collective_pct": 100.0 * mid / window_ns,
            "chips": len(busy),
            "breakdown": {"device_ops": top(per_op),
                          "idle_gaps": top(gap_names)}}
