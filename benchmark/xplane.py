"""Reduction of a profiler trace (.xplane.pb) to the numbers the benchmark
reports: device busy and idle time, the ops that took most of it, time in
collective ops, each idle gap named by what the host was doing, and, with
the compiled step's HLO text, the device's time by the regions and kernels
the program names.

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

A v5e trace carries no scope: an event is named by its instruction's HLO
text and nothing else. The region comes from the compiled step, whose HLO
text gives every instruction's `op_name`, the path of `jax.named_scope`s it
was traced under (`jit(_step)/loss_and_grad/jvp(mlp)/dot_general`). The join
below (event -> instruction -> op_name -> region, phase, kernel) is a copy of
ray_tpu/util/profiling.py's `device_regions` (PR 24), kept here so that no
later PR can move the yardstick; the program supplies only its names: the
vocabulary of regions and of kernels.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark.estimators import median

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench:window"
HOST_PREFIX = "host:"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?(\.\d+)?$")
OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
MIN_GAP_NS = 20_000   # shorter gaps are the device's own op-to-op turnaround
UNATTRIBUTED = "unattributed"   # an op whose op_name holds no region
NO_PHASE = "\u2014"              # neither forward, backward nor recompute
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
REFERENCE = re.compile(r"%([\w.\-]+)")

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


def instruction_paths(hlo_text: str
                      ) -> Tuple[Dict[str, str], Dict[str, str]]:
    """instruction name -> op_name, for every instruction of every
    computation of the module (names are unique across a module): those
    that carry one, and those that inherit one. What the compiler left
    without metadata (a fusion with a tuple at its root, a layout copy, an
    async slice: 7 % of the one-chip step's time) takes the op_name nearest
    the root of the computation it calls, or else of the first of its
    operands that has one."""
    named: Dict[str, str] = {}
    inherits: Dict[str, str] = {}
    refers: Dict[str, List[str]] = {}
    last_in: Dict[str, str] = {}     # computation -> its last op_name
    computation = ""
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if not m:
            header = COMPUTATION.match(line)
            if header:
                computation = header.group(1)
            continue
        found = OP_NAME.search(line, m.end())
        if found:
            named[m.group(1)] = last_in[computation] = found.group(1)
        else:
            refers[m.group(1)] = REFERENCE.findall(line, m.end())

    def inherited(name: str) -> Optional[str]:
        if name in refers:
            to = refers.pop(name)       # popped: looked at once
            found = (next((last_in[r] for r in to if r in last_in), None)
                     or next(filter(None, map(inherited, to)), None))
            if found:
                inherits[name] = found
        return named.get(name) or inherits.get(name)

    for name in list(refers):
        inherited(name)
    return named, inherits


def last_of(path: str, vocabulary: Sequence[str]) -> Optional[str]:
    """The last component of the scope path that is in the vocabulary,
    inside whatever transforms wrap it: `transpose(jvp(mlp))` is `mlp`."""
    for part in reversed(path.split("/")):
        word = part.rsplit("(", 1)[-1].rstrip(")")
        if word in vocabulary:
            return word
    return None


def phase(path: str) -> str:
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(" in path:
        return "backward"
    return "forward" if "jvp(" in path else NO_PHASE


def self_times(events) -> List[float]:
    """Each event's duration less that of the events nested in it (a
    `while` spans its body's ops on the same line), so that every busy
    nanosecond is counted under exactly one op. `events` sorted by start,
    the longer first."""
    own = [b - a for _n, a, b in events]
    enclosing: List[int] = []
    for i, (_n, a, b) in enumerate(events):
        while enclosing and events[enclosing[-1]][2] <= a:
            enclosing.pop()
        if enclosing:
            own[enclosing[-1]] -= min(b, events[enclosing[-1]][2]) - a
        enclosing.append(i)
    return own


Table = Dict[Tuple[str, ...], List[float]]


def _chip_tables(events, named: Dict[str, str], inherits: Dict[str, str],
                 regions: Sequence[str], kernels: Sequence[str]
                 ) -> Tuple[Dict[str, float], Table, Table, Table]:
    """One chip's op line, clipped to the window and sorted by start, the
    longer first, in ns: `ops` region/op -> own ns; `rows` (region, phase)
    -> [own ns, ops]; `calls` (kernel, phase) -> [ns, calls]; `waits`
    (region,) -> [ns in collective ops]."""
    ops: Dict[str, float] = {}
    rows: Table = {}
    calls: Table = {}
    waits: Table = {}
    for (n, a, b), own in zip(events, self_times(events)):
        op = op_name(n)
        path = named.get(op) or inherits.get(op, "")
        region = last_of(path, regions) or UNATTRIBUTED
        ops[region + "/" + op] = ops.get(region + "/" + op, 0.0) + own
        row = rows.setdefault((region, phase(path)), [0.0, 0])
        row[0] += own
        row[1] += 1
        kernel = last_of(named.get(op, ""), kernels)
        if kernel:
            call = calls.setdefault((kernel, phase(path)), [0.0, 0])
            call[0] += b - a
            call[1] += 1
        if is_collective(n):
            waits.setdefault((region,), [0.0])[0] += b - a
    return ops, rows, calls, waits


def _median_table(chips: List[Table]) -> List[Tuple[Tuple[str, ...],
                                                    List[float]]]:
    """Key by key the median over the chips (a key a chip lacks is 0
    there), the largest first."""
    widths = {k: len(v) for c in chips for k, v in c.items()}
    table = {k: [median([c.get(k, [0.0] * n)[i] for c in chips])
                 for i in range(n)] for k, n in widths.items()}
    return sorted(table.items(), key=lambda kv: -kv[1][0])


def reduce_trace(trace: Dict[str, Any],
                 window: Optional[Interval] = None,
                 hlo_text: Optional[str] = None,
                 regions: Sequence[str] = (),
                 kernels: Sequence[str] = ()) -> Dict[str, Any]:
    """Numbers of the traced window, in seconds, averaged over the chips:
    window_s, busy_s, idle_pct, collective_s and collective_pct (median over
    chips of the time the op line spends in collective ops: the time the
    core waits on or runs them, not what is hidden under compute), and the
    breakdown: device_ops [[region/op, seconds]], each op's own time (without
    the ops nested in it) summed over the window on the busiest chip,
    idle_gaps [[host span, seconds]] of the first chip.

    With `hlo_text`, the text of the compiled step that ran in the window,
    and the program's vocabularies of `regions` and `kernels`, also
    `regions`: the median over the chips of
      rows        [region, phase, seconds, % of window, ops] - an op's own
                  time, so the rows sum to the busy time; phase forward |
                  backward | recompute | NO_PHASE; an op whose op_name holds
                  no region under UNATTRIBUTED;
      kernels     [kernel, phase, seconds, calls, seconds a call] - their
                  time is in rows too, in the region that calls them;
      collectives [region, seconds] on the op line.
    Without it every op is UNATTRIBUTED."""
    if window is None:
        spans = [(a, b) for n, a, b in trace["host"] if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
        window = max(spans, key=lambda s: s[1] - s[0])
    start, end = window
    if not trace["devices"]:
        raise ValueError("the trace has no TPU plane: nothing ran on a chip")
    named, inherits = instruction_paths(hlo_text or "")
    host = [(n, a, b) for n, a, b in clip(trace["host"], start, end)
            if n != WINDOW_SPAN]
    busy, collective, per_op, gap_names = [], [], {}, {}
    rows, calls, waits = [], [], []
    for index in sorted(trace["devices"]):
        events = sorted(clip(trace["devices"][index], start, end),
                        key=lambda e: (e[1], -e[2]))
        spans = [(a, b) for _n, a, b in events]
        busy.append(union_length(spans))
        collective.append(sum(b - a for n, a, b in events if is_collective(n)))
        ops, *tables = _chip_tables(events, named, inherits, regions, kernels)
        for kept, table in zip((rows, calls, waits), tables):
            kept.append(table)
        if busy[-1] == max(busy):
            per_op = ops
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
    reduced = {"window_s": window_ns / 1e9, "busy_s": busy_ns / 1e9,
               "idle_pct": 100.0 * (1.0 - busy_ns / window_ns),
               "collective_s": mid / 1e9,
               "collective_pct": 100.0 * mid / window_ns,
               "chips": len(busy),
               "breakdown": {"device_ops": top(per_op),
                             "idle_gaps": top(gap_names)}}
    if hlo_text is not None:
        reduced["regions"] = {
            "rows": [list(k) + [v[0] / 1e9, 100.0 * v[0] / window_ns, v[1]]
                     for k, v in _median_table(rows)],
            "kernels": [list(k) + [v[0] / 1e9, v[1], v[0] / 1e9 / v[1]]
                        for k, v in _median_table(calls)],
            "collectives": [list(k) + [v[0] / 1e9]
                            for k, v in _median_table(waits)]}
    return reduced
