"""On-demand in-process profiling: CPU stack sampling + heap snapshots.

Reference parity: dashboard/modules/reporter/profile_manager.py (:75 CPU
via py-spy, :186 memory via memray) — the reference shells out to external
profilers; here the equivalents are built in (no dependencies): a
sampling profiler over sys._current_frames() and tracemalloc heap
snapshots, exposed as worker RPCs ("profile_cpu", "profile_memory") and
surfaced through the state API / dashboard.

The device half: `device_trace` records a jax.profiler trace of a stretch
of steady state, `device_regions` reduces it to a table by the regions the
model and the train step name with `jax.named_scope` (REGIONS) and by the
flash kernels' names (KERNELS). A v5e trace carries no scope: an event on a
chip's "XLA Ops" line is named by its instruction's HLO text and nothing
else, so the region comes from the compiled step, whose HLO text gives every
instruction's `op_name` (the scope path it was traced under).
`device_trace` also dates its stretch on the wall clock, so `device_slices`
can lay the same ops, by region, beside the flight recorder's spans
(`ray_tpu timeline --device-trace`).
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import statistics
import sys
import threading
import time
from collections import Counter
from typing import Any, Dict, List, Optional, Tuple


def sample_cpu(duration_s: float = 2.0, interval_s: float = 0.01,
               top: int = 40) -> dict:
    """Sample all threads' stacks for duration_s; returns aggregated stacks
    sorted by sample count (a textual flamegraph: leaf-first frames joined
    with ';')."""
    counts: Counter = Counter()
    thread_names = {}
    me = threading.get_ident()
    n_samples = 0
    deadline = time.monotonic() + duration_s
    for t in threading.enumerate():
        thread_names[t.ident] = t.name
    while time.monotonic() < deadline:
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue  # the sampler itself is noise
            stack: List[str] = []
            f = frame
            depth = 0
            while f is not None and depth < 60:
                code = f.f_code
                stack.append(f"{code.co_name} "
                             f"({code.co_filename.rsplit('/', 1)[-1]}"
                             f":{f.f_lineno})")
                f = f.f_back
                depth += 1
            key = (thread_names.get(ident, str(ident)),
                   ";".join(reversed(stack)))
            counts[key] += 1
        n_samples += 1
        time.sleep(interval_s)
    stacks = [{"thread": th, "stack": st, "count": c}
              for (th, st), c in counts.most_common(top)]
    return {"duration_s": duration_s, "samples": n_samples,
            "stacks": stacks}


_tracemalloc_started = False


def snapshot_memory(top: int = 30, group_by: str = "lineno") -> dict:
    """Heap snapshot via tracemalloc. The first call starts tracing and
    reports only allocations made AFTER it (tracemalloc semantics) — call
    once early, then again to diff, like memray attach."""
    import tracemalloc
    global _tracemalloc_started
    if not tracemalloc.is_tracing():
        tracemalloc.start(8)
        _tracemalloc_started = True
        return {"started": True, "note": "tracing started; snapshot again "
                                         "to see allocations", "top": []}
    snap = tracemalloc.take_snapshot()
    stats = snap.statistics(group_by)[:top]
    current, peak = tracemalloc.get_traced_memory()
    return {
        "started": False,
        "traced_current_bytes": current,
        "traced_peak_bytes": peak,
        "top": [{
            "location": str(s.traceback[0]) if s.traceback else "?",
            "size_bytes": s.size,
            "count": s.count,
        } for s in stats],
    }


def stack_dump() -> Dict[str, str]:
    """One-shot stack dump of every thread (the `ray stack` equivalent)."""
    import traceback
    out = {}
    names = {t.ident: t.name for t in threading.enumerate()}
    for ident, frame in sys._current_frames().items():
        out[names.get(ident, str(ident))] = "".join(
            traceback.format_stack(frame))
    return out


# ---------------------------------------------------------------------------
# Device time by region (jax.profiler trace + the compiled step)
# ---------------------------------------------------------------------------

# The scopes models/gpt.py and train/train_step.py open and the names
# ray_tpu/ops/*.py give their pallas_calls: tests/test_device_regions.py
# reads both off the code and holds these tuples to them. An op belongs to
# the LAST of these on its op_name path
# (`jit(_step)/loss_and_grad/jvp(mlp)/dot_general` is `mlp`), so a scope
# nested in another (`moe_route` in `moe`, `attn_window` in `attn_core`)
# takes its ops out of the outer one's row. What each name holds is said
# where it is opened.
REGIONS = ("embed", "attn_proj", "attn_latent", "attn_core", "attn_window",
           "attn_index", "attn_out", "attn_gate", "conv", "conv_mix", "kda",
           "kda_core", "ssm", "ssm_core", "mlp", "route_ahead",
           "moe", "moe_route", "moe_shared", "moe_latent", "mtp", "norm",
           "head", "exit_gate", "loss_and_grad",
           "grad_accum", "optimizer")
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_win_fwd",
           "flash_win_bwd_dq", "flash_win_bwd_dkv", "flash_sel_fwd",
           "flash_sel_bwd_dq", "flash_sel_bwd_dkv", "moe_gmm", "moe_tgmm",
           "moe_run_sum", "rope_split", "rope_merge", "short_conv_fwd",
           "short_conv_bwd", "latent_q_split", "latent_kv_split",
           "latent_q_merge", "latent_kv_merge", "index_scores",
           "index_search", "index_kl", "index_grad_q", "index_grad_k",
           "conv_silu_fwd", "conv_silu_bwd", "kda_fwd", "kda_bwd",
           "ssd_fwd", "ssd_bwd", "embed_grad")
UNATTRIBUTED = "unattributed"
STRETCH_SPAN = "device_trace"
HOST_SPAN_PREFIXES = ("train:", "host:", "compile:")

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REFERENCE = re.compile(r"%([\w.\-]+)")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?(\.\d+)?$")
_MIN_GAP_NS = 20_000        # shorter: the device's own op-to-op turnaround
_MIN_NAMED_GAP_NS = 3_000_000
CLOCK_SKEW_NOTE = (
    "device and host timestamps share one time base only to about a "
    "millisecond (the device clock ran 1.3 ms ahead of the host's in a "
    "recorded v5e trace), so an idle gap under 3 ms is not named by a host "
    "span: it is summed under 'short gaps'")
WALL_STAT = "wall_ns"       # STRETCH_SPAN's: time.time_ns() at its start
HLO_SUFFIX = ".hlo.txt"     # the compiled step's text beside a trace

Event = Tuple[str, float, float]     # name, start_ns, end_ns


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Record a jax.profiler trace of the enclosed stretch under `log_dir`
    (the Python tracer off: it slows the host), with one TraceAnnotation,
    STRETCH_SPAN, around it: the window `device_regions` reduces. The
    annotation carries the wall clock at its start (WALL_STAT), the one
    event dated on the profiler's time base and on the clock the flight
    recorder's spans use. Only the process that holds the chip can trace
    it."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(
                STRETCH_SPAN, **{WALL_STAT: time.time_ns()}):
            yield
    finally:
        jax.profiler.stop_trace()


def _load_xplane(path: str) -> Dict[str, Any]:
    """{'devices': {chip: [Event]}, 'host': [Event], 'wall_offset_ns'} of
    an .xplane.pb: each TPU plane's "XLA Ops" line (one event per executed
    HLO op, named by the op's whole HLO text), the host threads' spans
    named train:*, host:*, compile:* (_private/compile_cache.py's, one a
    compile's phase) or STRETCH_SPAN, and what to add to the trace's
    nanoseconds to get time.time_ns() (None where no STRETCH_SPAN says)."""
    from jax.profiler import ProfileData
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    offset: Optional[float] = None
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if (e.name == STRETCH_SPAN
                            or e.name.startswith(HOST_SPAN_PREFIXES)):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
                    if e.name == STRETCH_SPAN:
                        wall = dict(e.stats).get(WALL_STAT)
                        if wall is not None:
                            offset = int(wall) - e.start_ns
    return {"devices": devices, "host": host, "wall_offset_ns": offset}


def _instruction_op_names(hlo_text: str
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
        m = _INSTRUCTION.match(line)
        if not m:
            header = _COMPUTATION.match(line)
            if header:
                computation = header.group(1)
            continue
        found = _OP_NAME.search(line, m.end())
        if found:
            named[m.group(1)] = last_in[computation] = found.group(1)
        else:
            refers[m.group(1)] = _REFERENCE.findall(line, m.end())

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


def _last_of(op_name: str, vocabulary: Tuple[str, ...]) -> Optional[str]:
    """The last component of the scope path that is in the vocabulary,
    inside whatever transforms wrap it: `transpose(jvp(mlp))` is `mlp`."""
    for part in reversed(op_name.split("/")):
        word = part.rsplit("(", 1)[-1].rstrip(")")
        if word in vocabulary:
            return word
    return None


def _phase(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    return "forward" if "jvp(" in op_name else "—"


def _instruction(event_name: str) -> str:
    """'%fusion.3 = bf16[..] fusion(..)' -> 'fusion.3'."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _opcode(event_name: str) -> str:
    """The HLO opcode: the first lower-case word before a '(' after the
    result's shape (layouts write their tiles as T(8,128), in capitals)."""
    m = _OPCODE.search(event_name.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def _is_collective(event_name: str) -> bool:
    """By opcode, or by name where the compiler fused the collective: the
    definition benchmark/xplane.py uses for train_collective_pct."""
    return bool(_COLLECTIVE.match(_opcode(event_name))
                or _COLLECTIVE.match(_instruction(event_name)))


def _region_of(event_name: str, named: Dict[str, str],
               inherits: Dict[str, str]) -> Tuple[str, str]:
    """An op line's event -> (region, the op_name path it is read from)."""
    instruction = _instruction(event_name)
    path = named.get(instruction) or inherits.get(instruction, "")
    return _last_of(path, REGIONS) or UNATTRIBUTED, path


def _self_times(events: List[Event]) -> List[float]:
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


def _gap_name(a: float, b: float, host: List[Event]) -> str:
    """The host span that overlaps most of the gap; none for a short one."""
    if b - a < _MIN_NAMED_GAP_NS:
        return "short gaps"
    best, best_overlap = "unannotated", 0.0
    for name, h0, h1 in host:
        overlap = min(h1, b) - max(h0, a)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


Table = Dict[Tuple[str, ...], List[float]]


def _chip_tables(events: List[Event], host: List[Event], start: float,
                 end: float, named: Dict[str, str],
                 inherits: Dict[str, str]) -> Dict[str, Table]:
    """One chip's op line inside [start, end], in ns: `rows` (region,
    phase) -> [self ns, ops]; `kernels` (kernel, phase) -> [ns, calls];
    `collectives` (region,) -> [ns]; `idle_gaps` (host span,) -> [ns];
    `busy` () -> [ns], the union of the op intervals."""
    events = sorted(((n, max(a, start), min(b, end)) for n, a, b in events
                     if b > start and a < end), key=lambda e: (e[1], -e[2]))
    rows: Table = {}
    kernels: Table = {}
    collectives: Table = {}
    gaps: Table = {}
    for (name, a, b), own in zip(events, _self_times(events)):
        region, path = _region_of(name, named, inherits)
        row = rows.setdefault((region, _phase(path)), [0.0, 0])
        row[0] += own
        row[1] += 1
        kernel = _last_of(named.get(_instruction(name), ""), KERNELS)
        if kernel:
            calls = kernels.setdefault((kernel, _phase(path)), [0.0, 0])
            calls[0] += b - a
            calls[1] += 1
        if _is_collective(name):
            collectives.setdefault((region,), [0.0])[0] += b - a
    busy, at = 0.0, start
    for _n, a, b in events + [("", end, end)]:
        if a - at >= _MIN_GAP_NS:
            gaps.setdefault((_gap_name(at, a, host),), [0.0])[0] += a - at
        busy += max(b - max(a, at), 0.0)
        at = max(at, b)
    return {"rows": rows, "kernels": kernels, "collectives": collectives,
            "idle_gaps": gaps, "busy": {(): [busy]}}


def _listed(tables: Dict[str, Table], window_ns: float) -> Dict[str, Any]:
    """Tables in ns -> what device_regions returns, in seconds, the
    largest first."""
    def rows(table, finish):
        return [list(k) + finish(v) for k, v in
                sorted(table.items(), key=lambda kv: -kv[1][0])]
    busy = tables["busy"][()][0]
    return {
        "window_s": window_ns / 1e9, "busy_s": busy / 1e9,
        "busy_pct": 100.0 * busy / window_ns,
        # [region, phase, seconds, % of window, ops]
        "rows": rows(tables["rows"], lambda v: [
            v[0] / 1e9, 100.0 * v[0] / window_ns, v[1]]),
        # [kernel, phase, seconds, calls, seconds a call]
        "kernels": rows(tables["kernels"], lambda v: [
            v[0] / 1e9, v[1], v[0] / 1e9 / v[1]]),
        # [region, seconds in collective ops on the op line]
        "collectives": rows(tables["collectives"], lambda v: [v[0] / 1e9]),
        # [host span, seconds]
        "idle_gaps": rows(tables["idle_gaps"], lambda v: [v[0] / 1e9])}


def _median_tables(chips: List[Dict[str, Table]]) -> Dict[str, Table]:
    """Key by key the median over the chips; a key a chip lacks is 0 there."""
    out: Dict[str, Table] = {}
    for what in chips[0]:
        keys = {k: len(v) for c in chips for k, v in c[what].items()}
        out[what] = {k: [statistics.median(c[what].get(k, [0.0] * n)[i]
                                           for c in chips) for i in range(n)]
                     for k, n in keys.items()}
    return out


def _wall_offset_ns(trace: Dict[str, Any]) -> float:
    offset_ns = trace.get("wall_offset_ns")
    if offset_ns is None:
        raise ValueError("the trace does not say when it was taken on the "
                         "wall clock (device_trace records it), so it "
                         "cannot be read against wall-clock spans")
    return offset_ns


def _joined(trace, compiled) -> Tuple[Dict[str, Any], Dict[str, str],
                                      Dict[str, str]]:
    """device_regions' inputs -> the loaded trace and the compiled step's
    instruction -> op_name maps."""
    if isinstance(trace, str):
        trace = _load_xplane(trace)
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    return (trace,) + _instruction_op_names(text)


def device_regions(trace, compiled, spans=()) -> Dict[str, Any]:
    """Where the device's time went, by region: `trace` is the path of an
    .xplane.pb (or what `_load_xplane` makes of one), `compiled` the
    `jax.stages.Compiled` of the step that ran in it, or its HLO text,
    `spans` flight-recorder records (tracing.get_spans(): a train run's
    train:loop / train:report) that may name an idle gap beside the
    trace's own host spans.

    The window is the longest STRETCH_SPAN of the trace (device_trace
    opens it), or without one the extent of the device's ops. Returns
    `per_chip` {chip: tables} and `median` (the tables' median over the
    chips), each with window_s, busy_s, busy_pct and the lists
      rows        [region, phase, seconds, % of window, ops] — an op's own
                  time, without the ops nested in it, so the rows sum to
                  busy_s; phase forward | backward | recompute | —; ops
                  whose op_name holds no region under UNATTRIBUTED;
      kernels     [kernel, phase, seconds, calls, seconds a call] — the
                  KERNELS apart (their time is in rows too, in the region
                  that calls them);
      collectives [region, seconds]: time the op line spends in
                  collective ops (what the core waits in or runs, not what
                  compute hides);
      idle_gaps   [host span, seconds]: every gap of the op line by the
                  train:*, host:* or compile:* span that overlaps most of
                  it (see `clock_skew_note`).
    `wall_clock_offset_s` is what to add to the trace's seconds to get
    time.time(), None for a trace `device_trace` did not date.
    """
    trace, named, inherits = _joined(trace, compiled)
    if not trace["devices"]:
        raise ValueError("the trace has no TPU plane: nothing ran on a chip")
    stretches = [(a, b) for n, a, b in trace["host"] if n == STRETCH_SPAN]
    if stretches:
        start, end = max(stretches, key=lambda s: s[1] - s[0])
    else:
        ops = [e for events in trace["devices"].values() for e in events]
        start, end = min(a for _n, a, _b in ops), max(b for _n, _a, b in ops)
    offset_ns = trace.get("wall_offset_ns")
    host = [h for h in trace["host"] if h[0] != STRETCH_SPAN]
    if spans:       # wall-clock seconds -> the trace's time base
        shift = _wall_offset_ns(trace)
        host += [(s["name"], s["start"] * 1e9 - shift,
                  s["end"] * 1e9 - shift) for s in spans]
    chips = {chip: _chip_tables(events, host, start, end, named, inherits)
             for chip, events in sorted(trace["devices"].items())}
    return {"median": _listed(_median_tables(list(chips.values())),
                              end - start),
            "per_chip": {str(chip): _listed(tables, end - start)
                         for chip, tables in chips.items()},
            "wall_clock_offset_s": (None if offset_ns is None
                                    else offset_ns / 1e9),
            "clock_skew_note": CLOCK_SKEW_NOTE}


def trace_files(log_dir: str) -> Tuple[str, str]:
    """What `device_trace(log_dir)` left and the caller put beside it: the
    newest .xplane.pb under `log_dir` and the text of the one *.hlo.txt in
    it (`step.as_text()` of the jax.stages.Compiled that ran)."""
    planes = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                       recursive=True)
    texts = glob.glob(os.path.join(log_dir, "*" + HLO_SUFFIX))
    if not planes or len(texts) != 1:
        raise ValueError(
            f"{log_dir}: {len(planes)} .xplane.pb and {len(texts)} "
            f"*{HLO_SUFFIX} files; a device lane needs a trace and the one "
            "compiled step's HLO text")
    with open(texts[0]) as f:
        return max(planes, key=os.path.getmtime), f.read()


def device_slices(trace, compiled) -> List[dict]:
    """The trace's device ops as Chrome-trace slices on the wall clock, a
    lane a chip (`chip:<n>`): runs of back-to-back ops of one region and
    phase are one slice, named by the region, so the flight recorder's
    spans (worker_api.timeline) can be read against what the chip did.
    Inputs as `device_regions`; the trace must be one `device_trace`
    dated."""
    trace, named, inherits = _joined(trace, compiled)
    offset_ns = _wall_offset_ns(trace)
    out: List[dict] = []
    for chip, events in sorted(trace["devices"].items()):
        events = sorted(events, key=lambda e: (e[1], -e[2]))
        runs: List[list] = []       # [region, phase, start, end, ops]
        for i, (name, a, b) in enumerate(events):
            if i + 1 < len(events) and events[i + 1][1] < b:
                continue        # spans the ops nested in it: they draw it
            region, path = _region_of(name, named, inherits)
            phase = _phase(path)
            if (runs and runs[-1][:2] == [region, phase]
                    and a - runs[-1][3] < _MIN_GAP_NS):
                runs[-1][3] = max(runs[-1][3], b)
                runs[-1][4] += 1
            else:
                runs.append([region, phase, a, b, 1])
        out.extend({
            "cat": "device", "name": region, "ph": "X",
            "ts": (a + offset_ns) / 1e3, "dur": (b - a) / 1e3,
            "pid": f"chip:{chip}", "tid": 0,
            "args": {"phase": phase, "ops": ops}}
            for region, phase, a, b, ops in runs)
    return out
