"""Flight recorder: phase-stamp vocabulary + Chrome-trace assembly.

The task lifecycle is stamped at every hop (owner submit -> lease wait ->
lease grant -> dispatch -> worker receive -> args ready -> exec ->
result put -> owner reply handling). Owners keep their stamps on the
PendingTask; executors ship theirs back inside the task reply; the merged
record rides the FINISHED/FAILED task event to the GCS, where every
observability surface (timeline, /api/latency, summarize_tasks latency
columns, per-phase Prometheus histograms) reads the same record.

Wire/memory format: a phase record is a fixed-size LIST indexed by the
PH_* constants below (stamps are wall-clock floats, missing = None; the
last slot carries the executing worker's id). A positional list of
floats costs a fraction of a string-keyed dict to stamp, pickle, and
fold — the recorder rides the task hot path, so the dict form exists
only at the query surfaces (as_dict).

Stamps are wall-clock (`time.time()`): every daemon of this framework
shares a host (127.0.0.1 control plane), so cross-process gaps are
directly comparable; within-process durations are exact.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

# Ordered stamp names. A phase duration is the gap between two consecutive
# *present* stamps, reported under the LATER stamp's name (e.g. the
# "exec_end" phase is the user-code execution time; "received" is the
# dispatch->worker wire+decode gap). Not every task carries every stamp:
# actor calls skip the lease stamps, failed tasks stop wherever they died.
PHASE_ORDER = (
    "submitted",       # owner: .remote() accepted the call
    "lease_wait",      # owner: spec entered the per-class dispatch queue
    "lease_granted",   # owner: spec assigned to a leased worker slot
    "dispatched",      # owner: push RPC handed to the transport
    "received",        # worker: push handler started processing the spec
    "args_ready",      # worker: argument resolution finished
    "exec_start",      # worker: user code entered
    "exec_end",        # worker: user code returned
    "result_put",      # worker: returns serialized/stored
    "reply_handled",   # owner: reply applied, return objects ready
)

# Record-slot indices (a record is [*stamps, worker_hex]).
(PH_SUBMITTED, PH_LEASE_WAIT, PH_LEASE_GRANTED, PH_DISPATCHED,
 PH_RECEIVED, PH_ARGS_READY, PH_EXEC_START, PH_EXEC_END,
 PH_RESULT_PUT, PH_REPLY_HANDLED) = range(10)
N_STAMPS = 10
IDX_WORKER = 10
RECORD_LEN = 11


def new_record() -> list:
    return [None] * RECORD_LEN


# Fields of one owner-side task-event record (see EventRing).
EVENT_FIELDS = 8


class EventRing:
    """Fixed-slot ring buffer for owner-side task events.

    The recorder rides the submit/reply hot path: one event per state
    transition, three per task. The previous list-of-tuples buffer paid
    a tuple allocation per event plus list growth and a slicing trim on
    overflow; the ring pre-allocates `capacity` reusable 8-slot records
    and a write is eight slot stores under one small uncontended lock.
    Events fold into wire dicts only at flush (`drain`), off the hot
    path.

    Overflow is drop-oldest: a writer that laps the flush cursor
    overwrites unflushed records (the old buffer's del-oldest-10k
    behavior, now O(1)); `dropped` counts the loss.

    Slot writes AND the drain copy both run under the lock: index
    reservation alone would let a drain racing a mid-write slot ship a
    torn (or all-None) record. Drain holds the lock for its whole copy
    — bounded by capacity, ~100us for a 1000-event flush window, paid
    once per flush, not per event.
    """

    __slots__ = ("_slots", "_mask", "_head", "_tail", "_lock", "dropped")

    def __init__(self, capacity: int = 16384):
        cap = 1 << (capacity - 1).bit_length()
        self._slots = [[None] * EVENT_FIELDS for _ in range(cap)]
        self._mask = cap - 1
        self._head = 0
        self._tail = 0
        self._lock = threading.Lock()
        self.dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return min(self._head - self._tail, self._mask + 1)

    def record(self, f0, f1, f2, f3, f4, f5, f6, f7) -> int:
        """Write one event; returns the approximate pending count."""
        with self._lock:
            i = self._head
            self._head = i + 1
            slot = self._slots[i & self._mask]
            slot[0] = f0
            slot[1] = f1
            slot[2] = f2
            slot[3] = f3
            slot[4] = f4
            slot[5] = f5
            slot[6] = f6
            slot[7] = f7
            return i + 1 - self._tail

    def drain(self) -> list:
        """Copy out pending records oldest-first as tuples and advance the
        flush cursor. Overwritten (lapped) records are skipped and counted
        in `dropped`."""
        with self._lock:
            head = self._head
            i = self._tail
            cap = self._mask + 1
            if head - i > cap:
                self.dropped += head - i - cap
                i = head - cap
            out = []
            slots = self._slots
            mask = self._mask
            while i < head:
                s = slots[i & mask]
                out.append((s[0], s[1], s[2], s[3], s[4], s[5], s[6],
                            s[7]))
                i += 1
            self._tail = head
            return out


def as_dict(rec: Optional[Sequence]) -> Dict[str, Any]:
    """Named view of a phase record (query surfaces / debugging only)."""
    if not rec:
        return {}
    out = {PHASE_ORDER[i]: rec[i]
           for i in range(N_STAMPS) if rec[i] is not None}
    if len(rec) > IDX_WORKER and rec[IDX_WORKER]:
        out["w"] = rec[IDX_WORKER]
    return out


def phase_durations(rec: Sequence) -> List[Tuple[str, float]]:
    """(phase, seconds) for every consecutive pair of present stamps,
    plus ("total", submit->reply) when both endpoints exist. Negative
    gaps (cross-process clock skew) clamp to zero."""
    out: List[Tuple[str, float]] = []
    prev: Optional[float] = None
    for i in range(N_STAMPS):
        t = rec[i]
        if t is None:
            continue
        if prev is not None:
            out.append((PHASE_ORDER[i], max(0.0, t - prev)))
        prev = t
    t0, t1 = rec[PH_SUBMITTED], rec[PH_REPLY_HANDLED]
    if t0 is not None and t1 is not None:
        out.append(("total", max(0.0, t1 - t0)))
    return out


# ---------------------------------------------------------------------------
# Serve request phases (the request-scoped twin of the task vocabulary).
#
# A serve request crosses three hops — proxy ingress, handle/router, and
# replica — each of which stamps the subset of phases it owns into one
# fixed-index record and ships it as a `kind:"serve_request"` event on
# the same task-event channel (serve/request_trace.py owns the ring +
# flush). A phase duration is the gap between two consecutive present
# stamps, reported under the LATER stamp's name, exactly like tasks.
# ---------------------------------------------------------------------------

REQ_PHASE_ORDER = (
    "proxy_recv",      # proxy: request fully parsed off the socket
    "admission",       # replica: request arrived at the admission gate
    "queue_wait",      # replica: execution slot acquired (gap = queueing)
    "dispatch",        # proxy/handle: payload handed to handle.remote()
    "exec_start",      # replica: handler entered
    "exec_end",        # replica: handler returned
    "first_item",      # replica: first streamed item yielded
    "reply",           # hop-local: reply delivered / stream finished
    # Continuous-batching phase split (serve/continuous_batching.py):
    # the gap exec_start -> prefill_end is the sequence's prefill time,
    # prefill_end -> exec_end its decode time. Appended AFTER the
    # original eight so existing fixed-index records stay valid;
    # request_phase_durations sorts stamps by time, so position in this
    # tuple never inverts a gap.
    "prefill_end",     # replica: sequence left the prefill phase
)
(RQ_PROXY_RECV, RQ_ADMISSION, RQ_QUEUE_WAIT, RQ_DISPATCH, RQ_EXEC_START,
 RQ_EXEC_END, RQ_FIRST_ITEM, RQ_REPLY, RQ_PREFILL_END) = range(9)
REQ_RECORD_LEN = 9


def new_request_record() -> list:
    return [None] * REQ_RECORD_LEN


def request_phase_durations(rec: Sequence) -> List[Tuple[str, float]]:
    """(phase, seconds) pairs for one hop's request record, plus a
    ("total", first->last) row. Stamp order follows REQ_PHASE_ORDER
    except `dispatch`, which the proxy stamps BEFORE the replica's
    phases happen — sort present stamps by time so cross-hop records
    never produce inverted gaps."""
    # min(): records written by a pre-prefill_end process are 8 slots —
    # a version-skewed reader must fold them, not IndexError.
    present = [(rec[i], REQ_PHASE_ORDER[i])
               for i in range(min(len(rec), REQ_RECORD_LEN))
               if rec[i] is not None]
    present.sort()
    out: List[Tuple[str, float]] = []
    for (t0, _n0), (t1, n1) in zip(present, present[1:]):
        out.append((n1, max(0.0, t1 - t0)))
    if len(present) >= 2:
        out.append(("total", max(0.0, present[-1][0] - present[0][0])))
    return out


def new_trace_id() -> str:
    """An id for a trace or a span: 8 random bytes, as util/tracing draws."""
    return os.urandom(8).hex()


def stamp() -> Tuple[float, bool]:
    """An edge of a start-up span: time.time(), and whether jax is loaded in
    this process then (one lookup). A span that carries both of its edges'
    answers as `jax_loaded` says whether it paid the process's `import
    jax`, whichever way that import is reached."""
    return time.time(), "jax" in sys.modules


def span_event(name: str, trace_id: str, start: float, end: float,
               parent_id: str = "", span_id: str = "", **extra) -> dict:
    """One kind:"span" task-event record — the wire shape get_spans()
    and the timeline consume — for spans recorded OUTSIDE util/tracing's
    contextvar machinery: the GCS gang-drain spans, the compiled-DAG
    dag:compile / dag:tick spans and a train run's tree build these
    directly (a contextvar span would mis-parent them under whatever task
    happens to be running). `span_id` is for a parent whose children are
    exported before it ends; `extra` keys become the slice's `args`."""
    return {"kind": "span", "trace_id": trace_id,
            "span_id": span_id or new_trace_id(), "parent_id": parent_id,
            "name": name, "task_id": trace_id, "start": start, "end": end,
            "pid": os.getpid(), **extra}


# Worker-lane sub-slices drawn inside the task slice on the timeline.
SUB_SLICES = (
    ("args_resolve", PH_RECEIVED, PH_ARGS_READY),
    ("exec", PH_EXEC_START, PH_EXEC_END),
    ("result_put", PH_EXEC_END, PH_RESULT_PUT),
)

_EMPTY: tuple = (None,) * RECORD_LEN


def build_trace(events: List[dict]) -> List[dict]:
    """Chrome-trace (chrome://tracing / Perfetto) event list from raw task
    events.

    Emits, per completed task:
      - the task slice ("X", cat "task") on the executing worker's lane;
      - phase sub-slices ("X", cat "phase", tid 1) nested inside it
        (args_resolve / exec / result_put), clamped into the task slice;
      - a "submit" slice on the owner's lane covering submit->dispatch;
      - one flow-event pair (ph "s"/"f", shared id) connecting the submit
        on the owner to the execution start on the worker across pids.
    And one slice ("X", cat "span") per finished span record, whoever
    exported it (_span_slices); a serve request's spans are drawn with
    their request (_build_serve_trace).
    """
    trace: List[dict] = []
    starts: Dict[str, dict] = {}
    serve_events = [e for e in events if isinstance(e, dict)
                    and e.get("kind") == "serve_request"]
    if serve_events:
        trace.extend(_build_serve_trace(serve_events, events))
    served = {e["request_id"] for e in serve_events if e.get("request_id")}
    trace.extend(_span_slices([
        e for e in events if isinstance(e, dict) and e.get("kind") == "span"
        and e.get("end") is not None and e.get("trace_id") not in served]))
    for e in events:
        if not isinstance(e, dict) or e.get("kind") in (
                "span", "serve_request"):
            continue
        state = e.get("state")
        task_id = e.get("task_id")
        if state == "RUNNING":
            starts[task_id] = e
            continue
        if state not in ("FINISHED", "FAILED"):
            continue
        s = starts.pop(task_id, None)
        ph = e.get("phases") or _EMPTY
        owner_pid = (e.get("worker_id") or "")[:8]
        exec_pid = (ph[IDX_WORKER] or e.get("worker_id") or "")[:8]
        name = e.get("name", "")
        task_ts = task_end = None
        if s is not None:
            task_ts = s["time"] * 1e6
        else:
            # Coalesced flush dropped the RUNNING row (the terminal event
            # carries the full phase record instead): the slice starts at
            # the dispatch/receive stamp.
            start = ph[PH_DISPATCHED] or ph[PH_RECEIVED]
            if start is not None:
                task_ts = start * 1e6
        if task_ts is not None:
            task_end = max(e["time"] * 1e6, task_ts)
            trace.append({
                "cat": "task", "name": name, "ph": "X",
                "ts": task_ts, "dur": task_end - task_ts,
                "pid": exec_pid, "tid": 0, "state": state,
                "task_id": task_id,
            })
        for sub_name, a, b in SUB_SLICES:
            ta, tb = ph[a], ph[b]
            if ta is None or tb is None:
                continue
            ts, end = ta * 1e6, max(ta, tb) * 1e6
            if task_ts is not None:
                # Nest inside the task slice (clock skew must not push a
                # sub-slice outside its parent).
                ts = min(max(ts, task_ts), task_end)
                end = min(max(end, ts), task_end)
            trace.append({
                "cat": "phase", "name": sub_name, "ph": "X",
                "ts": ts, "dur": end - ts,
                "pid": exec_pid, "tid": 1, "task_id": task_id,
            })
        submitted = ph[PH_SUBMITTED]
        if submitted is None:
            continue
        sub_ts = submitted * 1e6
        dispatch_end = max(
            sub_ts, (ph[PH_DISPATCHED] or submitted) * 1e6)
        trace.append({
            "cat": "phase", "name": "submit", "ph": "X",
            "ts": sub_ts, "dur": dispatch_end - sub_ts,
            "pid": owner_pid, "tid": 0, "task_id": task_id,
        })
        exec_ts = ph[PH_EXEC_START]
        flow_end = (exec_ts * 1e6 if exec_ts is not None else task_ts)
        if flow_end is None:
            continue
        trace.append({
            "cat": "flow", "name": "task_flow", "ph": "s", "id": task_id,
            "ts": sub_ts, "pid": owner_pid, "tid": 0,
            "task_id": task_id,
        })
        trace.append({
            "cat": "flow", "name": "task_flow", "ph": "f", "bp": "e",
            "id": task_id, "ts": max(flow_end, sub_ts), "pid": exec_pid,
            "tid": 0, "task_id": task_id,
        })
    return trace


# Rows 0-2 of a lane are the tasks' and the serve hops'; a span's row is
# this plus its depth in its tree, so a child draws under its parent.
SPAN_ROW = 3
_SPAN_FIELDS = frozenset((
    "kind", "trace_id", "span_id", "parent_id", "name", "task_id", "start",
    "end", "pid", "node_id"))


def _span_slices(spans: List[dict]) -> List[dict]:
    """One "X" slice per finished span record. Lane: the recording
    process's pid (a raylet's span carries its node instead: `node:<id>`).
    Row: SPAN_ROW + the span's depth under the parents that are on the
    page. A child on its parent's lane is clamped into the parent's slice
    (the task sub-slices' rule); one on another lane (a worker's
    train:loop under the driver's train:round) keeps its own clock's
    extent. Whatever else the record holds (fun_name, cache, call_s, ...)
    is the slice's `args`."""
    by_id = {s["span_id"]: s for s in spans}
    placed: Dict[str, tuple] = {}        # span_id -> (lane, depth, ts, end)

    def place(span: dict) -> tuple:
        sid = span["span_id"]
        if sid in placed:
            return placed[sid]
        lane = (str(span["pid"]) if span.get("pid") is not None
                else "node:" + str(span.get("node_id", ""))[:8])
        ts = span["start"] * 1e6
        end = max(span["end"] * 1e6, ts)
        placed[sid] = (lane, 0, ts, end)     # a cycle of parents ends here
        parent = by_id.get(span.get("parent_id") or "")
        depth = 0
        if parent is not None:
            p_lane, p_depth, p_ts, p_end = place(parent)
            depth = p_depth + 1
            if p_lane == lane:
                ts = min(max(ts, p_ts), p_end)
                end = min(max(end, ts), p_end)
        placed[sid] = (lane, depth, ts, end)
        return placed[sid]

    out: List[dict] = []
    for span in spans:
        lane, depth, ts, end = place(span)
        args = {k: v for k, v in span.items() if k not in _SPAN_FIELDS}
        if span.get("task_id") not in (None, span.get("trace_id")):
            args["task_id"] = span["task_id"]
        out.append({
            "cat": "span", "name": span.get("name", ""), "ph": "X",
            "ts": ts, "dur": end - ts, "pid": lane, "tid": SPAN_ROW + depth,
            "trace_id": span.get("trace_id"), "span_id": span["span_id"],
            "parent_id": span.get("parent_id") or "", "args": args,
        })
    return out


def _build_serve_trace(serve_events: List[dict],
                       all_events: List[dict]) -> List[dict]:
    """Chrome-trace rows for serve requests: one trace per request id
    crossing every pid the request touched.

    Per `kind:"serve_request"` event (one per hop — proxy, replica,
    replay marker) this emits an enclosing hop slice on that process's
    lane, per-phase sub-slices, and flow arrows proxy -> replica keyed
    by the request id. Spans whose trace_id belongs to a serve request
    (the root request span, the replica exec span, and any task/nested
    spans the handler spawned — they inherit the trace through
    TaskSpec.trace_ctx) are drawn as `serve_span` slices on THEIR
    recording pid, which is what stitches proxy, replica, and spawned-
    task processes into one trace."""
    out: List[dict] = []
    by_req: Dict[str, list] = {}
    for e in serve_events:
        rid = e.get("request_id")
        if rid:
            by_req.setdefault(rid, []).append(e)
    for rid, evs in by_req.items():
        for e in evs:
            hop = e.get("hop", "")
            pid = str(e.get("pid", ""))
            dep = e.get("deployment", "")
            ph = e.get("phases") or [None] * REQ_RECORD_LEN
            if hop == "replay":
                out.append({
                    "cat": "serve", "name": "replay", "ph": "i",
                    "ts": e.get("time", 0.0) * 1e6, "pid": pid, "tid": 0,
                    "s": "p", "request_id": rid, "deployment": dep,
                })
                continue
            present = [(t, REQ_PHASE_ORDER[i])
                       for i, t in enumerate(ph) if t is not None]
            present.sort()
            if not present:
                continue
            ts = present[0][0] * 1e6
            end = max(present[-1][0] * 1e6, ts)
            hop_slice = {
                "cat": "serve", "name": f"{hop}:{dep}", "ph": "X",
                "ts": ts, "dur": end - ts, "pid": pid, "tid": 0,
                "request_id": rid, "deployment": dep, "hop": hop,
            }
            if e.get("replays"):
                hop_slice["replays"] = e["replays"]
            out.append(hop_slice)
            for (t0, _n0), (t1, n1) in zip(present, present[1:]):
                out.append({
                    "cat": "serve_phase", "name": n1, "ph": "X",
                    "ts": t0 * 1e6,
                    "dur": max(0.0, (t1 - t0)) * 1e6,
                    "pid": pid, "tid": 1, "request_id": rid,
                })
        # Flow arrows: proxy dispatch -> each replica exec_start.
        proxies = [e for e in evs if e.get("hop") == "proxy"]
        replicas = [e for e in evs if e.get("hop") == "replica"]
        if proxies and replicas:
            p = proxies[0]
            pph = p.get("phases") or []
            src = None
            if len(pph) > RQ_DISPATCH and pph[RQ_DISPATCH] is not None:
                src = pph[RQ_DISPATCH]
            elif len(pph) > RQ_PROXY_RECV:
                src = pph[RQ_PROXY_RECV]
            if src is not None:
                out.append({
                    "cat": "serve_flow", "name": "request", "ph": "s",
                    "id": "req:" + rid, "ts": src * 1e6,
                    "pid": str(p.get("pid", "")), "tid": 0,
                    "request_id": rid,
                })
                for r in replicas:
                    rph = r.get("phases") or []
                    dst = next((rph[i] for i in (RQ_EXEC_START,
                                                 RQ_ADMISSION)
                                if len(rph) > i and rph[i] is not None),
                               None)
                    if dst is None:
                        continue
                    out.append({
                        "cat": "serve_flow", "name": "request", "ph": "f",
                        "bp": "e", "id": "req:" + rid,
                        "ts": max(dst, src) * 1e6,
                        "pid": str(r.get("pid", "")), "tid": 0,
                        "request_id": rid,
                    })
    # Spans belonging to serve traces: drawn here, stamped with their
    # request (build_trace draws every other span), so the handler's
    # spawned tasks / nested calls appear in the same chrome trace on
    # their own pids.
    for e in all_events:
        if not isinstance(e, dict) or e.get("kind") != "span":
            continue
        tid = e.get("trace_id")
        if tid not in by_req or e.get("end") is None:
            continue
        out.append({
            "cat": "serve_span", "name": e.get("name", ""), "ph": "X",
            "ts": e["start"] * 1e6,
            "dur": max(0.0, e["end"] - e["start"]) * 1e6,
            "pid": str(e.get("pid", "")), "tid": 2,
            "request_id": tid, "span_id": e.get("span_id"),
            "parent_id": e.get("parent_id"),
        })
    return out


def latency_summary(events: List[dict]) -> List[dict]:
    """Per-(task name, phase) p50/p95 rows from task events with phases:
    the data behind `ray_tpu summary`'s latency table and the dashboard
    Latency panel."""
    acc: Dict[Tuple[str, str], List[float]] = {}
    for e in events:
        if not isinstance(e, dict):
            continue
        ph = e.get("phases")
        if not ph:
            continue
        if e.get("kind") == "serve_request":
            # Serve request hops fold under "serve:<deployment>" so the
            # same latency table covers tasks AND requests.
            name = "serve:" + e.get("deployment", "")
            for phase, d in request_phase_durations(ph):
                acc.setdefault((name, phase), []).append(d)
            continue
        name = e.get("name", "")
        for phase, d in phase_durations(ph):
            acc.setdefault((name, phase), []).append(d)
    rows = []
    for (name, phase), ds in sorted(acc.items()):
        ds.sort()
        n = len(ds)
        # Nearest-rank percentiles: ceil(q*n)-1. (int(q*n) is one rank
        # too high — for n<=20 it reports the sample MAX as the p95.)
        p50 = ds[max(0, -(-n // 2) - 1)]
        p95 = ds[max(0, -(-(n * 19) // 20) - 1)]
        rows.append({
            "name": name, "phase": phase, "count": n,
            "p50_ms": round(p50 * 1e3, 3),
            "p95_ms": round(p95 * 1e3, 3),
        })
    return rows
