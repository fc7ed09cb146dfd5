"""bench_smoke: the core runtime's fan-in rows in <60 s, on any host.

Runs the three control-plane shapes that collapse under multi-client
load — multi-client task bursts, n:n actor calls, and placement-group
create/remove — scaled down so the whole script finishes in well under a
minute on a 1-vCPU box. Prints ONE JSON line (multi_client_tasks_async,
n_n_actor_calls, pg_create_ms, pg_remove_ms, ...), so perf PRs get a cheap
directional signal for the control plane; none of it is a device metric
(benchmark/ is the benchmark). Wired into tier-1 as a completion-only sanity test
(tests/test_bench_smoke.py): the numbers are printed, never asserted —
a loaded CI box must not fail the suite on throughput noise.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main() -> dict:
    sys.path.insert(0, HERE)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import ray_tpu

    out: dict = {}
    ray_tpu.init(num_cpus=max(2, (os.cpu_count() or 1)))

    @ray_tpu.remote
    def nop():
        return None

    ray_tpu.get(nop.remote(), timeout=60)  # warm lease + worker
    ray_tpu.get([nop.remote() for _ in range(50)], timeout=60)

    # --- multi-client tasks: 2 extra driver processes + this one ---
    from ray_tpu._private import worker_api as _wapi
    gcs_addr = _wapi._state.gcs_address
    script = (
        "import os, sys, time\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        f"sys.path.insert(0, {HERE!r})\n"
        "import ray_tpu\n"
        f"ray_tpu.init(address={gcs_addr!r})\n"
        "@ray_tpu.remote\n"
        "def nop():\n"
        "    return None\n"
        "ray_tpu.get(nop.remote(), timeout=60)\n"
        "n = 200\n"
        "t0 = time.perf_counter()\n"
        "ray_tpu.get([nop.remote() for _ in range(n)], timeout=60)\n"
        "print('RATE', n / (time.perf_counter() - t0))\n"
        "ray_tpu.shutdown()\n")
    try:
        procs = [subprocess.Popen([sys.executable, "-c", script],
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True)
                 for _ in range(2)]
        n = 200
        t0 = time.perf_counter()
        ray_tpu.get([nop.remote() for _ in range(n)], timeout=60)
        rates = [n / (time.perf_counter() - t0)]
        for p in procs:
            stdout, _ = p.communicate(timeout=90)
            for ln in stdout.splitlines():
                if ln.startswith("RATE "):
                    rates.append(float(ln.split()[1]))
        out["multi_client_tasks_async"] = round(sum(rates), 1)
        log(f"multi_client_tasks_async: {sum(rates):,.0f}/s "
            f"({len(rates)} drivers)")
    except Exception as e:  # noqa: BLE001 — smoke must finish
        log(f"multi-client phase skipped: {type(e).__name__}: {e}")

    # --- n:n actor calls: 2 caller actors, each with its own sink ---
    @ray_tpu.remote
    class Sink:
        def ping(self, x=None):
            return x

    @ray_tpu.remote
    class Caller:
        def __init__(self):
            self.sink = Sink.remote()
            ray_tpu.get(self.sink.ping.remote(), timeout=60)

        def burst(self, n):
            t0 = time.perf_counter()
            ray_tpu.get([self.sink.ping.remote() for _ in range(n)])
            return n / (time.perf_counter() - t0)

    try:
        callers = [Caller.remote() for _ in range(2)]
        ray_tpu.get([c.burst.remote(5) for c in callers], timeout=90)
        # Best of 3 bursts (was median of 3). The row's bimodality was
        # isolated (PR 10): NOT multi-client leftovers (reproduces with
        # that phase removed), NOT memory pressure (>100 GB free), NOT
        # the sinks (their 150 execs span <0.5 ms even in slow bursts).
        # Two components: (a) gen-2 GC passes re-traversing the fork
        # template's preloaded heap in every worker — fixed at the
        # source (worker_forkserver gc.freeze(), +~20% fast-mode rate);
        # (b) a residual ~50-75 ms per-process scheduling stall that
        # hits ~1/4 of bursts even with GC fully disabled — environment-
        # level (sandboxed kernel), quarantined here: the row measures
        # control-plane throughput capacity, so take the best burst
        # (P(all 3 stalled) ~1-2%) and print the raw rates for eyes.
        rates = []
        for _ in range(3):
            n = 150
            t0 = time.perf_counter()
            ray_tpu.get([c.burst.remote(n) for c in callers], timeout=90)
            rates.append(2 * n / (time.perf_counter() - t0))
        v = max(rates)
        out["n_n_actor_calls"] = round(v, 1)
        log(f"n_n_actor_calls_async: {v:,.0f}/s (best of "
            f"{[round(r) for r in rates]})")
    except Exception as e:  # noqa: BLE001
        log(f"n:n phase skipped: {type(e).__name__}: {e}")

    # --- per-call allocation probe (caller-side hot path) ---
    # tracemalloc block count for 1k steady-state `.remote()` calls in
    # the driver process: the allocation-regression tripwire for the
    # templated submit path. Asserted under a ceiling in tier-1
    # (tests/test_bench_smoke.py) — unlike throughput, an allocation
    # count is deterministic enough to gate on a loaded CI box.
    try:
        import tracemalloc
        ray_tpu.get([nop.remote() for _ in range(300)], timeout=60)
        time.sleep(0.5)  # drain in-flight loop work
        tracemalloc.start()
        try:
            snap0 = tracemalloc.take_snapshot()
            refs = [nop.remote() for _ in range(1000)]
            snap1 = tracemalloc.take_snapshot()
            ray_tpu.get(refs, timeout=60)
        finally:
            # A failed probe must not leave tracing on: it would slow
            # (and silently skew) every later phase's numbers.
            tracemalloc.stop()
        blocks = sum(st.count_diff
                     for st in snap1.compare_to(snap0, "lineno")
                     if st.count_diff > 0)
        out["alloc_blocks_per_call"] = round(blocks / 1000, 2)
        log(f"alloc probe: {blocks / 1000:.1f} blocks per .remote() call")
    except Exception as e:  # noqa: BLE001
        log(f"alloc probe skipped: {type(e).__name__}: {e}")

    # --- object-plane put/get: small vs large + zero-copy proof ---
    # Small puts measure the control path (inline, below threshold);
    # 64MB puts/gets measure the shm plane. The get row also PROVES
    # zero-copy: the returned array's data pointer must lie inside a
    # store segment the driver attached — asserted in tier-1
    # (tests/test_bench_smoke.py), since unlike throughput a pointer
    # range is deterministic under CI load.
    try:
        out.update(_put_get_phase())
    except Exception as e:  # noqa: BLE001 — smoke must finish
        log(f"put/get phase skipped: {type(e).__name__}: {e}")

    # --- serve large-body p99: plane routing vs forced-inline ---
    # The acceptance A/B for ISSUE 17's serve story: 2MB echo bodies
    # through the handle with the object plane ON (bodies ride shm,
    # zero-copy views out) vs the SAME code with the plane thresholds
    # pushed above any payload (bodies pickled into RPC frames — the
    # r13 wire shape). Each leg runs in its own subprocess cluster so
    # the env-var threshold override reaches the forked workers.
    try:
        out.update(_serve_large_body_phase())
    except Exception as e:  # noqa: BLE001 — smoke must finish
        log(f"serve large-body phase skipped: {type(e).__name__}: {e}")

    # --- serve sustained-QPS smoke (the serve trajectory row) ---
    # 4 driver threads fire sync handle requests at a 2-replica echo
    # deployment for ~3s: QPS + p99 latency + requests shed by admission
    # control. Printed, never asserted (same policy as the other rows).
    try:
        import threading

        from ray_tpu import serve
        from ray_tpu.serve.exceptions import BackPressureError

        @serve.deployment(num_replicas=2, max_ongoing_requests=8,
                          max_queued_requests=64, request_replay=True)
        def echo(x):
            return x

        h = serve.run(echo.bind(), name="bench_serve",
                      route_prefix="/bench_serve")
        h.remote(0).result(timeout=60)  # warm router + replicas
        dropped = [0]
        lock = threading.Lock()

        def sustained(duration: float):
            """One 4-thread sustained-QPS burst -> (sorted lats, secs)."""
            lat: list = []
            stop_at = time.perf_counter() + duration

            def pump():
                while time.perf_counter() < stop_at:
                    t0 = time.perf_counter()
                    try:
                        h.remote(1).result(timeout=30)
                        dt = time.perf_counter() - t0
                        with lock:
                            lat.append(dt)
                    except BackPressureError:
                        with lock:
                            dropped[0] += 1
                    except Exception:  # noqa: BLE001 — keep pumping
                        pass

            threads = [threading.Thread(target=pump) for _ in range(4)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            lat.sort()
            return lat, time.perf_counter() - t0

        # A/B: request tracing sampled 1-in-1 vs fully off. The sampled
        # bit is minted caller-side and rides the wire, so toggling it
        # here switches replica-side recording too. A warm-up burst
        # first: the traced leg runs first, and without it the delta
        # would mostly measure cold leases/JIT, not tracing.
        from ray_tpu.serve import request_trace
        request_trace.set_sample_n(0)
        sustained(0.8)
        request_trace.set_sample_n(1)
        lat, elapsed = sustained(2.0)
        if lat:
            out["serve_qps"] = round(len(lat) / elapsed, 1)
            out["serve_p99_ms"] = round(
                lat[min(len(lat) - 1, int(0.99 * len(lat)))] * 1e3, 2)
        request_trace.set_sample_n(0)
        lat_off, elapsed_off = sustained(2.0)
        request_trace.set_sample_n(None)
        if lat and lat_off:
            qps_on = len(lat) / elapsed
            qps_off = len(lat_off) / elapsed_off
            # Positive = tracing costs throughput.
            out["serve_trace_overhead_pct"] = round(
                (qps_off - qps_on) / qps_off * 100.0, 1)
        else:
            out["serve_trace_overhead_pct"] = 0.0
        out["serve_requests_dropped"] = dropped[0]
        log(f"serve: {out.get('serve_qps', 0):,.0f} req/s, "
            f"p99 {out.get('serve_p99_ms', 0):.1f} ms, "
            f"{dropped[0]} shed, trace overhead "
            f"{out['serve_trace_overhead_pct']:+.1f}%")
        serve.shutdown()
    except Exception as e:  # noqa: BLE001
        log(f"serve phase skipped: {type(e).__name__}: {e}")

    # --- continuous-batching serve phase (token-streaming workload) ---
    # Iteration-level batching vs the single-request-per-call baseline
    # on the SAME simulated device: each decode step costs a fixed
    # device-lock hold (the jitted-step analogue — serialized across
    # requests like a real accelerator), so batching N sequences into
    # one step is the only way to amortize it. Records streams/s for
    # both paths, the speedup, batch-occupancy p50/p95, and per-phase
    # step times. Occupancy p50 > 1 and speedup >= 2x are tier-1
    # acceptance (tests/test_bench_smoke.py): unlike raw throughput,
    # the RATIO on one box is stable under CI load.
    try:
        out.update(_serve_cb_phase())
    except Exception as e:  # noqa: BLE001 — smoke must finish
        log(f"serve CB phase skipped: {type(e).__name__}: {e}")

    # --- placement group create/remove latency ---
    try:
        from ray_tpu.util.placement_group import (placement_group,
                                                  remove_placement_group)
        create_ms, remove_ms = [], []
        for _ in range(3):
            t0 = time.perf_counter()
            pg = placement_group([{"CPU": 1}], strategy="PACK")
            ray_tpu.get(pg.ready(), timeout=30)
            create_ms.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            remove_placement_group(pg)
            remove_ms.append((time.perf_counter() - t0) * 1e3)
        out["pg_create_ms"] = round(statistics.median(create_ms), 2)
        out["pg_remove_ms"] = round(statistics.median(remove_ms), 2)
        log(f"pg create/remove: {out['pg_create_ms']}/"
            f"{out['pg_remove_ms']} ms")
    except Exception as e:  # noqa: BLE001
        log(f"pg phase skipped: {type(e).__name__}: {e}")

    # --- compiled-DAG phase: per-tick latency vs the .remote() chain ---
    # A 3-stage actor pipeline compiled onto pre-leased workers + shm
    # ring channels vs the same three actors chained through ordinary
    # task RPCs. Records sequential per-tick latency, pipelined
    # throughput at depth 4, the transport-frame delta across the ticks
    # (the zero-per-tick-RPC proof), and the speedup RATIO — which is
    # tier-1-asserted >= 3x (tests/test_bench_smoke.py): like the CB
    # speedup, a same-box ratio is stable under CI load where absolute
    # rates are not.
    try:
        out.update(_dag_phase())
    except Exception as e:  # noqa: BLE001 — smoke must finish
        log(f"compiled-DAG phase skipped: {type(e).__name__}: {e}")

    # --- compiled-DAG recovery: kill -> first post-recovery tick ------
    # SIGKILL one executor of a tick_replay pipeline mid-stream and time
    # the outage as the caller sees it (detection + in-place recovery +
    # replay), plus the post-recovery steady-state rate vs pre-kill —
    # the self-healing row (dag_recovery_ms tier-1-asserted present).
    try:
        out.update(_dag_recovery_phase())
    except Exception as e:  # noqa: BLE001 — smoke must finish
        log(f"DAG-recovery phase skipped: {type(e).__name__}: {e}")

    # --- podracer RL substrate: compiled-DAG act->learn vs .remote() --
    # The sustained-workload row: N rollout actors feeding a PPO learner
    # through the compiled-DAG channel plane (weights broadcast via ONE
    # object-plane put per version) vs the SAME actor/learner classes
    # driven by naive per-tick `.remote()` fan-out. The steps/s RATIO is
    # tier-1-asserted >= 2x (tests/test_bench_smoke.py), and the
    # streaming-ingest sub-row asserts the host-side queue's peak depth
    # never passed its configured bound (writer-blocks backpressure).
    try:
        out.update(_podracer_phase())
    except Exception as e:  # noqa: BLE001 — smoke must finish
        log(f"podracer phase skipped: {type(e).__name__}: {e}")

    ray_tpu.shutdown()

    # --- telemetry overhead: metrics agent on vs off (ISSUE 18) -------
    # The same single-driver task burst on two fresh clusters, one with
    # the delta-frame MetricsAgent shipping every 0.5 s and one with
    # shipping fully off, plus the driver agent's own per-frame wire
    # cost. The overhead pct is tier-1-bounded (generously — CI noise)
    # in tests/test_bench_smoke.py; the acceptance <= 2% bound needs an
    # idle box.
    try:
        out.update(_telemetry_phase())
    except Exception as e:  # noqa: BLE001 — smoke must finish
        log(f"telemetry phase skipped: {type(e).__name__}: {e}")

    # --- launch storm: cold vs warm actor creation on a 3-node fake ---
    # The fleet-scale launch row: a cold storm (pools at their base
    # floor) and a warm storm (prestart-hinted pools) of actor creates
    # on the same 3-node topology, with the spawn-phase span breakdown
    # (actor:spawn / actor:register / actor:ctor) proving where the time
    # went. The warm rate is tier-1-asserted against a conservative
    # floor (tests/test_bench_smoke.py) so the 0.05x row can't silently
    # regress; the rest is printed, never asserted.
    try:
        out.update(_launch_storm_phase())
    except Exception as e:  # noqa: BLE001 — smoke must finish
        log(f"launch-storm phase skipped: {type(e).__name__}: {e}")
    return out


def _telemetry_phase() -> dict:
    import ray_tpu
    from ray_tpu._private import worker_api

    def burst_rate() -> float:
        @ray_tpu.remote
        def nop():
            return None

        ray_tpu.get([nop.remote() for _ in range(50)], timeout=60)  # warm
        rates = []
        for _ in range(5):
            n = 600
            t0 = time.perf_counter()
            ray_tpu.get([nop.remote() for _ in range(n)], timeout=60)
            rates.append(n / (time.perf_counter() - t0))
        # Best of 5: same stall quarantine as the n:n phase above —
        # the A/B compares capacity, and scheduling stalls on a loaded
        # box otherwise swamp the ~2% signal being measured.
        return max(rates)

    out: dict = {}
    rates: dict = {}
    frames = fbytes = 0.0
    for mode, enabled in (("off", False), ("on", True)):
        ray_tpu.init(num_cpus=max(2, (os.cpu_count() or 1)),
                     system_config={"metrics_agent_enabled": enabled,
                                    "metrics_report_interval_s": 0.5})
        try:
            rates[mode] = burst_rate()
            if enabled:
                # Worker agents ship these counters (the in-process GCS
                # force-claims the driver registry, so the driver itself
                # never frames); the tsdb folds all reporters together.
                # Their cumulative charge needs >= 2 report ticks per
                # worker, so poll rather than guess a sleep.
                core = worker_api.get_core()
                deadline = time.time() + 12
                while time.time() < deadline and frames <= 0:
                    time.sleep(0.5)
                    res = worker_api._call_on_core_loop(
                        core, core.gcs.request("metrics_query", {
                            "queries": [
                                {"name": "ray_tpu_metrics_frames_total",
                                 "fold": "latest"},
                                {"name":
                                 "ray_tpu_metrics_frame_bytes_total",
                                 "fold": "latest"}]}), 30)
                    frames = sum(s["points"][0][1] for s in res[0]
                                 if s["points"])
                    fbytes = sum(s["points"][0][1] for s in res[1]
                                 if s["points"])
        finally:
            ray_tpu.shutdown()
    overhead = (rates["off"] - rates["on"]) / rates["off"] * 100.0
    out["telemetry_off_rate"] = round(rates["off"], 1)
    out["telemetry_on_rate"] = round(rates["on"], 1)
    out["telemetry_overhead_pct"] = round(overhead, 2)
    out["telemetry_frames_shipped"] = int(frames)
    out["telemetry_frame_bytes_avg"] = \
        round(fbytes / frames, 1) if frames else 0.0
    log(f"telemetry overhead: {overhead:.2f}% "
        f"(off {rates['off']:,.0f}/s, on {rates['on']:,.0f}/s, "
        f"{out['telemetry_frame_bytes_avg']} B/frame over "
        f"{int(frames)} frames)")
    return out


def _put_get_phase() -> dict:
    import numpy as np

    import ray_tpu
    from ray_tpu._private import worker_api

    out: dict = {}
    # Small objects: per-call control cost, not bandwidth.
    small = np.zeros(8)
    for r in [ray_tpu.put(small) for _ in range(50)]:      # warm
        ray_tpu.get(r)
    n = 300
    t0 = time.perf_counter()
    refs = [ray_tpu.put(small) for _ in range(n)]
    out["put_small_calls_per_s"] = round(n / (time.perf_counter() - t0), 1)
    t0 = time.perf_counter()
    for r in refs:
        ray_tpu.get(r)
    out["get_small_calls_per_s"] = round(n / (time.perf_counter() - t0), 1)

    # 64MB through the plane. One warm round first (attaches the
    # segment); each measured put lands on a DISTINCT region of the
    # prefaulted initial segment — freeing between rounds would race the
    # async release and hand a later round cold pages. Best-of-3: the
    # same sandbox stall quarantine as the n:n row. A put is one memcpy
    # into shm by construction, so the box's warm copy rate is its
    # ceiling — recorded alongside as put_copy_ceiling_gbs so the ratio
    # survives VM-to-VM memory-bandwidth drift.
    big = np.ones(64 << 20, dtype=np.uint8)
    gbs = big.nbytes / 1e9
    ray_tpu.get(ray_tpu.put(big))
    scratch = np.empty_like(big)
    ceiling = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        scratch[:] = big
        ceiling = max(ceiling, gbs / (time.perf_counter() - t0))
    del scratch
    put_best = get_best = 0.0
    refs = []
    for _ in range(3):
        t0 = time.perf_counter()
        refs.append(ray_tpu.put(big))
        put_best = max(put_best, gbs / (time.perf_counter() - t0))
    val = None
    for _ in range(3):
        t0 = time.perf_counter()
        val = ray_tpu.get(refs[-1], timeout=60)
        get_best = max(get_best, gbs / (time.perf_counter() - t0))
    out["put_large_gbs"] = round(put_best, 2)
    out["get_large_gbs"] = round(get_best, 2)
    out["put_copy_ceiling_gbs"] = round(ceiling, 2)

    # Zero-copy proof: the array handed back by a same-node get is a
    # view INTO an attached shm segment, not a copy.
    assert isinstance(val, np.ndarray) and val.nbytes == big.nbytes
    addr = val.__array_interface__["data"][0]
    core = worker_api.peek_core()
    inside = False
    for shm in core.store._segments.values():
        seg = np.frombuffer(shm.buf, dtype=np.uint8)
        base = seg.__array_interface__["data"][0]
        if base <= addr < base + seg.nbytes:
            inside = True
            break
    out["put_get_zero_copy"] = inside
    log(f"put/get: small {out['put_small_calls_per_s']:,.0f}/"
        f"{out['get_small_calls_per_s']:,.0f} calls/s, 64MB "
        f"{out['put_large_gbs']}/{out['get_large_gbs']} GB/s put/get, "
        f"zero_copy={inside}")
    return out


_LB_SCRIPT = """
import json, os, sys, time
os.environ['JAX_PLATFORMS'] = 'cpu'
if {inline!r}:
    # Push every plane threshold above any payload: bodies ride the RPC
    # frame exactly as they did before the object plane landed.
    os.environ['RAY_TPU_OBJECT_PLANE_THRESHOLD'] = str(1 << 40)
sys.path.insert(0, {here!r})
import ray_tpu
from ray_tpu import serve
from ray_tpu._private import object_plane
ray_tpu.init(num_cpus=2)
body = b'x' * (2 << 20)

@serve.deployment(num_replicas=1, max_ongoing_requests=8)
def echo(b):
    return b

h = serve.run(echo.bind(), name='lb', route_prefix='/lb')
for _ in range(8):                       # warm lease + JIT + segment
    r = h.remote(body).result(timeout=60)
lats = []
for _ in range(60):
    t0 = time.perf_counter()
    r = h.remote(body).result(timeout=60)
    # Time-to-usable, not time-to-copy: a zero-copy consumer reads the
    # view in place (len + first byte), it does not materialize bytes.
    assert len(r) == len(body) and object_plane.body_view(r)[0] == 120
    lats.append(time.perf_counter() - t0)
lats.sort()
print('LBROW', json.dumps({{
    'p50_ms': round(lats[len(lats) // 2] * 1e3, 2),
    'p99_ms': round(lats[min(len(lats) - 1, int(0.99 * len(lats)))]
                    * 1e3, 2)}}))
serve.shutdown()
ray_tpu.shutdown()
"""


def _serve_large_body_phase() -> dict:
    out: dict = {}
    rows = {}
    for tag, inline in (("plane", False), ("inline", True)):
        script = _LB_SCRIPT.format(inline=inline, here=HERE)
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            raise RuntimeError(f"{tag} leg rc={proc.returncode}: "
                               f"{proc.stderr[-500:]}")
        for ln in proc.stdout.splitlines():
            if ln.startswith("LBROW "):
                rows[tag] = json.loads(ln[6:])
    out["serve_lb_p99_ms"] = rows["plane"]["p99_ms"]
    out["serve_lb_p50_ms"] = rows["plane"]["p50_ms"]
    out["serve_lb_inline_p99_ms"] = rows["inline"]["p99_ms"]
    out["serve_lb_inline_p50_ms"] = rows["inline"]["p50_ms"]
    out["serve_lb_p99_speedup"] = round(
        rows["inline"]["p99_ms"] / rows["plane"]["p99_ms"], 2) \
        if rows["plane"]["p99_ms"] else 0.0
    log(f"serve large-body (2MB): plane p50/p99 "
        f"{out['serve_lb_p50_ms']}/{out['serve_lb_p99_ms']} ms vs "
        f"inline {out['serve_lb_inline_p50_ms']}/"
        f"{out['serve_lb_inline_p99_ms']} ms -> "
        f"{out['serve_lb_p99_speedup']}x at p99")
    return out


def _serve_cb_phase() -> dict:
    import threading

    from ray_tpu import serve

    STEP_COST_S = 0.002      # device-lock hold per step (jit-step stand-in)
    TOKENS = 16              # tokens per stream
    CLIENTS = 6
    MEASURE_S = 2.5

    def make(name, continuous):
        @serve.deployment(name=name, num_replicas=1,
                          max_ongoing_requests=64)
        class LM:
            def __init__(self):
                import asyncio as _a
                self._dev = _a.Lock()   # the "accelerator": one step at a time

            @serve.continuous_batching(max_batch_size=8)
            async def step(self, phase, batch):
                import asyncio as _a
                async with self._dev:
                    await _a.sleep(STEP_COST_S)
                res = [None] * len(batch)
                for i, s in enumerate(batch):
                    if s is None:
                        continue
                    if phase == "prefill":
                        s.state = {"n": s.args[0], "i": 0}
                        res[i] = (None, False)
                    else:
                        st = s.state
                        tok = st["i"]
                        st["i"] += 1
                        res[i] = (tok, st["i"] >= st["n"])
                return res

            async def __call__(self, n):
                import asyncio as _a
                if continuous:
                    async for t in self.step(n):
                        yield t
                else:
                    # Baseline: one request per call, every token pays
                    # its own serialized device step.
                    async with self._dev:
                        await _a.sleep(STEP_COST_S)   # prefill
                    for i in range(n):
                        async with self._dev:
                            await _a.sleep(STEP_COST_S)
                        yield i

            def cb_stats(self):
                sched = getattr(self, "__serve_cb_scheduler_step", None)
                return sched.stats() if sched is not None else {}

        return LM

    def drive(handle) -> tuple:
        """CLIENTS threads stream TOKENS-token requests for MEASURE_S:
        -> (streams/s, tokens/s, sorted stream latencies)."""
        lats: list = []
        tokens = [0]
        lock = threading.Lock()
        stop_at = time.perf_counter() + MEASURE_S

        def pump():
            while time.perf_counter() < stop_at:
                t0 = time.perf_counter()
                try:
                    n = sum(1 for _ in handle.options(
                        stream=True).remote(TOKENS))
                    dt = time.perf_counter() - t0
                    with lock:
                        lats.append(dt)
                        tokens[0] += n
                except Exception:  # noqa: BLE001 — keep pumping
                    pass

        threads = [threading.Thread(target=pump) for _ in range(CLIENTS)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        elapsed = time.perf_counter() - t0
        lats.sort()
        return (len(lats) / elapsed, tokens[0] / elapsed, lats)

    out: dict = {}
    try:
        h_cb = serve.run(make("CbLM", True).bind(), name="bench_cb",
                         route_prefix="/bench_cb")
        h_base = serve.run(make("BaseLM", False).bind(), name="bench_base",
                           route_prefix="/bench_base")
        # Warm both paths (router refresh + scheduler/loop spin-up).
        sum(1 for _ in h_cb.options(stream=True).remote(2))
        sum(1 for _ in h_base.options(stream=True).remote(2))

        qps_cb, tok_cb, lats_cb = drive(h_cb)
        qps_base, _tok_base, lats_base = drive(h_base)

        def p99(lats):
            return (lats[min(len(lats) - 1, int(0.99 * len(lats)))] * 1e3
                    if lats else 0.0)

        stats = h_cb.cb_stats.remote().result(timeout=30)
        out["serve_cb_qps"] = round(qps_cb, 1)
        out["serve_cb_tokens_per_s"] = round(tok_cb, 1)
        out["serve_cb_baseline_qps"] = round(qps_base, 1)
        out["serve_cb_speedup"] = round(qps_cb / qps_base, 2) \
            if qps_base else 0.0
        out["serve_cb_p99_ms"] = round(p99(lats_cb), 2)
        out["serve_cb_baseline_p99_ms"] = round(p99(lats_base), 2)
        out["serve_cb_occupancy_p50"] = stats.get("occupancy_p50", 0.0)
        out["serve_cb_occupancy_p95"] = stats.get("occupancy_p95", 0.0)
        out["serve_cb_step_ms"] = stats.get("step_ms", {})
        log(f"serve CB: {qps_cb:,.1f} streams/s ({tok_cb:,.0f} tok/s) vs "
            f"baseline {qps_base:,.1f}/s -> {out['serve_cb_speedup']}x, "
            f"occupancy p50/p95 {out['serve_cb_occupancy_p50']}/"
            f"{out['serve_cb_occupancy_p95']}, p99 "
            f"{out['serve_cb_p99_ms']}/{out['serve_cb_baseline_p99_ms']} ms")
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001
            pass
    return out


def _dag_phase() -> dict:
    import statistics

    import ray_tpu
    from ray_tpu._private import rpc
    from ray_tpu.dag import InputNode
    from ray_tpu.dag.compiled import CompiledDAG

    # Fractional CPUs: the earlier phases' actors (callers/sinks) still
    # hold whole-CPU leases; the pipeline stages must schedule anyway.
    @ray_tpu.remote(num_cpus=0.01)
    class Stage:
        def __init__(self, off):
            self.off = off

        def apply(self, x):
            return x + self.off

    stages = [Stage.remote(1), Stage.remote(10), Stage.remote(100)]
    with InputNode() as inp:
        node = inp
        for s in stages:
            node = s.apply.bind(node)

    out: dict = {}
    compiled = CompiledDAG.compile(node, channel_depth=4)
    try:
        for i in range(10):                      # warm every hop
            assert compiled.execute(i, timeout=60) == i + 111
        n = 200
        frames0 = rpc.transport_stats()["frames"]
        per = []
        for i in range(n):
            t0 = time.perf_counter()
            compiled.execute(i, timeout=60)
            per.append(time.perf_counter() - t0)
        out["dag_tick_rpc_frames"] = \
            rpc.transport_stats()["frames"] - frames0
        out["dag_tick_ms"] = round(statistics.median(per) * 1e3, 3)
        out["dag_ticks_per_s"] = round(n / sum(per), 1)
        # Pipelined: windowed submit/collect (submitting unboundedly
        # ahead of collection from one thread would block the input
        # write with nobody draining outputs — see StagePipeline.run).
        from collections import deque
        pending = deque()
        t0 = time.perf_counter()
        for i in range(n):
            if len(pending) >= 4:
                pending.popleft().result(timeout=60)
            pending.append(compiled.execute_async(i))
        while pending:
            pending.popleft().result(timeout=60)
        out["dag_pipelined_ticks_per_s"] = round(
            n / (time.perf_counter() - t0), 1)
        out["dag_max_inflight"] = compiled.stats()["max_inflight"]
    finally:
        compiled.teardown()

    # Baseline: the same 3 actors chained through ordinary task RPCs.
    s1, s2, s3 = stages
    ray_tpu.get(s3.apply.remote(s2.apply.remote(s1.apply.remote(0))),
                timeout=60)
    per_b = []
    for i in range(60):
        t0 = time.perf_counter()
        ray_tpu.get(
            s3.apply.remote(s2.apply.remote(s1.apply.remote(i))),
            timeout=60)
        per_b.append(time.perf_counter() - t0)
    out["dag_chain_baseline_ms"] = round(
        statistics.median(per_b) * 1e3, 3)
    out["dag_speedup"] = round(
        out["dag_chain_baseline_ms"] / out["dag_tick_ms"], 2) \
        if out.get("dag_tick_ms") else 0.0
    log(f"compiled DAG: {out['dag_tick_ms']} ms/tick "
        f"({out['dag_ticks_per_s']}/s seq, "
        f"{out['dag_pipelined_ticks_per_s']}/s pipelined, "
        f"{out['dag_tick_rpc_frames']} rpc frames/{200} ticks) vs chain "
        f"{out['dag_chain_baseline_ms']} ms -> {out['dag_speedup']}x")
    return out


def _dag_recovery_phase() -> dict:
    import os
    import signal

    import ray_tpu
    from ray_tpu._private import worker_api
    from ray_tpu.dag import InputNode
    from ray_tpu.dag.compiled import CompiledDAG

    @ray_tpu.remote(num_cpus=0.01, max_restarts=-1)
    class Stage:
        def __init__(self, off):
            self.off = off

        def apply(self, x):
            return x + self.off

    stages = [Stage.remote(1), Stage.remote(10), Stage.remote(100)]
    with InputNode() as inp:
        node = inp
        for s in stages:
            node = s.apply.bind(node)

    out: dict = {}

    def rate(c, n=100):
        # Best of 3 windows: the same sandbox scheduling stall that
        # makes the n:n row bimodal (see that row's quarantine note)
        # can eat any single window; the pre/post RATIO is what the row
        # asserts, so both sides get the same treatment.
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            for i in range(n):
                c.execute(i, timeout=60)
            best = max(best, n / (time.perf_counter() - t0))
        return round(best, 1)

    compiled = CompiledDAG.compile(node, channel_depth=4,
                                   tick_replay=True)
    try:
        for i in range(10):
            assert compiled.execute(i, timeout=60) == i + 111
        pre_rate = rate(compiled)
        raylet = worker_api._state.head.raylet
        victim = next(h.pid for h in raylet.workers.values()
                      if h.actor_id == stages[1]._actor_id)
        # Kill mid-stream with ticks in flight, then time the outage as
        # the caller sees it: kill -> the next collected tick (watcher
        # detection + restart + re-pin + re-ship + replay).
        refs = [compiled.execute_async(1000 + i) for i in range(3)]
        os.kill(victim, signal.SIGKILL)
        t_kill = time.perf_counter()
        for r in refs:
            r.result(timeout=120)
        compiled.execute(2000, timeout=120)
        out["dag_recovery_ms"] = round(
            (time.perf_counter() - t_kill) * 1e3, 1)
        assert compiled.recoveries >= 1
        # Let the replacement worker + post-recovery careful window
        # settle before sampling steady state (the ratio judges the
        # recovered pipeline, not the restart's wake).
        for i in range(200):
            compiled.execute(i, timeout=60)
        time.sleep(0.3)
        post_rate = rate(compiled)
        out["dag_pre_kill_ticks_per_s"] = pre_rate
        out["dag_post_recovery_ticks_per_s"] = post_rate
        out["dag_post_recovery_ratio"] = round(post_rate / pre_rate, 3) \
            if pre_rate else 0.0
        out["dag_replayed_ticks"] = compiled.replayed_ticks
        log(f"DAG recovery: {out['dag_recovery_ms']} ms kill->tick, "
            f"rate {pre_rate}/s -> {post_rate}/s "
            f"({out['dag_post_recovery_ratio']}x), "
            f"{compiled.replayed_ticks} replayed")
    finally:
        compiled.teardown()
    return out


def _podracer_phase() -> dict:
    import ray_tpu
    from ray_tpu._private import rpc
    from ray_tpu.podracer import PodracerConfig, PodracerRun
    from ray_tpu.podracer.runtime import _Learner, _RolloutWorker
    from ray_tpu.rllib.env import get_env_creator, make_env

    # Fractional CPUs: earlier phases' actors still hold whole-CPU
    # leases (same reason as the DAG phase). Tiny fragments/net: this
    # row measures the per-tick SUBSTRATE overhead (channels vs task
    # RPCs, ring-slot weight broadcast vs per-actor pickle) — env/step
    # compute would mask exactly the thing being compared.
    cfg = PodracerConfig(num_actor_gangs=2, actors_per_gang=1,
                         num_envs=1, fragment_len=2, hidden=(4,),
                         minibatch_size=4, channel_depth=4,
                         actor_num_cpus=0.01, learner_num_cpus=0.01)
    out: dict = {}
    n = 40
    run = PodracerRun(cfg)
    try:
        run.run(5, window=1, timeout=120)        # warm every hop + jits
        frames0 = rpc.transport_stats()["frames"]
        best_dt = None
        for _ in range(2):   # best-of-2: the sandbox stall quarantine
            t0 = time.perf_counter()
            run.run(n, window=4, timeout=120)
            dt = time.perf_counter() - t0
            best_dt = dt if best_dt is None else min(best_dt, dt)
        out["podracer_rpc_frames"] = \
            rpc.transport_stats()["frames"] - frames0
        out["podracer_steps_per_s"] = round(
            n * cfg.steps_per_tick() / best_dt, 1)
        out["podracer_tick_ms"] = round(best_dt / n * 1e3, 3)
        out["podracer_weight_staleness_max"] = max(
            o["staleness"] for o in run.outputs)
        # Exactly-once across the measured window (cheap sanity, not a
        # perf row): the learner applied each tick exactly once.
        assert all(o["applied"] == o["tick"] + 1 for o in run.outputs)
    finally:
        run.teardown()

    # Naive baseline: the SAME actor/learner classes, driven tick by
    # tick through ordinary `.remote()` fan-out (rllib's historical
    # shape: sample fan-out -> learn -> broadcast, 3 task round trips
    # per tick instead of zero).
    creator = get_env_creator(cfg.env)
    env = make_env(creator, cfg.env_config)
    acls = ray_tpu.remote(num_cpus=0.01)(_RolloutWorker)
    lcls = ray_tpu.remote(num_cpus=0.01)(_Learner)
    actors = [acls.remote(creator, cfg.env_config, cfg.num_envs,
                          cfg.fragment_len, seed=1000 * (i + 1),
                          hidden=cfg.hidden)
              for i in range(cfg.num_actor_gangs)]
    learner = lcls.remote(env.observation_dim, env.num_actions,
                          lr=cfg.lr, hidden=cfg.hidden,
                          minibatch_size=cfg.minibatch_size,
                          num_epochs=cfg.num_epochs, seed=cfg.seed)
    try:
        version, weights = ray_tpu.get(learner.control.remote(),
                                       timeout=120)

        def naive_tick(tick, version, weights):
            # The historical fan-out shape: params pickled to EACH
            # actor (no shared ring slot), batches by ref, one task
            # round trip per hop.
            ctl = (tick, version, weights)
            brefs = [a.collect.remote(ctl) for a in actors]
            ob = ray_tpu.get(learner.learn.remote(*brefs), timeout=120)
            if ob["weights"] is not None:
                return ob["version"], ob["weights"]
            return version, weights

        for tick in range(5):                              # warm
            version, weights = naive_tick(tick, version, weights)
        nb = 20
        best_b = None
        tick = 5
        for _ in range(2):   # best-of-2, same treatment as above
            t0 = time.perf_counter()
            for _i in range(nb):
                version, weights = naive_tick(tick, version, weights)
                tick += 1
            dt_b = time.perf_counter() - t0
            best_b = dt_b if best_b is None else min(best_b, dt_b)
        out["podracer_baseline_steps_per_s"] = round(
            nb * cfg.steps_per_tick() / best_b, 1)
        out["podracer_speedup"] = round(
            out["podracer_steps_per_s"]
            / out["podracer_baseline_steps_per_s"], 2)
    finally:
        for a in actors + [learner]:
            try:
                ray_tpu.kill(a)
            except Exception:  # noqa: BLE001
                pass
    log(f"podracer: {out['podracer_steps_per_s']:,.0f} steps/s "
        f"({out['podracer_tick_ms']} ms/tick, "
        f"{out['podracer_rpc_frames']} rpc frames/{n} ticks) vs naive "
        f"{out.get('podracer_baseline_steps_per_s', 0):,.0f}/s -> "
        f"{out.get('podracer_speedup', 0)}x, staleness max "
        f"{out['podracer_weight_staleness_max']}")

    # Streaming ingest: bounded host-side queue under a slow consumer.
    from ray_tpu import data as rd
    depth = 4
    ds = rd.range(20000, parallelism=4)
    batches = 0
    t0 = time.perf_counter()
    with ds.iter_stream(batch_size=256, max_queue_depth=depth) as stream:
        for _batch in stream:
            time.sleep(0.002)          # slow learner: backpressure engages
            batches += 1
        st = stream.stats()
    out["ingest_batches_per_s"] = round(
        batches / (time.perf_counter() - t0), 1)
    out["ingest_peak_queue_depth"] = st["peak_depth"]
    out["ingest_queue_depth_bound"] = depth
    out["ingest_blocked_puts"] = st["blocked_puts"]
    log(f"ingest: {out['ingest_batches_per_s']}/s x256 rows, peak queue "
        f"{st['peak_depth']}/{depth} ({st['blocked_puts']} blocked puts)")
    return out


def _launch_storm_phase() -> dict:
    import collections

    import ray_tpu
    from ray_tpu._private import worker_api
    from ray_tpu.cluster_utils import Cluster

    out: dict = {}
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 64},
                      system_config={"worker_start_timeout_s": 120.0})
    for _ in range(2):
        cluster.add_node(num_cpus=64)
    cluster.connect()
    try:
        cluster.wait_for_nodes()

        @ray_tpu.remote(num_cpus=0.01)
        class Tiny:
            def ready(self):
                return 1

        def span_breakdown(since: float) -> dict:
            agg = collections.defaultdict(list)
            for e in cluster.gcs.task_events:
                if (e.get("kind") == "span" and e.get("start", 0) >= since
                        and str(e.get("name", "")).startswith("actor:")):
                    agg[e["name"]].append(e["end"] - e["start"])
            brk = {}
            for name, vals in agg.items():
                vals.sort()
                brk[name.split(":", 1)[1]] = {
                    "n": len(vals),
                    "p50_ms": round(vals[len(vals) // 2] * 1e3, 1),
                    "p90_ms": round(vals[int(len(vals) * 0.9)] * 1e3, 1),
                }
            return brk

        def storm(n: int) -> tuple:
            t_wall = time.time()
            t0 = time.perf_counter()
            actors = [Tiny.remote() for _ in range(n)]
            # Below the 260s harness cap (tests/test_bench_smoke.py): a
            # hung storm must surface as this phase's "skipped" log, not
            # a SIGKILLed bench with no JSON row.
            ray_tpu.get([a.ready.remote() for a in actors],
                        timeout=200)
            return n / (time.perf_counter() - t0), t_wall

        # Cold-ish storm first (8 warmed, then
        # 40 creates against pools at their base prestart floor).
        warm8 = [Tiny.remote() for _ in range(8)]
        ray_tpu.get([a.ready.remote() for a in warm8], timeout=120)
        rate, t_wall = storm(40)
        out["actor_launch_per_s"] = round(rate, 1)
        out["launch_storm_cold_spans"] = span_breakdown(t_wall)
        hits = sum(r._pools.hits for r in cluster.raylets)
        misses = sum(r._pools.misses for r in cluster.raylets)
        log(f"actor_launch (cold storm): {rate:,.1f}/s "
            f"(pool {hits} hits / {misses} misses)")

        # Warm storm: announce it (the serve/gang paths send the same
        # prestart hint), wait for the pools to fork the batch, fire.
        n = 40
        worker_api.prestart_workers(n)
        deadline = time.time() + 90
        while time.time() < deadline and \
                sum(len(r._pools) for r in cluster.raylets) < n:
            time.sleep(0.3)
        frames0 = cluster.gcs.alive_frames_published
        hits0 = sum(r._pools.hits for r in cluster.raylets)
        rate, t_wall = storm(n)
        out["actor_launch_warm_per_s"] = round(rate, 1)
        out["launch_storm_warm_spans"] = span_breakdown(t_wall)
        out["launch_storm_warm_pool_hits"] = \
            sum(r._pools.hits for r in cluster.raylets) - hits0
        out["launch_storm_alive_frames"] = \
            cluster.gcs.alive_frames_published - frames0
        out["launch_storm_reg_reply_dispatches"] = \
            sum(r.register_reply_dispatches for r in cluster.raylets)
        log(f"actor_launch (warm storm): {rate:,.1f}/s "
            f"({out['launch_storm_warm_pool_hits']} pool hits, "
            f"{out['launch_storm_alive_frames']} ALIVE frames)")
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001
            pass
        cluster.shutdown()
    return out


if __name__ == "__main__":
    result = main()
    result["smoke"] = True
    print(json.dumps(result), flush=True)
