"""Tier-1 sanity run of scripts/bench_smoke.py.

Completion-only: the smoke bench must run end to end and print one JSON
line with the three fan-in rows. Throughput is NEVER asserted here — CI
boxes are noisy. What this buys tier-1 is a cheap end-to-end drive of the
batched control-plane paths (multi-driver fan-in, n:n actors, push-based
PG readiness) in one subprocess.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "bench_smoke.py")

# Pipelined-vs-sequential speedup ratios need at least 2 cores: on a
# 1-core box every "parallel" stage timeslices at scheduler granularity
# (~5 ms/tick measured, vs 0.07 ms with 2 vCPUs) and the ratio inverts
# regardless of how the code performs. The rows are still asserted
# present — the phases must RUN everywhere — but the ratio floors only
# bind where the hardware can express them.
MULTI_CPU = (os.cpu_count() or 1) >= 2


@pytest.mark.timeout(280)
def test_bench_smoke_completes(jax_cpu):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                          text=True, timeout=260, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.strip().startswith("{")]
    assert lines, proc.stdout
    row = json.loads(lines[-1])
    assert row.get("smoke") is True
    # serve_requests_dropped is the serve-trajectory row: its presence
    # proves the serve request path (deploy, route, admission control)
    # ran end to end in the smoke.
    # serve_trace_overhead_pct proves the request-tracing A/B (sampled
    # 1-in-1 vs off) ran over the sustained-QPS serve phase.
    for key in ("multi_client_tasks_async", "n_n_actor_calls",
                "pg_create_ms", "serve_requests_dropped",
                "serve_trace_overhead_pct"):
        assert key in row, (key, row)
    # Object-plane put/get (ISSUE 17): throughput rows are printed only
    # (CI noise), but the zero-copy bit is a pointer-range check — a
    # same-node 64MB get must hand back a view INTO an attached shm
    # segment. A copy here silently doubles every large-payload hop.
    for key in ("put_small_calls_per_s", "get_small_calls_per_s",
                "put_large_gbs", "get_large_gbs", "put_get_zero_copy"):
        assert key in row, (key, row)
    assert row["put_get_zero_copy"] is True, row
    # Serve large-body A/B (plane vs forced-inline): presence only —
    # the p99 improvement needs an idle box, not CI load.
    for key in ("serve_lb_p99_ms", "serve_lb_inline_p99_ms",
                "serve_lb_p99_speedup"):
        assert key in row, (key, row)
    # Continuous-batching serve phase: a sustained token-streaming load
    # against the iteration-level scheduler vs the single-request-per-
    # call baseline on the SAME simulated device. Occupancy p50 > 1
    # proves requests actually shared steps (the whole point of
    # iteration-level batching), and the >= 2x speedup is a ratio on
    # one box — stable under CI load where absolute rates are not.
    for key in ("serve_cb_qps", "serve_cb_baseline_qps",
                "serve_cb_speedup", "serve_cb_p99_ms",
                "serve_cb_baseline_p99_ms", "serve_cb_occupancy_p50",
                "serve_cb_occupancy_p95", "serve_cb_step_ms"):
        assert key in row, (key, row)
    assert row["serve_cb_occupancy_p50"] > 1.0, row
    assert row["serve_cb_speedup"] >= 2.0, row
    # Per-phase step times recorded for both scheduled phases.
    assert set(row["serve_cb_step_ms"]) >= {"prefill", "decode"}, row
    # Compiled-DAG phase: a 3-stage pre-leased pipeline over shm ring
    # channels vs the same actors chained through task RPCs. The >= 3x
    # speedup is the ISSUE 12 acceptance ratio (stable on one box under
    # load); the frame delta proves ticks pay ZERO per-tick task RPCs
    # (background loops contribute O(1) frames across 200 ticks, a
    # per-tick RPC path would contribute >= 200).
    for key in ("dag_tick_ms", "dag_ticks_per_s",
                "dag_pipelined_ticks_per_s", "dag_chain_baseline_ms",
                "dag_speedup", "dag_tick_rpc_frames", "dag_max_inflight"):
        assert key in row, (key, row)
    if MULTI_CPU:
        assert row["dag_speedup"] >= 3.0, row
    assert row["dag_tick_rpc_frames"] <= 20, row
    assert row["dag_max_inflight"] >= 2, row
    # Self-healing DAG phase (ISSUE 13): SIGKILL one executor of a
    # tick_replay pipeline mid-stream; the row records kill -> first
    # post-recovery tick and the post/pre steady-state rate ratio.
    # Presence + a loose ratio floor are asserted (the recovery RAN and
    # the recovered pipeline is not degenerate); the 10%-of-pre-kill
    # acceptance ratio needs an idle box, not CI load.
    for key in ("dag_recovery_ms", "dag_pre_kill_ticks_per_s",
                "dag_post_recovery_ticks_per_s",
                "dag_post_recovery_ratio", "dag_replayed_ticks"):
        assert key in row, (key, row)
    assert row["dag_recovery_ms"] > 0, row
    assert row["dag_post_recovery_ratio"] >= 0.5, row
    # Hot-path allocation tripwire: a steady-state `.remote()` call must
    # stay a small, bounded number of allocations (measured ~19 blocks
    # with the recorder on after the template/flat-reply/event-ring
    # work, down from ~35; the ceiling leaves headroom for platform
    # variance, not for regressions). Unlike wall-clock rows this is
    # deterministic enough to assert in tier-1.
    assert "alloc_blocks_per_call" in row, row
    # On a 1-core box, background event-loop work interleaves INTO the
    # sampled calls and inflates the count nondeterministically
    # (measured 24.5 idle vs 39.5 under suite load, same code); the
    # ceiling is calibrated where sampling can isolate the hot path.
    if MULTI_CPU:
        assert row["alloc_blocks_per_call"] <= 28.0, row
    # Launch-storm floor: the warm path measured ~115/s on an idle
    # 2-vCPU box (the pre-pipeline row on the same box was 1.6/s). The
    # floor leaves ~6x headroom for CI load — this asserts the
    # warm-pool machinery ENGAGED (pool hits, not cold spawns), not a
    # throughput target.
    assert "actor_launch_warm_per_s" in row, row
    assert row["actor_launch_warm_per_s"] >= 20.0, row
    assert row.get("launch_storm_warm_pool_hits", 0) > 0, row
    # Podracer phase (ISSUE 15): the act->learn compiled-DAG substrate
    # vs the SAME actor/learner classes driven by naive `.remote()`
    # fan-out (the historical rllib shape: per-tick task round trips +
    # per-actor weight pickling). The >= 2x steps/s ratio is the issue's
    # acceptance bar — a same-box ratio, stable where absolute rates are
    # not — and the frame delta proves ticks pay zero per-tick task RPCs
    # (weights ride the input ring, not the wire).
    for key in ("podracer_steps_per_s", "podracer_baseline_steps_per_s",
                "podracer_speedup", "podracer_tick_ms",
                "podracer_rpc_frames", "podracer_weight_staleness_max"):
        assert key in row, (key, row)
    if MULTI_CPU:
        assert row["podracer_speedup"] >= 2.0, row
    assert row["podracer_rpc_frames"] <= 20, row
    # Streaming-ingest backpressure: the host-side queue's peak depth
    # never passed its configured bound while a slow consumer throttled
    # the producer (blocked puts prove the backpressure ENGAGED rather
    # than the bound being vacuously wide).
    for key in ("ingest_batches_per_s", "ingest_peak_queue_depth",
                "ingest_queue_depth_bound", "ingest_blocked_puts"):
        assert key in row, (key, row)
    assert row["ingest_peak_queue_depth"] <= \
        row["ingest_queue_depth_bound"], row
    assert row["ingest_blocked_puts"] > 0, row
    # Telemetry A/B (ISSUE 18): delta-frame shipping on vs off on fresh
    # clusters. Frames must actually have shipped (and stay small —
    # steady-state deltas are a few hundred bytes, not re-sent
    # catalogs). The acceptance <= 2% overhead bound needs an idle
    # box; here the bound is set at
    # the box's measured run-to-run burst noise so only a gross
    # regression (per-request shipping work) can trip it.
    for key in ("telemetry_off_rate", "telemetry_on_rate",
                "telemetry_overhead_pct", "telemetry_frames_shipped",
                "telemetry_frame_bytes_avg"):
        assert key in row, (key, row)
    assert row["telemetry_frames_shipped"] >= 1, row
    assert 1.0 <= row["telemetry_frame_bytes_avg"] <= 65536.0, row
    if MULTI_CPU:
        assert row["telemetry_overhead_pct"] <= 15.0, row
