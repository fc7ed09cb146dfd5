"""Ticks that pay no task RPC: the transport's frame count across them."""

import time


def assert_frames_do_not_grow_with_ticks(run_ticks, n: int) -> None:
    """Call `run_ticks()`, which runs n ticks, and hold the process's
    transport frames across it to a budget that is O(1) in n. Background
    loops (heartbeats, lease renewal) frame at a WALL-CLOCK rate
    independent of ticks; on a slow box the ticks take whole seconds and
    collect them. That idle rate is sampled first and subtracted: the
    claim is that frames don't scale with ticks, not that the transport
    goes silent while they run."""
    from ray_tpu._private import rpc
    idle0 = rpc.transport_stats()["frames"]
    time.sleep(1.0)
    idle_rate = rpc.transport_stats()["frames"] - idle0
    frames0 = rpc.transport_stats()["frames"]
    t0 = time.monotonic()
    run_ticks()
    elapsed = time.monotonic() - t0
    delta = rpc.transport_stats()["frames"] - frames0
    budget = n * 0.05 + idle_rate * elapsed * 2 + 2
    assert delta <= budget, \
        f"{delta} transport frames across {n} ticks " \
        f"({elapsed:.2f}s, idle rate {idle_rate}/s, budget " \
        f"{budget:.0f}) — the tick path is paying RPCs"
