"""Driver-exit lease + actor reclamation.

Reference parity: worker_pool.cc DisconnectClient (a departed client's
leased workers are destroyed, returning their resources) and
gcs_actor_manager.h OnWorkerDead (its non-detached actors die with it;
detached actors survive). Regression tests for the round-5 bug where
every exiting driver (clean or crashed) leaked its active leases: three
departed drivers pinned a 4-CPU node at 0 available CPUs forever (found
by a multi-client benchmark phase wedging a 10k-args probe).
"""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_driver(gcs_addr: str, body: str, crash: bool) -> None:
    script = (
        "import os, sys, time\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import ray_tpu\n"
        f"ray_tpu.init(address={gcs_addr!r})\n"
        + body
        + ("os._exit(1)\n" if crash else "ray_tpu.shutdown()\n"))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == (1 if crash else 0), proc.stderr[-500:]


def _wait_cpus(n: float, timeout: float = 30) -> float:
    deadline = time.time() + timeout
    while time.time() < deadline:
        avail = ray_tpu.available_resources().get("CPU", 0)
        if avail >= n:
            return avail
        time.sleep(0.5)
    return ray_tpu.available_resources().get("CPU", 0)


@pytest.mark.parametrize("crash", [False, True])
def test_departed_driver_releases_leases(crash):
    ray_tpu.init(num_cpus=2)
    try:
        from ray_tpu._private import worker_api
        gcs_addr = worker_api._state.gcs_address
        body = (
            "@ray_tpu.remote\n"
            "def nop():\n"
            "    return None\n"
            "ray_tpu.get([nop.remote() for _ in range(20)], timeout=60)\n")
        _run_driver(gcs_addr, body, crash)
        # The departed driver's lease must come back: on a 2-CPU node a
        # leaked lease leaves at most 1 CPU. Full availability recovers.
        assert _wait_cpus(2.0) >= 2.0

        @ray_tpu.remote
        def ping():
            return 42

        assert ray_tpu.get(ping.remote(), timeout=60) == 42
    finally:
        ray_tpu.shutdown()


def test_crashed_driver_kills_its_actors_but_not_detached():
    ray_tpu.init(num_cpus=3)
    try:
        from ray_tpu._private import worker_api
        gcs_addr = worker_api._state.gcs_address
        body = (
            "@ray_tpu.remote\n"
            "class A:\n"
            "    def ping(self):\n"
            "        return 1\n"
            "a = A.options(name='plain_actor').remote()\n"
            "d = A.options(name='kept_actor', lifetime='detached').remote()\n"
            "ray_tpu.get([a.ping.remote(), d.ping.remote()], timeout=60)\n")
        _run_driver(gcs_addr, body, crash=True)
        # The crashed driver's plain actor dies (its CPU returns); the
        # detached one survives and still serves calls.
        assert _wait_cpus(2.0) >= 2.0   # 3 total - detached actor - none
        kept = ray_tpu.get_actor("kept_actor")
        assert ray_tpu.get(kept.ping.remote(), timeout=60) == 1
        with pytest.raises(Exception):
            ray_tpu.get_actor("plain_actor")
    finally:
        ray_tpu.shutdown()
