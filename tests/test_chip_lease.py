"""One process per chip: a process that opened a TPU chip keeps it until it
exits, so the raylet never pools a worker whose lease held TPU and never
grants the TPU unit again before that worker has gone."""

import os
import time

import pytest

from chip_smoke import process_gone
from ray_tpu._private.config import Config


def test_returned_bundle_holds_back_leased_chips():
    from ray_tpu._private.raylet import ResourcePool
    pool = ResourcePool({"CPU": 4.0, "TPU": 4.0})
    key = (b"pg", 0)
    assert pool.reserve_bundle(key, {"CPU": 1.0, "TPU": 4.0})
    lease = {"CPU": 1.0, "TPU": 3.0}
    assert pool.acquire(lease, key)
    pool.return_bundle(key)
    # the CPU and the unleased chip are back; three chips are still held
    assert pool.available == {"CPU": 4.0, "TPU": 1.0}
    pool.release(lease, key)          # the worker has exited
    assert pool.available == {"CPU": 4.0, "TPU": 4.0}
    pool.release(lease, key)          # a replayed release adds nothing
    assert pool.available == {"CPU": 4.0, "TPU": 4.0}


@pytest.fixture
def one_chip_cluster():
    import ray_tpu
    ray_tpu.init(num_cpus=4, num_tpus=1)
    yield ray_tpu
    ray_tpu.shutdown()


def test_chip_task_worker_is_retired_not_pooled(one_chip_cluster):
    ray_tpu = one_chip_cluster

    @ray_tpu.remote(num_tpus=1)
    def first():
        return os.getpid()

    @ray_tpu.remote(num_tpus=1)
    def second(first_pid):
        # runs only once the one TPU unit was granted again
        return os.getpid(), process_gone(first_pid)

    @ray_tpu.remote
    def no_chip():
        return os.getpid()

    first_pid = ray_tpu.get(first.remote(), timeout=60)
    # a lease is reused while it is warm: same process, same chip
    assert ray_tpu.get(first.remote(), timeout=60) == first_pid
    time.sleep(3 * Config().idle_worker_lease_timeout_s)   # lease returned
    second_pid, first_gone = ray_tpu.get(second.remote(first_pid),
                                         timeout=60)
    assert second_pid != first_pid
    assert first_gone
    # workers without a chip are still pooled and reused
    assert ray_tpu.get(no_chip.remote(), timeout=60) == \
        ray_tpu.get(no_chip.remote(), timeout=60)
