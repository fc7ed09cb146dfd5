"""Core API tests: tasks, objects, put/get/wait.

Modeled on the reference's python/ray/tests/test_basic*.py coverage.
"""

import time

import numpy as np
import pytest


class TestTasks:
    def test_simple_task(self, ray_shared):
        ray = ray_shared

        @ray.remote
        def f(a, b):
            return a + b

        assert ray.get(f.remote(1, 2)) == 3

    def test_many_tasks(self, ray_shared):
        ray = ray_shared

        @ray.remote
        def sq(x):
            return x * x

        refs = [sq.remote(i) for i in range(50)]
        assert ray.get(refs) == [i * i for i in range(50)]

    def test_kwargs(self, ray_shared):
        ray = ray_shared

        @ray.remote
        def f(a, b=10, c=20):
            return a + b + c

        assert ray.get(f.remote(1, c=5)) == 16

    def test_multiple_returns(self, ray_shared):
        ray = ray_shared

        @ray.remote(num_returns=3)
        def f():
            return 1, 2, 3

        r1, r2, r3 = f.remote()
        assert ray.get([r1, r2, r3]) == [1, 2, 3]

    def test_task_dependency(self, ray_shared):
        ray = ray_shared

        @ray.remote
        def f(x):
            return x + 1

        ref = f.remote(0)
        for _ in range(5):
            ref = f.remote(ref)
        assert ray.get(ref) == 6

    def test_nested_tasks(self, ray_shared):
        ray = ray_shared

        @ray.remote
        def inner(x):
            return x * 2

        @ray.remote
        def outer(x):
            import ray_tpu
            return ray_tpu.get(inner.remote(x)) + 1

        assert ray.get(outer.remote(10)) == 21

    def test_task_error_propagation(self, ray_shared):
        ray = ray_shared

        @ray.remote
        def boom():
            raise ValueError("kaboom")

        with pytest.raises(ray.exceptions.TaskError) as ei:
            ray.get(boom.remote())
        assert isinstance(ei.value.cause, ValueError)
        assert "kaboom" in str(ei.value)

    def test_error_through_dependency(self, ray_shared):
        ray = ray_shared

        @ray.remote
        def boom():
            raise RuntimeError("first")

        @ray.remote
        def consume(x):
            return x

        with pytest.raises(ray.exceptions.TaskError):
            ray.get(consume.remote(boom.remote()))

    def test_direct_call_forbidden(self, ray_shared):
        ray = ray_shared

        @ray.remote
        def f():
            return 1

        with pytest.raises(TypeError):
            f()


class TestObjects:
    def test_put_get_roundtrip(self, ray_shared):
        ray = ray_shared
        for val in [1, "s", {"a": [1, 2]}, (None, True), b"bytes"]:
            assert ray.get(ray.put(val)) == val

    def test_large_object_shm(self, ray_shared):
        ray = ray_shared
        arr = np.random.rand(500_000)  # 4 MB > inline threshold
        ref = ray.put(arr)
        out = ray.get(ref)
        assert np.array_equal(arr, out)

    def test_same_node_large_get_is_a_view_into_an_attached_segment(
            self, ray_shared):
        """A same-node `get` of a plane-sized array hands back a view INTO
        a shm segment the driver attached. A copy here would silently
        double every large-payload hop."""
        from helpers.store_segments import in_attached_segment
        from ray_tpu._private import object_plane
        ray = ray_shared
        arr = np.ones(2 * object_plane.threshold(), dtype=np.uint8)
        out = ray.get(ray.put(arr), timeout=60)
        assert isinstance(out, np.ndarray) and out.nbytes == arr.nbytes
        assert out[0] == 1 and out[-1] == 1
        assert in_attached_segment(out)
        assert not in_attached_segment(arr)

    def test_large_task_arg_and_return(self, ray_shared):
        ray = ray_shared

        @ray.remote
        def double(a):
            return a * 2

        arr = np.ones(300_000)
        out = ray.get(double.remote(arr))
        assert out.shape == arr.shape
        assert float(out.sum()) == pytest.approx(600_000.0)

    def test_object_ref_in_container(self, ray_shared):
        ray = ray_shared
        inner_ref = ray.put(42)
        outer_ref = ray.put({"ref": inner_ref})

        @ray.remote
        def deref(d):
            import ray_tpu
            return ray_tpu.get(d["ref"])

        assert ray.get(deref.remote(ray.get(outer_ref))) == 42

    def test_handoff_credit_returned_on_probe_discard(self, ray_shared):
        """ADVICE r4 regression: the sync arg-probe serializes small args
        (granting handoff credits for contained self-owned refs), then
        discards the bytes when another arg needs plasma. The probe's
        credits must be returned, or the contained object's refcount is
        pinned one-high forever."""
        ray = ray_shared
        from ray_tpu._private import worker_api
        cw = worker_api._state.core
        inner = ray.put(12345)
        big = np.ones(300_000)  # plasma-sized: aborts the sync probe

        @ray.remote
        def f(d, a):
            import ray_tpu
            return ray_tpu.get(d["ref"]) + int(a.shape[0])

        assert ray.get(f.remote({"ref": inner}, big)) == 12345 + 300_000
        ent = cw.owned.get(inner.id)
        assert ent is not None
        # The real (loop-path) serialization's credit is consumed by the
        # worker's borrow registration; the discarded probe's credit must
        # have been returned — leaving zero outstanding once the worker's
        # borrow drains.
        for _ in range(100):
            if ent.handoff_credits == 0 and ent.borrowers == 0:
                break
            time.sleep(0.05)
        assert ent.handoff_credits == 0
        assert ent.borrowers == 0

    def test_get_timeout(self, ray_shared):
        ray = ray_shared

        @ray.remote
        def forever():
            time.sleep(60)

        ref = forever.remote()
        with pytest.raises(ray.exceptions.GetTimeoutError):
            ray.get(ref, timeout=0.3)
        ray.cancel(ref, force=True)


class TestWait:
    def test_wait_basic(self, ray_shared):
        ray = ray_shared

        @ray.remote
        def fast():
            return 1

        @ray.remote
        def slow():
            time.sleep(10)
            return 2

        r_fast, r_slow = fast.remote(), slow.remote()
        ready, not_ready = ray.wait([r_fast, r_slow], num_returns=1, timeout=5)
        assert ready == [r_fast]
        assert not_ready == [r_slow]
        ray.cancel(r_slow, force=True)

    def test_wait_all(self, ray_shared):
        ray = ray_shared

        @ray.remote
        def f(i):
            return i

        refs = [f.remote(i) for i in range(5)]
        ready, not_ready = ray.wait(refs, num_returns=5, timeout=10)
        assert len(ready) == 5 and not not_ready


class TestClusterInfo:
    def test_resources(self, ray_shared):
        ray = ray_shared
        total = ray.cluster_resources()
        assert total["CPU"] == 4.0

    def test_nodes(self, ray_shared):
        ray = ray_shared
        ns = ray.nodes()
        assert len(ns) == 1 and ns[0]["Alive"] and ns[0]["IsHead"]


def test_inspect_serializability(ray_shared):
    """Pinpoints the unserializable member (reference:
    ray.util.inspect_serializability)."""
    import threading

    from ray_tpu.util.serialization_helpers import inspect_serializability

    ok, failures = inspect_serializability({"x": 1}, print_report=False)
    assert ok and failures == []

    lock = threading.Lock()

    class Holder:
        def __init__(self):
            self.fine = 42
            self.bad = lock

    ok, failures = inspect_serializability(Holder(), print_report=False)
    assert not ok
    assert any("bad" in path for path, _t, _e in failures), failures

    captured = threading.Lock()

    def closure_fn():
        return captured

    ok, failures = inspect_serializability(closure_fn, print_report=False)
    assert not ok
    assert any("captured" in path for path, _t, _e in failures), failures
