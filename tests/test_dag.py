"""DAG + compiled-graph + channel tests.

Reference: python/ray/dag/tests/, python/ray/tests/test_channel.py
(round-2 VERDICT missing #5).
"""

import time

import pytest

import ray_tpu
from ray_tpu.dag import InputNode, MultiOutputNode
from ray_tpu.experimental.channel import Channel, ChannelClosedError


class TestChannel:
    def test_write_read_roundtrip(self):
        ch = Channel(1 << 16)
        try:
            ch.write({"x": [1, 2, 3]})
            # A fresh attachment (reader) sees the value.
            reader = Channel(1 << 16, _name=ch.name)
            assert reader.read(timeout=5) == {"x": [1, 2, 3]}
            ch.write("second")
            assert reader.read(timeout=5) == "second"
            reader.destroy()
        finally:
            ch.destroy()

    def test_read_blocks_until_write(self):
        ch = Channel(1 << 12)
        try:
            with pytest.raises(TimeoutError):
                ch.read(timeout=0.1)
        finally:
            ch.destroy()

    def test_oversize_rejected(self):
        ch = Channel(64)
        try:
            with pytest.raises(ValueError):
                ch.write("x" * 1000)
        finally:
            ch.destroy()

    def test_close_wakes_reader(self):
        ch = Channel(1 << 12)
        try:
            ch.close()
            with pytest.raises(ChannelClosedError):
                ch.read(timeout=5)
        finally:
            ch.destroy()

    def test_unpicklable_payload_raises_not_hangs(self):
        """A payload that consistently fails to unpickle is NOT a torn
        read (those resolve within nanoseconds): the reader must raise
        after a bounded number of stable-header retries instead of
        spinning forever on a timeout-less read."""
        import time as _time
        ch = Channel(1 << 12)
        try:
            ch._write_bytes(b"\x80\x05 this is not a pickle")
            t0 = _time.monotonic()
            with pytest.raises(Exception) as ei:
                ch.read(timeout=30)
            assert not isinstance(ei.value, TimeoutError)
            assert _time.monotonic() - t0 < 5  # bounded, not the timeout
            # The cursor did not advance: a fresh value still arrives.
            ch.write("after")
            assert ch.read(timeout=5) == "after"
        finally:
            ch.destroy()


class TestClassicDAG:
    def test_function_chain(self, ray_shared):
        @ray_tpu.remote
        def double(x):
            return x * 2

        @ray_tpu.remote
        def add(x, y):
            return x + y

        with InputNode() as inp:
            dag = add.bind(double.bind(inp), 10)
        assert ray_tpu.get(dag.execute(5), timeout=30) == 20
        assert ray_tpu.get(dag.execute(7), timeout=30) == 24

    def test_actor_method_dag(self, ray_shared):
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def add(self, x):
                self.n += x
                return self.n

        c = Counter.remote()
        with InputNode() as inp:
            dag = c.add.bind(inp)
        assert ray_tpu.get(dag.execute(3), timeout=30) == 3
        assert ray_tpu.get(dag.execute(4), timeout=30) == 7

    def test_multi_output(self, ray_shared):
        @ray_tpu.remote
        def inc(x):
            return x + 1

        @ray_tpu.remote
        def dec(x):
            return x - 1

        with InputNode() as inp:
            dag = MultiOutputNode([inc.bind(inp), dec.bind(inp)])
        up, down = dag.execute(10)
        assert ray_tpu.get([up, down], timeout=30) == [11, 9]


class TestCompiledDAG:
    def test_compiled_function_chain(self, ray_shared):
        @ray_tpu.remote
        def double(x):
            return x * 2

        @ray_tpu.remote
        def add_one(x):
            return x + 1

        with InputNode() as inp:
            dag = add_one.bind(double.bind(inp))
        compiled = dag.experimental_compile()
        try:
            assert compiled.execute(5) == 11
            assert compiled.execute(6) == 13
            # Repeated executes reuse the same channels/executors.
            for i in range(20):
                assert compiled.execute(i) == i * 2 + 1
        finally:
            compiled.teardown()

    def test_compiled_actor_chain(self, ray_shared):
        @ray_tpu.remote
        class Stage:
            def __init__(self, offset):
                self.offset = offset

            def apply(self, x):
                return x + self.offset

        s1 = Stage.remote(100)
        s2 = Stage.remote(1000)
        with InputNode() as inp:
            dag = s2.apply.bind(s1.apply.bind(inp))
        compiled = dag.experimental_compile()
        try:
            assert compiled.execute(5) == 1105
            assert compiled.execute(6) == 1106
        finally:
            compiled.teardown()

    def test_compiled_error_propagates(self, ray_shared):
        @ray_tpu.remote
        def boom(x):
            raise ValueError(f"bad {x}")

        with InputNode() as inp:
            dag = boom.bind(inp)
        compiled = dag.experimental_compile()
        try:
            with pytest.raises(ValueError, match="bad 1"):
                compiled.execute(1)
            # Pipeline survives an application error.
            with pytest.raises(ValueError, match="bad 2"):
                compiled.execute(2)
        finally:
            compiled.teardown()

    def test_compiled_two_nodes_same_actor(self, ray_shared):
        """Both nodes of one actor share a single loop (separate loops
        would deadlock on the actor's concurrency slot)."""
        @ray_tpu.remote
        class TwoStep:
            def step1(self, x):
                return x + 1

            def step2(self, x):
                return x * 10

        a = TwoStep.remote()
        with InputNode() as inp:
            dag = a.step2.bind(a.step1.bind(inp))
        compiled = dag.experimental_compile()
        try:
            assert compiled.execute(4) == 50
            assert compiled.execute(9) == 100
        finally:
            compiled.teardown()

    def test_compiled_kwargs_and_const_only(self, ray_shared):
        @ray_tpu.remote
        def affine(x, scale=1, offset=0):
            return x * scale + offset

        @ray_tpu.remote
        def const_stage():
            return 7

        with InputNode() as inp:
            dag = MultiOutputNode([
                affine.bind(inp, scale=3, offset=2),
                const_stage.bind(),     # const-only: input is its trigger
            ])
        compiled = dag.experimental_compile()
        try:
            assert compiled.execute(5) == [17, 7]
            assert compiled.execute(6) == [20, 7]
        finally:
            compiled.teardown()

    def test_compiled_diamond_same_node_twice(self, ray_shared):
        """The same upstream bound twice aliases to one attached channel
        in the executor (pickle memoization) — must not deadlock."""
        @ray_tpu.remote
        def double(x):
            return x * 2

        @ray_tpu.remote
        def mul(a, b):
            return a * b

        with InputNode() as inp:
            n = double.bind(inp)
            dag = mul.bind(n, n)
        compiled = dag.experimental_compile()
        try:
            assert compiled.execute(3) == 36
            assert compiled.execute(4) == 64
        finally:
            compiled.teardown()

    def test_input_kwargs_rejected(self, ray_shared):
        @ray_tpu.remote
        def ident(x):
            return x

        with InputNode() as inp:
            dag = ident.bind(inp)
        with pytest.raises(ValueError, match="positional"):
            dag.execute(x=5)

class TestCompiledDagSubsystem:
    """ISSUE 12 acceptance: pre-leased pipelines over ring channels."""

    def _three_stage(self, ray_tpu):
        @ray_tpu.remote
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
        return stages, node

    @pytest.mark.timeout(120)
    def test_zero_per_tick_rpcs(self, ray_shared):
        """A 3-stage actor pipeline ticks with ZERO per-tick task RPCs:
        the transport frame counter stays flat across hundreds of ticks
        (background loops contribute O(1), not O(ticks))."""
        from helpers.transport_frames import \
            assert_frames_do_not_grow_with_ticks
        from ray_tpu.dag.compiled import CompiledDAG
        _stages, node = self._three_stage(ray_shared)
        c = CompiledDAG.compile(node, channel_depth=2)
        try:
            for i in range(5):
                assert c.execute(i) == i + 111

            def ticks():
                for i in range(300):
                    assert c.execute(i) == i + 111

            assert_frames_do_not_grow_with_ticks(ticks, 300)
        finally:
            c.teardown()

    @pytest.mark.timeout(120)
    def test_overlapping_executions_bounded_by_depth(self, ray_shared):
        """execute_async overlaps ticks: with per-stage sleeps, k ticks
        finish in pipelined (not serial) time, and >= 2 executions are
        in flight at channel depth >= 2."""
        @ray_shared.remote
        class Slow:
            def apply(self, x):
                time.sleep(0.05)
                return x + 1

        stages = [Slow.remote(), Slow.remote(), Slow.remote()]
        with InputNode() as inp:
            node = inp
            for s in stages:
                node = s.apply.bind(node)
        from ray_tpu.dag.compiled import CompiledDAG
        c = CompiledDAG.compile(node, channel_depth=4)
        try:
            assert c.execute(0) == 3   # warm
            k = 8
            t0 = time.perf_counter()
            refs = [c.execute_async(i) for i in range(k)]
            outs = [r.result(timeout=30) for r in refs]
            dt = time.perf_counter() - t0
            assert outs == [i + 3 for i in range(k)]
            serial = k * 3 * 0.05
            assert dt < serial * 0.75, \
                f"{dt:.2f}s for {k} ticks — no overlap (serial {serial:.2f}s)"
            assert c.stats()["max_inflight"] >= 2
        finally:
            c.teardown()

    @pytest.mark.timeout(120)
    def test_worker_death_mid_tick_typed_and_teardown_clean(self,
                                                            ray_start):
        """Killing a pipeline worker mid-tick raises DagExecutionError on
        the in-flight execute (fast — the settled-ref watcher, not a
        polling backstop) and on every subsequent one; teardown then
        releases every pinned lease and unlinks every segment."""
        from ray_tpu._private import worker_api
        from ray_tpu.dag.compiled import CompiledDAG
        from ray_tpu.exceptions import DagExecutionError
        from ray_tpu.experimental.channels import local_segments

        @ray_start.remote
        class Stage:
            def __init__(self, off):
                self.off = off

            def apply(self, x):
                if x == 999:
                    time.sleep(60)
                return x + self.off

        stages = [Stage.remote(1), Stage.remote(10), Stage.remote(100)]
        with InputNode() as inp:
            node = inp
            for s in stages:
                node = s.apply.bind(node)
        c = CompiledDAG.compile(node, channel_depth=2)
        raylet = worker_api._state.head.raylet
        assert c._dag_id in raylet._dag_pins
        assert len(raylet._dag_pins[c._dag_id]) == 3
        seg_names = [ch.name for ch in c._channels if hasattr(ch, "name")]
        assert set(seg_names) <= set(local_segments())
        try:
            assert c.execute(0) == 111
            ref = c.execute_async(999)   # stage 1 wedges mid-tick
            time.sleep(0.2)
            ray_start.kill(stages[0])
            t0 = time.monotonic()
            with pytest.raises(DagExecutionError):
                ref.result(timeout=60)
            assert time.monotonic() - t0 < 30, "liveness window blown"
            with pytest.raises(DagExecutionError):
                c.execute(1)
        finally:
            c.teardown()
        # Lease accounting drained + every shm segment unlinked.
        assert c._dag_id not in raylet._dag_pins
        assert not any(h.dag_pins for h in raylet.workers.values())
        assert not set(seg_names) & set(local_segments())

    @pytest.mark.timeout(120)
    def test_compile_error_path_releases(self, ray_shared):
        """A compile that fails after acquiring resources must release
        them (channels + pinned leases) — the error-path teardown."""
        from ray_tpu._private import worker_api
        from ray_tpu.dag.compiled import CompiledDAG
        from ray_tpu.experimental.channels import local_segments

        @ray_shared.remote
        class Stage:
            def apply(self, x):
                return x

        s = Stage.remote()
        with InputNode() as inp:
            dag = s.apply.bind(inp)
        segs0 = set(local_segments())
        raylet = worker_api._state.head.raylet
        pins0 = {d for d, w in raylet._dag_pins.items() if w}

        class _Boom(CompiledDAG):
            def _arm_watcher(self, core):
                raise RuntimeError("injected compile failure")

        with pytest.raises(RuntimeError, match="injected"):
            _Boom(dag)
        assert {d for d, w in raylet._dag_pins.items() if w} == pins0
        assert set(local_segments()) == segs0

    @pytest.mark.timeout(120)
    def test_stage_pipeline_proof_workload(self, ray_shared):
        """dag.stage_pipeline.StagePipeline: the MPMD stage graph compiled
        onto the substrate — pipelined map, order preserved."""
        from ray_tpu.dag.stage_pipeline import StagePipeline

        @ray_shared.remote
        class Stage:
            def __init__(self, tag):
                self.tag = tag

            def apply(self, x):
                return x + [self.tag]

        stages = [Stage.remote(t) for t in ("a", "b", "c")]
        with StagePipeline(stages, method="apply",
                           channel_depth=4) as pipe:
            outs = pipe.run([[i] for i in range(10)], timeout=30)
            assert outs == [[i, "a", "b", "c"] for i in range(10)]
            assert pipe.stats()["ticks"] == 10

    @pytest.mark.timeout(60)
    def test_multi_output_timeout_resumes_aligned(self, ray_shared):
        """A result() timeout that interrupted a PARTIAL output drain
        (fast branch read, slow branch pending) must resume — not
        re-read the fast branch, which would pair tick N+1's fast value
        with tick N's slow one forever after."""
        @ray_shared.remote
        def fast(x):
            return ("fast", x)

        @ray_shared.remote
        def slow(x):
            time.sleep(0.8)
            return ("slow", x)

        with InputNode() as inp:
            dag = MultiOutputNode([fast.bind(inp), slow.bind(inp)])
        from ray_tpu.dag.compiled import CompiledDAG
        c = CompiledDAG.compile(dag, channel_depth=2)
        try:
            ref = c.execute_async(1)
            with pytest.raises(TimeoutError):
                ref.result(timeout=0.15)   # fast read, slow timed out
            assert ref.result(timeout=30) == [("fast", 1), ("slow", 1)]
            assert c.execute(2, timeout=30) == [("fast", 2), ("slow", 2)]
        finally:
            c.teardown()

    @pytest.mark.timeout(60)
    def test_result_is_one_shot_and_detached(self, ray_shared):
        """result() twice raises instead of wedging, and a HELD result
        array survives the writer recycling its ring slot (driver-side
        reads copy out of the ring)."""
        import numpy as np

        @ray_shared.remote
        def ident(x):
            return x

        with InputNode() as inp:
            dag = ident.bind(inp)
        from ray_tpu.dag.compiled import CompiledDAG
        c = CompiledDAG.compile(dag, channel_depth=2)
        try:
            ref = c.execute_async(np.full(2048, 7.0))
            held = ref.result(timeout=30)
            with pytest.raises(ValueError, match="already consumed"):
                ref.result(timeout=5)
            for i in range(6):   # lap every ring slot
                c.execute(np.full(2048, float(i)), timeout=30)
            assert (held == 7.0).all(), "held result was recycled"
        finally:
            c.teardown()

    @pytest.mark.timeout(60)
    def test_compiled_dag_metrics_and_span(self, ray_shared):
        """dag:compile span exported; tick histogram/in-flight gauge
        update (the observability satellite of the subsystem)."""
        from ray_tpu.dag.compiled import CompiledDAG
        from ray_tpu.util import metrics as _metrics

        @ray_shared.remote
        def ident(x):
            return x

        with InputNode() as inp:
            dag = ident.bind(inp)
        c = CompiledDAG.compile(dag)
        try:
            for i in range(3):
                assert c.execute(i) == i
            snap = {m["name"]: m for m in _metrics.snapshot()}
            assert snap["ray_tpu_dag_tick_seconds"]["count"] >= 3
            assert "ray_tpu_dag_inflight_executions" in snap
        finally:
            c.teardown()


class TestCompiledDagRecovery:
    """ISSUE 13 acceptance: self-healing compiled DAGs — in-place
    recovery, exactly-once tick replay, no teardown/recompile."""

    def _pids_by_actor(self, raylet):
        return {h.actor_id: h.pid for h in raylet.workers.values()
                if h.actor_id is not None}

    @pytest.mark.timeout(120)
    def test_sigkill_executor_exactly_once(self, ray_start, tmp_path):
        """SIGKILL one executor mid-pipelined-stream on a tick_replay
        DAG: every submitted tick's result is delivered exactly once (no
        duplicates, no gaps), the SAME CompiledDAG object keeps
        executing (no teardown/recompile by the caller), surviving
        executors keep their pids and never recompute a tick they
        already processed, pins are rebalanced onto the replacement
        worker, and ray_tpu_dag_recoveries_total increments once."""
        import os
        import signal

        from ray_tpu._private import worker_api
        from ray_tpu.dag.compiled import CompiledDAG
        from ray_tpu.util import metrics as _metrics

        log_dir = str(tmp_path)

        @ray_start.remote(max_restarts=-1)
        class Stage:
            def __init__(self, off):
                self.off = off
                self._log = open(f"{log_dir}/stage_{off}.log", "a")

            def apply(self, x):
                # Side-effect log: a survivor recomputing a tick after
                # recovery would duplicate its line here.
                self._log.write(f"{x}\n")
                self._log.flush()
                return x + self.off

        stages = [Stage.remote(1), Stage.remote(10), Stage.remote(100)]
        with InputNode() as inp:
            node = inp
            for s in stages:
                node = s.apply.bind(node)
        c = CompiledDAG.compile(node, channel_depth=4, tick_replay=True)
        raylet = worker_api._state.head.raylet
        pids0 = self._pids_by_actor(raylet)
        victim = pids0[stages[1]._actor_id]
        rec0 = {m["name"]: m.get("value", 0.0)
                for m in _metrics.snapshot()}.get(
                    "ray_tpu_dag_recoveries_total", 0.0)
        from collections import deque
        pending = deque()
        out = []
        try:
            for i in range(60):
                if len(pending) >= 4:
                    out.append(pending.popleft().result(timeout=90))
                pending.append(c.execute_async(i))
                if i == 25:
                    os.kill(victim, signal.SIGKILL)
            while pending:
                out.append(pending.popleft().result(timeout=90))
            # Exactly once, in order — no duplicates, no gaps, no typed
            # error ever surfaced to the caller.
            assert out == [i + 111 for i in range(60)]
            assert c.recoveries == 1 and c.replayed_ticks >= 1
            assert c.stats()["state"] == "running"
            snap = {m["name"]: m.get("value", 0.0)
                    for m in _metrics.snapshot()}
            assert snap["ray_tpu_dag_recoveries_total"] == rec0 + 1
            assert snap.get("ray_tpu_dag_replayed_ticks_total", 0) >= 1
            # Survivors kept their pids; the victim was replaced.
            pids1 = self._pids_by_actor(raylet)
            assert pids1[stages[0]._actor_id] == pids0[stages[0]._actor_id]
            assert pids1[stages[2]._actor_id] == pids0[stages[2]._actor_id]
            assert pids1[stages[1]._actor_id] != victim
            # Pins rebalanced: 3 again, dead worker's pin dropped.
            assert len(raylet._dag_pins[c._dag_id]) == 3
            # Survivors deduped by sequence: no tick recomputed (their
            # side-effect logs hold exactly one line per tick).
            lines = [ln for ln in
                     open(f"{log_dir}/stage_100.log").read().splitlines()]
            assert sorted(int(v) for v in lines) == \
                [i + 11 for i in range(60)]
            # Post-recovery steady state on the SAME object.
            for i in range(60, 70):
                assert c.execute(i, timeout=30) == i + 111
        finally:
            c.teardown()
        assert c._dag_id not in raylet._dag_pins

    @pytest.mark.timeout(120)
    def test_non_replayable_keeps_typed_fail_fast(self, ray_start):
        """Default (tick_replay=False) DAGs keep PR 12's contract: the
        kill surfaces as DagExecutionError, no silent recovery."""
        import os
        import signal
        import time as _time

        from ray_tpu._private import worker_api
        from ray_tpu.dag.compiled import CompiledDAG
        from ray_tpu.exceptions import DagExecutionError

        @ray_start.remote
        class Stage:
            def apply(self, x):
                return x + 1

        stages = [Stage.remote(), Stage.remote()]
        with InputNode() as inp:
            node = inp
            for s in stages:
                node = s.apply.bind(node)
        c = CompiledDAG.compile(node, channel_depth=2)
        try:
            assert c.execute(0) == 2
            raylet = worker_api._state.head.raylet
            pid = next(h.pid for h in raylet.workers.values()
                       if h.actor_id == stages[0]._actor_id)
            ref = c.execute_async(1)
            os.kill(pid, signal.SIGKILL)
            with pytest.raises(DagExecutionError):
                ref.result(timeout=60)
            with pytest.raises(DagExecutionError):
                c.execute(2)
            assert c.recoveries == 0
        finally:
            c.teardown()

    @pytest.mark.timeout(180)
    def test_double_death_and_death_during_recovery(self, ray_start):
        """Two executors dying at once are absorbed by one recovery
        pass; a replacement dying DURING recovery (injected right after
        the loop re-ship) is absorbed by the retrying watcher — the
        stream still completes exactly once."""
        import os
        import signal

        from ray_tpu._private import worker_api
        from ray_tpu.dag.compiled import CompiledDAG

        @ray_start.remote
        def double(x):
            return x * 2

        @ray_start.remote
        def add_one(x):
            return x + 1

        with InputNode() as inp:
            dag = add_one.bind(double.bind(inp))
        c = CompiledDAG.compile(dag, channel_depth=4, tick_replay=True)
        raylet = worker_api._state.head.raylet
        from collections import deque
        try:
            assert c.execute(5) == 11
            # Phase 1: kill BOTH executors' workers simultaneously.
            victims = [
                next(h.pid for h in raylet.workers.values()
                     if h.actor_id == p.handle._actor_id)
                for p in c._participants]
            pending = deque()
            out = []
            for i in range(40):
                if len(pending) >= 4:
                    out.append(pending.popleft().result(timeout=90))
                pending.append(c.execute_async(i))
                if i == 10:
                    for v in victims:
                        os.kill(v, signal.SIGKILL)
            while pending:
                out.append(pending.popleft().result(timeout=90))
            assert out == [i * 2 + 1 for i in range(40)]
            assert c.recoveries >= 1
            # Phase 2: kill one executor, then kill ANOTHER the moment
            # the recovery pass re-ships the loops.
            rec1 = c.recoveries
            victim = next(h.pid for h in raylet.workers.values()
                          if h.actor_id ==
                          c._participants[1].handle._actor_id)
            injected = []
            orig_ship = c._ship_loops

            def ship_then_kill(resume_map):
                orig_ship(resume_map)
                if resume_map and not injected:
                    injected.append(True)
                    aid = c._participants[0].handle._actor_id
                    pid = next((h.pid for h in raylet.workers.values()
                                if h.actor_id == aid), None)
                    if pid:
                        os.kill(pid, signal.SIGKILL)

            c._ship_loops = ship_then_kill
            pending = deque()
            out = []
            for i in range(40):
                if len(pending) >= 4:
                    out.append(pending.popleft().result(timeout=120))
                pending.append(c.execute_async(i))
                if i == 10:
                    os.kill(victim, signal.SIGKILL)
            while pending:
                out.append(pending.popleft().result(timeout=120))
            assert out == [i * 2 + 1 for i in range(40)]
            assert injected and c.recoveries > rec1
        finally:
            c.teardown()

    @pytest.mark.timeout(120)
    def test_stage_pipeline_survives_stage_death(self, ray_start):
        """StagePipeline (tick_replay default) absorbs a stage death
        transparently: run() returns every microbatch exactly once."""
        import os
        import signal
        import threading
        import time as _time

        from ray_tpu._private import worker_api
        from ray_tpu.dag.stage_pipeline import StagePipeline

        @ray_start.remote(max_restarts=-1)
        class Stage:
            def __init__(self, tag):
                self.tag = tag

            def apply(self, x):
                _time.sleep(0.01)   # keep the stream alive past the kill
                return x + [self.tag]

        stages = [Stage.remote(t) for t in ("a", "b", "c")]
        raylet = worker_api._state.head.raylet
        with StagePipeline(stages, method="apply",
                           channel_depth=4) as pipe:
            victim = next(h.pid for h in raylet.workers.values()
                          if h.actor_id == stages[1]._actor_id)
            timer = threading.Timer(
                0.4, lambda: os.kill(victim, signal.SIGKILL))
            timer.start()
            try:
                outs = pipe.run(([[i] for i in range(150)]), timeout=90)
            finally:
                timer.cancel()
            assert outs == [[i, "a", "b", "c"] for i in range(150)]
            assert pipe.stats()["recoveries"] >= 1

    @pytest.mark.timeout(120)
    def test_oversize_store_ref_replay_reseals_dangling_record(
            self, ray_start):
        """ISSUE 17 satellite: an oversize StoreChannel record points at
        an object owned by the writer; when that writer dies, the pin
        dies with it and the record dangles. The recovery resend path
        (what _run_compiled_loop runs on a resend_from directive) must
        RE-SEAL the record in place from the cached wire bytes so a
        reader paused at it still gets a payload — not a ref to memory
        the store has since unlinked."""
        import gc
        import pickle
        import time as _time

        import numpy as np

        from ray_tpu._private import worker_api
        from ray_tpu._private.serialization import context_for_process
        from ray_tpu.experimental.channels import StoreChannel

        ch = StoreChannel("testch/replay", depth=4, n_readers=1,
                          inline_limit=1024)
        try:
            big = np.arange(1 << 15, dtype=np.float64)   # 256 KiB
            wire = context_for_process().serialize((0, big)).to_bytes()
            ch.write_bytes(wire)           # oversize: rides the store
            oid = next(iter(ch._held_refs.values())).id.binary()
            # The writer "dies": its held pins are dropped and the owner
            # frees the payload — the KV record now dangles.
            ch._held_refs.clear()
            gc.collect()
            raylet = worker_api._state.head.raylet
            deadline = _time.time() + 15
            while raylet.store.contains(oid) and _time.time() < deadline:
                _time.sleep(0.05)
            assert not raylet.store.contains(oid), "free never landed"

            # Recovery re-ships the writer role (attach copy) and
            # replays the cached wire bytes through the resend hook,
            # exactly as the compiled loop's resume directive does.
            w2 = pickle.loads(pickle.dumps(ch))
            resend = getattr(w2, "resend_bytes", w2.write_bytes)
            resend(wire)

            r = ch.reader(0)
            t0 = _time.monotonic()
            seq, out = r.read(timeout=30)
            assert seq == 0 and np.array_equal(out, big)
            assert _time.monotonic() - t0 < 20, "re-sealed read hung"
            # The appended replay copy is also delivered (the compiled
            # loop dedupes replays by the embedded tick seq).
            seq2, out2 = r.read(timeout=30)
            assert seq2 == 0 and np.array_equal(out2, big)
            w2.destroy()
        finally:
            ch.destroy()

    @pytest.mark.timeout(120)
    def test_dangling_store_ref_fails_typed_without_resend(self, ray_start):
        """Without a recovery resend, a reader that hits a dangling
        oversize record must fail TYPED (ChannelDataLostError) within
        bounded time — never hang out a full object-get timeout on an
        object that can never materialize."""
        import gc
        import time as _time

        import numpy as np

        from ray_tpu._private import worker_api
        from ray_tpu.experimental.channels import (ChannelDataLostError,
                                                   StoreChannel)

        ch = StoreChannel("testch/dangle", depth=2, n_readers=1,
                          inline_limit=1024)
        try:
            big = np.arange(1 << 14, dtype=np.float64)
            ch.write(big)
            oid = next(iter(ch._held_refs.values())).id.binary()
            ch._held_refs.clear()
            gc.collect()
            raylet = worker_api._state.head.raylet
            deadline = _time.time() + 15
            while raylet.store.contains(oid) and _time.time() < deadline:
                _time.sleep(0.05)

            r = ch.reader(0)
            t0 = _time.monotonic()
            with pytest.raises(ChannelDataLostError):
                r.read(timeout=60)
            assert _time.monotonic() - t0 < 30, "typed failure too slow"
        finally:
            ch.destroy()


class TestCompiledDagLatency:
    @pytest.mark.timeout(60)
    def test_compiled_latency_beats_task_path(self, ray_shared):
        """The channel hand-off must be much cheaper than a task RPC.

        Deflaked: 50 calls sample the median hand-off as well as 200 did,
        and the tight timeout bounds the cost of the known contended-box
        mode (a starved executor turns each seqlock round trip into
        ~0.5s of spin-sleeps — the old 200-call loop could eat the full
        180s default budget before failing)."""
        @ray_tpu.remote
        def ident(x):
            return x

        with InputNode() as inp:
            dag = ident.bind(inp)
        compiled = dag.experimental_compile()
        try:
            compiled.execute(0)   # warm
            per = []
            n = 50
            for i in range(n):
                t0 = time.perf_counter()
                compiled.execute(i)
                per.append(time.perf_counter() - t0)
            per.sort()
            median = per[n // 2]
            assert median < 0.005, f"compiled call {median*1e3:.2f} ms"
        finally:
            compiled.teardown()
