"""Compiled DAGs spanning raylets (the store-channel fallback).

Own module: the fake multi-raylet Cluster cannot coexist with the
module-scoped single-node `ray_shared` cluster test_dag.py runs on
(ray_tpu.init is process-global).
"""

import pytest

from ray_tpu.dag import InputNode


@pytest.mark.timeout(180)
def test_cross_node_dag_spans_raylets(ray_cluster):
    """A compiled DAG whose stages live on different raylets falls back
    to store channels per edge (control via the GCS KV, payloads via
    the object store's transfer path) and still executes; teardown
    releases the pins on EVERY involved raylet."""
    import ray_tpu
    ray_cluster.add_node(num_cpus=2, resources={"far": 1})
    ray_cluster.connect()
    ray_cluster.wait_for_nodes()

    @ray_tpu.remote
    class Stage:
        def __init__(self, off):
            self.off = off

        def apply(self, x):
            return x + self.off

    s1 = Stage.options(resources={"far": 0.1}).remote(1)
    s2 = Stage.remote(10)
    with InputNode() as inp:
        dag = s2.apply.bind(s1.apply.bind(inp))
    from ray_tpu.dag.compiled import CompiledDAG
    from ray_tpu.experimental.channels import StoreChannel
    c = CompiledDAG.compile(dag, channel_depth=2)
    try:
        assert any(isinstance(ch, StoreChannel) for ch in c._channels), \
            "a cross-raylet edge must take the store fallback"
        assert c.execute(0) == 11
        assert c.execute(5) == 16
        assert sum(len(r._dag_pins.get(c._dag_id, ()))
                   for r in ray_cluster.raylets) == 2
    finally:
        c.teardown()
    assert all(c._dag_id not in r._dag_pins for r in ray_cluster.raylets)


@pytest.mark.timeout(180)
def test_drain_migrates_dag_and_rehomes_channels(ray_cluster):
    """ISSUE 13: a drain notice on the raylet hosting one stage migrates
    the DAG proactively — the stage restarts off the dying node
    (uncharged), its lease is re-pinned, the cross-node store edges
    RE-HOME to same-node shm rings once everything is co-located, zero
    DagExecutionError ever reaches the caller, and the drained raylet
    reports drain_complete well before its deadline (no pin wedge)."""
    import threading
    import time

    import ray_tpu
    from ray_tpu.dag.compiled import CompiledDAG
    from ray_tpu.experimental.channels import StoreChannel
    from ray_tpu.util.scheduling_strategies import \
        NodeAffinitySchedulingStrategy

    far = ray_cluster.add_node(num_cpus=2)
    ray_cluster.connect()
    ray_cluster.wait_for_nodes()

    @ray_tpu.remote
    class Stage:
        def __init__(self, off):
            self.off = off

        def apply(self, x):
            return x + self.off

    s1 = Stage.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(
            far.node_id, soft=True),
        max_restarts=-1).remote(1)
    s2 = Stage.options(max_restarts=-1).remote(10)
    with InputNode() as inp:
        dag = s2.apply.bind(s1.apply.bind(inp))
    c = CompiledDAG.compile(dag, channel_depth=4, tick_replay=True)
    try:
        assert any(isinstance(ch, StoreChannel) for ch in c._channels), \
            "setup must start with a cross-raylet (store) edge"
        assert c.execute(0) == 11

        errors, out, stop = [], [], threading.Event()

        def pump():
            i = 1
            while not stop.is_set() and i <= 400:
                try:
                    out.append((i, c.execute(i, timeout=60)))
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                    return
                i += 1
                time.sleep(0.005)

        t = threading.Thread(target=pump, daemon=True)
        t.start()
        time.sleep(0.3)
        t0 = time.time()
        ray_cluster.drain_node(far, deadline_s=8.0, grace_s=0.3,
                               wait=True)
        drain_dt = time.time() - t0
        time.sleep(1.0)
        stop.set()
        t.join(timeout=30)

        assert not errors, errors
        assert all(v == i + 11 for i, v in out), \
            [x for x in out if x[1] != x[0] + 11][:5]
        assert out, "pump never ticked"
        # drain_complete beat the deadline: no DAG-pin wedge.
        assert drain_dt < 7.0, drain_dt
        # Re-home: everything co-located now -> every edge is a ring.
        assert not any(isinstance(ch, StoreChannel)
                       for ch in c._channels), \
            "store edges should have re-homed to shm rings"
        for i in range(1000, 1010):
            assert c.execute(i, timeout=30) == i + 11
    finally:
        c.teardown()
    assert all(c._dag_id not in r._dag_pins for r in ray_cluster.raylets)


@pytest.mark.slow
@pytest.mark.timeout(300)
def test_soak_under_dag_executor_killer(ray_cluster):
    """Slow soak: a 3-stage pipeline keeps ticking while
    chaos.DagExecutorKiller repeatedly SIGKILLs pinned workers. (Lives
    in this module, not test_dag.py: the killer needs the fake Cluster,
    which cannot coexist with that module's shared single-node init.)"""
    import ray_tpu
    from ray_tpu.dag.stage_pipeline import StagePipeline
    from ray_tpu.util.chaos import DagExecutorKiller, run_with_chaos

    ray_cluster.add_node(num_cpus=2)
    ray_cluster.connect()
    ray_cluster.wait_for_nodes()

    @ray_tpu.remote(max_restarts=-1)
    class Stage:
        def __init__(self, off):
            self.off = off

        def apply(self, x):
            return x + self.off

    stages = [Stage.remote(1), Stage.remote(10), Stage.remote(100)]
    with StagePipeline(stages, method="apply", channel_depth=4) as pipe:
        killer = DagExecutorKiller(ray_cluster, interval_s=2.0,
                                   max_kills=2, seed=7)
        outs, kills = run_with_chaos(
            lambda: pipe.run(list(range(400)), timeout=120), [killer])
        assert outs == [i + 111 for i in range(400)]
        assert kills, "killer never found a pinned worker"
        assert pipe.stats()["recoveries"] >= 1
