"""Podracer RL substrate: topology planning, the act->learn compiled-DAG
data path, and the chaos proof — a gang drain mid-training costs zero
trajectory batches (exactly-once delivery, uncharged actor migration,
monotonic weight versions).

Reference: "Podracer architectures for scalable Reinforcement Learning"
(arXiv 2104.06272) — Anakin (co-located) and Sebulba (decoupled actor
gangs) on slice fault domains.
"""

import threading
import time

import numpy as np
import pytest

from ray_tpu.podracer import (PodracerConfig, PodracerRun, TopologyPlanner)


def _add_slice(cluster, slice_id: str, head_resource: str,
               num_hosts: int = 2, num_cpus: int = 1,
               tpus_per_host: float = 4.0):
    """Fake TPU slice (the test_gang_drain shape): num_hosts nodes in
    one fault domain, host 0 carrying the slice-head resource."""
    hosts = []
    for i in range(num_hosts):
        res = {"TPU": tpus_per_host}
        if i == 0:
            res[head_resource] = 1.0
        hosts.append(cluster.add_node(num_cpus=num_cpus, resources=res,
                                      slice_id=slice_id))
    return hosts


def _gcs_actor_info(handle):
    from ray_tpu._private import worker_api
    core = worker_api.get_core()
    return worker_api._call_on_core_loop(
        core, core.gcs.request("get_actor_info",
                               {"actor_id": handle._actor_id}), 10)


def _tiny_config(**over) -> PodracerConfig:
    base = dict(num_actor_gangs=2, actors_per_gang=1, num_envs=1,
                fragment_len=4, hidden=(8, 8), minibatch_size=8,
                num_epochs=1, channel_depth=2, seed=0)
    base.update(over)
    return PodracerConfig(**base)


def _assert_invariants(run, num_actors: int):
    """The substrate's standing guarantees over every collected output:
    contiguous ticks, learner applied each exactly once, every gang's
    batch present, aligned, and weight versions monotonic per actor.
    (`run.outputs` is a bounded deque — assert contiguity from its
    first retained tick.)"""
    outs = list(run.outputs)
    first = outs[0]["tick"] if outs else 0
    assert [o["tick"] for o in outs] == \
        list(range(first, first + len(outs)))
    bad = [(o["tick"], o["applied"]) for o in outs
           if o["applied"] != o["tick"] + 1]
    assert not bad, f"learn applied != exactly once: {bad[:5]}"
    assert all(o["tick_skew"] == 0 for o in outs)
    assert all(o["num_batches"] == num_actors for o in outs)
    for i in range(num_actors):
        seq = [o["versions"][i] for o in outs]
        assert all(b >= a for a, b in zip(seq, seq[1:])), \
            f"actor {i} observed a weight-version regression: {seq}"


# ---------------------------------------------------------------------------
# Topology planner
# ---------------------------------------------------------------------------

class TestTopologyPlanner:
    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError):
            TopologyPlanner(PodracerConfig(mode="vader"))

    def test_sebulba_separates_learner_from_actor_slices(self, ray_cluster):
        _add_slice(ray_cluster, "aaa-learn", "TPU-lrn-head")
        _add_slice(ray_cluster, "bbb-act", "TPU-act-head")
        ray_cluster.connect()
        ray_cluster.wait_for_nodes()
        cfg = _tiny_config(mode="sebulba")
        plan = TopologyPlanner(cfg).plan()
        try:
            assert plan.mode == "sebulba"
            assert plan.learner.slice_id == "aaa-learn"
            assert all(g.slice_id == "bbb-act" for g in plan.actor_gangs)
            # Fault isolation: the learner never shares a domain with an
            # actor gang.
            assert plan.learner.slice_id not in {
                g.slice_id for g in plan.actor_gangs}
            # Slice reservations: one PG per DISTINCT slice (second gang
            # on the same slice must not double-reserve).
            assert plan.learner.placement_group is not None
            assert plan.actor_gangs[0].placement_group is not None
            assert plan.actor_gangs[1].placement_group is None
            # Member options carry soft affinity onto the gang's hosts.
            opts = plan.actor_gangs[0].member_options[0]
            assert opts["scheduling_strategy"].soft is True
        finally:
            plan.teardown()
        assert plan.learner.placement_group is None

    def test_anakin_colocates_everything_on_one_domain(self, ray_cluster):
        _add_slice(ray_cluster, "mesh-a", "TPU-a-head", num_hosts=1)
        _add_slice(ray_cluster, "mesh-b", "TPU-b-head", num_hosts=2)
        ray_cluster.connect()
        ray_cluster.wait_for_nodes()
        plan = TopologyPlanner(_tiny_config(mode="anakin")).plan()
        try:
            # Largest slice wins; learner AND every actor gang share it.
            assert plan.learner.slice_id == "mesh-b"
            assert all(g.slice_id == "mesh-b" for g in plan.actor_gangs)
            # Act/learn co-location on one mesh: the learner's placement
            # is a sharding strategy, and the shared domain is reserved
            # exactly once (by the learner).
            assert plan.sharding is not None and plan.sharding.name == "dp"
            assert plan.learner.placement_group is not None
            assert all(g.placement_group is None for g in plan.actor_gangs)
        finally:
            plan.teardown()

    def test_sliceless_cluster_degrades_to_node_spread(self, ray_start):
        plan = TopologyPlanner(_tiny_config()).plan()
        assert plan.learner.slice_id == ""
        assert all(g.slice_id == "" for g in plan.actor_gangs)
        assert plan.learner.placement_group is None
        assert all(g.placement_group is None for g in plan.actor_gangs)
        assert plan.learner.node_ids  # still anchored somewhere real


# ---------------------------------------------------------------------------
# Runtime: the act->learn compiled-DAG data path
# ---------------------------------------------------------------------------

class TestPodracerRuntime:
    @pytest.mark.timeout(240)
    def test_ticks_exactly_once_with_monotonic_versions(self, ray_start):
        run = PodracerRun(_tiny_config())
        try:
            run.run(12, window=2, timeout=120)
            _assert_invariants(run, num_actors=2)
            st = run.stats()
            assert st["ticks"] == 12
            assert st["steps"] == 12 * run.config.steps_per_tick()
            # Pipelined up to the channel depth.
            assert st["max_inflight"] == 2
            assert st["recoveries"] == 0
        finally:
            run.teardown()

    @pytest.mark.timeout(240)
    def test_broadcast_cadence_and_staleness(self, ray_start):
        """broadcast_interval=3: the object-plane put happens every 3rd
        update; actors observe versions on that cadence and staleness
        stays bounded by the pipeline depth."""
        run = PodracerRun(_tiny_config(broadcast_interval=3,
                                       num_actor_gangs=1))
        try:
            outs = run.run(9, window=1, timeout=120)
            # Constructor broadcast = v1; updates 3/6/9 bump it.
            assert outs[-1]["version"] == 1 + 3
            # Sequential ticking: an actor is at most one broadcast
            # behind (it samples before the learner's update lands).
            assert all(o["staleness"] <= 3 for o in outs)
        finally:
            run.teardown()

    @pytest.mark.timeout(240)
    def test_ticks_pay_no_per_tick_task_rpc(self, ray_start):
        """Act -> learn ticks ride the compiled DAG's rings, the weights
        in the control tuple of the input ring: the transport's frame
        counter does not grow with the ticks (a `.remote()` fan-out would
        pay three task round trips a tick)."""
        from helpers.transport_frames import \
            assert_frames_do_not_grow_with_ticks
        run = PodracerRun(_tiny_config(channel_depth=4))
        try:
            run.run(5, window=1, timeout=120)   # warm every hop + jits
            assert_frames_do_not_grow_with_ticks(
                lambda: run.run(40, window=4, timeout=120), 40)
            _assert_invariants(run, num_actors=2)
        finally:
            run.teardown()


# ---------------------------------------------------------------------------
# Chaos proof: slice preemption mid-rollout
# ---------------------------------------------------------------------------

class TestPodracerGangDrain:
    @pytest.mark.timeout(300)
    def test_gang_drain_mid_training_zero_lost_batches(self, ray_cluster):
        """THE acceptance test: drain one host of the actor slice
        mid-training — the GCS escalates to an atomic gang drain, the
        compiled DAG migrates the gang proactively, and the run shows
        zero lost trajectory batches (exactly-once per tick via the
        learner's applied counter + per-batch tick seq), uncharged
        actor restarts (`preempted_restarts`), and weight versions
        monotonic at every actor across the migration."""
        act_hosts = _add_slice(ray_cluster, "act-slice", "TPU-act-head",
                               num_hosts=2, num_cpus=1)
        for _ in range(2):   # migration headroom off-slice
            ray_cluster.add_node(num_cpus=1)
        ray_cluster.connect()
        ray_cluster.wait_for_nodes()
        # Single slice in sebulba mode: actors take the slice, the
        # learner runs off-slice (the drain must never touch it).
        # reserve_slices=False keeps the test on the actor-migration
        # path (PG handoff needs a free replacement domain and is
        # covered by test_gang_drain.py).
        cfg = _tiny_config(mode="sebulba", reserve_slices=False)
        plan = TopologyPlanner(cfg).plan()
        assert all(g.slice_id == "act-slice" for g in plan.actor_gangs)
        assert plan.learner.slice_id == ""
        run = PodracerRun(cfg, plan)
        errors = []
        stop = threading.Event()
        try:
            run.run(5, window=1, timeout=120)  # warm every hop

            def pump():
                while not stop.is_set() and run.ticks < 400:
                    try:
                        run.step(timeout=120)
                    except Exception as e:  # noqa: BLE001
                        errors.append(e)
                        return

            t = threading.Thread(target=pump, daemon=True)
            t.start()
            # Mid-rollout means ticks actually in flight — wait for the
            # pump to tick rather than assuming a fixed nap suffices on
            # a loaded box.
            deadline = time.monotonic() + 30
            while run.ticks < 2 and not errors \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
            ticks_at_drain = run.ticks
            # Drain ONE member: the GCS escalates to the whole gang.
            ray_cluster.drain_node(act_hosts[0], deadline_s=8.0,
                                   grace_s=0.3, wait=True)
            # Post-drain progress is the condition under test; poll for
            # it instead of napping a wall-clock guess.
            deadline = time.monotonic() + 60
            while run.ticks <= ticks_at_drain and not errors \
                    and time.monotonic() < deadline:
                time.sleep(0.1)
            stop.set()
            t.join(timeout=60)
            assert not errors, errors
            assert run.ticks > ticks_at_drain, \
                "no progress after the drain"

            # Zero lost batches, exactly-once, monotonic versions.
            _assert_invariants(run, num_actors=2)

            # The drain escalated to the gang and the DAG migrated.
            assert ray_cluster.gcs.gang_drains_total >= 1
            assert run.stats()["recoveries"] >= 1

            # Uncharged migration: at least one actor restarted via the
            # preemption path, and NOBODY burned restart budget.
            infos = [_gcs_actor_info(a) for a in run.actors]
            assert any(i.preempted_restarts >= 1 for i in infos), \
                [(i.num_restarts, i.preempted_restarts) for i in infos]
            for i in infos:
                assert i.num_restarts - i.preempted_restarts == 0, \
                    (i.num_restarts, i.preempted_restarts)

            # Post-migration steady state: more ticks, same invariants.
            run.run(5, window=1, timeout=120)
            _assert_invariants(run, num_actors=2)
        finally:
            stop.set()
            run.teardown()

    @pytest.mark.slow
    @pytest.mark.timeout(600)
    def test_chaos_slice_preemption_soak(self, ray_cluster):
        """Soak: SlicePreemptionKiller reclaims the actor slice (notice
        -> jittered host kills -> respawn) mid-rollout while warm pools
        refill; the run keeps every exactly-once/monotonicity invariant
        and keeps making progress."""
        from ray_tpu.util.chaos import SlicePreemptionKiller

        _add_slice(ray_cluster, "act-slice", "TPU-act-head",
                   num_hosts=2, num_cpus=1)
        for _ in range(2):
            ray_cluster.add_node(num_cpus=1)
        ray_cluster.connect()
        ray_cluster.wait_for_nodes()
        cfg = _tiny_config(mode="sebulba", reserve_slices=False)
        run = PodracerRun(cfg)
        errors = []
        stop = threading.Event()
        killer = SlicePreemptionKiller(ray_cluster, interval_s=4.0,
                                       max_kills=2, seed=7,
                                       deadline_s=2.0, window_s=0.5,
                                       notice=True, respawn=True)
        try:
            run.run(5, window=1, timeout=120)

            def pump():
                while not stop.is_set() and run.ticks < 2000:
                    try:
                        run.step(timeout=120)
                    except Exception as e:  # noqa: BLE001
                        errors.append(e)
                        return

            t = threading.Thread(target=pump, daemon=True)
            t.start()
            killer.start()
            time.sleep(14.0)
            kills = killer.stop()
            time.sleep(2.0)
            stop.set()
            t.join(timeout=120)
            assert kills, "killer never fired"
            assert not errors, errors
            _assert_invariants(run, num_actors=2)
            assert run.ticks > 10
        finally:
            stop.set()
            killer.stop()
            run.teardown()
