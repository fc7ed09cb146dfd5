"""Host-plane collective tests (reference: python/ray/util/collective tests).

The device plane (psum/all_gather inside jit) is covered by test_parallel.py;
here we exercise the named-rendezvous host collectives between actors.
"""

import numpy as np
import pytest


def test_collective_ops(ray_shared):
    import ray_tpu
    from ray_tpu.util import collective

    @ray_tpu.remote
    class Member(collective.CollectiveGroupMixin):
        def __init__(self, rank, world):
            self.rank = rank
            self.world = world

        def run(self):
            from ray_tpu.util import collective as col
            out = {}
            x = np.full((4,), float(self.rank + 1))
            out["allreduce"] = col.allreduce(x, group_name="g1")
            out["bcast"] = col.broadcast(
                np.arange(3.0) if self.rank == 1 else None,
                src_rank=1, group_name="g1")
            out["allgather"] = col.allgather(
                np.array([self.rank]), group_name="g1")
            out["rs"] = col.reducescatter(
                np.arange(4, dtype=np.float64), group_name="g1")
            col.barrier(group_name="g1")
            if self.rank == 0:
                col.send(np.array([42.0]), dst_rank=1, group_name="g1")
            elif self.rank == 1:
                out["recv"] = col.recv(src_rank=0, group_name="g1")
            return out

    world = 2
    members = [Member.remote(r, world) for r in range(world)]
    collective.create_collective_group(
        members, world, list(range(world)), group_name="g1")
    # create_collective_group came back: both members have joined. The
    # results are waited for under the test's own time limit (conftest's,
    # 180 s), not a shorter one of its own: the two actors' workers start
    # while xdist's other workers compile, and 60 s has passed before.
    res = ray_tpu.get([m.run.remote() for m in members])

    # allreduce: sum of (1,1,1,1) and (2,2,2,2)
    for r in range(world):
        np.testing.assert_allclose(res[r]["allreduce"], np.full((4,), 3.0))
        np.testing.assert_allclose(res[r]["bcast"], np.arange(3.0))
        got = np.concatenate([np.atleast_1d(a) for a in res[r]["allgather"]])
        np.testing.assert_array_equal(np.sort(got), np.array([0, 1]))
    # reducescatter of sum [0,2,4,6] split across 2 ranks
    np.testing.assert_allclose(res[0]["rs"], np.array([0.0, 2.0]))
    np.testing.assert_allclose(res[1]["rs"], np.array([4.0, 6.0]))
    np.testing.assert_allclose(res[1]["recv"], np.array([42.0]))


def test_symmetric_send_recv(ray_shared):
    """Every rank sends to its partner then recvs — must not deadlock
    (send/recv tag counters are direction-separated)."""
    import ray_tpu
    from ray_tpu.util import collective

    @ray_tpu.remote
    class Member(collective.CollectiveGroupMixin):
        def run(self, rank):
            from ray_tpu.util import collective as col
            peer = 1 - rank
            col.send(np.array([float(rank)]), dst_rank=peer,
                     group_name="gsym")
            got = col.recv(src_rank=peer, group_name="gsym")
            return float(got[0])

    members = [Member.remote() for _ in range(2)]
    collective.create_collective_group(members, 2, [0, 1],
                                       group_name="gsym")
    res = ray_tpu.get([m.run.remote(i) for i, m in enumerate(members)],
                      timeout=30)
    assert res == [1.0, 0.0]


def test_allreduce_pytree(ray_shared):
    import ray_tpu
    from ray_tpu.util import collective

    @ray_tpu.remote
    class Member(collective.CollectiveGroupMixin):
        def run(self, rank):
            from ray_tpu.util import collective as col
            tree = {"w": np.ones((2, 2)) * (rank + 1),
                    "b": np.ones((2,)) * (rank + 1)}
            return col.allreduce(tree, group_name="g2")

    members = [Member.remote() for _ in range(2)]
    collective.create_collective_group(members, 2, [0, 1], group_name="g2")
    res = ray_tpu.get([m.run.remote(i) for i, m in enumerate(members)],
                      timeout=60)
    np.testing.assert_allclose(res[0]["w"], np.full((2, 2), 3.0))
    np.testing.assert_allclose(res[0]["b"], np.full((2,), 3.0))
