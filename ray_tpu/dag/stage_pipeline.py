"""MPMD stage pipelines over the compiled-DAG substrate.

parallel/pipeline.py's GPipe loss is SPMD: one XLA program, ppermute over
ICI. The MPMD shape (PAPERS.md, arXiv:2412.14374) runs each stage as its OWN
program on its own slice/process, with activations crossing stages through
channels — which is exactly the compiled-DAG substrate: a stage tick costs
one shm channel write, not a task RPC round trip.
"""

from __future__ import annotations

from collections import deque

from ray_tpu.dag.compiled import CompiledDAG
from ray_tpu.dag.dag_node import InputNode


class StagePipeline:
    """A linear chain of actor stages compiled onto reusable channels.

    ``stages`` are live actor handles; each tick flows the input through
    ``stage[0].method -> stage[1].method -> ...`` over pre-leased
    workers and shm ring channels (one channel write per hop).
    ``channel_depth`` microbatches can be in flight at once — the GPipe
    bubble shrinks to (n_stages - 1) ticks, and backpressure from the
    slowest stage bounds memory instead of an unbounded queue.

    Usage::

        pipe = StagePipeline([s0, s1, s2], method="apply", channel_depth=4)
        outs = pipe.run(microbatches)      # pipelined map, order-preserving
        pipe.teardown()                    # or `with StagePipeline(...)`
    """

    def __init__(self, stages, method: str = "__call__", *,
                 channel_depth: int = 4, max_message_size: int = 1 << 20,
                 tick_replay: bool = True):
        """tick_replay=True (default) arms the compiled DAG's in-place
        recovery: a stage actor dying mid-stream is restarted (give the
        stages `max_restarts`!), its lease re-pinned, channels re-homed
        and every unacknowledged microbatch replayed exactly once —
        run() simply keeps returning results. tick_replay=False keeps
        the typed fail-fast `DagExecutionError`."""
        if not stages:
            raise ValueError("StagePipeline needs at least one stage")
        with InputNode() as inp:
            node = inp
            for handle in stages:
                node = getattr(handle, method).bind(node)
        self.n_stages = len(stages)
        self.channel_depth = channel_depth
        self._dag = CompiledDAG.compile(
            node, channel_depth=channel_depth,
            max_message_size=max_message_size,
            tick_replay=tick_replay)

    def submit(self, value):
        """Inject one microbatch; returns a DagRef. The input write
        blocks once `channel_depth` ticks are in flight (backpressure) —
        a single-threaded caller must collect at least every
        `channel_depth` submissions or it deadlocks itself (run() does
        the windowing for you)."""
        return self._dag.execute_async(value)

    def run(self, inputs, timeout: float = None):
        """Pipelined map over `inputs`, outputs in input order.

        Windowed submit/collect: at most `channel_depth` ticks stay
        uncollected — that already keeps every stage busy (the rings
        hold `depth` messages per edge), and submitting further ahead
        from THIS thread would block the input write with nobody
        draining outputs."""
        pending = deque()
        out = []
        for x in inputs:
            if len(pending) >= self.channel_depth:
                out.append(pending.popleft().result(timeout))
            pending.append(self.submit(x))
        while pending:
            out.append(pending.popleft().result(timeout))
        return out

    def stats(self) -> dict:
        return self._dag.stats()

    def teardown(self):
        self._dag.teardown()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.teardown()
        return False
