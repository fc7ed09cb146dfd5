"""Pipeline parallelism: GPipe schedule over the 'pipeline' mesh axis.

TPU-first design (the reference's closest substrate is compiled DAGs over
mutable-plasma channels, python/ray/dag/compiled_dag_node.py:141 +
python/ray/experimental/channel.py:49 — actor stages linked by channels;
here the whole pipeline is ONE XLA program): transformer layers are stacked
on a leading axis sharded over 'pipeline', and a `shard_map` runs the GPipe
microbatch schedule as a `lax.scan` over ticks with `lax.ppermute` moving
activations stage->stage over ICI. Gradients flow through the schedule
(ppermute transposes to the reverse permute), so pipeline-parallel training
is just `jax.grad` of this loss.

Composes with data parallel (batch sharded over 'data') and tensor parallel
(Megatron column/row sharding inside each stage with manual psum over
'tensor' — inside shard_map collectives are explicit).

Memory: stage activations are carried through the scan (GPipe-style full
activation footprint / num_microbatches granularity); per-layer remat
(cfg.remat) bounds the within-stage footprint.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.models.gpt import GPTConfig, _rmsnorm, _rope
from ray_tpu.ops.attention import flash_attention, mha_reference


def gpt_params_to_pp(params: Dict) -> Dict:
    """Convert the GPT param pytree (list of per-layer dicts) to the
    pipeline layout: identical leaves stacked on a leading layer axis."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                     *params["layers"])
    out = {k: v for k, v in params.items() if k != "layers"}
    out["stacked"] = stacked
    return out


def pp_params_to_gpt(pp_params: Dict, n_layers: int) -> Dict:
    """Inverse of gpt_params_to_pp (checkpoint interchange)."""
    out = {k: v for k, v in pp_params.items() if k != "stacked"}
    out["layers"] = [
        jax.tree_util.tree_map(lambda x, i=i: x[i], pp_params["stacked"])
        for i in range(n_layers)
    ]
    return out


def _pp_attention(layer, x, cfg: GPTConfig, positions, tp: int):
    """Attention with heads split over 'tensor' (column-parallel qkv,
    row-parallel out projection; psum completes the row-parallel matmul)."""
    b, s, d = x.shape
    dt = cfg.dtype
    h_local = cfg.n_heads // tp
    hd = cfg.head_dim

    def proj(w):  # w local: [d, d/tp]
        return jnp.einsum("bsd,de->bse", x, w.astype(dt))

    q = proj(layer["attn"]["wq"]).reshape(b, s, h_local, hd)
    k = proj(layer["attn"]["wk"]).reshape(b, s, h_local, hd)
    v = proj(layer["attn"]["wv"]).reshape(b, s, h_local, hd)
    q = _rope(q.transpose(0, 2, 1, 3), cfg.rope_theta, positions)
    k = _rope(k.transpose(0, 2, 1, 3), cfg.rope_theta, positions)
    v = v.transpose(0, 2, 1, 3)
    if cfg.attention == "reference":
        o = mha_reference(q, k, v, causal=True)
    else:
        o = flash_attention(q, k, v, causal=True)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, d // tp)
    y = jnp.einsum("bse,ed->bsd", o, layer["attn"]["wo"].astype(dt))
    if tp > 1:
        y = lax.psum(y, "tensor")
    return y


def _pp_mlp(layer, x, cfg: GPTConfig, tp: int):
    dt = cfg.dtype
    m = layer["mlp"]
    gate = jnp.einsum("bsd,df->bsf", x, m["w_gate"].astype(dt))
    up = jnp.einsum("bsd,df->bsf", x, m["w_up"].astype(dt))
    y = jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                   m["w_down"].astype(dt))
    if tp > 1:
        y = lax.psum(y, "tensor")
    return y


def make_gpt_pp_loss(cfg: GPTConfig, mesh: Mesh, num_microbatches: int):
    """Build loss_fn(pp_params, batch) running the GPipe schedule.

    batch: {"tokens": [B, S+1]}; B is the GLOBAL batch, sharded over 'data'.
    The per-data-shard batch must divide num_microbatches.
    """
    n_stages = mesh.shape["pipeline"]
    tp = mesh.shape.get("tensor", 1)
    if cfg.n_layers % n_stages != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pipeline={n_stages}")
    if cfg.n_experts > 0:
        raise ValueError("pipeline preset supports dense MLP layers (use "
                         "'ep' compositions for MoE)")
    if cfg.n_heads % tp != 0:
        raise ValueError(f"n_heads={cfg.n_heads} not divisible by tp={tp}")
    M = num_microbatches
    eps = cfg.rmsnorm_eps
    dt = cfg.dtype

    def body(stacked, embed_tbl, final_scale, lm_head, inputs, targets):
        # Per-device blocks: stacked [L/S, ...] (+tensor-sharded matrices),
        # inputs/targets [B/data, S].
        rank = lax.axis_index("pipeline")
        b, s = inputs.shape
        mb = b // M
        if b % M != 0:
            raise ValueError(f"per-shard batch {b} not divisible by "
                             f"microbatches {M}")
        inputs_mb = inputs.reshape(M, mb, s)
        targets_mb = targets.reshape(M, mb, s)
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (mb, s))

        def stage_fn(x):
            def layer_body(x, layer):
                h = x + _pp_attention(
                    layer, _rmsnorm(x, layer["ln1"]["scale"], eps), cfg,
                    positions, tp)
                normed = _rmsnorm(h, layer["ln2"]["scale"], eps)
                return h + _pp_mlp(layer, normed, cfg, tp), None

            if cfg.remat:
                layer_body = jax.checkpoint(layer_body)
            x, _ = lax.scan(layer_body, x, stacked)
            return x

        def head_loss(y, tgt):
            xf = _rmsnorm(y, final_scale, eps)
            if cfg.tie_embeddings:
                logits = jnp.einsum("bsd,vd->bsv", xf, embed_tbl.astype(dt))
            else:
                logits = jnp.einsum("bsd,dv->bsv", xf, lm_head.astype(dt))
            logits = logits.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, tgt[..., None], axis=-1)[..., 0]
            mask = (tgt >= 0).astype(jnp.float32)
            return jnp.sum(nll * mask), jnp.sum(mask)

        n_ticks = M + n_stages - 1

        def tick(carry, t):
            recv, loss_sum, loss_cnt = carry
            inject_idx = jnp.clip(t, 0, M - 1)
            # Only rank 0 pays for the embedding lookup (real branch on TPU).
            injected = lax.cond(
                rank == 0,
                lambda: embed_tbl.astype(dt)[
                    lax.dynamic_index_in_dim(inputs_mb, inject_idx, 0,
                                             keepdims=False)],
                lambda: jnp.zeros((mb, s, embed_tbl.shape[1]), dt))
            x_in = jnp.where(rank == 0, injected, recv)
            y = stage_fn(x_in)
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (rank == n_stages - 1)
            tgt = lax.dynamic_index_in_dim(
                targets_mb, jnp.clip(out_idx, 0, M - 1), 0, keepdims=False)
            ls, lc = lax.cond(
                valid,
                lambda: head_loss(y, tgt),
                lambda: (jnp.float32(0), jnp.float32(0)))
            send = lax.ppermute(
                y, "pipeline",
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (send, loss_sum + ls, loss_cnt + lc), None

        zeros = jnp.zeros((mb, s, embed_tbl.shape[1]), dt)
        (_, lsum, lcnt), _ = lax.scan(
            tick, (zeros, jnp.float32(0), jnp.float32(0)),
            jnp.arange(n_ticks))
        # Loss lives on the last pipeline rank of each data shard; reduce to
        # the global mean, replicated everywhere (out_spec P()).
        lsum = lax.psum(lsum, ("data", "pipeline"))
        lcnt = lax.psum(lcnt, ("data", "pipeline"))
        return lsum / jnp.maximum(lcnt, 1.0)

    # Specs for the pp param layout; tensor-parallel matrices carry their
    # Megatron axes (must match the 'pp'/'pp_tp' ShardingRules).
    def _stacked_spec(path_leaf):
        path, leaf = path_leaf
        if tp > 1:
            name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path)
            if "wo" in name or "w_down" in name:
                return P("pipeline", "tensor", None)
            if any(k in name for k in ("wq", "wk", "wv", "w_gate", "w_up")):
                return P("pipeline", None, "tensor")
        return P("pipeline", *([None] * (leaf.ndim - 1)))

    def loss_fn(pp_params, batch):
        stacked = pp_params["stacked"]
        stacked_specs = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(stacked),
            [_stacked_spec(pl) for pl in
             jax.tree_util.tree_flatten_with_path(stacked)[0]])
        lm_head = pp_params.get("lm_head", pp_params["embed"]["table"])
        tokens = batch["tokens"]
        # check_vma off: the body mixes collectives manually, with
        # per-rank lax.cond branches the replication check rejects.
        fn = shard_map(
            body, mesh=mesh,
            in_specs=(stacked_specs, P(), P(), P(), P("data"), P("data")),
            out_specs=P(), check_vma=False)
        return fn(stacked, pp_params["embed"]["table"],
                  pp_params["final_norm"]["scale"], lm_head,
                  tokens[:, :-1], tokens[:, 1:])

    return loss_fn


# ---------------------------------------------------------------------------
# MPMD stage pipelines over the compiled-DAG substrate.
#
# The GPipe loss above is SPMD: one XLA program, ppermute over ICI. The
# MPMD shape (PAPERS.md, arXiv:2412.14374) runs each stage as its OWN
# program on its own slice/process, with activations crossing stages
# through channels — which is exactly the compiled-DAG substrate: a
# stage tick costs one shm channel write, not a task RPC round trip.
# ---------------------------------------------------------------------------


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
        from ray_tpu.dag.compiled import CompiledDAG
        from ray_tpu.dag.dag_node import InputNode
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
        from collections import deque
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
