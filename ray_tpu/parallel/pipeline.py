"""Pipeline parallelism: GPipe schedule over the 'pipeline' mesh axis.

TPU-first design (the reference's closest substrate is compiled DAGs over
mutable-plasma channels, python/ray/dag/compiled_dag_node.py:141 +
python/ray/experimental/channel.py:49 — actor stages linked by channels,
here dag/stage_pipeline.py; this file makes the whole pipeline ONE XLA
program): transformer layers are stacked on a leading axis sharded over
'pipeline', and a `shard_map` runs the GPipe microbatch schedule as a
`lax.scan` over ticks with `lax.ppermute` moving activations stage->stage
over ICI. Gradients flow through the schedule (ppermute transposes to the
reverse permute), so pipeline-parallel training is just `jax.grad` of this
loss.

The schedule only: what a stage computes is models/gpt.py's layer body,
scanned over the stage's layers, and the last rank's loss is its
head_xent_recompute (the formulation that saves nothing a tick: the
schedule differentiates a scan over ticks). Composes with data parallel (batch sharded over 'data') and
tensor parallel (Megatron column/row sharding inside each stage: the
parameters arrive as ShardingStrategy.pp_tp()'s rules cut them, and the
body is told to psum over 'tensor').

Memory: stage activations are carried through the scan (GPipe-style full
activation footprint / num_microbatches granularity); per-layer remat
(cfg.remat_policy) bounds the within-stage footprint, and where the devices
are reckoned to hold them (models/gpt.py:products_kept) every layer
also keeps the first rungs of its products through it (gpt.LADDER, five
choices: nothing more, its MLP's up x, gate x too, what a delta-rule or
state-space mixer's filters read, what they write too).
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.models.gpt import (GPTConfig, Setting, final_norm, gpt_init,
                                head_xent_recompute, layer_fn,
                                products_kept)
from ray_tpu.parallel.sharding import (MESH_AXES, ShardingStrategy,
                                       _path_str)


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


def _refuse_what_a_stage_cannot_run(cfg: GPTConfig, mesh: Mesh,
                                    strategy: ShardingStrategy):
    """The schedule scans one stack of identical layers, carries the
    residual stream alone from stage to stage, and cuts over 'tensor' what
    `strategy` has a rule for. Whether the model fits is read off what
    gpt_init and the block make of cfg (shapes only, nothing computed), so
    a mechanism the schedule has never heard of is refused by what it
    does."""
    def leaves(tree):
        return {_path_str(path): (leaf.shape, leaf.dtype) for path, leaf
                in jax.tree_util.tree_flatten_with_path(tree)[0]}
    params = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))
    if "exit_gate" in params:
        raise ValueError(
            f"the parameters hold an exit gate (GPTConfig.loop={cfg.loop!r}):"
            " a looped stack sends the stream through its layers "
            f"{cfg.loop.passes} times a step and weights every pass's "
            "cross-entropy; the schedule sends a microbatch down the stages "
            "once (a circular schedule is not built)")
    layers = params["layers"]
    first = leaves(layers[0])
    for i, layer in enumerate(layers[1:], 1):
        other = leaves(layer)
        differ = sorted(path for path in first.keys() | other.keys()
                        if first.get(path) != other.get(path))
        if differ:
            raise ValueError(
                f"layer {i}'s parameters are not layer 0's ({', '.join(differ)}"
                " differ): the pipeline preset scans one stack of identical "
                "layers")
    x = jax.ShapeDtypeStruct((1, cfg.max_seq, cfg.d_model), cfg.dtype)
    _, stats = jax.eval_shape(layer_fn(cfg, cfg.max_seq, Setting()),
                              x, layers[0])
    if stats:
        raise ValueError(
            f"the block hands back statistics ({', '.join(sorted(stats))}): "
            "the pipeline's scan carries no loss of a layer's own, and a "
            "stage's statistics do not reach the last rank's loss")
    tensor_axis = MESH_AXES["heads"]
    if mesh.shape.get(tensor_axis, 1) > 1:
        stacked = {"stacked": jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(
                (cfg.n_layers,) + leaf.shape, leaf.dtype), layers[0])}
        # (a spec has its leaf's rank: behind the layer dimension a matrix
        # has three entries or more, a scale two)
        whole = [_path_str(path)[len("stacked/"):] for path, sharding
                 in jax.tree_util.tree_flatten_with_path(
                     strategy.param_shardings(mesh, stacked))[0]
                 if len(sharding.spec) > 2 and tensor_axis not in sharding.spec]
        if whole:
            raise ValueError(
                f"ShardingStrategy.{strategy.name}() has no rule for "
                f"{', '.join(whole)}: every shard of '{tensor_axis}' would "
                "hold them whole and none its heads' part")


def make_gpt_pp_loss(cfg: GPTConfig, mesh: Mesh, num_microbatches: int):
    """Build loss_fn(pp_params, batch) running the GPipe schedule.

    batch: {"tokens": [B, S+1]}; B is the GLOBAL batch, sharded over 'data'.
    The per-data-shard batch must divide num_microbatches.
    """
    n_stages = mesh.shape["pipeline"]
    tensor_axis = MESH_AXES["heads"]
    tp = mesh.shape.get(tensor_axis, 1)
    if cfg.n_layers % n_stages != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pipeline={n_stages}")
    if cfg.n_heads % tp != 0:
        raise ValueError(f"n_heads={cfg.n_heads} not divisible by tp={tp}")
    if cfg.kv_heads % tp != 0:
        raise ValueError(f"n_kv_heads={cfg.kv_heads} is not whole key/value "
                         f"heads over tp={tp}")
    M = num_microbatches
    dt = cfg.dtype
    if tp > 1:
        strategy = ShardingStrategy.pp_tp()
        where = Setting(psum=lambda y: lax.psum(y, tensor_axis))
    else:
        strategy = ShardingStrategy.pp()
        where = Setting()
    if cfg.ssm is not None or cfg.mtp is not None:
        raise ValueError(
            "the pipeline preset carries the residual stream alone from "
            "stage to stage: no state-space layer's state along a sequence "
            "cut into microbatches has been shown right, and no prediction "
            "module's second stream and loss reach the last rank")
    if cfg.scales.embedding != 1.0:
        raise ValueError(
            f"multipliers.embedding={cfg.scales.embedding}: rank 0's "
            "lookup is written out in the tick and scales nothing (the "
            "residual's, the scores' and the logits' multipliers ride in "
            "layer_fn's block and head_xent_recompute)")
    _refuse_what_a_stage_cannot_run(cfg, mesh, strategy)
    # (what those observations let through and nothing has shown right: a
    # stack of window layers alone, a gate a head on one shard of 'tensor')
    if "window" in (cfg.layer_kinds or ()) or cfg.attention_gate:
        raise ValueError(
            "the pipeline preset has no sliding-window layers (its scan "
            "carries no period of kinds, head counts or rope tables) and "
            "no rule for a gate a head (attn/wg)")

    def body(kept, params, inputs, targets):
        # Per-device blocks: params["stacked"] [L/S, ...] (+tensor-sharded
        # matrices), inputs/targets [B/data, S].
        embed_tbl = params["embed"]["table"]
        rank = lax.axis_index("pipeline")
        b, s = inputs.shape
        mb = b // M
        if b % M != 0:
            raise ValueError(f"per-shard batch {b} not divisible by "
                             f"microbatches {M}")
        inputs_mb = inputs.reshape(M, mb, s)
        targets_mb = targets.reshape(M, mb, s)
        layer = layer_fn(cfg, s, where).keeping(kept)

        n_ticks = M + n_stages - 1

        def tick(carry, t):
            recv, loss_sum, loss_cnt = carry
            inject_idx = jnp.clip(t, 0, M - 1)
            # Only rank 0 pays for the embedding lookup (real branch on TPU).
            # Written out, not ops/embedding.py's embed_lookup: a branch a
            # tick inside the stages' shard_map, where XLA's gather and its
            # transpose stay (no cell runs a pipeline).
            injected = lax.cond(
                rank == 0,
                lambda: embed_tbl.astype(dt)[
                    lax.dynamic_index_in_dim(inputs_mb, inject_idx, 0,
                                             keepdims=False)],
                lambda: jnp.zeros((mb, s, embed_tbl.shape[1]), dt))
            x_in = jnp.where(rank == 0, injected, recv)
            y, _ = lax.scan(layer, x_in, params["stacked"])
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (rank == n_stages - 1)
            tgt = lax.dynamic_index_in_dim(
                targets_mb, jnp.clip(out_idx, 0, M - 1), 0, keepdims=False)
            ls, lc = lax.cond(
                valid,
                lambda: head_xent_recompute(
                    params, final_norm(params, y, cfg), tgt, cfg),
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

    def loss_fn(pp_params, batch):
        param_specs = jax.tree_util.tree_map(
            lambda sharding: sharding.spec,
            strategy.param_shardings(mesh, pp_params))
        tokens = batch["tokens"]
        # What a device keeps through the remat it keeps once a layer of
        # its stage AND tick (the schedule differentiates a scan over
        # ticks): reckoned as that many layers at a microbatch's tokens.
        one = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
            pp_params["stacked"])
        like = {**{k: v for k, v in pp_params.items() if k != "stacked"},
                "layers": [one] * (cfg.n_layers // n_stages
                                   * (M + n_stages - 1))}
        kept = products_kept(
            like, tokens.shape[0] // (mesh.shape["data"] * M),
            tokens.shape[1] - 1, cfg, Setting())
        # check_vma off: the body mixes collectives manually, with
        # per-rank lax.cond branches the replication check rejects.
        fn = shard_map(
            partial(body, kept), mesh=mesh,
            in_specs=(param_specs, P("data"), P("data")),
            out_specs=P(), check_vma=False)
        return fn(pp_params, tokens[:, :-1], tokens[:, 1:])

    return loss_fn
