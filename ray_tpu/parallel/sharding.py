"""Sharding strategies: DP / FSDP(ZeRO) / TP / PP / SP / EP as pjit specs.

The TPU-native replacement for the reference's wrapped-framework parallelism
(python/ray/train/torch/train_loop_utils.py:158 prepare_model DDP/FSDP wrap,
SURVEY.md §2.5): every strategy is a set of PartitionSpec rules applied to the
parameter pytree + a batch sharding, compiled by XLA/GSPMD — no runtime
process-group object.

Rules match on the parameter's path (joined with '/'); first match wins.
The transformer's (ray_tpu.models.gpt) are written once, as one table of
paths and the role of each dimension (_TRANSFORMER); `tp`, `tp_fsdp` and
`pp_tp` say which mesh axes the roles mean.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


@dataclass
class ShardingRules:
    """Ordered (regex, PartitionSpec) rules + a default."""

    rules: List[Tuple[str, P]] = field(default_factory=list)
    default: P = P()

    def spec_for(self, path: str, shape: Tuple[int, ...]):
        for pattern, spec in self.rules:
            if re.search(pattern, path):
                if spec is FSDP_LARGEST:
                    return spec
                if spec is PP_STACKED:
                    return P("pipeline", *([None] * (max(len(shape), 1) - 1)))
                return _truncate_spec(spec, shape)
        if self.default is FSDP_LARGEST:
            return self.default
        return _truncate_spec(self.default, shape)


def _truncate_spec(spec: P, shape: Tuple[int, ...]) -> P:
    """Trim/pad a spec to the array rank so one rule covers kernel+bias."""
    parts = tuple(spec)
    if len(parts) > len(shape):
        parts = parts[-len(shape):] if len(shape) > 0 else ()
    elif len(parts) < len(shape):
        parts = (None,) * (len(shape) - len(parts)) + parts
    return P(*parts)


# What a dimension's role means on the mesh, for whoever cuts by role: the
# presets below, a Mosaic kernel entered per shard (models/gpt.py:
# _per_shard) and a pipeline stage's psum (parallel/pipeline.py).
MESH_AXES = {"batch": ("data", "fsdp"), "heads": "tensor",
             "expert": "expert", None: None}

# How a transformer's parameters (ray_tpu.models.gpt) are divided: path ->
# the role of each dimension, first match wins (a window layer's
# `window_attn/..` finds the `attn/..` rows). A preset says which mesh axes
# the roles mean (MESH_AXES and, of its own, "model" and "vocab"); a row
# with a role the preset does not know is not the preset's. Roles:
#   "model"   the d_model side of a matrix (for w_kvb the latent's): what
#             'fsdp' takes under tp_fsdp, whole under tp
#   "heads"   whole heads, an MLP's columns, a convolution's channels
#   "expert"  the experts of a stack
#   "vocab"   embed/table's rows
#   None      not divided; a row of no roles leaves every dimension whole
# A new matrix takes one row here. What has no row falls to the preset's
# default (P() under tp, FSDP_LARGEST under tp_fsdp), which is meant for
# scales of d_model: tests/test_param_specs.py lists what may land there.
_COLUMN, _ROW = ("model", "heads"), ("heads", "model")
# The rows a stage of parallel/pipeline.py cuts too: the block finishes
# their sums over 'tensor' itself where it is told how (models/gpt.py:
# Setting.psum). `columns_scale` is a role of pp_tp alone: under GSPMD the
# q/k norm's scale falls to the default like any other scale.
_STAGE_ROWS = (
    (r"attn/(wq|wk|wv)", _COLUMN),
    (r"attn/(q|k)_norm", ("columns_scale",)),
    (r"attn/wo", _ROW),
    (r"mlp/(w_up|w_gate)", _COLUMN),
    (r"mlp/w_down", _ROW),
)
_TRANSFORMER = _STAGE_ROWS + (
    # the gate a head: its columns are heads
    (r"attn/wg", _COLUMN),
    # an indexer: whole index heads of wq's columns; its one key head, that
    # head's norm and the heads' weights are not cut over 'tensor' (the
    # scores' sum over the heads would then be a psum, which models/gpt.py
    # does not build: it refuses 'tensor' > 1)
    (r"attn/index/wq", _COLUMN),
    (r"attn/index/(wk|ww)", ("model", None)),
    (r"attn/index/k_norm", ()),
    # latent attention: whole heads of the up-projection's columns; the
    # down-projection, its norm and the shared rotated key part are not
    # divided
    (r"attn/w_kvb", _COLUMN),
    (r"attn/(w_kva|kv_norm)", ()),
    # one scale of head_dim for all the heads
    (r"attn/(q|k)_head_norm", ()),
    # the short convolution: w_in is its three chunks [3, d, d], each
    # column-parallel, so a shard holds the same channels of B, C and X,
    # its channels' filters and w_out's rows
    (r"conv/w_in", (None, "model", "heads")),
    (r"conv/filter", ("heads", None)),
    (r"conv/w_out", _ROW),
    # a delta-rule layer: whole heads of the three projections' columns, of
    # beta's, and of the up-halves of the decay's and the gate's low-rank
    # pairs (their down-halves are of rank head_dim: not cut over 'tensor');
    # a shard holds its heads' filters, decay rates and step biases and
    # wo's rows; the norm's one scale of head_dim is every head's
    (r"kda/(wq|wk|wv|w_qkv|w_beta)", _COLUMN),
    (r"kda/(wf|wg)_up", (None, "heads")),
    (r"kda/(wf|wg)_down", ("model", None)),
    # (a decay a head and a gate from a full matrix: columns of whole heads,
    # as are those of the three projections where they are one, `w_qkv`,
    # a head's q, k and v side by side, under one filter)
    (r"kda/(w_decay|wg)$", _COLUMN),
    (r"kda/(q|k|v|qkv)_conv", ("heads", None)),
    (r"kda/(a_log|dt_bias)", ("heads",)),
    (r"kda/o_norm", ()),
    (r"kda/wo", _ROW),
    # a state-space layer: whole heads of the gate's and the step's columns
    # and of w_out's rows, with their rates, step biases, skips and columns
    # of the gated norm's scale; what the filter reads holds heads' channels
    # and then groups' directions side by side, so it, its taps and their
    # bias are not cut over 'tensor'
    (r"ssm/(w_z|w_dt)", _COLUMN),
    (r"ssm/w_xbc", ("model", None)),
    (r"ssm/(conv|conv_bias)", ()),
    (r"ssm/(a_log|dt_bias|d)$", ("heads",)),
    (r"ssm/norm", ("heads",)),
    (r"ssm/w_out", _ROW),
    # a prediction module's projection [2 d, d]: rows like any d_model side
    (r"mtp/proj", ("model", None)),
    # a looped stack's exit gate, one column [d, 1] and its bias, and the
    # second scale of a half under a norm either side of it: not divided
    # (every shard norms and scores a whole row)
    (r"exit_gate/(w|b)", ()),
    (r"ln(1|2)_after", ()),
    # Vocab over both axes under tp_fsdp, d_model replicated: a d-sharded
    # gather output cannot transition to batch-sharded activations without
    # an involuntary full rematerialization (permuted tile order), while a
    # vocab-sharded gather resolves via masked lookup + all-reduce and
    # reshards to the batch spec cheaply.
    (r"embed/table", ("vocab", None)),
    # (its vocabulary is columns like any other: over 'tensor' alone)
    (r"lm_head", _COLUMN),
    # the shared expert is a dense MLP (two dimensions), not a stack of
    # experts
    (r"moe/shared/(w_up|w_gate)", _COLUMN),
    (r"moe/shared/w_down", _ROW),
    (r"moe/router_bias", ()),
    # the latent projections either side of the routed experts: dense
    (r"moe/w_latent_in", ("model", None)),
    (r"moe/w_latent_out", (None, "model")),
    (r"moe/.*w_(gate|up)", ("expert",) + _COLUMN),
    (r"moe/.*w_down", ("expert",) + _ROW),
    (r"moe/router", ()),
)


def _transformer_rules(axes: Dict, rows=_TRANSFORMER,
                       stacked: Optional[str] = None) -> List[Tuple[str, P]]:
    """The table x a preset's map of roles to mesh axes; with `stacked`,
    behind a leading layer dimension cut over that axis."""
    rules = []
    for pattern, roles in rows:
        if all(role in axes for role in roles):
            spec = tuple(axes[role] for role in roles)
            if stacked:
                pattern, spec = "stacked/" + pattern, (stacked,) + spec
            rules.append((pattern, P(*spec)))
    return rules


class ShardingStrategy:
    """A named parallelism strategy = param rules + batch spec + remat policy.

    TPU-first equivalents of the reference inventory (SURVEY.md §2.5):
      dp    -> pure data parallel (params replicated)
      fsdp  -> ZeRO-3: params/opt-state sharded over ('fsdp',) largest dim
      tp    -> Megatron-style tensor parallel over 'tensor'
      tp_fsdp / dp_tp / 3d -> compositions
      sp    -> sequence parallel: batch sharded over tokens ('sequence')
      ep    -> expert parallel (MoE layers over 'expert')
    """

    def __init__(self, name: str, param_rules: ShardingRules,
                 batch_spec: P, data_axes: Sequence[str] = ("data",)):
        self.name = name
        self.param_rules = param_rules
        self.batch_spec = batch_spec
        self.data_axes = tuple(data_axes)

    # ---- presets ----

    @staticmethod
    def dp() -> "ShardingStrategy":
        return ShardingStrategy("dp", ShardingRules(), P("data"))

    @staticmethod
    def fsdp() -> "ShardingStrategy":
        """ZeRO-3: every weight matrix sharded on its largest dim over
        ('fsdp',); XLA all-gathers params per layer and reduce-scatters
        grads (what DeepSpeed/FSDP do imperatively, done by GSPMD)."""
        rules = ShardingRules(rules=[(r".*", FSDP_LARGEST)], default=P())
        return ShardingStrategy("fsdp", rules, P(("data", "fsdp")))

    @staticmethod
    def tp_transformer() -> "ShardingStrategy":
        """Megatron TP for the transformer layout in ray_tpu.models.gpt:
        column-parallel qkv/up projections, row-parallel out/down."""
        axes = {**MESH_AXES, "model": None, "vocab": MESH_AXES["heads"]}
        return ShardingStrategy(
            "tp", ShardingRules(_transformer_rules(axes), default=P()),
            P("data"))

    @staticmethod
    def tp_fsdp() -> "ShardingStrategy":
        """2D: TP inner + FSDP outer on the complementary dim."""
        axes = {**MESH_AXES, "model": "fsdp",
                "vocab": (MESH_AXES["heads"], "fsdp")}
        return ShardingStrategy(
            "tp_fsdp",
            ShardingRules(_transformer_rules(axes), default=FSDP_LARGEST),
            P(MESH_AXES["batch"]))

    @staticmethod
    def pp() -> "ShardingStrategy":
        """Pipeline parallel: stacked layer params sharded on the leading
        (layer) axis over 'pipeline' (see ray_tpu.parallel.pipeline for the
        GPipe schedule those shardings feed)."""
        rules = ShardingRules(rules=[(r"stacked/", PP_STACKED)], default=P())
        return ShardingStrategy("pp", rules, P("data"))

    @staticmethod
    def pp_tp() -> "ShardingStrategy":
        """Pipeline outer + Megatron tensor parallel inside each stage: the
        rows of the table a stage cuts, behind the stacked layer dimension.
        A stage holds its shard and no partitioner, so the scale of a norm
        over a cut projection's columns is cut as they are."""
        axes = {**MESH_AXES, "model": None,
                "columns_scale": MESH_AXES["heads"]}
        rules = _transformer_rules(axes, _STAGE_ROWS, stacked="pipeline")
        return ShardingStrategy(
            "pp_tp",
            ShardingRules(rules + [(r"stacked/", PP_STACKED)], default=P()),
            P("data"))

    @staticmethod
    def sp() -> "ShardingStrategy":
        """Sequence/context parallel: tokens sharded over 'sequence';
        used with ring attention (ray_tpu.ops.ring_attention)."""
        return ShardingStrategy(
            "sp", ShardingRules(), P(("data",), "sequence"),
        )

    @property
    def activation_spec(self) -> P:
        """Canonical sharding for [batch, seq, d_model] activations.

        Constraining the residual stream to this spec at layer boundaries
        stops GSPMD from propagating conflicting weight shardings onto
        activation gradients (which shows up as "involuntary full
        rematerialization" warnings and replicated resharding on the
        backward add_any accumulations).
        """
        parts = tuple(self.batch_spec)
        assert len(parts) <= 3, f"batch_spec {self.batch_spec} has rank > 3"
        return P(*(parts + (None,) * (3 - len(parts))))

    def activation_sharding(self, mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.activation_spec)

    def batch_sharding(self, mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.batch_spec)

    def param_shardings(self, mesh: Mesh, params: Any):
        """Pytree of NamedShardings matching `params`' structure."""
        def spec(path, leaf):
            shape = np.shape(leaf)
            ps = self.param_rules.spec_for(_path_str(path), shape)
            ps = _subdivide_largest(ps, shape, mesh)
            return NamedSharding(mesh, ps)
        return jax.tree_util.tree_map_with_path(spec, params)

    def shard_params(self, mesh: Mesh, params: Any):
        shardings = self.param_shardings(mesh, params)
        return jax.device_put(params, shardings)


class _FsdpLargestMarker:
    """Sentinel: shard the largest divisible dim over 'fsdp'."""

    def __repr__(self):
        return "FSDP_LARGEST"


FSDP_LARGEST = _FsdpLargestMarker()


class _PpStackedMarker:
    """Sentinel: shard the leading (stacked-layer) dim over 'pipeline'."""

    def __repr__(self):
        return "PP_STACKED"


PP_STACKED = _PpStackedMarker()


def _subdivide_largest(spec, shape: Tuple[int, ...], mesh: Mesh) -> P:
    if spec is not FSDP_LARGEST:
        return spec
    fsdp_size = mesh.shape.get("fsdp", 1)
    if fsdp_size <= 1 or not shape:
        return P()
    # Pick the largest dim divisible by the fsdp axis.
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if shape[i] % fsdp_size == 0 and shape[i] >= fsdp_size:
            parts: List = [None] * len(shape)
            parts[i] = "fsdp"
            return P(*parts)
    return P()


def strategy_from_name(name: str) -> ShardingStrategy:
    presets = {
        "dp": ShardingStrategy.dp,
        "fsdp": ShardingStrategy.fsdp,
        "tp": ShardingStrategy.tp_transformer,
        "tp_fsdp": ShardingStrategy.tp_fsdp,
        "sp": ShardingStrategy.sp,
        "pp": ShardingStrategy.pp,
        "pp_tp": ShardingStrategy.pp_tp,
    }
    if name not in presets:
        raise ValueError(f"unknown strategy '{name}'; one of {list(presets)}")
    return presets[name]()


def shard_params(params, mesh: Mesh, strategy: "ShardingStrategy | str"):
    if isinstance(strategy, str):
        strategy = strategy_from_name(strategy)
    return strategy.shard_params(mesh, params)


def batch_sharding(mesh: Mesh, strategy: "ShardingStrategy | str"):
    if isinstance(strategy, str):
        strategy = strategy_from_name(strategy)
    return strategy.batch_sharding(mesh)
