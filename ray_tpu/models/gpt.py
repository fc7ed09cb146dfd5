"""GPT-style decoder LM, TPU-first (the flagship model family).

Pure-JAX pytree params (no framework wrapper) whose path names line up with
ray_tpu.parallel.sharding rules: `layers/<i>/attn/wq`, `mlp/w_up`,
`embed/table`, `lm_head`, `moe/...`. Design choices for the MXU/HBM:
bfloat16 activations + params with fp32 softmax/layernorm accumulation,
flash-attention Pallas kernel, optional ring attention (sequence sharded),
optional sparse experts (top-k routing with real dispatch: ops/moe.py;
softmax or sigmoid scores, a selection bias, shared experts, leading dense
layers, one chip's share of the experts: `experts_held`, a router that
reads the layer's normed input ahead of the mixer: `route_from`, and the
gate's activation: `gate_activation`), optional
latent attention (a low-rank k/v projection, q.k wider than v), optional
grouped-query attention with a norm a head, optional gated
short-convolution layers among the attention layers (ops/short_conv.py),
optional sliding-window layers among the full-attention layers (a head
count and a rotation of their own a kind, or no rotation at all, a gate a
head or an element on attention's output), optional gated delta-rule
linear-attention layers among them (ops/linear_attention.py: a state a head
that the data decays a channel at a time, or by one number a head, and
overwrites along the key; widths of their own for key and value: `delta`),
the norm of a half before it, after it or both (`norm_after`), the
stack run several times a step over the SAME weights with the final norm
inside the loop, every pass read by the head and the passes' cross-entropies
weighted a token by an exit gate (`loop`: a looped, weight-shared stack), an
optional learned sparse-attention indexer on the attention
layers (ops/indexer.py: it chooses the keys a query sees, and is trained by
a loss of its own),
per-layer jax.checkpoint (remat) for memory: a layer keeps its input and
what its kernels name (the flash kernel's output and row statistics, an
indexer's selection, a linear-attention or state-space layer's output and
chunk states) and recomputes the rest; where the devices' memory is
reckoned to hold them beside the state, every layer also keeps the first
rungs of ONE ladder of its products: its MLP's matmul results (MLP_OUT:
`up x`, then `gate x`), then what a delta-rule or state-space mixer's
filters read, then what they write (MIXER_OUT) (`products_kept`: from what
the train step's builder reports, parallel/memory.py, and the shapes;
nobody sets it). A layer is a mixer and then a feed-forward,
or ONE of the two alone (`_HALVES`); optional scalar multipliers on the
embedding, on what each half adds to the stream, on attention's scores and
on the logits (`Multipliers`).

Capability parity target: the models RLlib/Train wrap in the reference are
torch modules; here the model is a (init, apply) pair compatible with pjit.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import lru_cache, partial, update_wrapper
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import indexer, moe
from ray_tpu.ops.attention import (FLASH_LSE, FLASH_OUT, LANES,
                                   flash_attention_native, head_columns,
                                   mha_reference, qk_padding, ring_attention,
                                   tokens_first)
from ray_tpu.ops.embedding import embed_lookup
from ray_tpu.ops.linear_attention import (KDA_OUT, by_token, chunk_log_decay,
                                            kda)
from ray_tpu.ops.rope import (RopeSpec, as_spec, halves_apart, latent_split,
                              rope_frequencies, rope_split, rope_table)
from ray_tpu.ops.short_conv import short_conv, silu_conv
from ray_tpu.ops.state_space import SSD_OUT, ssd
from ray_tpu.ops.state_space import chunk_log_decay as ssm_log_decay
from ray_tpu.parallel import memory
from ray_tpu.parallel.sharding import MESH_AXES

# The name an MLP's matmul results carry (`_mlp_block`: up x and gate x,
# the pre-activations; jax.ad_checkpoint.checkpoint_name), as FLASH_OUT,
# KDA_OUT and SSD_OUT name the kernels' results: a block that keeps it
# (layer_fn's `keeping(n)`) computes the named products once a layer and
# step.
MLP_OUT = "mlp_out"
# The name of what a delta-rule or state-space mixer's `silu_conv` filters
# read (the projections' products: the filter's backward reads its input)
# and of what they write (the unit norms' backward and `kda_bwd` read the
# filter's result): `_filtered`.
MIXER_OUT = "mixer_out"
# What `layer_fn(..).keeping(n)` keeps of a layer through the remat beside
# its input and its kernels' named results: rungs 1..n, in this order, each
# in every layer that has it (`products_kept` reckons n).
LADDER = ("up x", "gate x", "what the mixer's filters read",
          "what they write")

# GPTConfig.gate_activation and ExpertForm.activation: relu's derivative at
# 0 is 0 (jax.nn.relu's), and so is relu2's, relu(.)^2.
_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
                "relu2": lambda x: jnp.square(jax.nn.relu(x))}

# A layer_kinds entry -> (the mixer's kind or None, whether a feed-forward
# follows it). The four first are the block as it always was.
_HALVES = {"attention": ("attention", True), "conv": ("conv", True),
           "window": ("window", True), "kda": ("kda", True),
           "attention_alone": ("attention", False),
           "ssm": ("ssm", False), "ff": (None, True),
           "ssm_ff": ("ssm", True)}


@dataclass(frozen=True)
class StateSpace:
    """An "ssm" or "ssm_ff" layer's sizes (GPTConfig.ssm): Mamba-2's mixer
    (`_ssm_block`; ops/state_space.py). heads x head_dim inner channels,
    `groups` pairs of input and output directions of `state` numbers that
    the heads of a group share, a causal depthwise filter of
    GPTConfig.conv_filter taps with a bias on [x | B | C], the scan in
    chunks of `chunk`, the gated norm over a group's inner channels."""
    heads: int
    head_dim: int
    groups: int
    state: int
    chunk: int = 128


@dataclass(frozen=True)
class DeltaRule:
    """A "kda" layer's widths and forms where they are not KDA's
    (GPTConfig.delta; `_kda_block`): n_heads heads of key_dim for q and k and
    value_dim for v, a state [key_dim, value_dim] a head, the norm on the
    heads' outputs over value_dim. decay "channel": a log-decay a channel of
    the key through a low-rank pair of rank key_dim with a step bias a
    channel; "head": ONE log-decay a head and token, -exp(a_log)
    softplus(x w_decay [d, heads] + dt_bias [heads]) (Gated DeltaNet,
    arXiv:2412.06464). gate "sigmoid": a sigmoid gate an element through a
    low-rank pair of rank key_dim; "silu": a SiLU gate an element from a
    full matrix wg [d, heads x value_dim]."""
    key_dim: int
    value_dim: int
    decay: str = "channel"            # channel | head
    gate: str = "sigmoid"             # sigmoid | silu


@dataclass(frozen=True)
class ExpertForm:
    """What a feed-forward computes, where it is not the gated MLP of three
    matrices under GPTConfig.gate_activation (GPTConfig.expert_form; it
    holds for the dense MLP, the shared expert and the routed experts
    alike). matrices 3: down(act(gate x) * up x); 2: down(act(up x)), no
    gate matrix. latent_dim > 0: the ROUTED experts read and write a latent
    width between two dense projections (`moe/w_latent_in` [d, latent],
    `moe/w_latent_out` [latent, d], scope `moe_latent`); the router and the
    shared expert keep the full width. shared_d_ff: the shared expert's
    width (0: n_shared_experts x d_ff)."""
    matrices: int = 3
    activation: str = "silu"
    latent_dim: int = 0
    shared_d_ff: int = 0


@dataclass(frozen=True)
class PredictionModule:
    """A multi-token prediction module of one depth (GPTConfig.mtp;
    arXiv:2412.19437, section 2.2): with h the stream before the final
    norm and e the embedding, g_i = [norm_e(e(t_{i+1})) ; norm_h(h_i)]
    W [2d, d], then the layers `layer_kinds` names (layer_fn's own block),
    a norm of its own and the SAME head; the loss gains loss_coef times
    the cross-entropy of t_{i+2} given g_i."""
    layer_kinds: Tuple[str, ...]
    loss_coef: float = 0.3


@dataclass(frozen=True)
class Multipliers:
    """Scalars on four places of the stack (GPTConfig.multipliers): the
    embedding's rows times `embedding` (scope `embed`; the lookup's backward
    reads the scaled cotangent); what each half of a layer adds to the
    residual stream times `residual`, x + residual f(norm(x)); attention's
    scores times `attention` in place of head_dim^-1/2 (None: that), the
    kernels' `sm_scale`; the logits divided by `logits` (the head's rows are
    scaled under `head`, so a tied matrix takes both its gradients on one
    leaf). A multiplier of 1 emits no op."""
    embedding: float = 1.0
    residual: float = 1.0
    attention: Optional[float] = None
    logits: float = 1.0


@dataclass(frozen=True)
class Loop:
    """A looped stack (GPTConfig.loop; arXiv:2510.25741): the layers run
    `passes` times a step over the SAME parameters, h_0 the embedding, h_t =
    final_norm(layers(h_{t-1})): the final norm sits INSIDE the loop, the
    normed output of a pass is what the next pass reads, and the positions
    are the same in every pass. Every pass is read by the same head. An exit
    gate (`exit_gate/w` [d, 1], `exit_gate/b` [1]; scope `exit_gate`) gives
    lam_t = sigmoid(h_t w + b) a token, and the exit distribution p_t =
    lam_t prod_{j<t} (1 - lam_j) for t < passes, p_T the rest. The loss is
    the mean over the tokens of sum_t p_t xent_t - entropy_coef H(p), the
    gradient through p as well as through the cross-entropies. A forward
    (gpt_forward, gpt_backbone) gives the LAST pass's: no token leaves
    early. The passes are one traced body (a lax.scan): a step holds each
    layer's kernels once, and a shared weight's gradient is the sum over
    the passes."""
    passes: int
    entropy_coef: float = 0.1


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304           # GPT-2 vocab padded to a multiple of 128
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    # Key/value heads (grouped-query attention): query head h reads
    # key/value head h // (n_heads // n_kv_heads). 0 = n_heads.
    n_kv_heads: int = 0
    # One head's width. 0 = d_model // n_heads; given, heads x head_dim
    # need not be d_model (wq [d, H * head_dim], wo [H * head_dim, d]).
    head_dim: int = 0
    d_ff: int = 3072                  # the MLP's width; of ONE expert's, if sparse
    max_seq: int = 1024
    dtype: Any = jnp.bfloat16
    rope_theta: float = 10000.0
    rmsnorm_eps: float = 1e-5
    # RMSNorm over the whole q and k projections, before the head split.
    qk_norm: bool = False
    # RMSNorm over each head's columns of q and of k, before the rotation:
    # one learned scale of head_dim for q and one for k, shared by the heads.
    qk_head_norm: bool = False
    # The kind of each layer, one a layer; None = "attention" everywhere.
    # "attention" | "conv" | "window" | "kda": that token mixer, then a
    # feed-forward (ln1 -> mixer -> ln2 -> MLP | experts). A layer may also
    # be ONE norm and ONE half: "ssm" (a state-space mixer, sized by `ssm`),
    # "attention_alone" (that mixer and no feed-forward), "ff" (a
    # feed-forward and no mixer: the MLP, or experts where the stack is
    # sparse). "ssm_ff": the state-space mixer, then a feed-forward (both
    # halves). A "conv" layer is a gated short
    # convolution: [B | C | X] = three projections of the normed input,
    # C * filter(B * X) with a causal depthwise filter of conv_filter taps
    # a channel, then an output projection. No bias, no activation.
    layer_kinds: Optional[Tuple[str, ...]] = None
    conv_filter: int = 3
    # A "window" layer is attention in which a query sees itself and the
    # attention_window - 1 positions before it (flash and reference paths).
    # The two kinds of attention layer share n_kv_heads and head_dim; each
    # has its own query heads (window_heads, 0 = n_heads) and its own
    # rotation (rope / window_rope, None = every column rotated as halves
    # at rope_theta): a table a kind, built once a step. A kind whose
    # RopeSpec rotates no column (rotated=0) rotates nothing beside one
    # that does: rope_of gives None for it, no table is built for it and
    # its q and k reach the kernels as projected.
    attention_window: int = 0
    window_heads: int = 0
    rope: Optional[RopeSpec] = None
    window_rope: Optional[RopeSpec] = None
    # False: no attention layer rotates q or k (no position enters the
    # scores but through the causal mask), and no rope table is built.
    use_rope: bool = True
    # Where the norm of a half sits: False, before it (h = x + f(norm(x)));
    # True, after it, on what the half ADDS (h = x + norm(f(x)): the mixer
    # and the feed-forward read the stream itself; ln1 and ln2 are those
    # norms' scales); "both", a norm either side, h = x + norm(f(norm(x))):
    # ln1 and ln2 before the halves, `ln1_after` and `ln2_after` on what
    # they add (four scales a layer).
    norm_after: Any = False
    # A gate on attention's output, from the normed input, before the
    # output projection: True, a gate a head, sigmoid(x wg [d, heads])
    # times the head's output; "element", a gate an element, wg [d, heads x
    # head_dim]. The block reads which off wg's shape.
    attention_gate: Any = False
    # A "kda" layer is gated delta-rule linear attention with a decay a
    # channel (`_kda_block`; ops/linear_attention.py): n_heads heads of
    # head_dim for q, k and v alike, a causal depthwise filter of
    # conv_filter taps and a SiLU on each of the three projections,
    # q and k normalised a head, a log-decay a channel and a beta a head
    # from the normed input (beta in (0, 1), or (0, 2) with
    # kda_neg_eigval), the heads' outputs under an RMSNorm a head times a
    # sigmoid gate an element, then the output projection. The decay and
    # the gate come through low-rank pairs of rank head_dim. `delta` gives
    # the layer widths of its own for key and value, a decay a head and a
    # SiLU gate from a full matrix.
    kda_neg_eigval: bool = False
    # index_topk > 0: every attention layer carries an indexer (`attn/index`:
    # index_heads thin heads of index_head_dim on ONE key head, which has a
    # LayerNorm; both rotated whole by the layer's rotation at that width; a
    # weight a head and token) that reads the layer's normed input DETACHED
    # and scores every causal key, I[t, s] = sum_j w[t, j] relu(qI[t, j] .
    # kI[s]); a query then sees its index_topk best keys and no others (all
    # of them while there are no more: a sequence of at most index_topk
    # positions runs the plain causal kernels). The choice carries no
    # gradient; the indexer learns from its KL to the attention's own
    # probabilities over the chosen keys (detached, averaged over the
    # heads), which the loss adds, summed over the layers, at
    # index_loss_coef. 0 = no indexer. Flash and reference paths.
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_loss_coef: float = 1.0
    # 0 = a dense MLP a layer; >0 = that many experts in its place, each
    # token through the expert_top_k the router gives the most probability
    # (used as they come out of the softmax, not renormalised). The loss
    # adds the load-balancing and the router z-loss at these weights.
    n_experts: int = 0
    expert_top_k: int = 2
    router_aux_loss_coef: float = 0.01
    router_z_loss_coef: float = 0.001
    # The second routing rule: "sigmoid" scores each expert on its own (no
    # softmax over them, and no router loss: cross-entropy alone is the
    # training loss). Either rule may pick by score + a per-expert bias
    # (router_bias_scale > 0: the bias is a parameter, seeded normal at
    # that scale; it enters the selection only, the kept weights are the
    # unbiased scores, its gradient is zero and nothing here moves it),
    # divide the kept weights by their sum, and scale them.
    router_score: str = "softmax"     # softmax | sigmoid
    router_bias_scale: float = 0.0
    router_renormalise: bool = False
    router_renormalise_eps: float = 1e-20   # added to the kept weights' sum
    router_scale: float = 1.0
    # What the router reads: "mixed", the normed residual stream after the
    # mixer (what the experts read); "input", the layer's normed INPUT,
    # ahead of the mixer: the routing (scores, top-k, weights, statistics,
    # the slots' order) is then worked out before attention in a scope of
    # its own, `route_ahead`, carried across the mixer, and the experts
    # gather their rows from the normed stream after it.
    route_from: str = "mixed"         # mixed | input
    # The activation on the gate of every gated MLP (dense, shared and
    # routed experts): down(act(gate x) * up x). "relu": its derivative at
    # 0 is 0, and a sparse layer's statistics gain
    # `expert_hidden_zero_share`.
    gate_activation: str = "silu"     # silu | relu
    # A dense gated MLP n_shared_experts x d_ff wide beside the routed sum,
    # every token through it.
    n_shared_experts: int = 0
    # The layer pattern: this many leading layers keep a dense MLP, of
    # width dense_d_ff, and the rest are sparse (n_experts > 0 only).
    dense_layers: int = 0
    dense_d_ff: int = 0
    # (first, count): the experts whose matrices live here, one chip's
    # share under expert parallelism. The router keeps its n_experts
    # outputs and its expert_top_k a token; a slot whose expert is
    # elsewhere is not gathered, multiplied or combined, and what it would
    # have added is left out of the layer's result. None = all of them.
    experts_held: Optional[Tuple[int, int]] = None
    # Latent attention (kv_latent_dim > 0): k and v come from ONE low-rank
    # projection (d_model -> kv_latent_dim, with its own RMSNorm, ->
    # heads x (qk_nope_dim + v_head_dim)); a head's q.k runs over
    # qk_nope_dim columns that carry no position and qk_rope_dim that are
    # rotated, the rotated key columns being one set that all heads share
    # (projected beside the latent); v and the output are v_head_dim wide.
    # rope_interleaved: the rotated columns are pairs [x0, y0, x1, y1, ..]
    # in the weights (de-interleaved there, once a step, then rotated as
    # halves). Under use_rope False the block rotates nothing: the
    # qk_rope_dim columns of q and the shared key part are plain columns.
    # 0 = multi-head attention at head_dim, q, k and v alike.
    kv_latent_dim: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_interleaved: bool = False
    # "full": keep a layer's input and, of its activations, the flash
    #   forward kernel's output and row statistics (lse) alone; everything
    #   XLA runs in the layer is recomputed in backward, the Pallas
    #   attention forward is not run a second time. The reference and ring
    #   paths name nothing to keep: the whole layer is recomputed there.
    #   Where a train step's builder reports the devices' memory
    #   (parallel/memory.py) every layer's MLP also keeps `up x`, or both
    #   its matmul results, and a delta-rule or state-space mixer what its
    #   filters read and write, as far as they are reckoned to fit
    #   (`products_kept`, the rungs of `LADDER`).
    # "none": save everything (max HBM, min FLOPs)
    remat_policy: str = "full"
    attention: str = "flash"          # flash | reference | ring
    tie_embeddings: bool = False
    # Sub-records, each None where the stack has nothing of the kind:
    # state-space layers' sizes, delta-rule layers' widths and forms (None:
    # KDA's at head_dim), the feed-forwards' form, a prediction module,
    # scalar multipliers, a looped stack (None: the layers run once).
    ssm: Optional[StateSpace] = None
    delta: Optional[DeltaRule] = None
    expert_form: Optional[ExpertForm] = None
    mtp: Optional[PredictionModule] = None
    multipliers: Optional[Multipliers] = None
    loop: Optional[Loop] = None

    def __post_init__(self):
        if not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        kinds = self.layer_kinds
        every = (kinds or ()) + (self.mtp.layer_kinds if self.mtp else ())
        if (kinds is not None and len(kinds) != self.n_layers) \
                or set(every) - set(_HALVES):
            raise ValueError(
                f"layer_kinds {kinds!r}"
                + (f" and mtp.layer_kinds {self.mtp.layer_kinds!r}"
                   if self.mtp else "")
                + f": expected n_layers={self.n_layers} of "
                + " | ".join(map(repr, _HALVES)))
        state_space = sorted(k for k in set(every) if _HALVES[k][0] == "ssm")
        if state_space and self.ssm is None:
            raise ValueError(f"{' and '.join(map(repr, state_space))} layers "
                             "need their sizes: GPTConfig.ssm")
        if state_space and self.attention == "ring":
            raise ValueError(
                f"an {state_space[0]!r} layer's state runs along the whole "
                "sequence: it is not sharded over 'sequence' "
                "(attention='ring')")
        if self.sm_scale is not None and (self.kv_latent_dim
                                          or self.index_topk):
            raise ValueError(
                f"multipliers.attention={self.sm_scale} scales the scores of "
                "multi-head attention layers (full and window; flash, "
                "reference and ring); it is not threaded to "
                + ("a latent block, whose scale is its q.k width's"
                   if self.kv_latent_dim
                   else "an indexer, whose KL target is scaled by "
                        "head_dim^-1/2"))
        if self.route_from == "input" and "ff" in every:
            raise ValueError("route_from='input' reads a layer's normed "
                             "input ahead of its mixer: an 'ff' layer has "
                             "none")
        if self.attention_gate not in (False, True, "element"):
            raise ValueError(f"attention_gate={self.attention_gate!r}: "
                             "expected False | True | 'element'")
        if self.norm_after not in (False, True, "both"):
            raise ValueError(f"norm_after={self.norm_after!r}: expected "
                             "False | True | 'both'")
        if self.loop is not None:
            if self.loop.passes < 1:
                raise ValueError(f"loop {self.loop!r}: expected passes >= 1")
            if self.mtp is not None:
                raise ValueError(
                    "loop runs the final norm inside every pass: a "
                    "prediction module (mtp) reads the stream BEFORE the "
                    "final norm, which a looped stack does not hand on")
            if self.attention == "ring":
                raise ValueError(
                    "a looped stack (loop) is built for the flash and "
                    "reference paths, not for attention='ring': the exit "
                    "gate's weights a token are not sharded over 'sequence'")
        if self.route_from not in ("mixed", "input"):
            raise ValueError(f"route_from={self.route_from!r}: expected "
                             "'mixed' | 'input'")
        if self.gate_activation not in _ACTIVATIONS:
            raise ValueError(f"gate_activation={self.gate_activation!r}: "
                             f"expected {' | '.join(map(repr, _ACTIVATIONS))}")
        form = self.feed_forward
        if form.matrices not in (2, 3) or form.activation not in _ACTIVATIONS:
            raise ValueError(
                f"expert_form {form!r}: expected matrices 2 | 3 and an "
                f"activation of {' | '.join(map(repr, _ACTIVATIONS))}")
        if self.delta is not None and (
                self.delta.decay not in ("channel", "head")
                or self.delta.gate not in ("sigmoid", "silu")):
            raise ValueError(f"delta {self.delta!r}: expected decay 'channel' "
                             "| 'head' and gate 'sigmoid' | 'silu'")
        if "kda" in (kinds or ()) and self.attention == "ring":
            raise ValueError(
                "a 'kda' layer's state runs along the whole sequence: it is "
                "not sharded over 'sequence' (attention='ring')")
        if self.rope_of("attention") is None and self.index_topk:
            raise ValueError(
                "attention layers that rotate nothing (use_rope=False, or "
                "a rope that rotates no column) are built for multi-head "
                "attention layers and latent blocks, not for an indexer")
        for heads in {self.n_heads, self.heads_of("window")}:
            if heads % self.kv_heads:
                raise ValueError(f"n_kv_heads={self.n_kv_heads} does not "
                                 f"divide n_heads={heads}")
        if "window" in (kinds or ()):
            if self.attention_window < 1:
                raise ValueError("layer_kinds has 'window' layers and "
                                 f"attention_window={self.attention_window}")
            if (self.kv_latent_dim or self.attention == "ring"
                    or self.qk_norm or self.qk_head_norm):
                raise ValueError(
                    "a 'window' layer is built for the flash and reference "
                    "paths of multi-head attention, not for "
                    + ("a latent block" if self.kv_latent_dim
                       else "attention='ring'" if self.attention == "ring"
                       else "qk_norm or qk_head_norm"))
        if self.index_topk:
            if self.index_heads < 1 or self.index_head_dim < 1:
                raise ValueError(
                    f"index_topk={self.index_topk} needs index_heads and "
                    f"index_head_dim, got {self.index_heads} and "
                    f"{self.index_head_dim}")
            if (self.kv_latent_dim or "window" in (kinds or ())
                    or self.attention == "ring"):
                raise ValueError(
                    "an indexer (index_topk > 0) is built for the flash and "
                    "reference paths of full multi-head attention layers, "
                    "not for "
                    + ("a latent block" if self.kv_latent_dim
                       else "'window' layers" if "window" in (kinds or ())
                       else "attention='ring': the selection is not sharded "
                            "over 'sequence'"))
        if self.kv_latent_dim and not (self.rope is None or self.rope.plain):
            raise ValueError("a latent block rotates qk_rope_dim columns at "
                             "rope_theta: it reads no RopeSpec")
        if self.kv_heads != self.n_heads and (self.kv_latent_dim
                                              or self.attention == "ring"):
            raise ValueError(
                f"n_kv_heads={self.n_kv_heads} != n_heads={self.n_heads}: "
                "grouped-query attention is built for the flash and "
                "reference paths of multi-head attention, not for "
                + ("a latent block" if self.kv_latent_dim
                   else "attention='ring'"))

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def scales(self) -> Multipliers:
        """multipliers, or the record in which every one is 1."""
        return self.multipliers or Multipliers()

    @property
    def sm_scale(self) -> Optional[float]:
        """The softmax scale of the attention layers where the configuration
        sets one (multipliers.attention); None: head_dim^-1/2, the kernels'
        own default."""
        return self.scales.attention

    @property
    def delta_rule(self) -> DeltaRule:
        """delta, or KDA's layer: q, k and v alike at head_dim."""
        return self.delta or DeltaRule(self.head_dim, self.head_dim)

    @property
    def feed_forward(self) -> ExpertForm:
        """expert_form, or the gated MLP under gate_activation."""
        return self.expert_form or ExpertForm(activation=self.gate_activation)

    def heads_of(self, kind: str) -> int:
        """Query heads of an "attention" or a "window" layer."""
        return (self.window_heads if kind == "window" else 0) or self.n_heads

    def rope_of(self, kind: str) -> Optional[RopeSpec]:
        """The rotation of an "attention" or a "window" layer; None where
        the kind carries none (use_rope False: no kind does; a RopeSpec
        that rotates no column of the head: this kind does not)."""
        if not self.use_rope:
            return None
        spec = self.window_rope if kind == "window" else self.rope
        if spec is None:
            return RopeSpec(theta=self.rope_theta)
        return spec if spec.columns(self.head_dim) else None

    @property
    def qk_head_dim(self) -> int:
        """Columns of one head's q.k product."""
        if self.kv_latent_dim:
            return self.qk_nope_dim + self.qk_rope_dim
        return self.head_dim

    @staticmethod
    def gpt2_small() -> "GPTConfig":
        return GPTConfig()

    @staticmethod
    def gpt2_medium() -> "GPTConfig":
        return GPTConfig(d_model=1024, n_layers=24, n_heads=16, d_ff=4096)

    @staticmethod
    def tiny() -> "GPTConfig":
        return GPTConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                         d_ff=256, max_seq=128)


# The name of an attention layer's parameters, by its kind.
_GROUP = {"attention": "attn", "window": "window_attn"}


def _init_dense(key, shape, scale=None, dtype=jnp.float32):
    if scale is None:
        scale = 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def gpt_init(key, cfg: GPTConfig) -> Dict:
    """Build the parameter pytree (fp32 master weights). A layer holds
    `ln1` and its mixer's group, then `ln2` and `mlp` | `moe`, or, where its
    kind is one half alone (`_HALVES`), `ln1` and that half's group: the
    block runs what a layer's parameters hold. Under norm_after "both" a
    half has a second scale, `ln1_after` | `ln2_after`; a looped stack
    (cfg.loop) has its exit gate, `exit_gate`: w [d, 1] at its fan-in's
    scale (a normed row then scores ~N(0, 1)) and a bias b [1] of 0."""
    keys = jax.random.split(key, cfg.n_layers + 3)
    params: Dict[str, Any] = {
        "embed": {"table": _init_dense(keys[0], (cfg.vocab_size, cfg.d_model),
                                       scale=0.02)},
        "final_norm": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _init_dense(keys[1], (cfg.d_model, cfg.vocab_size))
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    held = e if cfg.experts_held is None else cfg.experts_held[1]
    form = cfg.feed_forward
    kinds = cfg.layer_kinds or ("attention",) * cfg.n_layers
    # how many times the stack adds to the residual stream (two a layer
    # where every layer has both halves): an output projection's scale
    adds = sum((mixer is not None) + has_ff
               for mixer, has_ff in map(_HALVES.get, kinds))

    def mlp(k, width, fan_in=d, stack=()):
        """A feed-forward's matrices in cfg's form: [*stack, fan-in,
        fan-out], each at its fan-in's scale."""
        m = {"w_gate": _init_dense(k[0], stack + (fan_in, width),
                                   scale=1.0 / math.sqrt(fan_in)),
             "w_up": _init_dense(k[1], stack + (fan_in, width),
                                 scale=1.0 / math.sqrt(fan_in)),
             "w_down": _init_dense(k[2], stack + (width, fan_in),
                                   scale=1.0 / math.sqrt(adds * width))}
        if form.matrices == 2:
            del m["w_gate"]
        return m

    def build(layer_key, kind, sparse):
        k = jax.random.split(layer_key, 8)
        mixer, has_ff = _HALVES[kind]
        layer = {"ln1": {"scale": jnp.ones((d,), jnp.float32)}}
        if mixer is not None and has_ff:
            layer["ln2"] = {"scale": jnp.ones((d,), jnp.float32)}
        if cfg.norm_after == "both":
            for name in list(layer):         # ln1, and ln2 where it has one
                layer[name + "_after"] = {"scale": jnp.ones((d,),
                                                            jnp.float32)}
        if mixer == "conv":
            # w_in: the published [d, 3d] as its three chunks B, C, X
            layer["conv"] = {
                "w_in": _init_dense(k[0], (3, d, d), scale=1.0 / math.sqrt(d)),
                "filter": _init_dense(k[1], (d, cfg.conv_filter),
                                      scale=1.0 / math.sqrt(cfg.conv_filter)),
                "w_out": _init_dense(k[3], (d, d),
                                     scale=1.0 / math.sqrt(adds * d)),
            }
        elif mixer == "kda":
            layer["kda"] = _init_kda(jax.random.fold_in(layer_key, 12), cfg,
                                     adds)
        elif mixer == "ssm":
            layer["ssm"] = _init_ssm(jax.random.fold_in(layer_key, 13), cfg,
                                     adds)
        elif mixer is not None and cfg.kv_latent_dim:
            r, h = cfg.kv_latent_dim, cfg.n_heads
            layer["attn"] = {
                "wq": _init_dense(k[0], (d, h * cfg.qk_head_dim)),
                "w_kva": _init_dense(k[1], (d, r + cfg.qk_rope_dim)),
                "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
                "w_kvb": _init_dense(
                    k[2], (r, h * (cfg.qk_nope_dim + cfg.v_head_dim))),
                "wo": _init_dense(
                    k[3], (h * cfg.v_head_dim, d),
                    scale=1.0 / math.sqrt(adds * h * cfg.v_head_dim)),
            }
        elif mixer is not None:
            # a window layer's matrices lie under a name of their own: the
            # block reads a layer's kind off its parameters
            kv = cfg.kv_heads * cfg.head_dim
            wide = cfg.heads_of(mixer) * cfg.head_dim   # d, but for head_dim
            layer[_GROUP[mixer]] = {
                "wq": _init_dense(k[0], (d, wide)),
                "wk": _init_dense(k[1], (d, kv)),
                "wv": _init_dense(k[2], (d, kv)),
                "wo": _init_dense(k[3], (wide, d),
                                  scale=1.0 / math.sqrt(adds * wide)),
            }
            if cfg.attention_gate:
                layer[_GROUP[mixer]]["wg"] = _init_dense(
                    jax.random.fold_in(layer_key, 10),
                    (d, wide if cfg.attention_gate == "element"
                     else cfg.heads_of(mixer)))
        if "attn" in layer and cfg.index_topk:
            hi, di = cfg.index_heads, cfg.index_head_dim
            ik = jax.random.split(jax.random.fold_in(layer_key, 11), 3)
            layer["attn"]["index"] = {
                "wq": _init_dense(ik[0], (d, hi * di)),
                "wk": _init_dense(ik[1], (d, di)),
                "k_norm": {"scale": jnp.ones((di,), jnp.float32),
                           "bias": jnp.zeros((di,), jnp.float32)},
                "ww": _init_dense(ik[2], (d, hi)),
            }
        if "attn" in layer and cfg.qk_norm:
            # over the whole projection: its columns, which are d_model's
            # count only where heads x head_dim is
            for name, w in (("q_norm", "wq"), ("k_norm", "wk")):
                width = layer["attn"][w].shape[1] if w in layer["attn"] else d
                layer["attn"][name] = {"scale": jnp.ones((width,),
                                                         jnp.float32)}
        if "attn" in layer and cfg.qk_head_norm:
            for name in ("q_head_norm", "k_head_norm"):
                layer["attn"][name] = {
                    "scale": jnp.ones((cfg.head_dim,), jnp.float32)}
        if has_ff and sparse:
            # stacked [held, fan-in, fan-out]: the scale is the fan-in's,
            # which is the latent width where the experts work in one
            layer["moe"] = {
                "router": _init_dense(k[4], (d, e), scale=0.02),
                **mlp(k[5:], ff, form.latent_dim or d, (held,))}
            # (keys of what only some configurations have are folded in, so
            # that the others' weights stay what they were)
            if cfg.router_bias_scale:
                layer["moe"]["router_bias"] = _init_dense(
                    jax.random.fold_in(layer_key, 8), (e,),
                    scale=cfg.router_bias_scale)
            if cfg.n_shared_experts:
                layer["moe"]["shared"] = mlp(
                    jax.random.split(jax.random.fold_in(layer_key, 9), 3),
                    form.shared_d_ff or cfg.n_shared_experts * ff)
            if form.latent_dim:
                lk = jax.random.split(jax.random.fold_in(layer_key, 14), 2)
                layer["moe"]["w_latent_in"] = _init_dense(
                    lk[0], (d, form.latent_dim))
                layer["moe"]["w_latent_out"] = _init_dense(
                    lk[1], (form.latent_dim, d))
        elif has_ff:
            layer["mlp"] = mlp(k[5:], cfg.dense_d_ff if e > 0 else ff)
        return layer

    params["layers"] = [
        build(keys[i + 2], kind, e > 0 and i >= cfg.dense_layers)
        for i, kind in enumerate(kinds)]
    if cfg.mtp is not None:
        mk = jax.random.split(jax.random.fold_in(key, 15),
                              len(cfg.mtp.layer_kinds) + 1)
        params["mtp"] = {
            "proj": _init_dense(mk[0], (2 * d, d)),
            **{name: {"scale": jnp.ones((d,), jnp.float32)}
               for name in ("norm_e", "norm_h", "norm")},
            "layers": [build(mk[j + 1], kind, e > 0)
                       for j, kind in enumerate(cfg.mtp.layer_kinds)]}
    if cfg.loop is not None:
        params["exit_gate"] = {
            "w": _init_dense(jax.random.fold_in(key, 16), (d, 1)),
            "b": jnp.zeros((1,), jnp.float32)}
    return params


def _init_kda(key, cfg: GPTConfig, adds: int) -> Dict:
    """A "kda" layer's parameters (adds: gpt_init's), at cfg.delta_rule's
    widths and forms. The projections at their fan-in's scale;
    a filter's taps at 1 / sqrt(taps); the decay's two seeded ranges as the
    delta-rule papers publish them: exp(a_log), a head's decay rate,
    log-uniform over 1..16, and dt_bias the inverse softplus of a step
    log-uniform over 1e-3..0.1, a channel (a head, where the decay is a
    head's: `w_decay` [d, heads] then stands where the low-rank pair
    `wf_down`, `wf_up` does). A SiLU gate is one full matrix `wg` where the
    sigmoid gate is the pair `wg_down`, `wg_up`. Under cfg.delta the three
    projections are ONE matrix `w_qkv` and their filters one, `qkv_conv`,
    a head's dk + dk + dv columns side by side (heads x (2 dk + dv) fills
    lane tiles where heads x dk alone need not)."""
    d, h, size = cfg.d_model, cfg.n_heads, cfg.delta_rule
    keys, wide, rank = h * size.key_dim, h * size.value_dim, size.key_dim
    taps = cfg.conv_filter
    k = jax.random.split(key, 13)
    dt = jnp.exp(jax.random.uniform(
        k[11], (keys if size.decay == "channel" else h,),
        minval=math.log(1e-3), maxval=math.log(0.1)))
    layer = {
        "a_log": jax.random.uniform(k[10], (h,), maxval=math.log(16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "w_beta": _init_dense(k[8], (d, h)),
        "o_norm": {"scale": jnp.ones((size.value_dim,), jnp.float32)},
        "wo": _init_dense(k[3], (wide, d),
                          scale=1.0 / math.sqrt(adds * wide)),
    }
    if cfg.delta is None:
        filters = _init_dense(k[4], (3, wide, taps),
                              scale=1.0 / math.sqrt(taps))
        layer.update(wq=_init_dense(k[0], (d, keys)),
                     wk=_init_dense(k[1], (d, keys)),
                     wv=_init_dense(k[2], (d, wide)),
                     q_conv=filters[0], k_conv=filters[1], v_conv=filters[2])
    else:
        # ONE projection and ONE filter, a head's columns [q | k | v] side
        # by side: whole heads are whole columns of it
        layer.update(
            w_qkv=_init_dense(k[0], (d, 2 * keys + wide)),
            qkv_conv=_init_dense(k[4], (2 * keys + wide, taps),
                                 scale=1.0 / math.sqrt(taps)))
    if size.decay == "channel":
        layer.update(wf_down=_init_dense(k[6], (d, rank)),
                     wf_up=_init_dense(k[7], (rank, keys)))
    else:
        layer["w_decay"] = _init_dense(k[6], (d, h))
    if size.gate == "sigmoid":
        layer.update(wg_down=_init_dense(k[9], (d, rank)),
                     wg_up=_init_dense(k[12], (rank, wide)))
    else:
        layer["wg"] = _init_dense(k[9], (d, wide))
    return layer


def _init_ssm(key, cfg: GPTConfig, adds: int) -> Dict:
    """An "ssm" layer's parameters (adds: gpt_init's). The published
    in_proj [d, 2 HP + 2 GN + H] as its three parts: w_z (the gate), w_xbc
    (what the filter reads: x, then the G input and the G output
    directions) and w_dt (a step a head), each at its fan-in's scale; the
    taps at 1 / sqrt(taps), their bias 0; Mamba-2's published seeds:
    exp(a_log), a head's decay rate, uniform over 1..16; dt_bias the
    inverse softplus of a step log-uniform over 1e-3..0.1 (`_init_kda`'s
    range), floored at 1e-4; the skip d = 1; the gated norm's scale 1."""
    m, d = cfg.ssm, cfg.d_model
    inner, directions = m.heads * m.head_dim, 2 * m.groups * m.state
    k = jax.random.split(key, 7)
    dt = jnp.maximum(1e-4, jnp.exp(jax.random.uniform(
        k[5], (m.heads,), minval=math.log(1e-3), maxval=math.log(0.1))))
    return {
        "w_z": _init_dense(k[0], (d, inner)),
        "w_xbc": _init_dense(k[1], (d, inner + directions)),
        "w_dt": _init_dense(k[2], (d, m.heads)),
        "conv": _init_dense(k[3], (inner + directions, cfg.conv_filter),
                            scale=1.0 / math.sqrt(cfg.conv_filter)),
        "conv_bias": jnp.zeros((inner + directions,), jnp.float32),
        "a_log": jnp.log(jax.random.uniform(k[4], (m.heads,), minval=1.0,
                                            maxval=16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "d": jnp.ones((m.heads,), jnp.float32),
        "norm": {"scale": jnp.ones((inner,), jnp.float32)},
        "w_out": _init_dense(k[6], (inner, d),
                             scale=1.0 / math.sqrt(adds * inner)),
    }


def _whole(y):
    """Setting.psum where no shard holds a part only."""
    return y


class Setting(NamedTuple):
    """What a layer body is told about where it runs. The block is one
    piece of arithmetic under GSPMD (gpt_backbone: weights of global shape,
    the partitioner places the collectives) and inside a shard_map (a stage
    of parallel/pipeline.py: local shards of the weights, so the head count
    is read off their shapes, and collectives by hand). What differs:

    mesh: how a Mosaic kernel call is entered (`_per_shard`): through a
      shard_map over this mesh, or, with None, as it is, because there is
      one device or the caller holds its shard already.
    psum: how a sum is finished of which every shard of 'tensor' holds a
      part: the two row-parallel matmuls (attn/wo, mlp/w_down) and the q/k
      norm's mean square over column-parallel projections. Nothing to do
      under GSPMD; `lax.psum(y, "tensor")` inside a shard_map.
    act_sharding: the residual stream's NamedSharding under GSPMD (see
      gpt_backbone), None inside a shard_map."""
    mesh: Any = None
    act_sharding: Any = None
    psum: Callable = _whole

    def pin(self, x):
        if self.act_sharding is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.act_sharding)


def _rmsnorm(x, scale, eps, psum=_whole):
    """psum: Setting.psum where the row is split over 'tensor' (scale then
    holds this shard's columns); a whole row needs none."""
    with jax.named_scope("norm"):
        x32 = x.astype(jnp.float32)
        # of a Python int, psum is the int times the shards: the row's width
        var = (psum(jnp.sum(x32 * x32, axis=-1, keepdims=True))
               / psum(x.shape[-1]))
        return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _head_rmsnorm(y, scale, eps, dim: int = 0, exact: bool = False):
    """RMSNorm over each head's columns of y [B, S, heads * D], scale [D]
    shared by the heads (or, with dim = D given, a scale a column, [heads *
    D]: a state-space layer's norm a group), with the columns left where
    they are
    (`head_columns`): the heads' mean squares are a product with the 0/1
    matrix that says which column is in which head, and so is their way
    back to the columns: two thin matmuls, lane dense.
    One bf16 pass is enough for the squares (64 roundings of 2^-9 average
    out far below the result's own rounding); the way back takes three, so
    that a head's factor reaches its columns to 2^-16. exact: both at full
    precision, float32 sums of float32 squares and every bit of the factor
    (a delta-rule layer's output norm, whose sums were a float32 reduction
    over a [.., heads, D] view)."""
    squares, back = ((jax.lax.Precision.HIGHEST,) * 2 if exact else
                     (jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGH))
    with jax.named_scope("norm"):
        width, dim = y.shape[-1], dim or scale.shape[0]
        member = head_columns(width, dim)
        y32 = y.astype(jnp.float32)
        mean_sq = jnp.einsum("bsw,wh->bsh", y32 * y32, member,
                             precision=squares) / dim
        factor = jnp.einsum("bsh,wh->bsw", jax.lax.rsqrt(mean_sq + eps),
                            member, precision=back)
        return (y32 * factor
                * jnp.tile(scale, width // scale.shape[0])).astype(y.dtype)


def _rope(x, rope, positions):
    """Rotary position embeddings; x: [B, H, S, D]. rope: a theta, or a
    RopeSpec: the head's first spec.columns(D) columns are rotated as
    halves, the rest pass."""
    spec = as_spec(rope)
    rotated = spec.columns(x.shape[-1])
    half = rotated // 2
    angles = (positions[:, :, None].astype(jnp.float32)
              * rope_frequencies(spec, x.shape[-1]))          # [B,S,half]
    cos = jnp.cos(angles)[:, None, :, :]
    sin = jnp.sin(angles)[:, None, :, :]
    if spec.attention_factor != 1.0:
        cos, sin = cos * spec.attention_factor, sin * spec.attention_factor
    x1, x2 = x[..., :half], x[..., half:rotated]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rotated:]],
        axis=-1).astype(x.dtype)


def _per_shard(fn, mesh, in_dims, out_dims):
    """fn, entered as a Mosaic kernel call has to be. On a TPU the Pallas
    kernels are Mosaic custom calls, which GSPMD cannot partition, so under
    a mesh of more than one device they run per shard, inside a shard_map:
    the batch and whole heads over the axes parallel/sharding.py's
    MESH_AXES gives them ('data' x 'fsdp', 'tensor'), anything else
    handed whole, each device on its own slice with no collective. in_dims
    (one tuple an operand) and out_dims (one tuple, or for a tuple of
    results one each) say which dimension is which: "batch", "heads" or
    None. With no mesh (one device, or a caller inside a shard_map of its
    own) nothing is wrapped."""
    if mesh is None or mesh.size == 1:
        return fn

    def spec(dims):
        if dims and isinstance(dims[0], tuple):
            return tuple(map(spec, dims))
        return P(*(MESH_AXES[d] for d in dims))
    # check_vma off: pallas_call declares no varying axes for its outputs,
    # and the Pallas interpreter the CPU tests use fails the check inside.
    return shard_map(fn, mesh=mesh, in_specs=tuple(map(spec, in_dims)),
                     out_specs=spec(out_dims), check_vma=False)


def _heads_dims(by_kernels: bool):
    """`_per_shard`'s dims of the heads' outputs as a shard's flash kernels
    leave them: [B, S, H * Dv] where they write tokens first
    (ops/attention.py:tokens_first: heads of whole lane tiles, heads of 64
    that fill a shard's tiles in pairs), else [B, H, S, Dv]."""
    return (("batch", None, "heads") if by_kernels
            else ("batch", "heads", None, None))


def _tokens_first(o, by_kernels: bool = False):
    """The heads' outputs as `attn_out` reads them, [B, S, H * Dv]: as they
    are where the kernels wrote them so (heads of whole lane tiles, heads
    of 64 in pairs), else [B, H, S, Dv] turned by XLA ('reference', 'ring',
    a head count that fills no pairs): outside any shard_map and under the
    scope `attn_out`, whose first ops these two have always been."""
    if by_kernels:
        return o
    with jax.named_scope("attn_out"):
        o = o.transpose(0, 2, 1, 3)
        return o.reshape(*o.shape[:2], -1)


def _flash_on_mesh(q, k, v, table, cfg: GPTConfig, mesh, window=None):
    """The projections' outputs (q [B, S, H*D], k and v [B, S, Hkv*D])
    through the rotation and the flash kernels, per shard: the columns are
    whole heads, each device attends its own (batch, head) slice, a
    key/value head with the query heads that read it. A window layer's
    kernels run under scope `attn_window` (in `attn_core`). The scores'
    scale is the kernels' own, head_dim^-1/2, or cfg.sm_scale where the
    configuration sets one. -> the heads'
    outputs [B, S, H * D], the layout that leaves the kernels and the
    shard_map wherever `tokens_first` reads it off the shape a device
    holds:

    heads of whole lane tiles (128): ops/rope.py splits q, k and v by head
      on the way, one pass a tensor, straight into the kernels' [B, heads,
      S, D], and the kernels place a head's output among a token's columns.
    heads of 64 that fill lane tiles in pairs: q and k are rotated where
      they lie, v is what its projection wrote, and the kernels read, and
      write, [B, S, heads * 64]: nothing by head exists on either side, in
      either direction.
    any other (an odd count of heads of 64 a device): split by head as at
      128, and the kernels' [B, H, S, D] is turned by `_tokens_first`."""
    hd = cfg.head_dim
    tensor = 1 if mesh is None else mesh.shape.get(MESH_AXES["heads"], 1)
    heads = q.shape[-1] // hd // tensor, k.shape[-1] // hd // tensor
    by_kernels = tokens_first(hd, *heads)
    in_pairs = heads if by_kernels and hd < LANES else None

    def split_and_attend(q, k, v, *table):
        with jax.named_scope("attn_proj"):
            q = rope_split(q, hd, table, by_head=in_pairs is None)
            k = rope_split(k, hd, table, by_head=in_pairs is None)
            v = rope_split(v, hd, by_head=in_pairs is None)
        with jax.named_scope("attn_core"):
            if window is None:
                return flash_attention_native(q, k, v, causal=True,
                                              sm_scale=cfg.sm_scale,
                                              in_pairs=in_pairs)
            with jax.named_scope("attn_window"):
                return flash_attention_native(q, k, v, causal=True,
                                              sm_scale=cfg.sm_scale,
                                              window=window,
                                              in_pairs=in_pairs)

    columns = ("batch", None, "heads")
    o = _per_shard(split_and_attend, mesh,
                   (columns,) * 3 + ((),) * len(table),
                   _heads_dims(by_kernels))(q, k, v, *table)
    return _tokens_first(o, by_kernels)


def _index_projections(ix, x, cfg: GPTConfig):
    """The indexer's three projections of the layer's normed input, read
    DETACHED (no gradient of the indexer's loss reaches the block, and the
    block's reaches no parameter of the indexer): qI [B, S, Hi * Di], the
    one key head kI [B, S, Di] under its LayerNorm (weight and bias), and
    the heads' weights w [B, S, Hi] float32, times Hi^-1/2 Di^-1/2."""
    dt = cfg.dtype
    m = jax.lax.stop_gradient(x)
    qi = jnp.einsum("bsd,de->bse", m, ix["wq"].astype(dt))
    ki = jnp.einsum("bsd,de->bse", m, ix["wk"].astype(dt)).astype(jnp.float32)
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = (ki * jax.lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True)
                             + cfg.rmsnorm_eps)
          * ix["k_norm"]["scale"] + ix["k_norm"]["bias"]).astype(dt)
    w = jnp.einsum("bsd,dh->bsh", m, ix["ww"].astype(dt),
                   preferred_element_type=jnp.float32)
    return qi, ki, w * (cfg.index_heads * cfg.index_head_dim) ** -0.5


def _selected_attention(q, k, v, table, index, cfg: GPTConfig, mesh):
    """_flash_on_mesh for a layer with an indexer (index: its qI [B, S,
    Hi*Di], kI [B, S, Di], w [B, S, Hi] and its rope table): after the head
    split and the rotation (by head at every width: ops/indexer.py's kernels
    read q and k so, and the flash kernels take no heads in pairs under a
    selection), the indexer's heads are rotated whole by its
    table, ops/indexer.py chooses each query's index_topk keys from them
    (`select`: kernels, scope `attn_index`), the `flash_sel_*` kernels
    attend over the chosen keys under `attn_core` and hand out each head's
    log-sum-exp over them, and the indexer's KL is taken against q, k and
    that (`kl`: kernels, `attn_index` again). A sequence of at most
    index_topk positions has every causal key chosen and runs the plain
    causal kernels beside the plain walk. -> (the heads' outputs [B, S,
    H * D] (as `_flash_on_mesh` has them), the KL [shards], the selected
    pairs over the causal pairs [shards])."""
    qi, ki, w, index_table = index
    if mesh is not None and mesh.shape.get(MESH_AXES["heads"], 1) > 1:
        raise ValueError(
            "an indexer's selection is the same for every head of a token: "
            "its scores are a sum over index heads that 'tensor' > 1 would "
            "divide, and no psum of them is built")
    sm_scale = 1.0 / math.sqrt(cfg.head_dim)
    by_kernels = tokens_first(cfg.head_dim)

    n_table = len(table)

    def split_and_attend(q, k, v, qi, ki, w, *tables):
        table, index_table = tables[:n_table], tables[n_table:]
        with jax.named_scope("attn_proj"):
            q = rope_split(q, cfg.head_dim, table)
            k = rope_split(k, cfg.head_dim, table)
            v = rope_split(v, cfg.head_dim)
        with jax.named_scope("attn_index"):
            qi = rope_split(qi, cfg.index_head_dim, index_table)
            ki = rope_split(ki, cfg.index_head_dim, index_table)[:, 0]
        if q.shape[2] <= cfg.index_topk:
            # every causal key is chosen: the plain kernels, and the plain
            # walk for the indexer's loss
            with jax.named_scope("attn_index"):
                _, kl, share = indexer.select_and_kl(
                    qi, ki, w, q, k, topk=cfg.index_topk, sm_scale=sm_scale)
            with jax.named_scope("attn_core"):
                out = flash_attention_native(q, k, v, causal=True)
            return out, kl.reshape(1), share.reshape(1)
        with jax.named_scope("attn_index"):
            selected, kept, share = indexer.select(qi, ki, w,
                                                   topk=cfg.index_topk)
        with jax.named_scope("attn_core"):
            out, lse = flash_attention_native(
                q, k, v, causal=True, selected=selected, with_lse=True)
        with jax.named_scope("attn_index"):
            # the target's softmax is the kernel's: its lse over the chosen
            kl = indexer.kl(qi, ki, w, q, k, lse, selected, kept,
                            sm_scale=sm_scale)
        return out, kl.reshape(1), share.reshape(1)

    columns, whole = ("batch", None, "heads"), ("batch", None, None)
    o, kl, share = _per_shard(
        split_and_attend, mesh,
        (columns,) * 4 + (whole,) * 2
        + ((),) * (n_table + len(index_table)),
        (_heads_dims(by_kernels), ("batch",), ("batch",)))(
        q, k, v, qi, ki, w, *table, *index_table)
    return _tokens_first(o, by_kernels), kl, share


def _deinterleaved(w, heads: int, keep: int, pairs: int):
    """w [d, heads * (keep + pairs)]: in each head's last `pairs` columns,
    interleaved pairs [x0, y0, x1, y1, ..] -> halves [x0, x1, .. | y0, y1,
    ..]. Done to the weights, what it is to the activations they produce
    (a column's dot product does not change with its place), at a
    hundredth of the elements."""
    d = w.shape[0]
    w = w.reshape(d, heads, keep + pairs)
    tail = w[..., keep:].reshape(d, heads, pairs // 2, 2)
    tail = tail.swapaxes(-1, -2).reshape(d, heads, pairs)
    return jnp.concatenate([w[..., :keep], tail], axis=-1).reshape(d, -1)


def _rope_tail(t, table, n: int):
    """t [B, S, heads, width]: the last n columns of every head rotated as
    halves by `table` (rope_table of S and n), the rest as they are; t
    itself without a table (a block that rotates nothing). The
    jnp formulation of what ops/rope.py's latent kernels do in registers
    (`_latent_heads` says when it runs)."""
    if not table:
        return t
    cos, sin = (c[:, None, :n] for c in table)
    x = t[..., -n:].astype(jnp.float32)
    other = jnp.concatenate([x[..., n // 2:], x[..., :n // 2]], axis=-1)
    return jnp.concatenate(
        [t[..., :-n], (x * cos + other * sin).astype(t.dtype)], axis=-1)


def _latent_heads(q, kv, k_rope, table, nope: int, rope: int, dv: int,
                  fill: int):
    """The jnp formulation of ops/rope.py:latent_split, its fallback (head
    parts that do not fill whole or half lane tiles: the test sizes) and
    its oracle (`attention="reference"`): q [B, S, H * (nope + rope)], kv
    [B, S, H * (nope + dv)] and the shared k_rope [B, S, rope] -> q and k
    [B, H, S, nope + rope + fill] and v [B, H, S, dv], the rope columns
    rotated (`_rope_tail`; as projected without a table), the one key part
    repeated to every head, `fill` zero columns after them. On the chip it
    is slices and concatenates at 64-column granularity, float32 in the
    backward, and four transposes of [B, S, H, 256 | 128] tensors (PERF.md,
    PR 39)."""
    b, s, _ = q.shape
    q = q.reshape(b, s, -1, nope + rope)
    zeros = [jnp.zeros(q.shape[:3] + (fill,), q.dtype)] if fill else []
    q = jnp.concatenate([_rope_tail(q, table, rope)] + zeros,
                        axis=-1).transpose(0, 2, 1, 3)
    with jax.named_scope("attn_latent"):
        kv = kv.reshape(b, s, -1, nope + dv)
        k_rope = _rope_tail(k_rope[:, :, None, :], table, rope)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(
                k_rope, kv.shape[:3] + (rope,))] + zeros, axis=-1)
        return q, k.transpose(0, 2, 1, 3), kv[..., nope:].transpose(0, 2, 1, 3)


def _latent_attention(layer, x, cfg: GPTConfig, table, where: Setting):
    """The attention of a latent block, up to the heads' outputs
    [B, S, H * v_head_dim] (as `_flash_on_mesh` has them, by v's width; the
    reference's are turned): q straight from x, k and v up from one
    normalised latent, RoPE on qk_rope_dim columns of q's heads and on the
    one key part all heads share (table (): a block that rotates nothing,
    cfg.rope_of gives None; the same columns, as they were projected: no
    table, no rotation and no de-interleaving on any path), then the flash
    kernels at q.k width qk_nope_dim + qk_rope_dim and v width v_head_dim.
    Scope `attn_latent`
    (inside `attn_proj`) holds what exists only because attention is
    latent: both kv projections, their norm, the assembly of k and v.
    Between the projections and the flash kernels: ops/rope.py's latent
    kernels, one pass a tensor and direction (`latent_q_split` under
    `attn_proj`, `latent_kv_split` under `attn_latent`, their merges
    backward), where the shape a shard holds tiles (`latent_split`: nope
    and v of whole lane tiles, rope of a half or a whole one); else, and
    for `attention="reference"`, the jnp assembly (`_latent_heads`)."""
    a, dt = layer["attn"], cfg.dtype
    nope, rope, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    latent = cfg.kv_latent_dim
    wq, w_kva = a["wq"].astype(dt), a["w_kva"].astype(dt)
    # (pairs are put apart for the rotation's sake alone)
    interleaved = cfg.rope_interleaved and bool(table)
    with jax.named_scope("attn_proj"):
        if interleaved:
            wq = _deinterleaved(wq, wq.shape[1] // (nope + rope), nope, rope)
        q = jnp.einsum("bsd,de->bse", x, wq)
        with jax.named_scope("attn_latent"):
            if interleaved:
                w_kva = _deinterleaved(w_kva, 1, latent, rope)
            c = jnp.einsum("bsd,de->bse", x, w_kva)
            kv = jnp.einsum(
                "bsr,re->bse",
                _rmsnorm(c[..., :latent], a["kv_norm"]["scale"],
                         cfg.rmsnorm_eps), a["w_kvb"].astype(dt))
            k_rope = c[..., latent:]

    # the zero columns the flash kernels want after a head's q.k columns
    # (ops/attention.py:qk_padding), written where q and k are assembled
    # anyway and not in a pass of their own
    fill = 0 if cfg.attention == "reference" else qk_padding(nope + rope)
    sm_scale = 1.0 / math.sqrt(nope + rope)
    by_kernels = cfg.attention != "reference" and tokens_first(dv)

    def split_and_attend(q, kv, k_rope, *table):
        kernels = None if cfg.attention == "reference" else latent_split(
            q.shape[1], q.shape[2] // (nope + rope), nope, rope, dv, q.dtype)
        with jax.named_scope("attn_proj"):
            if kernels is None:
                q, k, v = _latent_heads(q, kv, k_rope, table, nope, rope, dv,
                                        fill)
            else:
                q_split, kv_split = kernels
                q = q_split(q, *table)
                with jax.named_scope("attn_latent"):
                    k, v = kv_split(kv, k_rope, *table)
        with jax.named_scope("attn_core"):
            if cfg.attention == "reference":
                return mha_reference(q, k, v, causal=True, sm_scale=sm_scale)
            return flash_attention_native(q, k, v, causal=True,
                                          sm_scale=sm_scale)

    if cfg.attention == "ring":
        raise ValueError("attention='ring' has no latent form: the shared "
                         "rotated key part is not sharded over 'sequence'")
    columns = ("batch", None, "heads")
    o = _per_shard(split_and_attend, where.mesh,
                   (columns, columns, ("batch", None, None))
                   + ((),) * len(table),
                   _heads_dims(by_kernels))(q, kv, k_rope, *table)
    return _tokens_first(o, by_kernels)


def _attention_block(layer, x, cfg: GPTConfig, table, where: Setting,
                     kind: str = "attention", index_table=()):
    """-> (what attention adds to the residual stream, the layer's
    statistics: {} but for a layer with an indexer, which gives `index_kl`
    (its loss, unweighted) and `index_selected_share` (selected pairs over
    causal pairs); index_table: rope_table at the indexer's head width).

    table: rope_table(S, the rotated width, the kind's rotation), built
    once a step by the caller (layer_fn, outside the remat). The flash path
    alone reads it: 'reference' and 'ring' keep the jnp `_rope` on
    [B, H, S, D] (the oracle, and ring's sequence shards need their global
    positions); a latent block (`_latent_attention`) reads it on every
    path. kind: "attention" | "window", which the layer's parameters are
    named by. Where the layer has a gate (`wg`: a column a head, or a column
    an element of the heads' outputs), scope `attn_gate` holds its matmul,
    its sigmoid and the product with the heads' outputs. Those arrive
    tokens first, [B, S, H * Dv], from every path, so `wo` reads them as
    they are, and a gate a head reaches a head's columns through
    `head_columns` (its gradient back the same way), never through a
    [B, S, H, Dv] view: at heads of whole lane tiles, and at heads of 64
    that fill lane tiles in pairs, nothing turns or copies them between the
    flash kernels and `wo`, in either direction
    (ops/attention.py:tokens_first); at a head count that fills no pairs
    `_tokens_first` turns them, under this block's scope `attn_out`."""
    dt = cfg.dtype
    a = layer[_GROUP[kind]]
    stats = {}
    if cfg.kv_latent_dim:
        o = _latent_attention(layer, x, cfg, table, where)
    elif "index" in a:
        o, kl, share = _multi_head_attention(a, x, cfg, table, where, kind,
                                             index_table)
        stats = {"index_kl": jnp.mean(kl),
                 "index_selected_share": jnp.mean(share)}
    else:
        o = _multi_head_attention(a, x, cfg, table, where, kind)
    with jax.named_scope("attn_out"):
        if "wg" in a:
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(jnp.einsum(
                    "bsd,dh->bsh", x, a["wg"].astype(dt),
                    preferred_element_type=jnp.float32))
                if gate.shape[-1] != o.shape[-1]:
                    # a column a head: three bf16 passes hand the head's
                    # columns 16 bits of it, under a product rounded to 8
                    gate = jnp.einsum(
                        "bsh,wh->bsw", gate,
                        head_columns(o.shape[-1],
                                      o.shape[-1] // gate.shape[-1]),
                        precision=jax.lax.Precision.HIGH)
                o = (o * gate).astype(dt)
        return where.psum(jnp.einsum("bsd,de->bse", o,
                                     a["wo"].astype(dt))), stats


def _multi_head_attention(a, x, cfg: GPTConfig, table, where: Setting,
                          kind: str = "attention", index_table=()):
    """q, k, v of one head width from the three projections of `a` (an
    attention layer's matrices; the query heads are wq's columns over
    head_dim, k and v at the key/value heads' count) -> the heads' outputs
    [B, S, H * head_dim] (tokens first: as the flash kernels leave them at
    heads of 128 and at heads of 64 in pairs, turned by XLA on the
    'reference' and 'ring' paths and at a head count that fills no pairs:
    `_tokens_first`, `_flash_on_mesh`). kind "window": under the sliding
    window, and in either kind the rotation is the kind's (cfg.rope_of;
    none where it gives None: q and k go to the kernels as projected).
    Where `a` has an indexer (`index`) the result is (the heads' outputs over the keys it
    chose, its KL [shards], its selected share [shards])."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    dt = cfg.dtype
    spec = cfg.rope_of(kind)
    window = cfg.attention_window if kind == "window" else None
    tensor = (1 if where.mesh is None
              else where.mesh.shape.get(MESH_AXES["heads"], 1))
    if cfg.kv_heads % tensor:
        raise ValueError(f"n_kv_heads={cfg.kv_heads} is not whole key/value "
                         f"heads over tensor={tensor}")

    def proj(w, norm=None, head_norm=None):
        y = jnp.einsum("bsd,de->bse", x, w.astype(dt))
        if norm is not None:
            y = _rmsnorm(y, norm["scale"], cfg.rmsnorm_eps, where.psum)
        if head_norm is not None:
            # a head is whole wherever its columns are: no psum
            y = _head_rmsnorm(y, head_norm["scale"], cfg.rmsnorm_eps)
        return y

    def heads(y):
        return y.reshape(b, s, -1, hd).transpose(0, 2, 1, 3)

    flash = cfg.attention not in ("ring", "reference")
    with jax.named_scope("attn_proj"):
        wq, wk = a["wq"], a["wk"]
        if flash and spec is not None and spec.columns(hd) != hd:
            # the kernels rotate partners half a head apart: a head's
            # columns in that order, in the weights (ops/rope.py)
            order = jnp.asarray(halves_apart(hd, spec.columns(hd)))
            wq, wk = (w.astype(dt).reshape(w.shape[0], -1, hd)[
                ..., order].reshape(w.shape) for w in (wq, wk))
        q = proj(wq, a.get("q_norm"), a.get("q_head_norm"))
        k = proj(wk, a.get("k_norm"), a.get("k_head_norm"))
        v = proj(a["wv"])
    index = None
    if "index" in a:
        with jax.named_scope("attn_index"):
            index = _index_projections(a["index"], x, cfg)
    if flash and index is not None:
        return _selected_attention(q, k, v, table, index + (index_table,),
                                   cfg, where.mesh)
    if flash:
        return _flash_on_mesh(q, k, v, table, cfg, where.mesh, window)
    with jax.named_scope("attn_proj"):
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        q, k, v = heads(q), heads(k), heads(v)
        if spec is not None:
            q, k = _rope(q, spec, positions), _rope(k, spec, positions)
    if index is not None:
        # the oracle of `_selected_attention`: the jnp rotation, and the
        # reference attention under the same selection
        with jax.named_scope("attn_index"):
            qi, ki, w = index
            di = cfg.index_head_dim
            qi = _rope(qi.reshape(b, s, -1, di).transpose(0, 2, 1, 3), spec,
                       positions)
            ki = _rope(ki[:, None], spec, positions)[:, 0]
            selected, kl, share = indexer.select_and_kl(
                qi, ki, w, q, k, topk=cfg.index_topk,
                sm_scale=1.0 / math.sqrt(hd))
        with jax.named_scope("attn_core"):
            o = mha_reference(q, k, v, causal=True, selected=selected)
        return _tokens_first(o), kl.reshape(1), share.reshape(1)
    with jax.named_scope("attn_core"):
        if cfg.attention == "ring":
            o = ring_attention(q, k, v, mesh=where.mesh, causal=True,
                               sm_scale=cfg.sm_scale)
        else:
            o = mha_reference(q, k, v, causal=True, window=window,
                              sm_scale=cfg.sm_scale)
    return _tokens_first(o)


def _conv_block(m, x, cfg: GPTConfig, where: Setting):
    """The gated short convolution in attention's place: B, C, X = three
    projections of x (the chunks of the published in_proj), C * filter(B *
    X) along the sequence (ops/short_conv.py: a causal depthwise filter,
    zeros before the sequence's start), then the output projection. The
    filter works on a channel alone, so column-parallel w_in and
    row-parallel w_out leave it local to a shard of 'tensor'. Scope `conv`
    holds the projections, `conv_mix` (nested) the gates and the filter."""
    dt = cfg.dtype
    with jax.named_scope("conv"):
        gate_in, gate_out, value = (
            jnp.einsum("bsd,de->bse", x, m["w_in"][j].astype(dt))
            for j in range(3))
        with jax.named_scope("conv_mix"):
            columns = ("batch", None, "heads")
            y = _per_shard(short_conv, where.mesh,
                           (columns,) * 3 + (("heads", None),), columns)(
                gate_in, gate_out, value, m["filter"])
        return where.psum(
            jnp.einsum("bsd,de->bse", y, m["w_out"].astype(dt)))


def _filtered(conv, product, *taps, named: int = 0):
    """silu(filter(product)) as `conv` runs it (ops/short_conv.py:silu_conv
    on a shard's columns), for a projection's product [B, S, columns].
    named: how many of the filter's two operands-to-be carry the name
    MIXER_OUT, what it reads first (its backward's residual, so that the
    projection's matmul is not run again) and what it writes second (so that
    the filter is not: the mixer's norms and its kernel's backward read the
    result); layer_fn's keeping blocks, whose remat policy saves the name.
    The values are the same either way."""
    y = conv(checkpoint_name(product, MIXER_OUT) if named > 0 else product,
             *taps)
    return checkpoint_name(y, MIXER_OUT) if named > 1 else y


def _kda_block(m, x, cfg: GPTConfig, where: Setting, named: int = 0):
    """Gated delta-rule linear attention in attention's place (KDA, or the
    layer cfg.delta describes; the recurrence and its chunked form:
    ops/linear_attention.py). From the block's input x (normed, or under
    cfg.norm_after the stream itself), a head h of dk columns for q and k
    and dv for v (cfg.delta_rule: head_dim both, or cfg.delta's own):

      q, k, v = silu(filter(x wq | wk | wv)), a causal depthwise filter a
            channel (ops/short_conv.py:silu_conv; where m holds `w_qkv`,
            one projection and one filter, a head's columns [q | k | v]);
            q and k divided by their head's norm, q times dk^-1/2
      log-decay a channel: -exp(a_log_h) softplus((x wf_down) wf_up + dt_bias),
            or, where m holds `w_decay`, ONE a head:
            -exp(a_log_h) softplus(x w_decay + dt_bias_h)
      beta a head: sigmoid(x w_beta), doubled under cfg.kda_neg_eigval
      o = kda(q, k, v, log-decay, beta)
      y = [RMSNorm_head(o) * sigmoid((x wg_down) wg_up)] wo, or, where m
            holds `wg`, [RMSNorm_head(o) * silu(x wg)] wo

    -> (y, the layer's statistics: `kda_log_decay_min`, the smallest
    cumulative log-decay inside a chunk, and `kda_beta_mean`). The filters,
    the norms and the state work on a head's own columns, so
    column-parallel projections and a row-parallel wo leave them local to a
    shard of 'tensor'. Scope `kda` holds the layer; `kda_core`, nested, the
    delta rule alone.

    Which layout the layer's tensors have between the filter and `wo` is
    read off the widths (ops/linear_attention.py:by_token). At heads of
    whole lane tiles (128 / 128) q, k, v, the log-decay, o and their
    gradients stay [B, S, H w], as the projections wrote them and `wo` reads
    them: `kda`'s block maps place a head, a head's sum of squares (the unit
    norm of q and k, `o_norm`) is a product with the 0/1 membership matrix
    and so is the factor's way back (`head_columns`; both at full precision:
    float32 sums of float32 squares, as the reduction over a [.., H, w] view
    was), and beta and ONE decay a head are [B, S, H]. No [.., H, w] view
    exists, which on the chip is a relayout pass a tensor and direction.
    At any other width (96 / 192 inside one [q | k | v] filter) the tensors
    are turned by head after the filter, [B, H, S, w], padded inside `kda`,
    and o is turned back under the gated norm.

    named: how many of each filter's operands carry MIXER_OUT (`_filtered`:
    0, 1 what it reads, 2 what it writes too)."""
    dt, f32 = cfg.dtype, jnp.float32
    b, s, _ = x.shape
    size = cfg.delta_rule
    dk, dv = size.key_dim, size.value_dim
    stay = by_token(dk, dv)     # heads of whole lane tiles: by token all along
    columns = ("batch", None, "heads")
    conv = partial(_filtered, _per_shard(
        silu_conv, where.mesh, (columns, ("heads", None)), columns),
        named=named)

    def placed(y, width):
        """A tensor of the heads' columns where `kda` takes it: [B, S, H w]
        as it is, or by head, [B, H, S, w]."""
        return y if stay else y.reshape(b, s, -1, width).transpose(0, 2, 1, 3)

    def a_row(y):
        """A number a head and token, [B, S, H]: as it is, or [B, H, S]."""
        return y if stay else y.transpose(0, 2, 1)

    def unit(y):
        y = y.astype(f32)
        if not stay:
            return y * jax.lax.rsqrt(
                jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)
        # float32 sums of float32 squares, and every bit of a head's factor
        # back on its columns: two thin matmuls at full precision
        member = head_columns(y.shape[-1], dk)
        exact = jax.lax.Precision.HIGHEST
        norm = jax.lax.rsqrt(jnp.einsum("bsw,wh->bsh", y * y, member,
                                        precision=exact) + 1e-6)
        return y * jnp.einsum("bsh,wh->bsw", norm, member, precision=exact)

    def low_rank(down, up):
        return jnp.einsum(
            "bsr,re->bse", jnp.einsum("bsd,dr->bsr", x, m[down].astype(dt)),
            m[up].astype(dt), preferred_element_type=f32)

    def a_head(w):                       # x w [d, H], float32 -> [B, S, H]
        return jnp.einsum("bsd,dh->bsh", x, m[w].astype(dt),
                          preferred_element_type=f32)

    with jax.named_scope("kda"):
        if "w_qkv" in m:
            # one projection, one filter; a head's columns are [q | k | v]
            qkv = conv(jnp.einsum("bsd,de->bse", x, m["w_qkv"].astype(dt)),
                       m["qkv_conv"])
            if stay:
                # each part's lane tiles of every head, side by side
                q, k, v = (jnp.concatenate(
                    [qkv[..., at + first:at + first + width]
                     for at in range(0, qkv.shape[-1], 2 * dk + dv)], axis=-1)
                    for first, width in ((0, dk), (dk, dk), (2 * dk, dv)))
            else:
                qkv = placed(qkv, 2 * dk + dv)
                q, k, v = qkv[..., :dk], qkv[..., dk:2 * dk], qkv[..., 2 * dk:]
        else:
            q, k, v = (
                placed(conv(jnp.einsum("bsd,de->bse", x, m[w].astype(dt)),
                            m[taps]), width)
                for w, taps, width in (("wq", "q_conv", dk),
                                       ("wk", "k_conv", dk),
                                       ("wv", "v_conv", dv)))
        q = (unit(q) * dk ** -0.5).astype(dt)
        k = unit(k).astype(dt)
        if "w_decay" in m:
            # one number a head and token: [B, S, H], by head [B, H, S, 1]
            log_decay = a_row(-jnp.exp(m["a_log"].astype(f32))
                              * jax.nn.softplus(a_head("w_decay")
                                                + m["dt_bias"]))
            if not stay:
                log_decay = log_decay[..., None]
        else:
            rate = jnp.repeat(jnp.exp(m["a_log"].astype(f32)), dk)
            log_decay = placed(-rate * jax.nn.softplus(
                low_rank("wf_down", "wf_up") + m["dt_bias"]), dk)
        beta = a_row(jax.nn.sigmoid(a_head("w_beta")))
        if cfg.kda_neg_eigval:
            beta = 2.0 * beta
        with jax.named_scope("kda_core"):
            whole_heads = ("batch", "heads", None, None)
            dims = ((columns,) * 5 if stay else
                    (whole_heads,) * 4 + (("batch", "heads", None),))
            o = _per_shard(kda, where.mesh, dims,
                           columns if stay else whole_heads)(
                q, k, v, log_decay, beta)
        stats = {"kda_log_decay_min": jnp.min(chunk_log_decay(log_decay)),
                 "kda_beta_mean": jnp.mean(beta)}
        # a head is whole wherever its columns are: no psum
        if stay:
            o = _head_rmsnorm(o.astype(f32), m["o_norm"]["scale"],
                              cfg.rmsnorm_eps, exact=True)
        else:
            o = _rmsnorm(o.transpose(0, 2, 1, 3).astype(f32),  # [B, S, H, dv]
                         m["o_norm"]["scale"], cfg.rmsnorm_eps)
        if "wg" in m:
            gate = jax.nn.silu(jnp.einsum("bsd,de->bse", x, m["wg"].astype(dt),
                                          preferred_element_type=f32))
        else:
            gate = jax.nn.sigmoid(low_rank("wg_down", "wg_up"))
        o = (o.reshape(b, s, -1) * gate).astype(dt)
        return where.psum(jnp.einsum("bsd,de->bse", o,
                                     m["wo"].astype(dt))), stats


def _ssm_block(m, x, cfg: GPTConfig, where: Setting, named: int = 0):
    """A state-space mixer in attention's place (Mamba-2; the recurrence
    and its chunked form: ops/state_space.py). From the normed input x, with
    H heads of P channels and G groups of N (cfg.ssm):

      z, xBC, dt = x w_z | x w_xbc | x w_dt
      [xs | B | C] = silu(filter(xBC) + bias), a causal depthwise filter a
            channel (ops/short_conv.py:silu_conv); xs [H, P], B and C [G, N]
      dt = softplus(dt + dt_bias) a head; the decay a_t = exp(-exp(a_log) dt)
      y = ssd(xs, dt, a_log, B, C, d)
      out = [RMSNorm_group(y * silu(z)) * scale] w_out, the mean square over
            each group's H P / G columns (the gate BEFORE the norm)

    -> (out, the layer's statistics: `ssm_dt_mean`, the mean step, and
    `ssm_log_decay_min`, the smallest cumulative log-decay inside a chunk).
    The filter, the scan and the norm work on a head's or a group's own
    columns, so column-parallel projections and a row-parallel w_out leave
    them local to a shard of 'tensor' that holds whole groups. Scope `ssm`
    holds the layer; `ssm_core`, nested, the scan alone. named: how many of
    the filter's operands carry MIXER_OUT (`_filtered`)."""
    dt_, f32, size = cfg.dtype, jnp.float32, cfg.ssm
    b, s, _ = x.shape
    columns = ("batch", None, "heads")
    conv = partial(_filtered, _per_shard(
        silu_conv, where.mesh, (columns, ("heads", None), ("heads",)),
        columns), named=named)
    with jax.named_scope("ssm"):
        z = jnp.einsum("bsd,de->bse", x, m["w_z"].astype(dt_))
        xbc = conv(jnp.einsum("bsd,de->bse", x, m["w_xbc"].astype(dt_)),
                   m["conv"], m["conv_bias"])
        step = jax.nn.softplus(jnp.einsum(
            "bsd,dh->bsh", x, m["w_dt"].astype(dt_),
            preferred_element_type=f32) + m["dt_bias"])
        inner = z.shape[-1]
        directions = (xbc.shape[-1] - inner) // 2

        def groups(t):                    # [B, S, G * N] -> [B, S, G, N]
            return t.reshape(b, s, -1, size.state)
        with jax.named_scope("ssm_core"):
            whole_heads = ("batch", None, "heads", None)
            # a shard's heads with their groups; one group serves every
            # shard whole
            shared = (whole_heads if size.groups > 1
                      else ("batch", None, None, None))
            y = _per_shard(
                partial(ssd, chunk=size.chunk), where.mesh,
                (whole_heads, ("batch", None, "heads"), ("heads",),
                 shared, shared, ("heads",)), whole_heads)(
                xbc[..., :inner].reshape(b, s, -1, size.head_dim), step,
                m["a_log"], groups(xbc[..., inner:inner + directions]),
                groups(xbc[..., inner + directions:]), m["d"])
        stats = {"ssm_dt_mean": jnp.mean(step),
                 "ssm_log_decay_min": jnp.min(ssm_log_decay(
                     step, m["a_log"], size.chunk))}
        gated = y.reshape(b, s, inner).astype(f32) * jax.nn.silu(
            z.astype(f32))
        # a group is whole wherever its columns are: no psum
        normed = _head_rmsnorm(
            gated, m["norm"]["scale"], cfg.rmsnorm_eps,
            dim=inner // (directions // size.state)).astype(dt_)
        return where.psum(jnp.einsum("bsd,de->bse", normed,
                                     m["w_out"].astype(dt_))), stats


def _mlp_block(m, x, cfg: GPTConfig, where: Setting, named: int = 0):
    """The gated MLP through m's three matrices, down(act(gate x) * up x)
    with cfg.gate_activation (SwiGLU by default), or, where m holds no gate
    matrix (cfg.expert_form: two matrices), down(act(up x)): a dense
    layer's MLP, or the shared expert of a sparse one. named: how many of
    its products carry the name MLP_OUT, up x first and gate x second
    (layer_fn's keeping blocks, whose remat policy saves the name; the
    value is the product either way)."""
    dt = cfg.dtype
    act = _ACTIVATIONS[cfg.feed_forward.activation]

    def product(w, name):
        y = jnp.einsum("bsd,df->bsf", x, m[w].astype(dt))
        return checkpoint_name(y, MLP_OUT) if name else y

    if "w_gate" in m:
        gate, up = product("w_gate", named > 1), product("w_up", named > 0)
        hidden = act(gate) * up
    else:
        hidden = act(product("w_up", named > 0))
    return where.psum(jnp.einsum("bsf,fd->bsd", hidden,
                                 m["w_down"].astype(dt)))


def _route(m, x, cfg: GPTConfig):
    """The router, in float32, over x: the tensor the configuration names
    (cfg.route_from: the normed stream after the mixer, or the layer's
    normed input ahead of it; `_routing` is its one caller). A score for
    every expert (softmax over them,
    or a sigmoid each: cfg.router_score), each token's expert_top_k largest
    — by score plus the selection bias where the layer has one, the kept
    weights being the scores without it —, the weights as they come or,
    as the configuration says, divided by their sum and scaled. With them
    the layer's routing statistics: the largest expert's load over the
    mean load, the share of the token-slots that fall to the experts held
    here and, under the softmax rule, its two losses: load balancing
    E x sum_e f_e P_e (f_e the share of tokens that chose e among ALL
    their k choices, P_e the mean probability of e) and the z-loss
    mean(logsumexp(logits)^2)."""
    e, k = cfg.n_experts, cfg.expert_top_k
    # HIGHEST: at the default precision a TPU rounds a float32 matmul's
    # operands to bfloat16, and near-tied experts then swap
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        m["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router_score {cfg.router_score!r} "
                         "(expected 'softmax' | 'sigmoid')")
    if "router_bias" in m:
        _, idx = jax.lax.top_k(scores + m["router_bias"], k)
        # scores[idx] as a one-hot product (one term of each sum is not
        # zero, and idx's k are distinct: the values and the gradient are
        # take_along_axis's to the bit): the TPU serialises an element
        # gather, and its transpose, a scatter-add
        weights = jnp.sum(
            jnp.where(idx[..., None] == jnp.arange(e), scores[..., None, :],
                      0), axis=-1)
    else:
        weights, idx = jax.lax.top_k(scores, k)
    if cfg.router_renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + cfg.router_renormalise_eps)
    if cfg.router_scale != 1.0:
        weights = weights * cfg.router_scale
    load = jnp.mean(jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32),
                            axis=2), axis=(0, 1))
    stats = {}
    if cfg.router_score == "softmax":
        stats.update(
            router_balance_loss=e * jnp.sum(
                load * jnp.mean(scores, axis=(0, 1))),
            router_z_loss=jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2))
    stats["expert_load_max_over_mean"] = jnp.max(load) * e / k
    if cfg.experts_held is None:
        stats["expert_slots_held_share"] = 1.0
    else:
        first, count = cfg.experts_held
        stats["expert_slots_held_share"] = jnp.sum(
            load[first:first + count]) / k
    return weights, idx, stats


def _expert_rows(tiles, rows, order, x, weights, *matrices,
                 activation="silu"):
    """_experts in a row space of `tiles` tiles of `rows` rows (which has to
    hold the order's: moe.in_row_space): dispatch, the grouped matmuls with
    the activation between (three matrices, gate, up and down: down(act(gate
    x) * up x); two, up and down: down(act(up x))), weighted return. Under
    "relu" and "relu2" the result is (y, [zeros, units] float32): how many
    of the held experts' hidden units act(.) are exactly 0 over the rows
    that hold a slot, and how many such units there are (the padding rows,
    zeros all, and the tiles nobody computes are not counted)."""
    b, s, d = x.shape
    with jax.named_scope("moe_route"):
        plan = moe.lay_out(order, rows, tiles)
        rows_in = moe.dispatch(x.reshape(b * s, d), plan)
    *w_gate, w_up, w_down = matrices
    if w_gate:
        gate = moe.grouped_matmul(rows_in, w_gate[0], plan)
        up = moe.grouped_matmul(rows_in, w_up, plan)
        hidden = _ACTIVATIONS[activation](gate)
        out = moe.grouped_matmul(hidden * up, w_down, plan)
    else:
        hidden = _ACTIVATIONS[activation](
            moe.grouped_matmul(rows_in, w_up, plan))
        out = moe.grouped_matmul(hidden, w_down, plan)
    with jax.named_scope("moe_route"):
        y = moe.combine(out, weights.reshape(b * s, -1), plan)
    y = y.reshape(b, s, d)
    if activation not in _COUNTS_ZEROS:
        return y
    real = plan.row_slot < order.order.shape[0]
    zeros = jnp.sum(jnp.where(real[:, None], hidden == 0, False),
                    dtype=jnp.float32)
    units = jnp.sum(real, dtype=jnp.float32) * hidden.shape[1]
    return y, jax.lax.stop_gradient(jnp.stack([zeros, units]))


# The activations whose zeros a sparse layer counts
# (`expert_hidden_zero_share`).
_COUNTS_ZEROS = ("relu", "relu2")


@lru_cache(maxsize=None)
def _expert_rows_with(activation: str):
    """_expert_rows at an activation, ONE function an activation:
    moe.in_row_space jits what it is handed, and layers of one shape share
    one trace of it only if they hand it the same function (under
    _expert_rows' own name, which the lowered step's text carries)."""
    return update_wrapper(partial(_expert_rows, activation=activation),
                          _expert_rows)


def _held(cfg: GPTConfig):
    """None where the layer's matrices are all the experts, else (the first
    one held here, of how many there are)."""
    if cfg.experts_held is None:
        return None
    return cfg.experts_held[0], cfg.n_experts


def _order_dims(held):
    """`_per_shard`'s dims of `_slot_order`'s arrays: each shard's own,
    side by side along their first dimension."""
    return ((("batch",),) * 5
            + (("batch", None),) * (1 if held is None else 2))


def _row_space(n_slots: int, groups: int, held, dtype):
    """(the slots expected on the `groups` experts whose matrices are here,
    the rows of a tile), from shapes alone. held: None where those are all
    the experts, else (first, of how many)."""
    expected = n_slots if held is None else n_slots * groups // held[1]
    return expected, moe.tile_rows(expected, groups, dtype)


def _slot_order(idx, groups: int, held, dtype):
    """idx [b, s, k], each token's chosen experts -> the slots in expert
    order (moe.order_slots) over the `groups` experts here, as a tuple of
    its arrays: what of the dispatch depends on the routing decision alone,
    on no row. held as in `_row_space`: a slot chosen for an expert that is
    not here gets no row."""
    idx = idx.reshape(-1, idx.shape[-1])
    _, rows = _row_space(idx.size, groups, held, dtype)
    if held is not None:
        idx = idx - held[0]
    order = moe.order_slots(idx, groups, rows, partial=held is not None)
    return tuple(field for field in order if field is not None)


def _routing(m, x, cfg: GPTConfig, where: Setting):
    """A sparse layer's routing from x [b, s, d], the tensor the
    configuration names (cfg.route_from), under the caller's scope
    (`moe_route` in `moe`, or `route_ahead` ahead of the mixer): _route's
    weights and statistics over the whole batch, and per shard
    (`_per_shard`) the order of each device's own slots over the experts
    here. -> (weights [b, s, k] float32, the order's arrays, the
    statistics): all `_moe_block` needs of the decision."""
    weights, idx, stats = _route(m, x, cfg)
    held = _held(cfg)
    order = _per_shard(
        partial(_slot_order, groups=m["w_down"].shape[0], held=held,
                dtype=x.dtype),
        where.mesh, (("batch", None, None),), _order_dims(held))(idx)
    return weights, order, stats


def _experts(x, weights, order, *matrices, held=None, activation="silu"):
    """x [b, s, d] through each token's chosen experts (ops/moe.py): rows
    in the order `_slot_order` gave the slots, the grouped matmuls (gate,
    up and down, or up and down) with the activation between, weighted
    return. The gathers either side
    are the layer's sparsity, not its arithmetic: scope `moe_route`. held:
    None where the matrices are all the experts', else (first, of how
    many): the matrices are experts first .. first + len - 1, and a slot
    chosen for another is left out. -> (y, and then, each where it
    exists: [1] whether the row space sized for the slots expected here
    held them (a share: moe.in_row_space); [1, 2] `_expert_rows`' zero
    and all hidden units ("relu", "relu2"))."""
    groups = matrices[0].shape[0]
    order = moe.Order(*order)
    expected, rows = _row_space(order.order.shape[0], groups, held, x.dtype)
    out, fitted = moe.in_row_space(_expert_rows_with(activation), order,
                                   rows, expected, x, weights, *matrices)
    y, *hidden = out if activation in _COUNTS_ZEROS else (out,)
    flag = [jnp.reshape(fitted, (1,))] if held is not None else []
    return (y, *flag, *(h[None] for h in hidden))


def _moe_block(layer, x, cfg: GPTConfig, where: Setting, routing=None,
               named_mlp: int = 0):
    """Sparse experts in the MLP's place: y = sum over a token's top-k of
    p_e x down_e(act(gate_e x) * up_e x), act = cfg.gate_activation (or, as
    cfg.expert_form says, down_e(act(up_e x)) with no gate matrix, and the
    routed experts in a latent width: x w_latent_in before them, w_latent_out
    after their weighted sum, scope `moe_latent`; the router and the shared
    expert read the full width). No
    capacity and no dropped token: every token-slot is computed, by its
    own expert only. The grouped matmuls run per shard (`_per_shard`): each
    device dispatches its own tokens to all the experts, whose matrices it
    is handed whole (in cfg.dtype under a mesh, as they are kept on one
    device); the router and its losses stay outside, over the whole batch.

    routing: `_routing`'s result where the block worked it out ahead of the
    mixer from the layer's normed input (cfg.route_from "input", scope
    `route_ahead`); None: it is worked out here from x, the tensor the rows
    come from, under `moe_route`. Either way `_route` runs once a layer.

    With cfg.experts_held the sum runs over the chosen experts that are
    held here (one chip's share under expert parallelism, without its
    exchange): the partial result, nothing standing in for the rest, in a
    row space sized for the slots expected here; `expert_rows_bounded`
    joins _route's statistics: the share of the devices on which that row
    space held the routing at hand (the others ran every slot's, the same
    arithmetic: moe.in_row_space), the constant 1.0 where all the experts
    are held. Under the activations "relu" and "relu2",
    `expert_hidden_zero_share` joins them: the share of the experts' hidden units relu(gate) that are
    exactly 0, over the rows computed. With cfg.n_shared_experts a dense
    gated MLP of every token is added (scope `moe_shared`). Scope `moe`
    (layer_fn's, around this) keeps the experts' own arithmetic: grouped
    matmuls, the activation, the casts of their matrices; `moe_route`,
    nested, what exists only because the layer is sparse and needs the
    rows: the gathers either side, the tables of the row space and, unless
    the routing came from ahead, router, top-k, ordering, both router
    losses."""
    dt = cfg.dtype
    m = layer["moe"]
    form = cfg.feed_forward
    if routing is None:
        with jax.named_scope("moe_route"):
            routing = _routing(m, x, cfg, where)
    weights, order, stats = routing
    matrices = [m[name] for name in ("w_gate", "w_up", "w_down")
                if name in m]
    if where.mesh is not None and where.mesh.size > 1:
        # handed whole to every device: gather them in the rows' type. On
        # one device the kernels read the masters and round a block in VMEM
        # (moe.grouped_matmul), and no cast pass runs here
        matrices = [w.astype(dt) for w in matrices]
    held, counts = _held(cfg), form.activation in _COUNTS_ZEROS
    tokens = ("batch", None, None)
    # what `_experts` hands back beside y: a share's flag, relu's counts
    extra_dims = ([("batch",)] if held is not None else []) \
        + ([("batch", None)] if counts else [])
    rows = x
    if "w_latent_in" in m:
        with jax.named_scope("moe_latent"):
            rows = jnp.einsum("bsd,dl->bsl", x, m["w_latent_in"].astype(dt))
    y, *extras = _per_shard(
        partial(_experts, held=held, activation=form.activation),
        where.mesh,
        (tokens, tokens, _order_dims(held)) + ((),) * len(matrices),
        (tokens, *extra_dims))(rows, weights, order, *matrices)
    if "w_latent_out" in m:
        with jax.named_scope("moe_latent"):
            y = jnp.einsum("bsl,ld->bsd", y, m["w_latent_out"].astype(dt))
    stats = dict(stats, expert_rows_bounded=(
        1.0 if held is None else jnp.mean(extras[0])))
    if counts:
        zeros, units = jnp.sum(extras[-1], axis=0)
        stats["expert_hidden_zero_share"] = zeros / jnp.maximum(units, 1.0)
    if "shared" in m:
        with jax.named_scope("moe_shared"):
            y = y + _mlp_block(m["shared"], x, cfg, where, named_mlp)
    return y, stats


def layer_fn(cfg: GPTConfig, seq: int, where: Setting):
    """(x [B, seq, D], one layer's parameters) -> (x, the layer's
    statistics: _route's dict for a sparse layer, {} for a dense one, and
    an indexer's two, a delta-rule layer's two or a state-space layer's
    two where the layer has them; which it is, whether its mixer is
    attention, the short convolution, the delta rule or the state-space
    scan, and whether it is both halves (ln1 -> mixer -> ln2 -> MLP |
    experts) or ONE norm and one half alone, the layer's own parameters
    say, as gpt_init built them). The one
    transformer block, rematted as cfg.remat_policy says, for whoever
    walks the layers: gpt_backbone loops over their list, a stage of
    parallel/pipeline.py scans over stacked ones. Under "full" it is ONE
    body under a policy: the block returned keeps a layer's input and its
    kernels' named results and computes every XLA matmul of the layer
    again in the backward pass; its attribute `keeping(n)` gives the
    block that also keeps the first n rungs of `LADDER`, five choices in
    all, so that the backward pass reads them where it would compute them
    again (0: the block itself): 1 and 2, the MLP's matmul results
    (MLP_OUT, [B, S, d_ff] each: up x, then gate x); 3, what a delta-rule
    or state-space mixer's `silu_conv` filters read, the projections'
    products (MIXER_OUT: x wq, x wk, x wv or the one x w_qkv; x w_xbc);
    4, what those filters write too, so that the backward pass neither
    multiplies nor filters again. A layer keeps the rungs it has: one
    without such a mixer (a latent or attention layer) its MLP's products
    and nothing more at 3 and 4. Who
    walks the layers runs ONE block for all of them, so that a step traces
    as many kinds of layer as it did, with n reckoned from the devices'
    memory (`products_kept`). Under "none" every block is the bare
    body. Under cfg.route_from
    "input" a sparse layer's routing (`_routing`) is worked out from the
    normed INPUT under scope `route_ahead`, before the mixer, and handed
    across it to `_moe_block`, whose rows come from the normed stream
    after the mixer; by default the block routes from that stream, inside
    `moe`."""
    # once a step, not once a layer and recompute: outside the remat; one
    # table for each kind of attention layer the stack has
    every = (cfg.layer_kinds or ("attention",)) + (
        cfg.mtp.layer_kinds if cfg.mtp else ())
    kinds = {_HALVES[kind][0] for kind in every} & set(_GROUP)
    with jax.named_scope("attn_proj"):
        # (no table for a kind that does not rotate)
        tables = {
            kind: rope_table(seq, cfg.qk_rope_dim if cfg.kv_latent_dim
                             else cfg.head_dim, cfg.rope_of(kind))
            if cfg.rope_of(kind) is not None else ()
            for kind in sorted(kinds)}
    index_table = ()
    if cfg.index_topk:
        with jax.named_scope("attn_index"):
            index_table = rope_table(seq, cfg.index_head_dim,
                                     cfg.rope_of("attention"))

    residual = cfg.scales.residual

    def add(x, delta):
        """x + residual f: what a half adds to the stream."""
        return where.pin(x + (delta if residual == 1.0 else delta * residual))

    def norm(name, y, layer):
        return _rmsnorm(y, layer[name]["scale"], cfg.rmsnorm_eps)

    def block(x, layer, kept=0):
        # (kept: the rungs of LADDER whose products carry their names)
        named_mlp, named_mixer = min(kept, 2), max(kept - 2, 0)
        # (under norm_after a half reads the stream itself and its norm
        # sits on what it adds, where every shard of 'tensor' has the sum;
        # under "both" a half reads its own norm of the stream and a second
        # scale, `<name>_after`, norms what it adds)
        both = cfg.norm_after == "both"
        after = bool(cfg.norm_after) and not both
        normed = x if after else norm("ln1", x, layer)
        mixer_stats, routing = {}, None
        if cfg.route_from == "input" and "moe" in layer:
            with jax.named_scope("route_ahead"):
                routing = _routing(layer["moe"], normed, cfg, where)
        if "conv" in layer:
            mixed = _conv_block(layer["conv"], normed, cfg, where)
        elif "kda" in layer:
            mixed, mixer_stats = _kda_block(layer["kda"], normed, cfg, where,
                                            named_mixer)
        elif "ssm" in layer:
            mixed, mixer_stats = _ssm_block(layer["ssm"], normed, cfg, where,
                                            named_mixer)
        elif _GROUP["window"] in layer or _GROUP["attention"] in layer:
            kind = "window" if _GROUP["window"] in layer else "attention"
            mixed, mixer_stats = _attention_block(
                layer, normed, cfg, tables[kind], where, kind, index_table)
        else:
            mixed = None                 # a feed-forward alone
        if (after or both) and mixed is not None:
            mixed = norm("ln1_after" if both else "ln1", mixed, layer)
        h = x if mixed is None else add(x, mixed)
        if "moe" not in layer and "mlp" not in layer:
            return h, mixer_stats        # a mixer alone
        second = "ln1" if mixed is None else "ln2"
        if after:
            normed = h
        elif mixed is not None:
            normed = norm("ln2", h, layer)
        if "moe" in layer:
            with jax.named_scope("moe"):
                delta, stats = _moe_block(layer, normed, cfg, where, routing,
                                          named_mlp)
        else:
            with jax.named_scope("mlp"):
                delta, stats = _mlp_block(
                    layer["mlp"], normed, cfg, where, named_mlp), {}
        if after or both:
            delta = norm(second + "_after" if both else second, delta, layer)
        return add(h, delta), {**stats, **mixer_stats}

    if cfg.remat_policy == "full":
        # (of an indexer, its selection and its loss's gradients: the walk
        # over the score tiles then runs once a layer and step; of a
        # delta-rule layer, its output, its chunks' states and their A,
        # Aqk and inverse: `kda_bwd` reads them; of a state-space layer,
        # its output and states: the scan over the chunks runs once; of
        # LADDER's products, those a keeping block names: they carry their
        # names in no other, so a step that keeps none is the text it was)
        policy = jax.checkpoint_policies.save_only_these_names(
            FLASH_OUT, FLASH_LSE, indexer.INDEX_MASK, indexer.INDEX_GRADS,
            KDA_OUT, SSD_OUT, MLP_OUT, MIXER_OUT)
        recompute = jax.checkpoint(block, policy=policy)

        @lru_cache(maxsize=None)
        def keeping(n):
            return recompute if n == 0 else jax.checkpoint(
                partial(block, kept=n), policy=policy)
        recompute.keeping = keeping
        return recompute
    if cfg.remat_policy != "none":
        raise ValueError(f"unknown remat_policy {cfg.remat_policy!r} "
                         "(expected 'full' | 'none')")
    block.keeping = lambda n: block
    return block


def _mlp_of(layer):
    """The matrices of the layer's `_mlp_block`: its dense MLP's, or its
    sparse block's shared expert's; None where it has neither."""
    return layer.get("mlp", layer.get("moe", {}).get("shared"))


def _a_devices_part(batch: int, seq: int, cfg: GPTConfig, where: Setting):
    """-> (the rows of a [batch, seq] batch that one device holds, over how
    many devices whole heads and an MLP's columns are cut: 'tensor'; the
    residual stream is cut over the batch alone)."""
    if where.act_sharding is not None:
        batch = where.act_sharding.shard_shape(
            (batch, seq, cfg.d_model))[0]
    return batch, (where.mesh.shape.get(MESH_AXES["heads"], 1)
                   if where.mesh is not None else 1)


def _layer_bytes(layer, batch: int, seq: int, cfg: GPTConfig,
                 where: Setting):
    """-> (kept, products), bytes a device: what layer_fn's block keeps of
    one layer with these parameters at [batch, seq] tokens (its input and
    what its mixer's kernels name: the flash kernels' output and row
    statistics, an indexer's selection and gradients, a delta-rule or
    state-space layer's output and chunk states, a delta-rule layer's
    kept matrices), and what `keeping(n)` keeps more, n = 0 .. 4, the five
    choices of `LADDER`: 1 and 2, of the MLP's matmul results as many as it
    has (none where the layer has no `_mlp_block`: `_mlp_of`); 3, what the
    filters of a delta-rule or state-space mixer read beside them, the
    columns of wq, wk, wv (or w_qkv), or of w_xbc, a token; 4, as many
    again, what they write (a layer with no such mixer keeps at 3 and 4
    what it keeps at 2). From the shapes alone; tests/test_mlp_kept.py
    holds both to what jax.checkpoint saves of each family's layers."""
    batch, heads_over = _a_devices_part(batch, seq, cfg, where)
    tokens, item = batch * seq, jnp.dtype(cfg.dtype).itemsize
    named = 0
    for kind, group in _GROUP.items():
        if group not in layer or cfg.attention != "flash":
            continue
        heads = cfg.heads_of(kind)
        named += tokens * heads * (
            (cfg.v_head_dim or cfg.head_dim) * item + 4)   # out, lse
        if "index" in layer[group]:
            hi, di = cfg.index_heads, cfg.index_head_dim
            named += batch * seq * seq + tokens * (
                (hi * di + di) * item + hi * 4)
    if "kda" in layer:
        # (the kernels hold a head's columns in whole lane tiles)
        heads, size = cfg.n_heads, cfg.delta_rule
        dk, dv = (w + -w % LANES for w in (size.key_dim, size.value_dim))
        # the output, and a chunk of 64's state and its A, Aqk and inverse
        # packed into [64, 2 x 64]
        named += tokens * heads * dv * item \
            + batch * heads * (seq // 64) * (dv * dk + 64 * 2 * 64) * 4
    if "ssm" in layer:
        size = cfg.ssm
        named += tokens * size.heads * size.head_dim * item \
            + batch * size.heads * -(-seq // size.chunk) \
            * size.head_dim * size.state * 4
    one, has = 0, 0
    m = _mlp_of(layer)
    if m is not None:
        one = tokens * m["w_up"].shape[-1] * item // heads_over
        has = len(m) - 1
    mixer = layer.get("kda", layer.get("ssm", {}))
    filtered = tokens * item * sum(
        mixer[w].shape[-1] for w in ("w_qkv", "wq", "wk", "wv", "w_xbc")
        if w in mixer) // heads_over
    mlp = min(2, has) * one
    return (tokens * cfg.d_model * item + named // heads_over,
            (0, min(1, has) * one, mlp, mlp + filtered, mlp + 2 * filtered))


def _working_set(layers, batch: int, seq: int, cfg: GPTConfig,
                 where: Setting):
    """-> (bytes a device needs beside what is kept while ONE of `layers`
    is differentiated, bytes the head needs): the part of the peak that no
    list of kept values shows. From the shapes, so that a cell of 65 536
    tokens a device is given its 3 GB and one of 8192 its 1.2: a token's
    rows of the widest layer with their cotangents (the MLP's and the
    chosen experts' hidden rows; the mixer's q, k, v and output, an
    indexer's scores over the sequence; a delta-rule layer's float32
    tensors a column, once each), beside the residual stream's
    float32 copies; or the head's chunk of logits. The factors are whole
    numbers under which the reckoned peak of every cell's step is at or
    over what the chip read (PERF.md section 6, PRs 63 and 72)."""
    batch, heads_over = _a_devices_part(batch, seq, cfg, where)
    tokens, item = batch * seq, jnp.dtype(cfg.dtype).itemsize

    def rows(layer):
        """The widths a token's activations take in `layer`: the
        feed-forward's hidden rows, the mixer's, and the float32 columns
        the mixer holds beside them."""
        m = _mlp_of(layer)
        hidden = 0 if m is None else m["w_up"].shape[-1]
        if "moe" in layer:
            # (of a token's chosen experts, at most those held here)
            held, _, width = layer["moe"]["w_up"].shape
            hidden += min(cfg.expert_top_k, held) * width
        mixer = floats = 0
        for kind, group in _GROUP.items():
            if group in layer:
                wide = cfg.qk_head_dim + qk_padding(cfg.qk_head_dim)
                mixer = cfg.heads_of(kind) * wide
                if "wg" in layer[group]:        # the gate on the output
                    mixer += layer[group]["wg"].shape[-1]
                if "index" in layer[group]:
                    mixer += 2 * seq            # float32 scores and their KL
        if "kda" in layer:
            size = cfg.delta_rule
            keys, values = (cfg.n_heads * w
                            for w in (size.key_dim, size.value_dim))
            mixer = keys + values
            # what the recomputed layer holds in float32 while its
            # feed-forward is differentiated (kimi's compiled step, PERF.md
            # section 6, PR 72): the gate's pre-activation, the log-decay
            # and its pre-activation (a channel's or a head's) and, by
            # token, the three norms' factors spread over their heads'
            # columns by the membership product
            floats = values + 2 * layer["kda"]["dt_bias"].shape[-1]
            if by_token(size.key_dim, size.value_dim):
                floats += 2 * keys + values
        if "ssm" in layer:
            mixer = layer["ssm"]["w_xbc"].shape[-1]
        if "conv" in layer:
            mixer = 3 * cfg.d_model
        return hidden, mixer, floats

    widest = max((_MLP_ROWS * hidden + _MIXER_ROWS * mixer) * item
                 + floats * 4 for hidden, mixer, floats in map(rows, layers))
    layer = tokens * (widest // heads_over
                      + _STREAM_COPIES * cfg.d_model * 4)
    # (chunked_xent's default rows a chunk)
    head = _rows_a_chunk(tokens, 16384) * cfg.vocab_size * item \
        * _HEAD_COPIES // (2 * heads_over)
    return layer, head


# `_working_set`'s factors: tensors a hidden row, a mixer's row and the
# residual stream take while one layer is differentiated.
_MLP_ROWS, _MIXER_ROWS, _STREAM_COPIES, _HEAD_COPIES = 5, 4, 3, 3


def final_norm(params, x, cfg: GPTConfig):
    """The last layer's output -> what the head reads."""
    return _rmsnorm(x, params["final_norm"]["scale"], cfg.rmsnorm_eps)


def _last_normed(params, h, cfg: GPTConfig):
    """`_stack`'s stream -> what the head reads of the LAST pass [B, S, D]:
    the final norm of it, or, of a looped stack's passes [T, B, S, D]
    (normed inside the loop), the last."""
    return final_norm(params, h, cfg) if cfg.loop is None else h[-1]


def gpt_forward(params, tokens, cfg: GPTConfig, mesh=None, act_sharding=None):
    """tokens: [B, S] int32 -> (logits [B, S, vocab] (cfg.dtype), the
    router's statistics as gpt_backbone gives them). Of a looped stack
    (cfg.loop): the LAST pass's logits."""
    dt = cfg.dtype
    x, router = gpt_backbone(params, tokens, cfg, mesh, act_sharding)
    with jax.named_scope("head"):
        x = _head_rows(x, cfg)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["embed"]["table"].astype(dt))
        else:
            logits = jnp.einsum("bsd,dv->bsv", x,
                                params["lm_head"].astype(dt))
    return logits, router


def gpt_forward_both(params, tokens, cfg: GPTConfig):
    """tokens [B, S + 1] -> (logits [B, S, vocab] of the token after each of
    the first S, logits [B, S, vocab] through the prediction module of the
    token TWO after each (its last position has none to predict); cfg.dtype
    both, the second None without cfg.mtp): what gpt_loss_and_aux takes its
    two cross-entropies from, as logits (of a looped stack, the last
    pass's: gpt_forward_passes gives every pass's)."""
    where = Setting()
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    h, _, block = _stack(params, inputs, cfg, where)
    w_head = _head_operands(params, h[-1] if cfg.loop else h, targets,
                            cfg)[1]
    with jax.named_scope("head"):
        logits = jnp.einsum("bsd,dv->bsv",
                            _head_rows(_last_normed(params, h, cfg), cfg),
                            w_head)
    if cfg.mtp is None:
        return logits, None
    g, _ = _prediction_module(params, h, targets, block, cfg, where)
    with jax.named_scope("head"):
        return logits, jnp.einsum("bsd,dv->bsv", _head_rows(g, cfg), w_head)


def gpt_backbone(params, tokens, cfg: GPTConfig, mesh=None, act_sharding=None):
    """tokens: [B, S] -> (final hidden states [B, S, D] (pre-LM-head), the
    layers' statistics, each averaged over the layers that have it:
    _route's dict for a sparse model, an indexer's two (`index_kl`,
    `index_selected_share`), a delta-rule layer's two
    (`kda_log_decay_min`, `kda_beta_mean`) and a state-space layer's two
    (`ssm_dt_mean`, `ssm_log_decay_min`) where the layers have them, {}
    for a dense model).

    act_sharding (a NamedSharding for [B, S, D] activations, usually
    ``strategy.activation_sharding(mesh)``) pins the residual stream at
    layer boundaries so GSPMD never back-propagates weight shardings onto
    activation gradients (the "involuntary full rematerialization" failure
    mode on 2D tp_fsdp meshes).
    """
    x, per_layer, _ = _stack(params, tokens, cfg, Setting(mesh, act_sharding))
    router = _layer_means(per_layer)
    return _last_normed(params, x, cfg), router


def gpt_forward_passes(params, tokens, cfg: GPTConfig):
    """A looped stack's every pass: tokens [B, S] -> (logits [T, B, S,
    vocab] in cfg.dtype, pass t's under the one head, the exit distribution
    p [T, B, S] float32: what gpt_loss_and_aux weights the passes'
    cross-entropies by)."""
    h, _, _ = _stack(params, tokens, cfg, Setting())
    w_head = _head_operands(params, h[-1], tokens, cfg)[1]
    with jax.named_scope("head"):
        logits = jnp.einsum("tbsd,dv->tbsv", _head_rows(h, cfg), w_head)
    return logits, jnp.exp(_exit_log_p(params, h))


def _embed(params, tokens, cfg: GPTConfig, where: Setting):
    """tokens [B, S] -> their rows of the embedding [B, S, D] in cfg.dtype,
    times multipliers.embedding where the configuration has one (the
    caller's scope `embed` holds both)."""
    x = embed_lookup(params["embed"]["table"], tokens, cfg.dtype, where.mesh)
    scale = cfg.scales.embedding
    return x if scale == 1.0 else x * scale


def _stack(params, tokens, cfg: GPTConfig, where: Setting):
    """tokens [B, S] through the embedding and the layers -> (the residual
    stream BEFORE the final norm, the statistics of each layer that has
    any, the block the layers ran: layer_fn's, keeping as many of the
    layers' products through the remat as `products_kept` reckons,
    for a caller that runs further layers at this sequence length).

    A looped stack (cfg.loop) runs the layers `passes` times over the same
    parameters, the final norm inside the loop, as ONE traced body (a
    lax.scan over the passes whose carry is the stream: the program holds
    each layer's ops once, and the scan's transpose sums a shared weight's
    gradients over the passes) -> the passes' NORMED streams [T, B, S, D]
    in the stream's place, each statistic averaged over the passes."""
    with jax.named_scope("embed"):
        x = where.pin(_embed(params, tokens, cfg, where))
    layer = layer_fn(cfg, tokens.shape[1], where).keeping(
        products_kept(params, *tokens.shape, cfg, where))
    if cfg.loop is None:
        x, per_layer = _walk(layer, x, params["layers"])
        return x, per_layer, layer

    # (the norm keeps its input alone through the loop, as a layer does:
    # its float32 copies of a pass's stream would be kept once a pass)
    normed = jax.checkpoint(partial(final_norm, cfg=cfg))

    def one_pass(x, _):
        x, per_layer = _walk(layer, x, params["layers"])
        x = where.pin(normed(params, x))
        return x, (x, per_layer)

    _, (streams, per_layer) = jax.lax.scan(one_pass, x, None,
                                           length=cfg.loop.passes)
    return streams, jax.tree_util.tree_map(
        lambda stat: jnp.mean(stat, axis=0), per_layer), layer


def _walk(layer, x, layers):
    """x through `layers` (their parameters, in order) by the block `layer`
    -> (x, the statistics of each layer that has any)."""
    per_layer = []
    for layer_params in layers:
        x, stats = layer(x, layer_params)
        if stats:
            per_layer.append(stats)
    return x, per_layer


def memory_plan(params, batch: int, seq: int, cfg: GPTConfig,
                where: Setting, share: float):
    """What memory.reckoned_peak reckons with, bytes a device of a step
    over [batch, seq] tokens, in its order: the gradient born before the
    layers' (of every parameter outside them but the embedding's table,
    whose gradient comes last unless the head reads the table too), each
    layer's gradient, the embedding's, what each layer keeps through the
    remat keeping the first 0 .. 4 rungs of `LADDER` (five lists), the
    working set of one layer and of the head; the prediction module's
    layers after the stack's. share: the part of the parameters' bytes
    that a device holds. What a layer keeps is of ONE application of it: a
    looped stack keeps that once a pass (memory.reckoned_peak's
    `passes`)."""
    layers = params["layers"] + params.get("mtp", {}).get("layers", [])
    grads = [int(share * memory.tree_bytes(layer)) for layer in layers]
    after = (0 if cfg.tie_embeddings
             else int(share * memory.tree_bytes(params["embed"])))
    kept, products = zip(*(_layer_bytes(layer, batch, seq, cfg, where)
                           for layer in layers))
    held = [[k + p[n] for k, p in zip(kept, products)]
            for n in range(len(LADDER) + 1)]
    working, head = _working_set(layers, batch, seq, cfg, where)
    if cfg.loop is not None:
        # what the loop itself keeps once a pass: the normed stream it
        # hands the head, and the final norm's input; and what the chip's
        # compiler keeps across both loops: it casts every layer's matrices
        # to the model dtype once, outside them, where a stack walked once
        # casts a layer's as it comes to it (a compile for the described
        # v5e: PERF.md section 6, PR 71)
        rows, _ = _a_devices_part(batch, seq, cfg, where)
        item = jnp.dtype(cfg.dtype).itemsize
        working += (2 * cfg.loop.passes * rows * seq * cfg.d_model * item
                    + sum(grads) * item // 4)
    return (int(share * memory.tree_bytes(params)) - sum(grads) - after,
            grads, after, held, working, head)


def products_kept(params, batch: int, seq: int, cfg: GPTConfig,
                  where: Setting) -> int:
    """How many rungs of `LADDER` every layer keeps through the remat
    (layer_fn's `keeping(n)`), five choices: 0, 1 (its MLP's up x), 2 (gate
    x too), 3 (what a delta-rule or state-space mixer's filters read), 4
    (what they write too); a layer keeps the rungs it has, and the answer
    is the lowest rung that keeps as much as the one reckoned to fit (2, not
    4, in a stack without such a mixer):
    observed, not set. The most that memory.reckoned_peak puts under
    memory.CEILING of the devices' limit beside the state that the step's
    builder reports (memory.budget: train/train_step.py gives it around
    the trace); 0 where nobody reports (a loss differentiated by hand, the
    serving forward), where the platform gives no limit (the CPU), and
    under remat_policy "none", which keeps everything as it is. All layers
    keep alike, so that the step traces and lowers as many kinds of layer
    as it did (a second kind cost gpt2s 3.4-5 s of set-up: PERF.md section
    6, PR 63). A looped stack (cfg.loop) keeps a layer's bytes once a pass
    and holds every layer's gradient, a sum over the passes, across the
    whole loop. The traced step says what it chose (memory.report)."""
    told = memory.budget()
    if told is None or cfg.remat_policy != "full":
        return 0
    before, grads, after, held, working, head = memory_plan(
        params, batch, seq, cfg, where, told.share)
    passes = cfg.loop.passes if cfg.loop else 1
    peaks = [memory.reckoned_peak(told.state, before, grads, after, h,
                                  working, head, passes) for h in held]
    n = memory.most_kept(told.limit, peaks)
    if passes > 1:
        # A loop's kept values are stacked, one buffer of [passes, ...] a
        # layer and product, written in the forward loop and read in the
        # backward one: with the MLPs' products among them the chip's
        # compiler laid the two loops' buffers out with as much lost
        # between them as they hold (5.55 GB of 11.37 at 8 layers) and
        # refused a step whose values fit (PERF.md section 6, PR 71). A
        # looped stack keeps nothing more until a layout holds them.
        n = 0
    while n and sum(held[n]) == sum(held[n - 1]):
        n -= 1            # no gate, no such mixer, no MLP at all: no more kept
    having = sum(h2 > h0 for h0, h2 in zip(held[0], held[2]))
    mixers = sum(h > h2 for h2, h in zip(held[2], held[n]))
    memory.report(n, having, mixers, passes * (sum(held[n]) - sum(held[0])),
                  peaks[n], told.limit, passes)
    return n


def _layer_means(per_layer):
    """Each statistic over the layers that have it."""
    router = {}
    for name in sorted({name for stats in per_layer for name in stats}):
        layers = [stats[name] for stats in per_layer if name in stats]
        router[name] = sum(layers) / len(layers)
    return router


def _prediction_module(params, h, targets, layer, cfg: GPTConfig,
                       where: Setting):
    """The multi-token prediction module (cfg.mtp; `PredictionModule`) on
    h [B, S, D], the stream before the final norm, and targets [B, S], the
    token after each position: g = [norm_e(e(targets)) ; norm_h(h)] proj,
    through the module's layers (`layer`: layer_fn's block) and its norm ->
    (what the head reads to predict the token TWO after each position, the
    statistics of its layers). Scope `mtp` holds the module's own ops (its
    three norms, the concatenation, proj); its lookup, its layers and the
    head pass fall under the regions they always do."""
    m, eps = params["mtp"], cfg.rmsnorm_eps
    with jax.named_scope("embed"):
        e = _embed(params, jnp.maximum(targets, 0), cfg, where)
    with jax.named_scope("mtp"):
        g = where.pin(jnp.einsum(
            "bse,ed->bsd",
            jnp.concatenate([_rmsnorm(e, m["norm_e"]["scale"], eps),
                             _rmsnorm(h, m["norm_h"]["scale"], eps)], -1),
            m["proj"].astype(cfg.dtype)))
    g, per_layer = _walk(layer, g, m["layers"])
    with jax.named_scope("mtp"):
        return _rmsnorm(g, m["norm"]["scale"], eps), per_layer


def _rows_a_chunk(n, chunk_rows):
    """Never chunk coarser than the batch itself: padding a small batch up
    to a full 16k-row chunk would both waste LM-head FLOPs and raise the
    HBM peak the chunking exists to cut."""
    return min(chunk_rows, max(128, n))


def _xent_chunks(x, targets, mask, chunk_rows):
    """Rows [N, ...] -> chunks [n_chunks, chunk_rows, ...], padded with
    masked-out rows."""
    n, d = x.shape
    chunk_rows = _rows_a_chunk(n, chunk_rows)
    pad = (-n) % chunk_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    n_chunks = (n + pad) // chunk_rows
    return (x.reshape(n_chunks, chunk_rows, d),
            targets.reshape(n_chunks, chunk_rows),
            mask.reshape(n_chunks, chunk_rows))


def _chunk_nll(xk, w_head, tk):
    """One chunk's fp32 logits [chunk, V], their logsumexp and the rows'
    negative log-likelihoods [chunk]: the arithmetic both formulations of
    the loss share, so that they agree to the last bit."""
    logits = xk @ w_head
    # The target's logit is read from the matmul's result in the model
    # dtype: the value the logsumexp sees. Read after the cast, XLA writes
    # an fp32 copy of the logits to HBM for the gather alone (3.3 GB a
    # 16 384-row chunk at a 50k vocabulary: it was the dense step's peak)
    # and on the TPU fills it with the matmul's unrounded accumulator, so
    # the two reads differ by one bf16 rounding a row (PERF.md, PR 30).
    picked = jnp.take_along_axis(logits, tk[:, None], axis=-1)[:, 0]
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return logits, lse, lse - picked.astype(jnp.float32)


def chunked_xent_recompute(x, w_head, targets, mask, chunk_rows: int = 16384,
                           plain: bool = False):
    """chunked_xent differentiated by autodiff: each chunk's body is under
    jax.checkpoint, so the backward computes the chunk's logits and their
    logsumexp a second time (four vocabulary matmuls a chunk) and a
    differentiated call saves nothing but its inputs. For a caller that is
    differentiated INSIDE a scan (parallel/pipeline.py's last rank, once a
    tick): there chunked_xent's residuals would be saved once an iteration,
    w_head's fp32 gradient among them. mask and plain: chunked_xent's."""
    xc, tc, mc = _xent_chunks(x, targets, mask, chunk_rows)

    @jax.checkpoint
    def body(carry, args):
        xk, tk, mk = args
        _, _, nll = _chunk_nll(xk, w_head, tk)
        return ((carry[0] + jnp.sum(nll * mk), carry[1] + jnp.sum(mk)),
                jax.lax.stop_gradient(nll) if plain else None)

    sums, nll = jax.lax.scan(body, (0.0, 0.0), (xc, tc, mc))
    return sums + (nll.reshape(-1)[:x.shape[0]],) if plain else sums


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def chunked_xent(x, w_head, targets, mask, chunk_rows: int = 16384,
                 plain: bool = False):
    """Next-token cross-entropy WITHOUT materializing full [N, vocab] fp32
    logits (12.8 GB at bs=64/seq=1024/vocab=50k — an HBM-capacity bug for
    any capacity-size batch): rows go through the head in chunks of a scan.
    TPU-native analogue of fused linear+cross-entropy.

    x: [N, D] (model dtype), w_head: [D, V], targets: [N] int32,
    mask: [N] fp32, a WEIGHT a row: 0 / 1 where a caller leaves rows out,
    any float where it weights them (a looped stack's exit distribution).
    Returns (sum of mask x nll, sum_mask): the weighted sum and the
    weights' sum; d / d mask is the row's nll (and 1 through the second
    sum), in both formulations. plain: a third result from the same pass
    over the logits, the rows' own nll [N] float32, unweighted (what a plain
    mean cross-entropy over any of the rows needs beside the weighted sum);
    it carries no gradient.

    Differentiated, the same scan takes the gradient while the chunk's
    logits are there (_chunked_xent_fwd): p = (softmax - onehot) * mask
    rounded to the model dtype, where autodiff rounds d logits, then
    dx_k = p @ w_head.T and dW += xk.T @ p, both accumulated in fp32. That
    is three vocabulary matmuls a chunk and one softmax; no jax.checkpoint,
    no second logits matmul. The residuals are dx [N, D] (x's dtype),
    dW [D, V] (fp32) and the rows' nll [N], and the backward rule scales
    them by the cotangents (and hands the two gradients on together where
    the compiler would else hold a lone chunk's logits: _chunked_xent_bwd),
    so nothing of a chunk outlives its iteration.
    Evaluated only, no gradient is computed. Inside a differentiated scan
    use chunked_xent_recompute (its docstring says why)."""
    return chunked_xent_recompute(x, w_head, targets, mask, chunk_rows, plain)


def _chunked_xent_fwd(x, w_head, targets, mask, chunk_rows, plain):
    xc, tc, mc = _xent_chunks(x, targets, mask, chunk_rows)
    vocab = w_head.shape[1]

    def body(carry, args):
        total, denom, dw = carry
        xk, tk, mk = args
        logits, lse, nll = _chunk_nll(xk, w_head, tk)
        onehot = tk[:, None] == jnp.arange(vocab, dtype=tk.dtype)
        p = ((jnp.exp(logits - lse[:, None]) - onehot) * mk[:, None]
             ).astype(xk.dtype)
        dxk = jnp.einsum("nv,dv->nd", p, w_head,
                         preferred_element_type=jnp.float32).astype(xk.dtype)
        dw = dw + jnp.einsum("nd,nv->dv", xk, p,
                             preferred_element_type=jnp.float32)
        return (total + jnp.sum(nll * mk), denom + jnp.sum(mk), dw), (dxk, nll)

    (total, denom, dw), (dx, nll) = jax.lax.scan(
        body, (0.0, 0.0, jnp.zeros(w_head.shape, jnp.float32)), (xc, tc, mc))
    n = x.shape[0]
    dx, nll = dx.reshape(-1, x.shape[1])[:n], nll.reshape(-1)[:n]
    # (a residual has to be an array: w_head's dtype rides on an empty one)
    return (total, denom) + (nll,) * plain, (
        dx, dw, nll, jnp.zeros((0,), w_head.dtype))


def _chunked_xent_bwd(chunk_rows, plain, residuals, cotangents):
    dx, dw, nll, w_like = residuals
    g_total, g_denom = cotangents[:2]    # (the rows' plain nll's: none)
    gx = (g_total * dx).astype(dx.dtype)
    gw = (g_total * dw).astype(w_like.dtype)
    n, d = dx.shape
    rows = _rows_a_chunk(n, chunk_rows)
    if n <= rows and rows * dx.dtype.itemsize < 3 * 4 * d:
        # A scan of one chunk is inlined into the step, and dW's one reader
        # is then the optimizer, so the chip's compiler fuses the chunk's
        # dW product into w_head's update. Its scheduler counts that
        # update's three float32 results [D, V] as new memory against the
        # chunk's logits [rows, V] that it frees. Where the logits are the
        # larger, the fusion stands right after dx and the update's traffic
        # rides under the product: nothing to add. Where they are the
        # smaller (rows < 6 D in bf16), it sinks to the end of the step and
        # the logits are held through the whole backward, or computed
        # again where memory is short (PERF.md, PR 59). There the two
        # gradients leave together: tied to dx, which the backward reads
        # at once, dW is made in the head, scaled and rounded in its
        # product's epilogue, and the logits die there.
        gx, gw = jax.lax.optimization_barrier((gx, gw))
    return (gx, gw,
            np.zeros(nll.shape, jax.dtypes.float0),     # targets: integers
            g_total * nll + g_denom)


chunked_xent.defvjp(_chunked_xent_fwd, _chunked_xent_bwd)


def _head_rows(x, cfg: GPTConfig):
    """What the head's matmul reads of the final hidden states: x, or, where
    the logits are divided by multipliers.logits, x over it (x W / c = (x /
    c) W: at a power of two, Granite's 8, no bit of x moves; the head's dW
    and, tied, the embedding's gradient are then of the scaled logits)."""
    scale = cfg.scales.logits
    return x if scale == 1.0 else x * (1.0 / scale)


def _head_operands(params, x, targets, cfg: GPTConfig):
    """head_xent's arguments as chunked_xent's: rows (`_head_rows`), the
    head's matrix in the model dtype (the embedding table's transpose if
    tied), targets and the mask that leaves negative targets out."""
    b, s, d = x.shape
    x = _head_rows(x, cfg)
    if cfg.tie_embeddings:
        w_head = params["embed"]["table"].astype(cfg.dtype).T
    else:
        w_head = params["lm_head"].astype(cfg.dtype)
    mask = (targets >= 0).astype(jnp.float32)
    return (x.reshape(b * s, d), w_head, targets.reshape(b * s),
            mask.reshape(b * s))


def head_xent(params, x, targets, cfg: GPTConfig):
    """x: the final hidden states [B, S, D] (after final_norm), targets
    [B, S] with negatives left out -> (sum of the next-token
    cross-entropies, how many). The LM-head matmul + softmax run chunked
    (chunked_xent) so the full fp32 logits tensor never exists in HBM."""
    with jax.named_scope("head"):
        return chunked_xent(*_head_operands(params, x, targets, cfg))


def head_xent_recompute(params, x, targets, cfg: GPTConfig):
    """head_xent over chunked_xent_recompute: the same loss to the bit, for
    a caller inside a differentiated scan."""
    with jax.named_scope("head"):
        return chunked_xent_recompute(
            *_head_operands(params, x, targets, cfg))


def _exit_log_p(params, h):
    """A looped stack's exit distribution: the passes' normed streams h
    [T, B, S, D] -> log p [T, B, S] float32, with lam_t = sigmoid(h_t . w +
    b) (`exit_gate`; the product a float32 multiply and sum over the row, no
    matmul of one column): p_t = lam_t prod_{j<t} (1 - lam_j) before the
    last pass, p_T = prod_{j<T} (1 - lam_j), the rest; as logarithms, from
    log_sigmoid of the score and of its negative, so that a token sure of
    its exit gives a finite log and p sums to one over the passes to
    float32's rounding."""
    gate = params["exit_gate"]

    @jax.checkpoint
    def scores(h, w, b):
        # (recomputed in the backward pass: kept, the float32 copy of the
        # passes' streams would be twice their bytes)
        return jnp.sum(h.astype(jnp.float32) * w[:, 0], axis=-1) + b

    with jax.named_scope("exit_gate"):
        score = scores(h, gate["w"], gate["b"])
        stay = jax.nn.log_sigmoid(-score)            # log (1 - lam_t)
        stayed = jnp.cumsum(stay, axis=0) - stay     # sum over j < t
        return jnp.concatenate(
            [jax.nn.log_sigmoid(score[:-1]) + stayed[:-1], stayed[-1:]])


def _looped_xent(params, h, targets, cfg: GPTConfig):
    """A looped stack's objective (`Loop`): the passes' normed streams h
    [T, B, S, D], targets [B, S] with negatives left out -> (the mean over
    the tokens of sum_t p_t xent_t - entropy_coef H(p), aux). Every pass
    goes through the one head under its tokens' weights p_t (chunked_xent's
    float `mask`, whose cotangent is the token's cross-entropy: the
    gradient reaches the gate through p as well as the stack through the
    cross-entropies), the passes' rows end to end in ONE call, so that the
    head's matrix has one gradient and the program one head; the same pass
    over the logits gives the rows' plain nll. aux: `xent` (the last pass's
    plain mean cross-entropy), `xent_pass_<t>`, `exit_p_<t>` (the mean of
    p_t over the tokens), `exit_entropy`, `exit_expected_passes` (the mean
    of sum_t t p_t), t from 1."""
    passes = h.shape[0]
    log_p = _exit_log_p(params, h)
    with jax.named_scope("exit_gate"):
        valid = (targets >= 0).astype(jnp.float32)
        p = jnp.exp(log_p)
        weights = p * valid
    with jax.named_scope("head"):
        rows, w_head, flat_targets, _ = _head_operands(
            params, h.reshape(-1, *h.shape[2:]),
            jnp.tile(targets, (passes, 1)), cfg)
        total, _, nll = chunked_xent(rows, w_head, flat_targets,
                                     weights.reshape(-1), plain=True)
    with jax.named_scope("exit_gate"):
        n = jnp.maximum(jnp.sum(valid), 1.0)
        entropy = -jnp.sum(jnp.sum(p * log_p, axis=0) * valid) / n
        loss = total / n - cfg.loop.entropy_coef * entropy
        xent = jnp.sum(nll.reshape(passes, -1) * valid.reshape(-1),
                       axis=1) / n
        exits = jnp.sum(weights, axis=(1, 2)) / n
        aux = {"xent": xent[-1], "exit_entropy": entropy,
               "exit_expected_passes": sum(
                   (t + 1) * exits[t] for t in range(passes))}
        for t in range(passes):
            aux[f"xent_pass_{t + 1}"] = xent[t]
            aux[f"exit_p_{t + 1}"] = exits[t]
    return loss, aux


def gpt_loss_and_aux(params, batch, cfg: GPTConfig, mesh=None,
                     act_sharding=None):
    """batch: {"tokens": [B, S+1]} -> (loss, aux): the mean next-token
    cross-entropy, plus under the softmax routing rule the router's two
    losses at the configuration's weights, plus the indexers' KL summed
    over the layers at index_loss_coef, plus, with a prediction module
    (cfg.mtp), loss_coef times the cross-entropy of the token two ahead
    through it and the same head (the mean over the positions that have
    one; "mtp_xent"); of a looped stack (cfg.loop) the cross-entropy's
    place is taken by `_looped_xent`'s objective, every pass's
    cross-entropy weighted a token by the exit gate, and aux gains its
    statistics; aux holds the cross-entropy alone
    ("xent": a looped stack's last pass's)
    and the router's statistics (the two losses unweighted, the largest
    expert's load over the mean, the share of the slots that fall to the
    held experts and of the layers whose bounded row space held them), for
    a step written with
    jax.value_and_grad(..., has_aux=True)."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    where = Setting(mesh, act_sharding)
    h, per_layer, block = _stack(params, inputs, cfg, where)
    if cfg.mtp is not None:
        g, module_stats = _prediction_module(params, h, targets, block, cfg,
                                             where)
        per_layer = per_layer + module_stats
    router = _layer_means(per_layer)
    if cfg.loop is not None:
        loss, aux = _looped_xent(params, h, targets, cfg)
    else:
        total, denom = head_xent(params, final_norm(params, h, cfg), targets,
                                 cfg)
        loss = xent = total / jnp.maximum(denom, 1.0)
        aux = {"xent": xent}
    if cfg.mtp is not None:
        # the token two ahead: the targets shifted once more, the last
        # position (which has none) left out
        ahead = jnp.concatenate(
            [targets[:, 1:], jnp.full_like(targets[:, :1], -1)], axis=1)
        total, denom = head_xent(params, g, ahead, cfg)
        aux["mtp_xent"] = total / jnp.maximum(denom, 1.0)
        loss = loss + cfg.mtp.loss_coef * aux["mtp_xent"]
    if "router_balance_loss" in router:
        loss = (loss
                + cfg.router_aux_loss_coef * router["router_balance_loss"]
                + cfg.router_z_loss_coef * router["router_z_loss"])
    if "index_kl" in router:
        # a loss of the layers' own: the mean over the layers (what the
        # statistics hold) times how many have an indexer is their sum
        indexed = sum("index" in layer.get("attn", ())
                      for layer in params["layers"])
        loss = loss + cfg.index_loss_coef * indexed * router["index_kl"]
    return loss, {**aux, **router}


def gpt_loss(params, batch, cfg: GPTConfig, mesh=None, act_sharding=None):
    """gpt_loss_and_aux's loss alone: what make_train_step differentiates."""
    return gpt_loss_and_aux(params, batch, cfg, mesh, act_sharding)[0]


def count_params(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
