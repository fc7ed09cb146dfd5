"""GPT-style decoder LM, TPU-first (the flagship model family).

Pure-JAX pytree params (no framework wrapper) whose path names line up with
ray_tpu.parallel.sharding rules: `layers/<i>/attn/wq`, `mlp/w_up`,
`embed/table`, `lm_head`, `moe/...`. Design choices for the MXU/HBM:
bfloat16 activations + params with fp32 softmax/layernorm accumulation,
flash-attention Pallas kernel, optional ring attention (sequence sharded),
optional sparse experts (top-k routing with real dispatch: ops/moe.py),
per-layer jax.checkpoint (remat) for memory.

Capability parity target: the models RLlib/Train wrap in the reference are
torch modules; here the model is a (init, apply) pair compatible with pjit.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import moe
from ray_tpu.ops.attention import flash_attention, mha_reference, ring_attention
from ray_tpu.ops.rope import rope_split, rope_table


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304           # GPT-2 vocab padded to a multiple of 128
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072                  # the MLP's width; of ONE expert's, if sparse
    max_seq: int = 1024
    dtype: Any = jnp.bfloat16
    rope_theta: float = 10000.0
    rmsnorm_eps: float = 1e-5
    # RMSNorm over the whole q and k projections, before the head split.
    qk_norm: bool = False
    # 0 = a dense MLP a layer; >0 = that many experts in its place, each
    # token through the expert_top_k the router gives the most probability
    # (used as they come out of the softmax, not renormalised). The loss
    # adds the load-balancing and the router z-loss at these weights.
    n_experts: int = 0
    expert_top_k: int = 2
    router_aux_loss_coef: float = 0.01
    router_z_loss_coef: float = 0.001
    # "full": recompute the whole layer in backward (min HBM, max FLOPs)
    # "none": save everything (max HBM, min FLOPs)
    remat_policy: str = "full"
    attention: str = "flash"          # flash | reference | ring
    tie_embeddings: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

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


def _init_dense(key, shape, scale=None, dtype=jnp.float32):
    if scale is None:
        scale = 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def gpt_init(key, cfg: GPTConfig) -> Dict:
    """Build the parameter pytree (fp32 master weights)."""
    keys = jax.random.split(key, cfg.n_layers + 3)
    params: Dict[str, Any] = {
        "embed": {"table": _init_dense(keys[0], (cfg.vocab_size, cfg.d_model),
                                       scale=0.02)},
        "final_norm": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _init_dense(keys[1], (cfg.d_model, cfg.vocab_size))
    layers = []
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i + 2], 8)
        layer = {
            "ln1": {"scale": jnp.ones((d,), jnp.float32)},
            "ln2": {"scale": jnp.ones((d,), jnp.float32)},
            "attn": {
                "wq": _init_dense(k[0], (d, d)),
                "wk": _init_dense(k[1], (d, d)),
                "wv": _init_dense(k[2], (d, d)),
                "wo": _init_dense(k[3], (d, d),
                                  scale=1.0 / math.sqrt(2 * cfg.n_layers * d)),
            },
        }
        if cfg.qk_norm:
            layer["attn"]["q_norm"] = {"scale": jnp.ones((d,), jnp.float32)}
            layer["attn"]["k_norm"] = {"scale": jnp.ones((d,), jnp.float32)}
        if e > 0:
            # stacked [e, fan-in, fan-out]: the scale is the fan-in's
            layer["moe"] = {
                "router": _init_dense(k[4], (d, e), scale=0.02),
                "w_gate": _init_dense(k[5], (e, d, ff),
                                      scale=1.0 / math.sqrt(d)),
                "w_up": _init_dense(k[6], (e, d, ff),
                                    scale=1.0 / math.sqrt(d)),
                "w_down": _init_dense(k[7], (e, ff, d),
                                      scale=1.0 / math.sqrt(2 * cfg.n_layers * ff)),
            }
        else:
            layer["mlp"] = {
                "w_gate": _init_dense(k[5], (d, ff)),
                "w_up": _init_dense(k[6], (d, ff)),
                "w_down": _init_dense(k[7], (ff, d),
                                      scale=1.0 / math.sqrt(2 * cfg.n_layers * ff)),
            }
        layers.append(layer)
    params["layers"] = layers
    return params


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


def _rope(x, theta: float, positions):
    """Rotary position embeddings; x: [B, H, S, D]."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # [B,S,half]
    cos = jnp.cos(angles)[:, None, :, :]
    sin = jnp.sin(angles)[:, None, :, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _per_shard(fn, mesh, in_dims, out_dims):
    """fn, entered as a Mosaic kernel call has to be. On a TPU the Pallas
    kernels are Mosaic custom calls, which GSPMD cannot partition, so under
    a mesh of more than one device they run per shard, inside a shard_map:
    batch over 'data' x 'fsdp', whole heads over 'tensor', anything else
    handed whole, each device on its own slice with no collective. in_dims
    (one tuple an operand) and out_dims say which dimension is which:
    "batch", "heads" or None. With no mesh (one device, or a caller inside
    a shard_map of its own) nothing is wrapped."""
    if mesh is None or mesh.size == 1:
        return fn
    axes = {"batch": ("data", "fsdp"), "heads": "tensor", None: None}

    def spec(dims):
        return P(*(axes[d] for d in dims))
    # check_vma off: pallas_call declares no varying axes for its outputs,
    # and the Pallas interpreter the CPU tests use fails the check inside.
    return shard_map(fn, mesh=mesh, in_specs=tuple(map(spec, in_dims)),
                     out_specs=spec(out_dims), check_vma=False)


def _flash_on_mesh(q, k, v, table, cfg: GPTConfig, mesh):
    """The projections' outputs [B, S, H*D] through the head split, the
    rotation (ops/rope.py: one pass a tensor, straight into the kernels'
    [B, H, S, D]) and the flash kernel, per shard: the H*D columns are
    whole heads, each device attends its own (batch, head) slice."""
    def split_and_attend(q, k, v, *table):
        with jax.named_scope("attn_proj"):
            q = rope_split(q, cfg.head_dim, table)
            k = rope_split(k, cfg.head_dim, table)
            v = rope_split(v, cfg.head_dim)
        with jax.named_scope("attn_core"):
            return flash_attention(q, k, v, causal=True)

    columns = ("batch", None, "heads")
    return _per_shard(split_and_attend, mesh,
                      (columns,) * 3 + ((),) * len(table),
                      ("batch", "heads", None, None))(q, k, v, *table)


def _attention_block(layer, x, cfg: GPTConfig, table, where: Setting):
    """table: rope_table(S, head_dim, theta), built once a step by the
    caller (layer_fn, outside the remat). The flash path alone reads it:
    'reference' and 'ring' keep the jnp `_rope` on [B, H, S, D] (the
    oracle, and ring's sequence shards need their global positions)."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    dt = cfg.dtype

    def proj(w, norm=None):
        y = jnp.einsum("bsd,de->bse", x, w.astype(dt))
        if norm is not None:
            y = _rmsnorm(y, norm["scale"], cfg.rmsnorm_eps, where.psum)
        return y

    def heads(y):
        return y.reshape(b, s, -1, hd).transpose(0, 2, 1, 3)

    with jax.named_scope("attn_proj"):
        q = proj(layer["attn"]["wq"], layer["attn"].get("q_norm"))
        k = proj(layer["attn"]["wk"], layer["attn"].get("k_norm"))
        v = proj(layer["attn"]["wv"])
    if cfg.attention not in ("ring", "reference"):
        o = _flash_on_mesh(q, k, v, table, cfg, where.mesh)
    else:
        with jax.named_scope("attn_proj"):
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
            q = _rope(heads(q), cfg.rope_theta, positions)
            k = _rope(heads(k), cfg.rope_theta, positions)
            v = heads(v)
        with jax.named_scope("attn_core"):
            if cfg.attention == "ring":
                o = ring_attention(q, k, v, mesh=where.mesh, causal=True)
            else:
                o = mha_reference(q, k, v, causal=True)
    with jax.named_scope("attn_out"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return where.psum(
            jnp.einsum("bsd,de->bse", o, layer["attn"]["wo"].astype(dt)))


def _mlp_block(layer, x, cfg: GPTConfig, where: Setting):
    dt = cfg.dtype
    m = layer["mlp"]
    gate = jnp.einsum("bsd,df->bsf", x, m["w_gate"].astype(dt))
    up = jnp.einsum("bsd,df->bsf", x, m["w_up"].astype(dt))
    return where.psum(jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                                 m["w_down"].astype(dt)))


def _route(m, x, cfg: GPTConfig):
    """The router, in float32: probabilities over the experts, each token's
    expert_top_k largest as they come (not renormalised), and the layer's
    routing statistics: the load-balancing loss E x sum_e f_e P_e (f_e the
    share of tokens that chose e among ALL their k choices, P_e the mean
    probability of e), the z-loss mean(logsumexp(logits)^2), and the
    largest expert's load over the mean load."""
    e, k = cfg.n_experts, cfg.expert_top_k
    # HIGHEST: at the default precision a TPU rounds a float32 matmul's
    # operands to bfloat16, and near-tied experts then swap
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        m["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, k)
    load = jnp.mean(jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32),
                            axis=2), axis=(0, 1))
    stats = {
        "router_balance_loss": e * jnp.sum(load * jnp.mean(probs, axis=(0, 1))),
        "router_z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "expert_load_max_over_mean": jnp.max(load) * e / k,
    }
    return weights, idx, stats


def _experts(x, weights, idx, w_gate, w_up, w_down):
    """x [b, s, d] through each token's chosen experts (ops/moe.py): rows
    ordered by expert, three grouped matmuls with SwiGLU between, weighted
    return. The gathers either side are the layer's sparsity, not its
    arithmetic: scope `moe_route`."""
    b, s, d = x.shape
    e = w_gate.shape[0]
    with jax.named_scope("moe_route"):
        idx = idx.reshape(b * s, -1)
        plan = moe.plan_dispatch(
            idx, e, moe.tile_rows(idx.size, e, x.dtype))
        rows = moe.dispatch(x.reshape(b * s, d), plan)
    gate = moe.grouped_matmul(rows, w_gate, plan)
    up = moe.grouped_matmul(rows, w_up, plan)
    out = moe.grouped_matmul(jax.nn.silu(gate) * up, w_down, plan)
    with jax.named_scope("moe_route"):
        y = moe.combine(out, weights.reshape(b * s, -1), plan)
    return y.reshape(b, s, d)


def _moe_block(layer, x, cfg: GPTConfig, mesh):
    """Sparse experts in the MLP's place: y = sum over a token's top-k of
    p_e x down_e(silu(gate_e x) * up_e x). No capacity and no dropped
    token: every token-slot is computed, by its own expert only. The
    grouped matmuls run per shard (`_per_shard`): each device dispatches
    its own tokens to all the experts, whose matrices it is handed whole;
    the router and its losses stay outside, over the whole batch."""
    dt = cfg.dtype
    m = layer["moe"]
    with jax.named_scope("moe_route"):
        weights, idx, stats = _route(m, x, cfg)
    matrices = [m[name].astype(dt) for name in ("w_gate", "w_up", "w_down")]
    tokens = ("batch", None, None)
    y = _per_shard(_experts, mesh, (tokens,) * 3 + ((),) * 3, tokens)(
        x, weights, idx, *matrices)
    return y, stats


def layer_fn(cfg: GPTConfig, seq: int, where: Setting):
    """(x [B, seq, D], one layer's parameters) -> (x, the router's
    statistics: _route's dict for a sparse layer, {} for a dense one). The
    one transformer block, rematted as cfg.remat_policy says, for whoever
    walks the layers: gpt_backbone loops over their list, a stage of
    parallel/pipeline.py scans over stacked ones."""
    # once a step, not once a layer and recompute: outside the remat
    with jax.named_scope("attn_proj"):
        table = rope_table(seq, cfg.head_dim, cfg.rope_theta)

    def block(x, layer):
        h = where.pin(x + _attention_block(layer, _rmsnorm(
            x, layer["ln1"]["scale"], cfg.rmsnorm_eps), cfg, table, where))
        normed = _rmsnorm(h, layer["ln2"]["scale"], cfg.rmsnorm_eps)
        if cfg.n_experts > 0:
            with jax.named_scope("moe"):
                delta, stats = _moe_block(layer, normed, cfg, where.mesh)
        else:
            with jax.named_scope("mlp"):
                delta, stats = _mlp_block(layer, normed, cfg, where), {}
        return where.pin(h + delta), stats

    if cfg.remat_policy == "full":
        return jax.checkpoint(block)
    if cfg.remat_policy != "none":
        raise ValueError(f"unknown remat_policy {cfg.remat_policy!r} "
                         "(expected 'full' | 'none')")
    return block


def final_norm(params, x, cfg: GPTConfig):
    """The last layer's output -> what the head reads."""
    return _rmsnorm(x, params["final_norm"]["scale"], cfg.rmsnorm_eps)


def gpt_forward(params, tokens, cfg: GPTConfig, mesh=None, act_sharding=None):
    """tokens: [B, S] int32 -> (logits [B, S, vocab] (cfg.dtype), the
    router's statistics as gpt_backbone gives them)."""
    dt = cfg.dtype
    x, router = gpt_backbone(params, tokens, cfg, mesh, act_sharding)
    with jax.named_scope("head"):
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["embed"]["table"].astype(dt))
        else:
            logits = jnp.einsum("bsd,dv->bsv", x,
                                params["lm_head"].astype(dt))
    return logits, router


def gpt_backbone(params, tokens, cfg: GPTConfig, mesh=None, act_sharding=None):
    """tokens: [B, S] -> (final hidden states [B, S, D] (pre-LM-head), the
    router's statistics averaged over the layers: _route's dict for a
    sparse model, {} for a dense one).

    act_sharding (a NamedSharding for [B, S, D] activations, usually
    ``strategy.activation_sharding(mesh)``) pins the residual stream at
    layer boundaries so GSPMD never back-propagates weight shardings onto
    activation gradients (the "involuntary full rematerialization" failure
    mode on 2D tp_fsdp meshes).
    """
    where = Setting(mesh, act_sharding)
    with jax.named_scope("embed"):
        x = where.pin(params["embed"]["table"].astype(cfg.dtype)[tokens])
    layer = layer_fn(cfg, tokens.shape[1], where)
    per_layer = []
    for layer_params in params["layers"]:
        x, stats = layer(x, layer_params)
        per_layer.append(stats)
    router = jax.tree_util.tree_map(
        lambda *layers: sum(layers) / len(layers), *per_layer)
    return final_norm(params, x, cfg), router


def _xent_chunks(x, targets, mask, chunk_rows):
    """Rows [N, ...] -> chunks [n_chunks, chunk_rows, ...], padded with
    masked-out rows."""
    n, d = x.shape
    # Never chunk coarser than the batch itself: padding a small batch up
    # to a full 16k-row chunk would both waste LM-head FLOPs and raise the
    # HBM peak the chunking exists to cut.
    chunk_rows = min(chunk_rows, max(128, n))
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


def chunked_xent_recompute(x, w_head, targets, mask, chunk_rows: int = 16384):
    """chunked_xent differentiated by autodiff: each chunk's body is under
    jax.checkpoint, so the backward computes the chunk's logits and their
    logsumexp a second time (four vocabulary matmuls a chunk) and a
    differentiated call saves nothing but its inputs. For a caller that is
    differentiated INSIDE a scan (parallel/pipeline.py's last rank, once a
    tick): there chunked_xent's residuals would be saved once an iteration,
    w_head's fp32 gradient among them."""
    xc, tc, mc = _xent_chunks(x, targets, mask, chunk_rows)

    @jax.checkpoint
    def body(carry, args):
        xk, tk, mk = args
        _, _, nll = _chunk_nll(xk, w_head, tk)
        return (carry[0] + jnp.sum(nll * mk), carry[1] + jnp.sum(mk)), None

    (total, denom), _ = jax.lax.scan(body, (0.0, 0.0), (xc, tc, mc))
    return total, denom


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def chunked_xent(x, w_head, targets, mask, chunk_rows: int = 16384):
    """Next-token cross-entropy WITHOUT materializing full [N, vocab] fp32
    logits (12.8 GB at bs=64/seq=1024/vocab=50k — an HBM-capacity bug for
    any capacity-size batch): rows go through the head in chunks of a scan.
    TPU-native analogue of fused linear+cross-entropy.

    x: [N, D] (model dtype), w_head: [D, V], targets: [N] int32,
    mask: [N] fp32. Returns (sum_nll, sum_mask).

    Differentiated, the same scan takes the gradient while the chunk's
    logits are there (_chunked_xent_fwd): p = (softmax - onehot) * mask
    rounded to the model dtype, where autodiff rounds d logits, then
    dx_k = p @ w_head.T and dW += xk.T @ p, both accumulated in fp32. That
    is three vocabulary matmuls a chunk and one softmax; no jax.checkpoint,
    no second logits matmul. The residuals are dx [N, D] (x's dtype),
    dW [D, V] (fp32) and the rows' nll [N], and the backward rule scales
    them by the cotangents, so nothing of a chunk outlives its iteration.
    Evaluated only, no gradient is computed. Inside a differentiated scan
    use chunked_xent_recompute (its docstring says why)."""
    return chunked_xent_recompute(x, w_head, targets, mask, chunk_rows)


def _chunked_xent_fwd(x, w_head, targets, mask, chunk_rows):
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
    # (a residual has to be an array: w_head's dtype rides on an empty one)
    return (total, denom), (dx.reshape(-1, x.shape[1])[:n], dw,
                            nll.reshape(-1)[:n],
                            jnp.zeros((0,), w_head.dtype))


def _chunked_xent_bwd(chunk_rows, residuals, cotangents):
    dx, dw, nll, w_like = residuals
    g_total, g_denom = cotangents
    return ((g_total * dx).astype(dx.dtype),
            (g_total * dw).astype(w_like.dtype),
            np.zeros(nll.shape, jax.dtypes.float0),     # targets: integers
            g_total * nll + g_denom)


chunked_xent.defvjp(_chunked_xent_fwd, _chunked_xent_bwd)


def _head_operands(params, x, targets, cfg: GPTConfig):
    """head_xent's arguments as chunked_xent's: rows, the head's matrix in
    the model dtype (the embedding table's transpose if tied), targets and
    the mask that leaves negative targets out."""
    b, s, d = x.shape
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


def gpt_loss_and_aux(params, batch, cfg: GPTConfig, mesh=None,
                     act_sharding=None):
    """batch: {"tokens": [B, S+1]} -> (loss, aux): the mean next-token
    cross-entropy, plus for a sparse model the router's two losses at the
    configuration's weights; aux holds the cross-entropy alone ("xent")
    and the router's statistics (the two losses unweighted, the largest
    expert's load over the mean), for a step written with
    jax.value_and_grad(..., has_aux=True)."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, router = gpt_backbone(params, inputs, cfg, mesh, act_sharding)
    total, denom = head_xent(params, x, targets, cfg)
    loss = xent = total / jnp.maximum(denom, 1.0)
    if cfg.n_experts > 0:
        loss = (xent
                + cfg.router_aux_loss_coef * router["router_balance_loss"]
                + cfg.router_z_loss_coef * router["router_z_loss"])
    return loss, {"xent": xent, **router}


def gpt_loss(params, batch, cfg: GPTConfig, mesh=None, act_sharding=None):
    """gpt_loss_and_aux's loss alone: what make_train_step differentiates."""
    return gpt_loss_and_aux(params, batch, cfg, mesh, act_sharding)[0]


def count_params(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
