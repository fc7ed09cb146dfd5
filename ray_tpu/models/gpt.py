"""GPT-style decoder LM, TPU-first (the flagship model family).

Pure-JAX pytree params (no framework wrapper) whose path names line up with
ray_tpu.parallel.sharding rules: `layers/<i>/attn/wq`, `mlp/w_up`,
`embed/table`, `lm_head`, `moe/...`. Design choices for the MXU/HBM:
bfloat16 activations + params with fp32 softmax/layernorm accumulation,
flash-attention Pallas kernel, optional ring attention (sequence sharded),
optional MoE (expert-parallel), per-layer jax.checkpoint (remat) for memory.

Capability parity target: the models RLlib/Train wrap in the reference are
torch modules; here the model is a (init, apply) pair compatible with pjit.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import flash_attention, mha_reference, ring_attention


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304           # GPT-2 vocab padded to a multiple of 128
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq: int = 1024
    dtype: Any = jnp.bfloat16
    rope_theta: float = 10000.0
    rmsnorm_eps: float = 1e-5
    # MoE: 0 = dense MLPs; >0 = that many experts with top-2 routing.
    n_experts: int = 0
    expert_top_k: int = 2
    remat: bool = True
    # Remat granularity: None -> "full" if remat else "none".
    #   "full": recompute the whole layer in backward (min HBM, max FLOPs)
    #   "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable —
    #           weight-matmul outputs saved, elementwise recomputed
    #   "none": save everything (max HBM, min FLOPs)
    remat_policy: Optional[str] = None
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
        if e > 0:
            layer["moe"] = {
                "router": _init_dense(k[4], (d, e), scale=0.02),
                "w_gate": _init_dense(k[5], (e, d, ff)),
                "w_up": _init_dense(k[6], (e, d, ff)),
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


def _rmsnorm(x, scale, eps):
    with jax.named_scope("norm"):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
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


def _flash_on_mesh(q, k, v, cfg: GPTConfig, mesh):
    """The flash kernel on [B, H, S, D]. On a TPU it is a Mosaic custom
    call, which GSPMD cannot partition, so under a mesh of more than one
    device it runs per shard: batch over 'data' x 'fsdp', heads over
    'tensor', each device attending its own (batch, head) slice with no
    collective."""
    flash = partial(flash_attention, causal=True)
    if mesh is None or mesh.size == 1:
        return flash(q, k, v)
    spec = P(("data", "fsdp"), "tensor", None, None)
    # check_vma off: pallas_call declares no varying axes for its outputs,
    # and the Pallas interpreter the CPU tests use fails the check inside.
    return shard_map(flash, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def _attention_block(layer, x, cfg: GPTConfig, positions, mesh):
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    dt = cfg.dtype

    def proj(w):
        return jnp.einsum("bsd,de->bse", x, w.astype(dt)).reshape(
            b, s, h, hd).transpose(0, 2, 1, 3)

    with jax.named_scope("attn_proj"):
        q = proj(layer["attn"]["wq"])
        k = proj(layer["attn"]["wk"])
        v = proj(layer["attn"]["wv"])
        q = _rope(q, cfg.rope_theta, positions)
        k = _rope(k, cfg.rope_theta, positions)
    with jax.named_scope("attn_core"):
        if cfg.attention == "ring":
            o = ring_attention(q, k, v, mesh=mesh, causal=True)
        elif cfg.attention == "reference":
            o = mha_reference(q, k, v, causal=True)
        else:
            o = _flash_on_mesh(q, k, v, cfg, mesh)
    with jax.named_scope("attn_out"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
        return jnp.einsum("bsd,de->bse", o, layer["attn"]["wo"].astype(dt))


def _mlp_block(layer, x, cfg: GPTConfig):
    dt = cfg.dtype
    m = layer["mlp"]
    gate = jnp.einsum("bsd,df->bsf", x, m["w_gate"].astype(dt))
    up = jnp.einsum("bsd,df->bsf", x, m["w_up"].astype(dt))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                      m["w_down"].astype(dt))


def _moe_block(layer, x, cfg: GPTConfig):
    """Top-k routed MoE with dense dispatch (einsum over one-hot combine
    weights) — compiles to static shapes; the 'expert' mesh axis shards the
    expert dimension of w_gate/w_up/w_down (expert parallelism, net-new vs
    the reference per SURVEY.md §2.5)."""
    dt = cfg.dtype
    m = layer["moe"]
    e = cfg.n_experts
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        m["router"].astype(jnp.float32))
    weights, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                 cfg.expert_top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)       # [b,s,k,e]
    combine = jnp.einsum("bsk,bske->bse", weights, onehot)   # [b,s,e]
    gate = jnp.einsum("bsd,edf->bsef", x, m["w_gate"].astype(dt))
    up = jnp.einsum("bsd,edf->bsef", x, m["w_up"].astype(dt))
    act = jax.nn.silu(gate) * up
    out = jnp.einsum("bsef,efd->bsed", act, m["w_down"].astype(dt))
    y = jnp.einsum("bsed,bse->bsd", out.astype(jnp.float32), combine)
    # Load-balancing auxiliary loss (Switch-style).
    density = jnp.mean(onehot[:, :, 0, :], axis=(0, 1))
    router_prob = jnp.mean(jax.nn.softmax(logits, -1), axis=(0, 1))
    aux = e * jnp.sum(density * router_prob)
    return y.astype(dt), aux


def gpt_forward(params, tokens, cfg: GPTConfig, mesh=None, act_sharding=None):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (cfg.dtype)."""
    dt = cfg.dtype
    x, aux_total = gpt_backbone(params, tokens, cfg, mesh, act_sharding)
    with jax.named_scope("head"):
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["embed"]["table"].astype(dt))
        else:
            logits = jnp.einsum("bsd,dv->bsv", x,
                                params["lm_head"].astype(dt))
    return logits, aux_total


def gpt_backbone(params, tokens, cfg: GPTConfig, mesh=None, act_sharding=None):
    """tokens: [B, S] -> final hidden states [B, S, D] (pre-LM-head).

    act_sharding (a NamedSharding for [B, S, D] activations, usually
    ``strategy.activation_sharding(mesh)``) pins the residual stream at
    layer boundaries so GSPMD never back-propagates weight shardings onto
    activation gradients (the "involuntary full rematerialization" failure
    mode on 2D tp_fsdp meshes).
    """
    b, s = tokens.shape
    dt = cfg.dtype

    def _c(x):
        if act_sharding is None:
            return x
        return jax.lax.with_sharding_constraint(x, act_sharding)

    with jax.named_scope("embed"):
        x = _c(params["embed"]["table"].astype(dt)[tokens])
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    aux_total = 0.0

    def layer_fn(x, layer):
        h = _c(x + _attention_block(layer, _rmsnorm(
            x, layer["ln1"]["scale"], cfg.rmsnorm_eps), cfg, positions, mesh))
        normed = _rmsnorm(h, layer["ln2"]["scale"], cfg.rmsnorm_eps)
        if cfg.n_experts > 0:
            with jax.named_scope("moe"):
                delta, aux = _moe_block(layer, normed, cfg)
        else:
            with jax.named_scope("mlp"):
                delta, aux = _mlp_block(layer, normed, cfg), 0.0
        return _c(h + delta), aux

    policy = cfg.remat_policy or ("full" if cfg.remat else "none")
    if policy == "full":
        layer_fn = jax.checkpoint(layer_fn)
    elif policy == "dots":
        layer_fn = jax.checkpoint(
            layer_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif policy != "none":
        raise ValueError(f"unknown remat_policy {policy!r} "
                         "(expected 'full' | 'dots' | 'none')")
    for layer in params["layers"]:
        x, aux = layer_fn(x, layer)
        aux_total = aux_total + aux
    return _rmsnorm(x, params["final_norm"]["scale"], cfg.rmsnorm_eps), \
        aux_total


def chunked_xent(x, w_head, targets, mask, chunk_rows: int = 16384):
    """Next-token cross-entropy WITHOUT materializing full [N, vocab] fp32
    logits (12.8 GB at bs=64/seq=1024/vocab=50k — an HBM-capacity bug for
    any capacity-size batch). Rows are processed in chunks under
    jax.checkpoint, so the backward recomputes each chunk's logits instead
    of saving them. TPU-native analogue of fused linear+cross-entropy.

    x: [N, D] (model dtype), w_head: [D, V], targets: [N] int32,
    mask: [N] fp32. Returns (sum_nll, sum_mask).
    """
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
    xc = x.reshape(n_chunks, chunk_rows, d)
    tc = targets.reshape(n_chunks, chunk_rows)
    mc = mask.reshape(n_chunks, chunk_rows)

    @jax.checkpoint
    def body(carry, args):
        xk, tk, mk = args
        logits = (xk @ w_head).astype(jnp.float32)       # [chunk, V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tk[:, None], axis=-1)[:, 0]
        nll = lse - picked
        return (carry[0] + jnp.sum(nll * mk), carry[1] + jnp.sum(mk)), None

    (total, denom), _ = jax.lax.scan(body, (0.0, 0.0), (xc, tc, mc))
    return total, denom


def gpt_loss(params, batch, cfg: GPTConfig, mesh=None, act_sharding=None):
    """batch: {"tokens": [B, S+1]} -> mean next-token cross-entropy.

    The LM-head matmul + softmax run chunked (chunked_xent) so the full
    fp32 logits tensor never exists in HBM.
    """
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, aux = gpt_backbone(params, inputs, cfg, mesh, act_sharding)
    b, s, d = x.shape
    dt = cfg.dtype
    with jax.named_scope("head"):
        if cfg.tie_embeddings:
            w_head = params["embed"]["table"].astype(dt).T
        else:
            w_head = params["lm_head"].astype(dt)
        mask = (targets >= 0).astype(jnp.float32)
        total, denom = chunked_xent(x.reshape(b * s, d), w_head,
                                    targets.reshape(b * s),
                                    mask.reshape(b * s))
    loss = total / jnp.maximum(denom, 1.0)
    if cfg.n_experts > 0:
        loss = loss + 0.01 * aux / cfg.n_layers
    return loss


def count_params(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
