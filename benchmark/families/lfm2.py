"""The family `lfm2`: the decoder stack of LFM2-24B-A2B (the `lfm2_moe` model
code its config.json names): two kinds of layer in one stack, a gated short
convolution in most and grouped-query attention with a norm a head in the
rest (`layer_types`), a dense SwiGLU in the leading layers and sparse
experts routed by sigmoid scores with a selection bias in the others. What a
family module holds is listed in gpt_dense.py.

The layer, as the reference below writes it out. x is [S, d]; RMSNorm with
`norm_eps` everywhere; no bias anywhere (`conv_bias` false). Layer l:
  h = x + Op_l(norm1(x));   y = h + FF_l(norm2(h))
Op_l, `layer_types[l]` "conv" (L = `conv_L_cache` taps):
  [B | C | X] = n W_in, three chunks of d columns in that order
  u = B * X;  c_t = sum_{j < L} w[:, j] * u_{t - (L - 1) + j}, u zero before
        the sequence's start: one causal filter a channel, a
        cross-correlation (Conv1d, groups = d, left padding L - 1)
  Op = (C * c) W_out.  No activation: both gates are products.
Op_l, "full_attention" (H query heads on Hkv key/value heads, D = d / H):
  q = n Wq as H heads, k = n Wk and v = n Wv as Hkv heads
  q, k <- RMSNorm over each head's D columns (one scale of D for q, one for
        k, shared by the heads), THEN rotated as halves, theta `rope_theta`
  query head h reads key/value head h // (H / Hkv); causal softmax of
        q k^T / sqrt(D) in float32;  Op = concat_h(P_h v_{h // (H/Hkv)}) Wo
FF_l, l < `num_dense_layers`: Wdown(silu(Wgate m) * Wup m),
  `intermediate_size` wide. Otherwise:
  s = sigmoid(m Wr) over ALL the experts, float32
  chosen: the `num_experts_per_tok` largest s_e + b_e (`use_expert_bias`)
  w_e = s_e / (sum of the chosen s + 1e-6) (`norm_topk_prob`), times
        `routed_scaling_factor`
  FF = sum over chosen e of w_e down_e(silu(gate_e m) * up_e m); no shared
        expert; cross-entropy alone (the model code has no router loss)
Final RMSNorm, then the head, tied to the embedding.

The chip's share (`share` in the configuration file; model-configs guide,
section 4): the file's `num_experts` and `vocab_size` are what is HELD here,
experts rank * held .. + held - 1 of `share.num_experts`. The router keeps
its published width and its experts a token; the sum above runs over the
chosen experts that are held, and what the others would have added is left
out, here and in the program alike. A file without `share` is the whole
layer (tests/test_conv_gqa.py adds the shares up to it).

Departures, each also in the configuration file: the program holds W_in as
its three chunks stacked ([3, d, d]); the selection bias is a seeded
constant; the router's matmul is float32 in program and reference alike;
seeded random weights.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict, Tuple

# queries a block of the reference's attention: [heads, block, S] float32
# scores are 1 GB at 32 heads and 8192 positions
QUERY_BLOCK = 1024
RENORMALISE_EPS = 1e-6          # the model code's, added to the chosen sum


def share(config: Dict[str, Any]) -> Tuple[int, int, int]:
    """(first expert held here, how many, of how many experts)."""
    held = config["num_experts"]
    s = config.get("share")
    if s is None:
        return 0, held, held
    return s["rank"] * held, held, s["num_experts"]


def layer_kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    """`layer_types` in GPTConfig's words, one a layer."""
    names = {"conv": "conv", "full_attention": "attention"}
    kinds = tuple(names[t] for t in config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError(f"{len(kinds)} layer_types for "
                         f"{config['num_hidden_layers']} layers")
    return kinds


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    if config["conv_bias"]:
        raise ValueError("models/gpt.py's short convolution has no bias, the "
                         "configuration has conv_bias true")
    if config["rope_parameters"]["rope_type"] != "default":
        raise ValueError("models/gpt.py rotates with rope_type 'default' only")
    first, held, of = share(config)
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "qk_head_norm": True,
        "layer_kinds": layer_kinds(config),
        "conv_filter": config["conv_L_cache"],
        "d_ff": config["moe_intermediate_size"],    # the width of ONE expert
        "max_seq": config["max_position_embeddings"],
        "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        "rmsnorm_eps": float(config["norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "n_experts": of,
        "expert_top_k": config["num_experts_per_tok"],
        "experts_held": None if held == of else (first, held),
        "router_score": "sigmoid",
        "router_bias_scale": (float(config["selection_bias_init_std"])
                              if config["use_expert_bias"] else 0.0),
        "router_renormalise": bool(config["norm_topk_prob"]),
        "router_renormalise_eps": RENORMALISE_EPS,
        "router_scale": float(config["routed_scaling_factor"]),
        "dense_layers": config["num_dense_layers"],
        "dense_d_ff": config["intermediate_size"],
    }


def _train_config(config: Dict[str, Any]):
    """The GPTConfig the step is built from (bf16 activations, flash
    attention, the grouped-matmul kernels, remat of the whole layer)."""
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(**gpt_config_kwargs(config), attention="flash",
                     remat_policy="full")


def program(config: Dict[str, Any], serving: bool = False):
    """The system under test at these sizes, as gpt_dense.program: the same
    models/gpt.py, told the kinds of its layers, the key/value heads, the
    norm a head, the routing rule and the share of the experts."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init, gpt_loss

    if serving:
        cfg = GPTConfig(**gpt_config_kwargs(config), attention="flash")
    else:
        cfg = _train_config(config)

    def init(key):
        if not serving:
            return gpt_init(key, cfg)
        return jax.tree_util.tree_map(
            lambda x: x.astype(cfg.dtype), gpt_init(key, cfg))

    def loss(params, batch, mesh, act_sharding):
        return gpt_loss(params, batch, cfg, mesh=mesh,
                        act_sharding=act_sharding)

    def score(params, tokens):
        logits, _ = gpt_forward(params, tokens, cfg)
        logits = logits[:, :-1].astype(jnp.float32)
        picked = jnp.take_along_axis(
            logits, tokens[:, 1:, None], axis=-1)[..., 0]
        return picked - jax.nn.logsumexp(logits, axis=-1)

    return SimpleNamespace(init=init, loss=loss, score=score)


# ---------------------------------------------------------------------------
# The plain reference: float32, nothing of ray_tpu in it. One sequence at a
# time (lax.map over the batch), attention a block of queries at a time, the
# routed sum one expert at a time and the filter as L shifted products. Call
# it under jax.default_matmul_precision("highest"). (program_logprob_gap,
# below the reference, is not part of it: it runs the program, to hold it to
# the reference where the harness cannot.)
# ---------------------------------------------------------------------------

def _norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _swiglu(m, h, f32):
    import jax
    return (jax.nn.silu(h @ m["w_gate"].astype(f32))
            * (h @ m["w_up"].astype(f32))) @ m["w_down"].astype(f32)


def _filtered(u, w):
    """u [S, d], w [d, L] -> c [S, d], c_t = sum_j w[:, j] u_{t-(L-1)+j}:
    L products of u shifted down the sequence, zeros shifted in."""
    import jax.numpy as jnp
    taps = w.shape[1]
    c = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j                      # u_{t - back}
        shifted = jnp.concatenate(
            [jnp.zeros_like(u[:back]), u[:u.shape[0] - back]], axis=0)
        c = c + w[:, j] * shifted
    return c


def _gated(b, c, x, w):
    return c * _filtered(b * x, w)


def reference_conv(m, n, config: Dict[str, Any]):
    """n [S, d], a conv layer's normed input -> what the operator adds."""
    import jax.numpy as jnp
    f32 = jnp.float32
    # the program holds the published [d, 3d] as its chunks [3, d, d]
    w_in = jnp.concatenate(list(m["w_in"].astype(f32)), axis=1)
    b, c, x = jnp.split(n @ w_in, 3, axis=-1)
    return _gated(b, c, x, m["filter"].astype(f32)) @ m["w_out"].astype(f32)


def _rotated(t, cos, sin):
    """t [S, heads, D] rotated as halves."""
    import jax.numpy as jnp
    half = t.shape[-1] // 2
    a, c = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - c * sin, a * sin + c * cos], -1)


def _norm_heads(t, scale, eps):
    """t [S, heads, D]: RMSNorm over each head's D columns, one scale."""
    return _norm(t, scale, eps)


def _kv_head_of(heads: int, kv_heads: int):
    """The key/value head each query head reads."""
    import jax.numpy as jnp
    return jnp.arange(heads) // (heads // kv_heads)


def reference_attention(a, n, config: Dict[str, Any]):
    """n [S, d], an attention layer's normed input -> what attention adds."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim = config["hidden_size"] // heads
    eps = float(config["norm_eps"])
    theta = float(config["rope_parameters"]["rope_theta"])
    s = n.shape[0]
    angles = (jnp.arange(s, dtype=f32)[:, None]
              * theta ** (-jnp.arange(dim // 2, dtype=f32) / (dim // 2)))
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]

    q = (n @ a["wq"].astype(f32)).reshape(s, heads, dim)
    k = (n @ a["wk"].astype(f32)).reshape(s, kv_heads, dim)
    v = (n @ a["wv"].astype(f32)).reshape(s, kv_heads, dim)
    q = _rotated(_norm_heads(q, a["q_head_norm"]["scale"], eps), cos, sin)
    k = _rotated(_norm_heads(k, a["k_head_norm"]["scale"], eps), cos, sin)
    # each query head's own key/value head, written out
    reads = _kv_head_of(heads, kv_heads)
    k, v = k[:, reads], v[:, reads]

    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} positions are not whole blocks of {block}")
    at = jnp.arange(s)

    def queries(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dim)
        seen = (start + jnp.arange(block))[:, None] >= at[None, :]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", weights, v).reshape(
            block, heads * dim)
    mixed = jax.lax.map(queries, jnp.arange(0, s, block)).reshape(
        s, heads * dim)
    return mixed @ a["wo"].astype(f32)


def reference_routing(m, h, config: Dict[str, Any]):
    """h [S, d] -> [S, E] float32: w_e where expert e is among the token's
    chosen, 0 elsewhere, over ALL the experts."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    n_all = share(config)[2]
    scores = jax.nn.sigmoid(h @ m["router"].astype(f32))
    biased = scores
    if config["use_expert_bias"]:
        biased = scores + m["router_bias"].astype(f32)
    _, chosen = jax.lax.top_k(biased, config["num_experts_per_tok"])
    picked = jax.nn.one_hot(chosen, n_all, dtype=f32).sum(axis=1)   # [S, E]
    kept = picked * scores
    if config["norm_topk_prob"]:
        kept = kept / (jnp.sum(kept, -1, keepdims=True) + RENORMALISE_EPS)
    return kept * float(config["routed_scaling_factor"])


def reference_experts(m, h, config: Dict[str, Any]):
    """h [S, d], a sparse layer's normed input -> what the layer adds: the
    weighted sum over each token's chosen experts THAT ARE HELD (m's
    matrices: experts first .. first + held - 1)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    first, held, _ = share(config)
    weights = reference_routing(m, h, config)

    def expert(y, e):
        out = _swiglu({k: m[k][e] for k in ("w_gate", "w_up", "w_down")},
                      h, f32)
        return y + weights[:, first + e, None] * out, None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(held))
    return y


def _sequence(params, tokens, config):
    """tokens [S] -> final-normed hidden states [S, d]."""
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = float(config["norm_eps"])
    x = params["embed"]["table"].astype(f32)[tokens]
    for i, (layer, kind) in enumerate(zip(params["layers"],
                                          config["layer_types"])):
        n = _norm(x, layer["ln1"]["scale"], eps)
        if kind == "conv":
            x = x + reference_conv(layer["conv"], n, config)
        else:
            x = x + reference_attention(layer["attn"], n, config)
        h = _norm(x, layer["ln2"]["scale"], eps)
        if i < config["num_dense_layers"]:
            x = x + _swiglu(layer["mlp"], h, f32)
        else:
            x = x + reference_experts(layer["moe"], h, config)
    return _norm(x, params["final_norm"]["scale"], eps)


def _head(params, config):
    import jax.numpy as jnp
    if not config["tie_word_embeddings"]:
        return params["lm_head"].astype(jnp.float32)
    return params["embed"]["table"].astype(jnp.float32).T


def reference_logits(params, tokens, config: Dict[str, Any]):
    """tokens [B, S] -> float32 logits [B, S, vocab held]."""
    import jax
    x = jax.lax.map(lambda row: _sequence(params, row, config), tokens)
    return x @ _head(params, config)


def reference_logprobs(params, tokens, config: Dict[str, Any]):
    """[B, S] -> [B, S-1]: log-probability of each token after the first
    given the tokens before it, over the vocabulary held."""
    import jax
    import jax.numpy as jnp

    def sequence(row):
        z = _sequence(params, row[:-1], config) @ _head(params, config)
        picked = jnp.take_along_axis(z, row[1:, None], axis=-1)[:, 0]
        return picked - jax.nn.logsumexp(z, axis=-1)
    return jax.lax.map(sequence, tokens)


def reference_loss(params, tokens, config: Dict[str, Any]):
    """tokens [B, S+1] -> the training loss over B x S: the mean next-token
    cross-entropy, and nothing else.

    Where the configuration has a `program_check`, the number comes back
    only if the program's own forward agrees with the reference token by
    token (program_logprob_gap below), and is nan otherwise: the harness
    (train_cell.py) holds a run to this one number, and nan is within no
    tolerance of any first loss."""
    import jax.numpy as jnp
    logp = reference_logprobs(params, tokens, config)
    loss = -jnp.mean(logp)
    check = config.get("program_check")
    if check is None:
        return loss
    median, rms = program_logprob_gap(params, tokens, config, logp)
    held = (median <= check["logprob_median_tol"]) \
        & (rms <= check["logprob_rms_tol"])
    return jnp.where(held, loss, jnp.nan)


def program_logprob_gap(params, tokens, config: Dict[str, Any], reference):
    """The sharper half of `correct`, as families/olmoe.py and kanana.py
    have it: over the B x S predicted tokens, the program's log-probability
    less the reference's, as (median of the absolute gap, root mean
    square). The first loss at random weights is log V plus half the
    logits' variance whatever the block computes; the tokens' own
    log-probabilities tell a reversed or missing filter, a dropped gate, a
    missing norm a head, the wrong key/value head, a dropped selection bias,
    a top-k not renormalised and fp8 weights from bf16 rounding (the
    readings behind both bounds are in the configuration file). The program
    is the forward the step was built from, on one device, at the default
    matmul precision whatever the caller's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import gpt_forward
    with jax.default_matmul_precision("default"):
        logits, _ = gpt_forward(params, tokens[:, :-1], _train_config(config))
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    gap = picked - jax.nn.logsumexp(logits, axis=-1) - reference
    return jnp.median(jnp.abs(gap)), jnp.sqrt(jnp.mean(gap * gap))


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

def _matrices(config: Dict[str, Any]) -> Dict[str, int]:
    """Elements of each group of matrices: an attention layer's four, a conv
    layer's two projections, its filter, the dense MLP, one expert, the
    router."""
    d = config["hidden_size"]
    kv = d // config["num_attention_heads"] * config["num_key_value_heads"]
    return {
        "attention": 2 * d * d + 2 * d * kv,
        "conv": 3 * d * d + d * d,
        "filter": d * config["conv_L_cache"],
        "dense": 3 * d * config["intermediate_size"],
        "expert": 3 * d * config["moe_intermediate_size"],
        "router": d * share(config)[2],
    }


def _layers(config: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """(attention layers, conv layers, dense layers, sparse layers)."""
    kinds = layer_kinds(config)
    dense = config["num_dense_layers"]
    return (kinds.count("attention"), kinds.count("conv"), dense,
            len(kinds) - dense)


def param_count(config: Dict[str, Any]) -> int:
    """Every parameter resident on the device: an attention layer's four
    matrices and two head norms, a conv layer's projections and filter, two
    layer norms a layer; the dense layers' MLP; in a sparse layer the
    router at its published width, its selection bias and the experts HELD;
    the embedding over the vocabulary held (the head is tied to it), the
    final norm."""
    m = _matrices(config)
    d, v = config["hidden_size"], config["vocab_size"]
    head_dim = d // config["num_attention_heads"]
    _, held, of = share(config)
    attention, conv, dense, sparse = _layers(config)
    bias = of if config["use_expert_bias"] else 0
    return (attention * (m["attention"] + 2 * head_dim)
            + conv * (m["conv"] + m["filter"])
            + (attention + conv) * 2 * d + dense * m["dense"]
            + sparse * (m["router"] + bias + held * m["expert"])
            + v * d + d + (0 if config["tie_word_embeddings"] else d * v))


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 x the matrix parameters a token activates HERE + attention's two
    products: the mixers' matrices (the filter's taps counted: 6 x L x d a
    conv layer), the dense MLP or the router and the routed slots expected
    on this chip (experts a token x held / all: the true count moves with
    the routing), the head over the vocabulary held; q.k and p.v at
    head_dim under the causal mask: S / 2 keys a query, 3 S x 2 head_dim a
    query head and attention layer with the backward's two for one
    (kanana's convention). Left out: the embedding lookup, the norms, the
    softmaxes, RoPE, both gates, the routing's sorts and gathers, and
    recomputation (remat)."""
    m = _matrices(config)
    _, held, of = share(config)
    attention, conv, dense, sparse = _layers(config)
    slots = config["num_experts_per_tok"] * held / of
    active = (attention * m["attention"] + conv * (m["conv"] + m["filter"])
              + dense * m["dense"]
              + sparse * (m["router"] + slots * m["expert"])
              + config["hidden_size"] * config["vocab_size"])
    products = attention * 2 * config["hidden_size"]   # heads x 2 head_dim
    return 6.0 * active + 3.0 * products * seq


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0


def attention_call(config: Dict[str, Any], mix: Dict[str, Any]
                   ) -> Dict[str, int]:
    """One flash-kernel call on one chip under a training mix: q and the
    output [batch, heads, seq, head_dim], k and v [batch, kv_heads, seq,
    head_dim] ([2, 32 on 8, 8192, 64] at lfm2_train_1chip).
    benchmark/kernels/gqa_attention.py counts K, V, dK and dV at
    `kv_heads`; flash_attention.py and rope.py count one head count a call
    and are not this family's."""
    mesh = mix["mesh"]
    tensor = mesh.get("tensor", 1)
    return {"batch": mix["global_batch"] // (mesh.get("data", 1)
                                             * mesh.get("fsdp", 1)),
            "heads": config["num_attention_heads"] // tensor,
            "kv_heads": config["num_key_value_heads"] // tensor,
            "seq": mix["seq"],
            "head_dim": (config["hidden_size"]
                         // config["num_attention_heads"])}
