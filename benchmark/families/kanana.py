"""The family `kanana`: the decoder block of Kanana-2-30B-A3B (the
`deepseek_v3` model code its config.json names; the architecture of
arXiv:2412.19437 without the low-rank q projection): latent attention, a
leading dense layer, then sparse layers routed by sigmoid scores with a
selection bias, beside shared experts. What a family module holds is listed
in gpt_dense.py.

The layer, as the reference below writes it out. x is [S, d]; RMSNorm
everywhere; no biases. With H heads, a head's q.k over nope + rope columns
and its v over dv:
  n = norm1(x);  q = n Wq, a head's columns [q_nope | q_rope]
  c = n Wkva = [c_kv (kv_lora_rank) | k_rope (rope)]
  kv = norm_kv(c_kv) Wkvb, a head's columns [k_nope | v]
  q_h = [q_nope_h | rot(q_rope_h)],  k_h = [k_nope_h | rot(k_rope)]: ONE
        rotated key part for all the heads. rot: `rope_interleave` true, so
        the columns are de-interleaved ([x0, x1, x2, ..] -> [x0, x2, .. |
        x1, x3, ..]) and then rotated as halves, theta `rope_theta`
  scores q_h k_h^T / sqrt(nope + rope), causal, softmax in float32
  h = x + concat_h(P_h v_h) Wo
Layers below `first_k_dense_replace`: y = h + Wdown(silu(Wgate m) * Wup m),
`intermediate_size` wide, m = norm2(h). The others:
  s = sigmoid(m Wr) over ALL the routed experts, float32
  chosen: the `num_experts_per_tok` experts with the largest s_e + b_e (b
        the layer's selection bias; n_group = topk_group = 1: no groups)
  w_e = s_e / (sum of the chosen s + 1e-20) * `routed_scaling_factor`
  y = h + sum over chosen e of w_e down_e(silu(gate_e m) * up_e m)
        + Shared(m), one SwiGLU n_shared_experts x moe_intermediate_size wide
Final RMSNorm, untied head, cross-entropy alone (the model code has no
router loss).

The chip's share (`share` in the configuration file; model-configs guide,
section 4): the file's `n_routed_experts` and `vocab_size` are what is HELD
here, experts rank * held .. + held - 1 of `share.n_routed_experts`. The
router keeps its published width and its experts a token; the sum above
runs over the chosen experts that are held, and what the others would have
added is left out, here and in the program alike. A file without `share`
is the whole layer (tests/test_latent_moe.py adds eight shares up to it).

Departures, each also in the configuration file: the selection bias is a
seeded constant (the rule that moves it between steps is in no key of the
config and is not built); the router's matmul is float32 in program and
reference alike; seeded random weights.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict, Tuple

# queries a block of the reference's attention: [heads, block, S] float32
# scores are 1 GB at 32 heads and 8192 positions
QUERY_BLOCK = 1024


def share(config: Dict[str, Any]) -> Tuple[int, int, int]:
    """(first expert held here, how many, of how many routed experts)."""
    held = config["n_routed_experts"]
    s = config.get("share")
    if s is None:
        return 0, held, held
    return s["rank"] * held, held, s["n_routed_experts"]


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    unbuilt = {"q_lora_rank": None, "rope_scaling": None, "n_group": 1,
               "topk_group": 1, "moe_layer_freq": 1, "attention_bias": False,
               "num_key_value_heads": config["num_attention_heads"],
               "hidden_act": "silu"}
    for key, built in unbuilt.items():
        if config[key] != built:
            raise ValueError(f"models/gpt.py is built for {key} = {built!r} "
                             f"only, the configuration has {config[key]!r}")
    first, held, of = share(config)
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "d_ff": config["moe_intermediate_size"],    # the width of ONE expert
        "max_seq": config["max_position_embeddings"],
        "rope_theta": float(config["rope_theta"]),
        "rmsnorm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "n_experts": of,
        "expert_top_k": config["num_experts_per_tok"],
        "experts_held": None if held == of else (first, held),
        "router_score": config["scoring_func"],
        "router_bias_scale": (float(config["selection_bias_init_std"])
                              if config["topk_method"] == "noaux_tc" else 0.0),
        "router_renormalise": bool(config["norm_topk_prob"]),
        "router_scale": float(config["routed_scaling_factor"]),
        "n_shared_experts": config["n_shared_experts"],
        "dense_layers": config["first_k_dense_replace"],
        "dense_d_ff": config["intermediate_size"],
        "kv_latent_dim": config["kv_lora_rank"],
        "qk_nope_dim": config["qk_nope_head_dim"],
        "qk_rope_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "rope_interleaved": bool(config["rope_interleave"]),
    }


def _train_config(config: Dict[str, Any]):
    """The GPTConfig the step is built from (bf16 activations, flash
    attention, the grouped-matmul kernels, remat of the whole layer)."""
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(**gpt_config_kwargs(config), attention="flash",
                     remat_policy="full")


def program(config: Dict[str, Any], serving: bool = False):
    """The system under test at these sizes, as gpt_dense.program: the same
    models/gpt.py, told the layer pattern, the routing rule, the share of
    the experts and the latent attention's widths."""
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
# time (lax.map over the batch), attention a block of queries at a time and
# the routed sum one expert at a time, so that neither [heads, S, S] nor
# [S, experts, width] has to exist at the published widths. Call it under
# jax.default_matmul_precision("highest"). (program_logprob_gap, below the
# reference, is not part of it: it runs the program, to hold it to the
# reference where the harness cannot.)
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


def _rotated(t, cos, sin):
    """t [S, heads, rope], interleaved pairs as the weights give them ->
    de-interleaved, then rotated as halves."""
    import jax.numpy as jnp
    s, heads, rope = t.shape
    t = t.reshape(s, heads, rope // 2, 2).swapaxes(-1, -2).reshape(
        s, heads, rope)
    a, c = t[..., :rope // 2], t[..., rope // 2:]
    return jnp.concatenate([a * cos - c * sin, a * sin + c * cos], -1)


def reference_attention(a, n, config: Dict[str, Any]):
    """n [S, d], one layer's normed input -> what attention adds [S, d]."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, latent = config["v_head_dim"], config["kv_lora_rank"]
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    s = n.shape[0]
    angles = (jnp.arange(s, dtype=f32)[:, None]
              * theta ** (-jnp.arange(rope // 2, dtype=f32) / (rope // 2)))
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    if not config["rope_interleave"]:
        raise ValueError("the reference writes rope_interleave true only")

    q = (n @ a["wq"].astype(f32)).reshape(s, heads, nope + rope)
    c = n @ a["w_kva"].astype(f32)
    kv = (_norm(c[:, :latent], a["kv_norm"]["scale"], eps)
          @ a["w_kvb"].astype(f32)).reshape(s, heads, nope + dv)
    k_rope = _rotated(c[:, None, latent:], cos, sin)         # [S, 1, rope]
    q = jnp.concatenate([q[..., :nope], _rotated(q[..., nope:], cos, sin)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (s, heads, rope))], -1)
    v = kv[..., nope:]

    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} positions are not whole blocks of {block}")
    at = jnp.arange(s)

    def queries(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(nope + rope)
        seen = (start + jnp.arange(block))[:, None] >= at[None, :]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", weights, v).reshape(
            block, heads * dv)
    mixed = jax.lax.map(queries, jnp.arange(0, s, block)).reshape(
        s, heads * dv)
    return mixed @ a["wo"].astype(f32)


def reference_routing(m, h, config: Dict[str, Any]):
    """h [S, d] -> [S, E] float32: w_e where expert e is among the token's
    chosen, 0 elsewhere, over ALL the routed experts."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    n_all = share(config)[2]
    if config["scoring_func"] != "sigmoid" or config["topk_method"] != "noaux_tc":
        raise ValueError("the reference writes sigmoid scores with a "
                         "selection bias (noaux_tc) only")
    scores = jax.nn.sigmoid(h @ m["router"].astype(f32))
    _, chosen = jax.lax.top_k(scores + m["router_bias"].astype(f32),
                              config["num_experts_per_tok"])
    picked = jax.nn.one_hot(chosen, n_all, dtype=f32).sum(axis=1)   # [S, E]
    kept = picked * scores
    if config["norm_topk_prob"]:
        kept = kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20)
    return kept * float(config["routed_scaling_factor"])


def reference_experts(m, h, config: Dict[str, Any]):
    """h [S, d], a sparse layer's normed input -> what the layer adds: the
    weighted sum over each token's chosen experts THAT ARE HELD (m's
    matrices: experts first .. first + held - 1), and the shared expert."""
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
    if config["n_shared_experts"]:
        y = y + _swiglu(m["shared"], h, f32)
    return y


def _sequence(params, tokens, config):
    """tokens [S] -> final-normed hidden states [S, d]."""
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = float(config["rms_norm_eps"])
    x = params["embed"]["table"].astype(f32)[tokens]
    for i, layer in enumerate(params["layers"]):
        x = x + reference_attention(
            layer["attn"], _norm(x, layer["ln1"]["scale"], eps), config)
        h = _norm(x, layer["ln2"]["scale"], eps)
        if i < config["first_k_dense_replace"]:
            x = x + _swiglu(layer["mlp"], h, f32)
        else:
            x = x + reference_experts(layer["moe"], h, config)
    return _norm(x, params["final_norm"]["scale"], eps)


def reference_logits(params, tokens, config: Dict[str, Any]):
    """tokens [B, S] -> float32 logits [B, S, vocab held]."""
    import jax
    import jax.numpy as jnp
    x = jax.lax.map(lambda row: _sequence(params, row, config), tokens)
    return x @ params["lm_head"].astype(jnp.float32)


def reference_logprobs(params, tokens, config: Dict[str, Any]):
    """[B, S] -> [B, S-1]: log-probability of each token after the first
    given the tokens before it, over the vocabulary held."""
    import jax
    import jax.numpy as jnp

    def sequence(row):
        z = _sequence(params, row[:-1], config) \
            @ params["lm_head"].astype(jnp.float32)
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
    """The sharper half of `correct`, as families/olmoe.py has it: over the
    B x S predicted tokens, the program's log-probability less the
    reference's, as (median of the absolute gap, root mean square). The
    first loss at random weights is log V plus half the logits' variance
    whatever the block computes; the tokens' own log-probabilities tell a
    dropped selection bias, an unscaled or un-renormalised top-k, a missing
    shared expert, a rotation of the wrong columns and fp8 weights from
    bf16 rounding (the readings behind both bounds are in the configuration
    file). The program is the forward the step was built from, on one
    device, at the default matmul precision whatever the caller's."""
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
    """Elements of each group of matrices: attention a layer, the dense
    MLP, one routed expert, the shared expert, the router."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, latent = config["v_head_dim"], config["kv_lora_rank"]
    f = config["moe_intermediate_size"]
    return {
        "attention": (d * h * (nope + rope) + d * (latent + rope)
                      + latent * h * (nope + dv) + h * dv * d),
        "dense": 3 * d * config["intermediate_size"],
        "expert": 3 * d * f,
        "shared": 3 * d * f * config["n_shared_experts"],
        "router": d * share(config)[2],
    }


def param_count(config: Dict[str, Any]) -> int:
    """Every parameter resident on the device: attention's four matrices
    and the latent's norm, two layer norms; the dense layers' MLP; in a
    sparse layer the router at its published width, its selection bias, the
    experts HELD and the shared expert; embedding, final norm, untied head
    over the vocabulary held."""
    m = _matrices(config)
    d, v = config["hidden_size"], config["vocab_size"]
    _, held, of = share(config)
    dense = config["first_k_dense_replace"]
    sparse = config["num_hidden_layers"] - dense
    layer = m["attention"] + config["kv_lora_rank"] + 2 * d
    return (config["num_hidden_layers"] * layer + dense * m["dense"]
            + sparse * (m["router"] + of + held * m["expert"] + m["shared"])
            + v * d + d + (0 if config["tie_word_embeddings"] else d * v))


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 x the matrix parameters a token activates HERE + attention's two
    products: the attention matrices, the dense MLP or the router, the
    shared expert and the routed slots expected on this chip (experts a
    token x held / all: the true count moves with the routing), the head
    over the vocabulary held; q.k at nope + rope columns and p.v at
    v_head_dim, under the causal mask: S / 2 keys a query, 3 S (dqk + dv)
    a head and layer with the backward's two for one. (gpt_dense counts
    causal attention as full; at 8192 positions attention is most of this
    step, and counting the masked half would read as utilization the work
    the kernels rightly skip.) Left out: the embedding lookup, the norms,
    the softmaxes, RoPE, the routing's sorts and gathers, and recomputation
    (remat)."""
    m = _matrices(config)
    _, held, of = share(config)
    dense = config["first_k_dense_replace"]
    sparse = config["num_hidden_layers"] - dense
    slots = config["num_experts_per_tok"] * held / of
    active = (config["num_hidden_layers"] * m["attention"]
              + dense * m["dense"]
              + sparse * (m["router"] + m["shared"] + slots * m["expert"])
              + config["hidden_size"] * config["vocab_size"])
    products = (config["num_hidden_layers"] * config["num_attention_heads"]
                * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
                   + config["v_head_dim"]))
    return 6.0 * active + 3.0 * products * seq


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0


def attention_call(config: Dict[str, Any], mix: Dict[str, Any]
                   ) -> Dict[str, int]:
    """One flash-kernel call on one chip under a training mix: q and k
    [batch, heads, seq, qk_dim], v and the output [batch, heads, seq,
    v_dim] ([2, 32, 8192, 192 / 128] at kanana2_train_1chip). No
    `head_dim`: benchmark/kernels/flash_attention.py and rope.py count one
    width and are not this family's; mla_attention.py is."""
    mesh = mix["mesh"]
    heads = config["num_attention_heads"]
    return {"batch": mix["global_batch"] // (mesh.get("data", 1)
                                             * mesh.get("fsdp", 1)),
            "heads": heads // mesh.get("tensor", 1),
            "seq": mix["seq"],
            "qk_dim": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            "v_dim": config["v_head_dim"]}
