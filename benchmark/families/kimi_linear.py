"""The family `kimi_linear`: the decoder stack of Kimi-Linear-48B-A3B
(`model_type` `kimi_linear`, arXiv:2510.26692): three gated delta-rule
linear-attention layers (KDA, `linear_attn_config.kda_layers`) to one
latent-attention layer that rotates nothing (`full_attn_layers`,
`mla_use_nope`), a leading dense layer whose mixer is a delta-rule layer
(`first_k_dense_replace` 1), then sparse layers routed by sigmoid scores with
a selection bias beside one shared expert. What a family module holds is
listed in gpt_dense.py.

The layer, as the reference below writes it out. x is [S, d]; RMSNorm with
`rms_norm_eps` everywhere; no bias anywhere. Layer l (1-based, as the two
lists of `linear_attn_config` count):
  h = x + Mix_l(norm1(x));   y = h + FF_l(norm2(h))
Mix_l is KDA where l is in `kda_layers`, latent attention where it is in
`full_attn_layers`. n is the normed input.

KDA (`linear_attn_config`: H = `num_heads` heads of D = `head_dim` for q, k
and v alike, H D = 4096 columns on a hidden size of 2304; the config's
top-level `head_dim` 72 is hidden_size / heads and sizes nothing):
  q = l2norm_h(silu(conv(n Wq))) D^-1/2,  k = l2norm_h(silu(conv(n Wk))),
  v = silu(conv(n Wv)): conv a causal depthwise filter of
        `short_conv_kernel_size` taps a channel (zeros before the start, the
        last tap on the token itself); l2norm_h divides a head's D columns by
        sqrt(their squares' sum + 1e-6)
  log-decay a channel  a_t = -exp(A_h) softplus((n Wf_down) Wf_up + b_dt),
        alpha_t = exp(a_t) in (0, 1)^D, the pair of rank D
  beta_t = sigmoid(n Wbeta)_h, in (0, 1): the config has no key that doubles
        it (solar's `kda_allow_neg_eigval`)
  a state S [D, D] a head, S_0 = 0, a token at a time:
        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
  Mix = [RMSNorm_D(o_t) * sigmoid((n Wg_down) Wg_up)_h] Wo
Latent attention (`q_lora_rank` null; H = `num_attention_heads`, a head's
q.k over nope + rope columns and its v over dv):
  q = n Wq, a head's columns [q_nope | q_rope]
  c = n Wkva = [c_kv (`kv_lora_rank`) | k_rope (rope)]
  kv = norm_kv(c_kv) Wkvb, a head's columns [k_nope | v]
  k_h = [k_nope_h | k_rope]: ONE set of rope columns for all the heads
  `mla_use_nope` true: NOTHING is rotated, the "rope" columns of q and k are
        plain columns and the causal mask alone orders the tokens (the KDA
        layers carry position). (false, which no published model of the
        family has and a control uses: q_rope and k_rope rotated as halves at
        `rope_theta`.)
  scores q_h k_h^T / sqrt(nope + rope), causal, softmax in float32
  Mix = concat_h(P_h v_h) Wo
FF_l, l <= `first_k_dense_replace`: Wdown(silu(Wgate m) * Wup m),
`intermediate_size` wide. The others:
  s = sigmoid(m Wr) over ALL `num_experts`, float32
        (`moe_router_activation_func`)
  chosen: the `num_experts_per_token` largest s_e + bias_e (the bias enters
        the choice alone; `num_expert_group` = `topk_group` = 1: one group, a
        plain top-k)
  w_e = s_e / (sum of the chosen s + 1e-20) (`moe_renormalize`) times
        `routed_scaling_factor`
  FF = sum over chosen e of w_e down_e(silu(gate_e m) * up_e m) + Shared(m),
        experts `moe_intermediate_size` wide, the shared one
        `num_shared_experts` times that; cross-entropy alone
Final RMSNorm, then an untied head. No prediction module
(`num_nextn_predict_layers` 0).

The chip's share (`share` in the configuration file; model-configs guide,
section 4): the file's `num_experts` and `vocab_size` are what is HELD here,
experts rank * held .. + held - 1 of `share.num_experts`. The router keeps
its published width and its experts a token; the sum above runs over the
chosen experts that are held, and what the others would have added is left
out, here and in the program alike; both mixers, the shared expert and the
dense layer are whole on every chip. A file without `share` is the whole
layer (tests/test_kimi_linear_model.py adds the shares up to it).

Departures and assumptions, each also in the configuration file: the program
runs the delta rule in chunks of 64 tokens (ops/linear_attention.py), the
reference a token at a time; the router's matmul is float32 in program and
reference alike; the selection bias's update rule is not built; seeded
random weights, the embedding's rows at the spread `embedding_init_std`
(keye's and solar's reason: a token's own row then leads its residual stream
and the seeded router is near the balance a deployment's balancing keeps it
at).
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict, Tuple

# queries a block of the reference's attention: [heads, block, S] float32
# scores are 0.5 GB at 32 heads and 8192 positions
QUERY_BLOCK = 512

# the chunk ops/linear_attention.py runs the delta rule in: the arithmetic
# of benchmark/kernels/kda.py is stated at it
KDA_CHUNK = 64

# the spread models/gpt.py:gpt_init draws the embedding's rows at
GPT_INIT_EMBEDDING_STD = 0.02


def share(config: Dict[str, Any]) -> Tuple[int, int, int]:
    """(first expert held here, how many, of how many routed experts)."""
    held = config["num_experts"]
    s = config.get("share")
    if s is None:
        return 0, held, held
    return s["rank"] * held, held, s["num_experts"]


def _kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    """"kda" | "attention" a layer, from `linear_attn_config`'s two lists,
    which count the layers from 1 and between them name each once."""
    linear = config["linear_attn_config"]
    n = config["num_hidden_layers"]
    kda, full = set(linear["kda_layers"]), set(linear["full_attn_layers"])
    if kda & full or (kda | full) != set(range(1, n + 1)):
        raise ValueError(
            f"kda_layers {sorted(kda)} and full_attn_layers {sorted(full)} "
            f"do not name each of the layers 1..{n} once")
    return tuple("kda" if i in kda else "attention" for i in range(1, n + 1))


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    unbuilt = {"q_lora_rank": None, "rope_scaling": None,
               "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
               "num_key_value_heads": config["num_attention_heads"],
               "hidden_act": "silu", "num_nextn_predict_layers": 0,
               "moe_router_activation_func": "sigmoid"}
    for key, built in unbuilt.items():
        if config[key] != built:
            raise ValueError(f"models/gpt.py is built for {key} = {built!r} "
                             f"only, the configuration has {config[key]!r}")
    linear = config["linear_attn_config"]
    if linear["num_heads"] != config["num_attention_heads"]:
        raise ValueError("models/gpt.py keeps one head count for both mixers")
    first, held, of = share(config)
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        # the delta-rule heads' width; the latent block reads its own three
        "head_dim": linear["head_dim"],
        "layer_kinds": _kinds(config),
        "use_rope": not config["mla_use_nope"],
        "rope_theta": float(config["rope_theta"]),
        "conv_filter": linear["short_conv_kernel_size"],
        "d_ff": config["moe_intermediate_size"],    # the width of ONE expert
        "max_seq": config["model_max_length"],
        "rmsnorm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "n_experts": of,
        "expert_top_k": config["num_experts_per_token"],
        "experts_held": None if held == of else (first, held),
        "router_score": config["moe_router_activation_func"],
        "router_bias_scale": float(config["selection_bias_init_std"]),
        "router_renormalise": bool(config["moe_renormalize"]),
        "router_scale": float(config["routed_scaling_factor"]),
        "n_shared_experts": config["num_shared_experts"],
        "dense_layers": config["first_k_dense_replace"],
        "dense_d_ff": config["intermediate_size"],
        "kv_latent_dim": config["kv_lora_rank"],
        "qk_nope_dim": config["qk_nope_head_dim"],
        "qk_rope_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
    }


def _train_config(config: Dict[str, Any]):
    """The GPTConfig the step is built from (bf16 activations, flash
    attention, the delta rule's, the filters' and the grouped-matmul
    kernels, remat of the whole layer)."""
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(**gpt_config_kwargs(config), attention="flash",
                     remat_policy="full")


def program(config: Dict[str, Any], serving: bool = False):
    """The system under test at these sizes, as gpt_dense.program: the same
    models/gpt.py, told the kinds of its layers, the latent block's widths
    and that it rotates nothing, the leading dense layer, the routing rule
    and the share of the experts."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init, gpt_loss

    if serving:
        cfg = GPTConfig(**gpt_config_kwargs(config), attention="flash")
    else:
        cfg = _train_config(config)

    def init(key):
        params = gpt_init(key, cfg)
        # the configuration's `assumed.embedding_init_std`: the embedding's
        # rows at that spread, not gpt_init's
        table = params["embed"]["table"]
        params["embed"]["table"] = table * (
            float(config["embedding_init_std"]) / GPT_INIT_EMBEDDING_STD)
        if not serving:
            return params
        return jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype), params)

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
# time (lax.map over the batch), the delta rule a token at a time, attention
# a block of queries at a time, the routed sum one expert at a time. Call it
# under jax.default_matmul_precision("highest"). (program_logprob_gap, below
# the reference, is not part of it: it runs the program, to hold it to the
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


def _filtered(x, taps):
    """x [S, C], taps [C, L]: silu of the causal depthwise filter, tap L - 1
    on the token itself, zeros before the sequence's start."""
    import jax
    import jax.numpy as jnp
    s, n = x.shape[0], taps.shape[1]
    padded = jnp.pad(x, ((n - 1, 0), (0, 0)))
    return jax.nn.silu(sum(taps[:, j].astype(jnp.float32) * padded[j:j + s]
                           for j in range(n)))


def _unit(x):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def reference_delta_rule(q, k, v, log_decay, beta):
    """q, k, v, log_decay [S, H, D], beta [S, H] -> o [S, H, D]: the
    recurrence, a token a step."""
    import jax
    import jax.numpy as jnp

    def token(state, x):
        q_t, k_t, v_t, a_t, b_t = x
        state = jnp.exp(a_t)[:, :, None] * state             # Diag(alpha) S
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + jnp.einsum(
            "hk,hv->hkv", k_t, b_t[:, None] * (v_t - read))
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, heads, dim = q.shape
    _, o = jax.lax.scan(token, jnp.zeros((heads, dim, dim), jnp.float32),
                        (q, k, v, log_decay, beta))
    return o


def reference_kda(m, n, config: Dict[str, Any]):
    """n [S, d], a KDA layer's normed input -> what the layer adds."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    dim = config["linear_attn_config"]["head_dim"]
    s = n.shape[0]

    def heads(y):
        return y.reshape(s, -1, dim)
    q, k, v = (heads(_filtered(n @ m[w].astype(f32), m[taps]))
               for w, taps in (("wq", "q_conv"), ("wk", "k_conv"),
                               ("wv", "v_conv")))
    q, k = _unit(q) / math.sqrt(dim), _unit(k)
    step = jax.nn.softplus(n @ m["wf_down"].astype(f32)
                           @ m["wf_up"].astype(f32) + m["dt_bias"])
    log_decay = -jnp.exp(m["a_log"].astype(f32))[None, :, None] * heads(step)
    beta = jax.nn.sigmoid(n @ m["w_beta"].astype(f32))
    o = reference_delta_rule(q, k, v, log_decay, beta)
    o = _norm(o, m["o_norm"]["scale"], float(config["rms_norm_eps"]))
    gate = jax.nn.sigmoid(n @ m["wg_down"].astype(f32)
                          @ m["wg_up"].astype(f32))
    return (o.reshape(s, -1) * gate) @ m["wo"].astype(f32)


def _rotated(t, config: Dict[str, Any]):
    """t [S, heads, rope]: every column rotated as halves at `rope_theta`
    (the form `mla_use_nope` false takes: a control's, no published
    model's)."""
    import jax.numpy as jnp
    s, _, dim = t.shape
    angles = (jnp.arange(s, dtype=jnp.float32)[:, None]
              * float(config["rope_theta"])
              ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, c = t[..., :dim // 2], t[..., dim // 2:]
    return jnp.concatenate([a * cos - c * sin, a * sin + c * cos], -1)


def reference_attention(a, n, config: Dict[str, Any]):
    """n [S, d], a latent layer's normed input -> what attention adds
    [S, d]: no rotation, the causal mask alone."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, latent = config["v_head_dim"], config["kv_lora_rank"]
    eps = float(config["rms_norm_eps"])
    s = n.shape[0]

    q = (n @ a["wq"].astype(f32)).reshape(s, heads, nope + rope)
    c = n @ a["w_kva"].astype(f32)
    kv = (_norm(c[:, :latent], a["kv_norm"]["scale"], eps)
          @ a["w_kvb"].astype(f32)).reshape(s, heads, nope + dv)
    q_rope, k_rope = q[..., nope:], c[:, None, latent:]      # [S, 1, rope]
    if not config["mla_use_nope"]:
        q_rope, k_rope = _rotated(q_rope, config), _rotated(k_rope, config)
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
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
    scores = jax.nn.sigmoid(h @ m["router"].astype(f32))
    _, chosen = jax.lax.top_k(scores + m["router_bias"].astype(f32),
                              config["num_experts_per_token"])
    kept = jax.nn.one_hot(chosen, n_all, dtype=f32).sum(axis=1) * scores
    if config["moe_renormalize"]:
        kept = kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20)
    return kept * float(config["routed_scaling_factor"])


def reference_experts(m, h, config: Dict[str, Any]):
    """h [S, d], a sparse layer's second normed input -> what the layer
    adds: the weighted sum over each token's chosen experts THAT ARE HELD
    (m's matrices: experts first .. first + held - 1), and the shared
    expert."""
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
    return y + _swiglu(m["shared"], h, f32)


def reference_mixer(layer, n, config: Dict[str, Any]):
    """A layer's mixer, by what its parameters hold."""
    if "kda" in layer:
        return reference_kda(layer["kda"], n, config)
    return reference_attention(layer["attn"], n, config)


def reference_feed_forward(layer, h, config: Dict[str, Any]):
    """A layer's second half, by what its parameters hold: the dense MLP,
    or the experts beside the shared one."""
    import jax.numpy as jnp
    if "mlp" in layer:
        return _swiglu(layer["mlp"], h, jnp.float32)
    return reference_experts(layer["moe"], h, config)


def _sequence(params, tokens, config):
    """tokens [S] -> final-normed hidden states [S, d]."""
    import jax.numpy as jnp
    eps = float(config["rms_norm_eps"])
    dense = config["first_k_dense_replace"]
    x = params["embed"]["table"].astype(jnp.float32)[tokens]
    for i, (layer, kind) in enumerate(zip(params["layers"], _kinds(config))):
        if ("kda" in layer) != (kind == "kda") or ("mlp" in layer) != (
                i < dense):
            raise ValueError("the parameters' layers are not the "
                             "configuration's")
        x = x + reference_mixer(layer, _norm(x, layer["ln1"]["scale"], eps),
                                config)
        x = x + reference_feed_forward(
            layer, _norm(x, layer["ln2"]["scale"], eps), config)
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
    median, rms, tail = program_logprob_gap(params, tokens, config, logp)
    held = (median <= check["logprob_median_tol"]) \
        & (rms <= check["logprob_rms_tol"]) \
        & (tail <= check["logprob_p99_tol"])
    return jnp.where(held, loss, jnp.nan)


def program_logprob_gap(params, tokens, config: Dict[str, Any], reference):
    """The sharper half of `correct`, as the other share families have it:
    over the B x S predicted tokens, the program's log-probability less the
    reference's, as (median of the absolute gap, root mean square, 99th
    percentile of the absolute gap). The first loss at random weights is log
    V plus half the logits' variance whatever the block computes; the tokens'
    own log-probabilities tell a decay left out, a beta doubled, the 64
    shared key columns rotated, a held expert left out and rounded weights
    from the step's own rounding (the readings behind the bounds are in the
    configuration file). The one latent layer adds an average over a token's
    whole prefix, next to nothing but for the sequence's first tokens, so a
    fault of it moves few tokens much: the percentile's to catch. The
    program is the forward the step was built from, on one device, at the
    default matmul precision whatever the caller's (the delta rule's own
    products ask for full precision themselves)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import gpt_forward
    with jax.default_matmul_precision("default"):
        logits, _ = gpt_forward(params, tokens[:, :-1], _train_config(config))
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    gap = picked - jax.nn.logsumexp(logits, axis=-1) - reference
    return (jnp.median(jnp.abs(gap)), jnp.sqrt(jnp.mean(gap * gap)),
            jnp.quantile(jnp.abs(gap), 0.99))


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

def _matrices(config: Dict[str, Any]) -> Dict[str, int]:
    """Elements of each group of matrices: the two mixers, the dense MLP,
    one routed expert, the shared one, the router."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, latent = config["v_head_dim"], config["kv_lora_rank"]
    linear = config["linear_attn_config"]
    lin, rank = linear["head_dim"] * linear["num_heads"], linear["head_dim"]
    f = config["moe_intermediate_size"]
    return {
        "attention": (d * h * (nope + rope) + d * (latent + rope)
                      + latent * h * (nope + dv) + h * dv * d),
        # q, k, v and the output; the decay's and the gate's low-rank pairs;
        # beta a head
        "kda": (4 * d * lin + 2 * (d * rank + rank * lin)
                + d * linear["num_heads"]),
        "dense": 3 * d * config["intermediate_size"],
        "expert": 3 * d * f,
        "shared": 3 * d * f * config["num_shared_experts"],
        "router": d * share(config)[2]}


def _layers(config: Dict[str, Any]) -> Tuple[int, int, int]:
    """(latent layers, KDA layers, of both the leading dense ones)."""
    kinds = _kinds(config)
    return (kinds.count("attention"), kinds.count("kda"),
            config["first_k_dense_replace"])


def param_count(config: Dict[str, Any]) -> int:
    """Every parameter resident on the device: a mixer's matrices (a KDA
    layer's three filters, its decay rate a head, its step bias a channel
    and its norm's scale beside them; the latent's norm), two layer norms a
    layer; the dense layers' MLP; in a sparse layer the router at its
    published width with its bias, the experts HELD and the shared expert;
    embedding and head over the vocabulary held, the final norm."""
    m = _matrices(config)
    d, v = config["hidden_size"], config["vocab_size"]
    linear = config["linear_attn_config"]
    lin = linear["head_dim"] * linear["num_heads"]
    small = (3 * lin * linear["short_conv_kernel_size"] + linear["num_heads"]
             + lin + linear["head_dim"])
    mla, kda, dense = _layers(config)
    _, held, of = share(config)
    return (mla * (m["attention"] + config["kv_lora_rank"])
            + kda * (m["kda"] + small) + (mla + kda) * 2 * d
            + dense * m["dense"]
            + (mla + kda - dense) * (m["router"] + of + held * m["expert"]
                                     + m["shared"])
            + v * d + d + (0 if config["tie_word_embeddings"] else d * v))


def active_param_count(config: Dict[str, Any]) -> int:
    """param_count with, of a sparse layer's experts, the
    `num_experts_per_token` a token goes through: the "A3B" of the name, at
    the published sizes."""
    mla, kda, dense = _layers(config)
    idle = share(config)[1] - config["num_experts_per_token"]
    return param_count(config) - (mla + kda - dense) * idle * _matrices(
        config)["expert"]


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 x the matrix parameters a token activates HERE + 3 x the two
    mixers' own products: both mixers' projections and low-rank pairs, the
    dense MLP, the router, the shared expert and the routed slots expected on
    this chip (experts a token x held / all: the true count moves with the
    routing), the head over the vocabulary held; in a latent layer q.k at
    nope + rope columns and p.v at v_head_dim under the causal mask (S / 2
    keys a query); in a KDA layer the delta rule's products a token and head
    at the chunk the program runs
    (benchmark/kernels/kda.py:delta_rule_flops_per_token). The backward's two
    for one. Left out: the embedding lookup, the norms, the filters, the
    decays' exponentials, the softmax, the routing's sorts and gathers, and
    recomputation (remat)."""
    from benchmark.kernels.kda import delta_rule_flops_per_token
    m = _matrices(config)
    _, held, of = share(config)
    mla, kda, dense = _layers(config)
    slots = config["num_experts_per_token"] * held / of
    active = (mla * m["attention"] + kda * m["kda"] + dense * m["dense"]
              + (mla + kda - dense) * (m["router"] + m["shared"]
                                       + slots * m["expert"])
              + config["hidden_size"] * config["vocab_size"])
    linear = config["linear_attn_config"]
    products = (mla * config["num_attention_heads"] * 2.0
                * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
                   + config["v_head_dim"]) * seq / 2.0
                + kda * linear["num_heads"] * delta_rule_flops_per_token(
                    KDA_CHUNK, linear["head_dim"], linear["head_dim"]))
    return 6.0 * active + 3.0 * products


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0


def _a_chips_rows(mix: Dict[str, Any]) -> int:
    mesh = mix["mesh"]
    return mix["global_batch"] // (mesh.get("data", 1) * mesh.get("fsdp", 1))


def attention_call(config: Dict[str, Any], mix: Dict[str, Any]
                   ) -> Dict[str, int]:
    """One call of the latent layer's flash kernels on one chip under a
    training mix: q and k [batch, heads, seq, qk_dim], v and the output
    [batch, heads, seq, v_dim] ([1, 32, 8192, 192 / 128] at
    kimilinear_train_1chip). benchmark/kernels/mla_attention.py counts
    it."""
    return {"batch": _a_chips_rows(mix),
            "heads": config["num_attention_heads"]
            // mix["mesh"].get("tensor", 1),
            "seq": mix["seq"],
            "qk_dim": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            "v_dim": config["v_head_dim"]}


def kda_call(config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, int]:
    """One KDA layer's tensors on one chip under a training mix: q, k, v and
    the output [batch, heads, seq, head_dim] ([1, 32, 8192, 128] at
    kimilinear_train_1chip), the filters' taps. benchmark/kernels/kda.py
    counts the filter kernels' calls (one a tensor: [batch, seq, heads x
    head_dim]), delta_rule.py the delta rule's two."""
    linear = config["linear_attn_config"]
    return {"batch": _a_chips_rows(mix),
            "heads": linear["num_heads"] // mix["mesh"].get("tensor", 1),
            "seq": mix["seq"], "head_dim": linear["head_dim"],
            "taps": linear["short_conv_kernel_size"], "chunk": KDA_CHUNK}
