"""The family `solar`: the decoder stack of Solar-Open2-250B (`model_type`
`solar_open2`): three gated delta-rule linear-attention layers (KDA, the
`kda_*` and `linear_attn_config` keys) to one grouped-query softmax layer
that rotates nothing (`use_rope` false) and gates its output an element
(`use_gqa_gate`), every layer with sparse experts beside a shared one. What a
family module holds is listed in gpt_dense.py.

The layer, as the reference below writes it out. x is [S, d]; RMSNorm with
`rms_norm_eps` everywhere; no bias anywhere; no leading dense layer
(`first_k_dense_replace` 0: `intermediate_size` is used by no layer). Layer l:
  h = x + Mix_l(norm1(x));   y = h + FF(norm2(h))
Mix_l is GQA where l is in `gqa_layers`, else KDA. n is the normed input.

KDA (`linear_attn_config`: H = `num_heads` heads of D = `head_dim` for q, k
and v alike, `num_kv_heads` null; the form arXiv:2510.26692 publishes):
  q = l2norm_h(silu(conv(n Wq))) D^-1/2,  k = l2norm_h(silu(conv(n Wk))),
  v = silu(conv(n Wv)): conv a causal depthwise filter of
        `short_conv_kernel_size` taps a channel (zeros before the start, the
        last tap on the token itself); l2norm_h divides a head's D columns by
        sqrt(their squares' sum + 1e-6)
  log-decay a channel  a_t = -exp(A_h) softplus((n Wf_down) Wf_up + b_dt),
        alpha_t = exp(a_t) in (0, 1)^D
  beta_t = sigmoid(n Wbeta)_h, doubled under `kda_allow_neg_eigval`: (0, 2)
  a state S [D, D] a head, S_0 = 0, a token at a time:
        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
  Mix = [RMSNorm_D(o_t) * sigmoid((n Wg_down) Wg_up)_h] Wo
  `kda_use_full_proj` false: Wf and Wg are low-rank pairs of rank D.
GQA: H = `num_attention_heads` query heads on `num_key_value_heads` at
  `head_dim`, q = n Wq, k = n Wk, v = n Wv, NO rotation (`use_rope` false;
  true: every column as halves at `rope_theta`); query head h reads
  key/value head h // (H / Hkv); softmax of q k^T / sqrt(D) in float32 over
  j <= i; `use_gqa_gate`: Mix = [o * sigmoid(n Wgate)] Wo with Wgate [d, H D],
  a gate an element (arXiv:2505.06708's form)
FF (the `deepseek_v3` keys):
  s = sigmoid(m Wr) over ALL `n_routed_experts`, float32
  chosen: the `num_experts_per_tok` largest s_e + bias_e (the bias enters
        the choice alone)
  w_e = s_e / (sum of the chosen s) (`norm_topk_prob`) times
        `routed_scaling_factor`
  FF = sum over chosen e of w_e down_e(silu(gate_e m) * up_e m) + Shared(m),
        experts `moe_intermediate_size` wide, the shared one `n_shared_experts`
        times that; cross-entropy alone
Final RMSNorm, then an untied head.

The chip's share (`share` in the configuration file; model-configs guide,
section 4): the file's `n_routed_experts`, `vocab_size`, `num_attention_heads`,
`num_key_value_heads` and `linear_attn_config.num_heads` are what is HELD
here: experts rank * held .. + held - 1 of `share.n_routed_experts`, and of
the mixers the heads whose matrices the parameters hold (a mixer's head
needs nothing of another's before Wo, whose rows are summed: a share's
mixer output is its heads' part of that sum). The router keeps its published
width and its experts a token; what the experts and the heads that are not
held would have added is left out, here and in the program alike; the shared
expert is whole on every chip. A file without `share` is the whole layer
(tests/test_linear_attention.py adds the shares up to it).

Departures and assumptions, each also in the configuration file: k and v of
the GQA layer stay at Hkv heads in the program; the program runs the delta
rule in chunks of 64 tokens (ops/linear_attention.py), the reference a token
at a time; the router's matmul is float32 in program and reference alike;
the selection bias's update rule is not built; seeded random weights, the
embedding's rows at the spread `embedding_init_std` (keye's reason: a token's
own row then leads its residual stream and the seeded router is near the
balance a deployment's balancing keeps it at).
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict, Tuple

# queries a block of the reference's attention
QUERY_BLOCK = 512

# the chunk ops/linear_attention.py runs the delta rule in: the arithmetic
# of benchmark/kernels/kda.py is stated at it
KDA_CHUNK = 64

# the spread models/gpt.py:gpt_init draws the embedding's rows at
GPT_INIT_EMBEDDING_STD = 0.02


def share(config: Dict[str, Any]) -> Tuple[int, int, int]:
    """(first expert held here, how many, of how many experts)."""
    held = config["n_routed_experts"]
    s = config.get("share")
    if s is None:
        return 0, held, held
    return s["rank"] * held, held, s["n_routed_experts"]


def _kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    """"attention" | "kda" a layer, from `gqa_layers`."""
    n = config["num_hidden_layers"]
    gqa = set(config["gqa_layers"])
    if not gqa <= set(range(n)):
        raise ValueError(f"gqa_layers {sorted(gqa)} name layers past {n}")
    return tuple("attention" if i in gqa else "kda" for i in range(n))


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    linear = config["linear_attn_config"]
    if config["first_k_dense_replace"]:
        raise ValueError("the family builds no leading dense layer")
    if linear["head_dim"] != config["head_dim"]:
        raise ValueError("models/gpt.py keeps one head_dim for both mixers")
    if linear["num_heads"] != config["num_attention_heads"]:
        raise ValueError("models/gpt.py keeps one head count for both mixers")
    if linear["num_kv_heads"] not in (None, linear["num_heads"]):
        raise ValueError("models/gpt.py's 'kda' layer has as many k / v "
                         "heads as q heads")
    if config["kda_use_full_proj"]:
        raise ValueError("models/gpt.py's 'kda' layer projects the decay "
                         "and the gate through low-rank pairs")
    first, held, of = share(config)
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "layer_kinds": _kinds(config),
        "use_rope": bool(config["use_rope"]),
        "rope_theta": float(config["rope_theta"]),
        "attention_gate": "element" if config["use_gqa_gate"] else False,
        "conv_filter": linear["short_conv_kernel_size"],
        "kda_neg_eigval": bool(config["kda_allow_neg_eigval"]),
        "d_ff": config["moe_intermediate_size"],    # the width of ONE expert
        "max_seq": config["max_position_embeddings"],
        "rmsnorm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "n_experts": of,
        "expert_top_k": config["num_experts_per_tok"],
        "experts_held": None if held == of else (first, held),
        "router_score": "sigmoid",
        "router_bias_scale": float(config["selection_bias_init_std"]),
        "router_renormalise": bool(config["norm_topk_prob"]),
        "router_scale": float(config["routed_scaling_factor"]),
        "n_shared_experts": config["n_shared_experts"],
    }


def _train_config(config: Dict[str, Any]):
    """The GPTConfig the step is built from (bf16 activations, flash
    attention, the chunked delta rule, the grouped-matmul kernels, remat of
    the whole layer)."""
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(**gpt_config_kwargs(config), attention="flash",
                     remat_policy="full")


def program(config: Dict[str, Any], serving: bool = False):
    """The system under test at these sizes, as gpt_dense.program: the same
    models/gpt.py, told the kinds of its layers, the heads held of each, no
    rotation, the gate an element, the routing rule and the share of the
    experts."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init, gpt_loss

    if serving:
        cfg = GPTConfig(**gpt_config_kwargs(config), attention="flash")
    else:
        cfg = _train_config(config)

    def init(key):
        params = gpt_init(key, cfg)
        # the configuration's `assumed.init`: the embedding's rows at the
        # spread `embedding_init_std` gives them, not gpt_init's
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
    """n [S, d], a KDA layer's normed input -> what the layer adds, over the
    heads m's matrices hold."""
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
    if config["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    o = reference_delta_rule(q, k, v, log_decay, beta)
    o = _norm(o, m["o_norm"]["scale"], float(config["rms_norm_eps"]))
    gate = jax.nn.sigmoid(n @ m["wg_down"].astype(f32)
                          @ m["wg_up"].astype(f32))
    return (o.reshape(s, -1) * gate) @ m["wo"].astype(f32)


def _rotated(t, config: Dict[str, Any]):
    """t [S, heads, D]: every column rotated as halves at `rope_theta`."""
    import jax.numpy as jnp
    s, _, dim = t.shape
    angles = (jnp.arange(s, dtype=jnp.float32)[:, None]
              * float(config["rope_theta"])
              ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, c = t[..., :dim // 2], t[..., dim // 2:]
    return jnp.concatenate([a * cos - c * sin, a * sin + c * cos], -1)


def reference_attention(a, n, config: Dict[str, Any]):
    """n [S, d], a GQA layer's normed input -> what attention adds, over the
    heads a's matrices hold: no rotation, the causal mask, a gate an
    element."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    dim = config["head_dim"]
    s = n.shape[0]
    q = (n @ a["wq"].astype(f32)).reshape(s, -1, dim)
    k = (n @ a["wk"].astype(f32)).reshape(s, -1, dim)
    v = (n @ a["wv"].astype(f32)).reshape(s, -1, dim)
    if config["use_rope"]:
        # (the published model rotates nothing: the form a control takes)
        q, k = _rotated(q, config), _rotated(k, config)
    # each query head's own key/value head, written out
    reads = jnp.arange(q.shape[1]) // (q.shape[1] // k.shape[1])
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
        return jnp.einsum("hqk,khd->qhd", weights, v)
    mixed = jax.lax.map(queries, jnp.arange(0, s, block)).reshape(s, -1)
    if config["use_gqa_gate"]:
        mixed = mixed * jax.nn.sigmoid(n @ a["wg"].astype(f32))
    return mixed @ a["wo"].astype(f32)


def reference_routing(m, h, config: Dict[str, Any]):
    """h [S, d] -> [S, E] float32: w_e where expert e is among the token's
    chosen, 0 elsewhere, over ALL the experts."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    n_all = share(config)[2]
    scores = jax.nn.sigmoid(h @ m["router"].astype(f32))
    _, chosen = jax.lax.top_k(scores + m["router_bias"].astype(f32),
                              config["num_experts_per_tok"])
    kept = jax.nn.one_hot(chosen, n_all, dtype=f32).sum(axis=1) * scores
    if config["norm_topk_prob"]:
        kept = kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20)
    return kept * float(config["routed_scaling_factor"])


def reference_experts(m, h, config: Dict[str, Any]):
    """h [S, d], a layer's second normed input -> what the experts add: the
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
    return y + _swiglu(m["shared"], h, f32)


def reference_mixer(layer, n, config: Dict[str, Any]):
    """A layer's mixer, by what its parameters hold."""
    if "kda" in layer:
        return reference_kda(layer["kda"], n, config)
    return reference_attention(layer["attn"], n, config)


def _sequence(params, tokens, config):
    """tokens [S] -> final-normed hidden states [S, d]."""
    import jax.numpy as jnp
    eps = float(config["rms_norm_eps"])
    x = params["embed"]["table"].astype(jnp.float32)[tokens]
    for layer, kind in zip(params["layers"], _kinds(config)):
        if ("kda" in layer) != (kind == "kda"):
            raise ValueError("the parameters' layers are not gqa_layers'")
        x = x + reference_mixer(layer, _norm(x, layer["ln1"]["scale"], eps),
                                config)
        x = x + reference_experts(
            layer["moe"], _norm(x, layer["ln2"]["scale"], eps), config)
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
    reference's, as (median of the absolute gap, root mean square) and,
    this family's own, the 99th percentile of the absolute gap: what the one
    softmax layer adds is an average over a token's whole prefix, next to
    nothing but for the sequence's first tokens, so a fault of that layer
    moves a few tokens much and the median not at all. The
    first loss at random weights is log V plus half the logits' variance
    whatever the block computes; the tokens' own log-probabilities tell a
    decay left out, a beta not doubled, keys not normalised, a filter turned
    round, a dropped gate, a rotation where there is none and fp8 weights
    from bf16 rounding (the readings behind both bounds are in the
    configuration file). The program is the forward the step was built from,
    on one device, at the default matmul precision whatever the caller's
    (the delta rule's own products ask for full precision themselves)."""
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
    """Elements of each group of matrices: the two mixers (at the heads
    held), one expert, the shared one, the router."""
    d, dim = config["hidden_size"], config["head_dim"]
    wide = dim * config["num_attention_heads"]
    kv = dim * config["num_key_value_heads"]
    linear = config["linear_attn_config"]
    lin = linear["head_dim"] * linear["num_heads"]
    rank = linear["head_dim"]
    return {
        "attention": (2 * d * wide + 2 * d * kv
                      + (d * wide if config["use_gqa_gate"] else 0)),
        # q, k, v and the output; the decay's and the gate's low-rank pairs;
        # beta a head
        "kda": (4 * d * lin + 2 * (d * rank + rank * lin)
                + d * linear["num_heads"]),
        "expert": 3 * d * config["moe_intermediate_size"],
        "shared": (3 * d * config["moe_intermediate_size"]
                   * config["n_shared_experts"]),
        "router": d * share(config)[2]}


def _layers(config: Dict[str, Any]) -> Tuple[int, int]:
    """(GQA layers, KDA layers)."""
    kinds = _kinds(config)
    return kinds.count("attention"), kinds.count("kda")


def param_count(config: Dict[str, Any]) -> int:
    """Every parameter resident on the device: a mixer's matrices at the
    heads held (a KDA layer's three filters, its decay rate a head, its step
    bias a channel and its norm's scale beside them), two layer norms a
    layer; the router at its published width with its bias, the experts
    HELD and the shared expert; embedding and head over the vocabulary
    held, the final norm."""
    m = _matrices(config)
    d, v = config["hidden_size"], config["vocab_size"]
    linear = config["linear_attn_config"]
    lin = linear["head_dim"] * linear["num_heads"]
    small = (3 * lin * linear["short_conv_kernel_size"] + linear["num_heads"]
             + lin + linear["head_dim"])
    gqa, kda = _layers(config)
    _, held, of = share(config)
    return (gqa * m["attention"] + kda * (m["kda"] + small)
            + (gqa + kda) * (2 * d + m["router"] + of + held * m["expert"]
                             + m["shared"])
            + v * d + d + (0 if config["tie_word_embeddings"] else d * v))


def active_param_count(config: Dict[str, Any]) -> int:
    """param_count with, of a layer's experts, the `num_experts_per_tok` a
    token goes through: the "A15B" of the name, at the published sizes."""
    idle = share(config)[1] - config["num_experts_per_tok"]
    return param_count(config) - sum(_layers(config)) * idle * _matrices(
        config)["expert"]


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 x the matrix parameters a token activates HERE + 3 x the two
    mixers' own products: both mixers' projections, gates and low-rank
    pairs, the router, the shared expert and the routed slots expected on
    this chip (experts a token x held / all: the true count moves with the
    routing), the head over the vocabulary held; in a GQA layer q.k and p.v
    at head_dim under the causal mask (S / 2 keys a query: 4 head_dim x S /
    2 a query head forward); in a KDA layer the delta rule's products a
    token and head at the chunk the program runs
    (benchmark/kernels/kda.py:delta_rule_flops_per_token). The backward's two
    for one. Left out: the embedding lookup, the norms, the filters, the
    decays' exponentials, the softmax, the routing's sorts and gathers, and
    recomputation (remat)."""
    from benchmark.kernels.kda import delta_rule_flops_per_token
    m = _matrices(config)
    _, held, of = share(config)
    gqa, kda = _layers(config)
    slots = config["num_experts_per_tok"] * held / of
    active = (gqa * m["attention"] + kda * m["kda"]
              + (gqa + kda) * (m["router"] + m["shared"]
                               + slots * m["expert"])
              + config["hidden_size"] * config["vocab_size"])
    linear = config["linear_attn_config"]
    products = (gqa * config["num_attention_heads"] * 4.0
                * config["head_dim"] * seq / 2.0
                + kda * linear["num_heads"] * delta_rule_flops_per_token(
                    KDA_CHUNK, linear["head_dim"], linear["head_dim"]))
    return 6.0 * active + 3.0 * products


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0


def attention_call(config: Dict[str, Any], mix: Dict[str, Any]
                   ) -> Dict[str, int]:
    """One call of the GQA layer's flash kernels (`flash_fwd`,
    `flash_bwd_dq`, `flash_bwd_dkv`) on one chip under a training mix: q and
    the output [batch, heads, seq, head_dim], k and v [batch, kv_heads, seq,
    head_dim] ([1, 8 on 1, 8192, 128] at solar2_train_1chip).
    benchmark/kernels/gqa_attention.py counts it."""
    mesh = mix["mesh"]
    tensor = mesh.get("tensor", 1)
    return {"batch": mix["global_batch"] // (mesh.get("data", 1)
                                             * mesh.get("fsdp", 1)),
            "heads": config["num_attention_heads"] // tensor,
            "kv_heads": config["num_key_value_heads"] // tensor,
            "seq": mix["seq"], "head_dim": config["head_dim"]}


def kda_call(config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, int]:
    """One KDA layer's tensors on one chip under a training mix: q, k, v and
    the output [batch, heads, seq, head_dim] ([1, 8, 8192, 128] at
    solar2_train_1chip), the filters' taps. benchmark/kernels/kda.py counts
    the filter kernels' calls (one a tensor: [batch, seq, heads x head_dim])
    and the delta rule's."""
    mesh = mix["mesh"]
    linear = config["linear_attn_config"]
    return {"batch": mix["global_batch"] // (mesh.get("data", 1)
                                             * mesh.get("fsdp", 1)),
            "heads": linear["num_heads"] // mesh.get("tensor", 1),
            "seq": mix["seq"], "head_dim": linear["head_dim"],
            "taps": linear["short_conv_kernel_size"], "chunk": KDA_CHUNK}
