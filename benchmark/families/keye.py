"""The family `keye`: the language model of Keye-VL-2.0-30B-A3B (the
`KeyeVL2` model code its config.json names): a Qwen3-MoE decoder block,
grouped-query attention with a norm a head and sparse experts routed by a
renormalised softmax top-k, whose attention sees only the keys a learned
indexer chooses (`sa_config`; the form arXiv:2512.02556 publishes for
DeepSeek sparse attention), the indexer trained by a KL loss of its own.
What a family module holds is listed in gpt_dense.py.

The layer, as the reference below writes it out. x is [S, d]; RMSNorm at
`rms_norm_eps`; no bias. Every layer alike (`decoder_sparse_step` 1,
`mlp_only_layers` []):
  h = x + Attn(norm1(x));   y = h + MoE(norm2(h))
Attn, n the normed input, H = `num_attention_heads` query heads on Hkv =
`num_key_value_heads`, D = `head_dim`:
  q = n Wq as H heads, k = n Wk and v = n Wv as Hkv heads
  RMSNorm over each head's D columns of q and of k (one weight [D] each),
        then all D columns rotated as halves, theta `rope_theta`
  the indexer, on m = stop_gradient(n), Hi = `indexer_num_heads` heads of
        Di = `indexer_head_dim` on ONE key head:
        qI = m WqI as Hi heads;  kI = LayerNorm(m WkI) (weight, bias);
        both rotated whole, as halves, by the same theta at width Di
        w = m Ww * Hi^-1/2 * Di^-1/2                              [S, Hi]
        I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
  S_t = the min(t + 1, `topk`) keys s <= t of largest I[t, s], the lower s
        at a tie (what jax.lax.top_k chooses); a constant of the backward
  query head h reads key/value head h // (H / Hkv); P_h = softmax over S_t
        of q_h k^T / sqrt(D) in float32;  Attn = concat_h(P_h v) Wo
  the indexer's loss: p[t, s] = (1 / H) sum_h P_h[t, s], detached;
        L_I = mean_t KL(p[t, .] || softmax_{s in S_t} I[t, s])
MoE, m = norm2(h):
  s = softmax(m Wr) over ALL the experts, float32; the
  `num_experts_per_tok` largest, divided by their sum (`norm_topk_prob`);
  MoE = sum over chosen e of w_e down_e(silu(gate_e m) * up_e m), experts
        `moe_intermediate_size` wide; no shared expert
Final RMSNorm, an untied head. The training loss: cross-entropy +
`router_aux_loss_coef` x (E sum_e f_e P_e, a layer's own, averaged over
the layers) + the layers' L_I summed, at weight 1.

The chip's share (`share` in the configuration file; model-configs guide,
section 4): the file's `num_experts` and `vocab_size` are what is HELD
here, experts rank * held .. + held - 1 of `share.num_experts`. The router
keeps its published width and its experts a token; the sum above runs over
the chosen experts that are held, and what the others would have added is
left out, here and in the program alike. A file without `share` is the
whole layer (tests/test_selected_attention.py adds the shares up to it).

Departures and assumptions, each also in the configuration file: text
traffic, where `mrope_section`'s three position streams all hold the
token's index and the rotation is the plain one; no vision tower; the
published indexer's Hadamard rotation and fp8 cast are left out; k and v
stay at Hkv heads in the program; the router's matmul is float32 in
program and reference alike; seeded random weights.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict, Tuple

# queries a block of the reference's attention, scores, top-k and KL:
# [heads, block, S] float32 scores are 537 MB at 32 heads and 8192 positions
QUERY_BLOCK = 512

# the spread models/gpt.py:gpt_init draws the embedding's rows at
GPT_INIT_EMBEDDING_STD = 0.02


def share(config: Dict[str, Any]) -> Tuple[int, int, int]:
    """(first expert held here, how many, of how many experts)."""
    held = config["num_experts"]
    s = config.get("share")
    if s is None:
        return 0, held, held
    return s["rank"] * held, held, s["num_experts"]


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    if config["attention_bias"]:
        raise ValueError("models/gpt.py's projections have no bias, the "
                         "configuration has attention_bias true")
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise ValueError("every layer of this family is sparse: "
                         "decoder_sparse_step 1, mlp_only_layers []")
    if config["use_sliding_window"] or config["sliding_window"]:
        raise ValueError("this family has no sliding window")
    if config["rope_scaling"]["rope_type"] != "default":
        raise ValueError("the rotation is the plain one (rope_type default)")
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("ops/indexer.py scores against ONE key head")
    first, held, of = share(config)
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "qk_head_norm": True,
        "rope_theta": float(config["rope_theta"]),
        "index_heads": sa["indexer_num_heads"],
        "index_head_dim": sa["indexer_head_dim"],
        "index_topk": sa["topk"],
        "index_loss_coef": float(config["indexer_loss_coef"]),
        "d_ff": config["moe_intermediate_size"],    # the width of ONE expert
        "max_seq": config["max_position_embeddings"],
        "rmsnorm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "n_experts": of,
        "expert_top_k": config["num_experts_per_tok"],
        "experts_held": None if held == of else (first, held),
        "router_score": "softmax",
        "router_renormalise": bool(config["norm_topk_prob"]),
        "router_aux_loss_coef": float(config["router_aux_loss_coef"]),
        "router_z_loss_coef": 0.0,
    }


def _train_config(config: Dict[str, Any]):
    """The GPTConfig the step is built from (bf16 activations, the
    `flash_sel_*` kernels under the indexer's selection, the grouped-matmul
    kernels, remat of the whole layer)."""
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(**gpt_config_kwargs(config), attention="flash",
                     remat_policy="full")


def program(config: Dict[str, Any], serving: bool = False):
    """The system under test at these sizes, as gpt_dense.program: the same
    models/gpt.py, told the grouped queries, the norm a head, the indexer,
    the routing rule and the share of the experts."""
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
# time (lax.map over the batch), attention, the indexer's scores, top-k and
# KL a block of queries at a time, the routed sum one expert at a time. Call
# it under jax.default_matmul_precision("highest"). (program_logprob_gap,
# below the reference, is not part of it: it runs the program, to hold it to
# the reference where the harness cannot.)
# ---------------------------------------------------------------------------

def _norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _layer_norm(x, weights, eps):
    import jax
    import jax.numpy as jnp
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weights["scale"].astype(jnp.float32) \
        + weights["bias"].astype(jnp.float32)


def _rotated(t, theta: float):
    """t [S, heads, D]: all D columns rotated as halves."""
    import jax.numpy as jnp
    s, _, dim = t.shape
    half = dim // 2
    angles = (jnp.arange(s, dtype=jnp.float32)[:, None]
              * theta ** (-jnp.arange(half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, c = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - c * sin, a * sin + c * cos], -1)


def _kv_head_of(heads: int, kv_heads: int):
    """The key/value head each query head reads."""
    import jax.numpy as jnp
    return jnp.arange(heads) // (heads // kv_heads)


def reference_index(ix, n, config: Dict[str, Any]):
    """n [S, d], the layer's normed input -> the indexer's (qI [S, Hi, Di],
    kI [S, Di], w [S, Hi]), rotated and scaled."""
    import jax.numpy as jnp
    f32 = jnp.float32
    sa = config["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    s = n.shape[0]
    qi = _rotated((n @ ix["wq"].astype(f32)).reshape(s, hi, di), theta)
    ki = _layer_norm(n @ ix["wk"].astype(f32), ix["k_norm"], eps)
    ki = _rotated(ki[:, None, :], theta)[:, 0]
    w = (n @ ix["ww"].astype(f32)) * hi ** -0.5 * di ** -0.5
    return qi, ki, w


def index_scores(qi, ki, w):
    """I [Q, S] of a block of queries: sum_j w[t, j] relu(qI[t, j] .
    kI[s])."""
    import jax
    import jax.numpy as jnp
    return jnp.einsum("qh,qhk->qk", w,
                      jax.nn.relu(jnp.einsum("qhd,kd->qhk", qi, ki)))


def chosen_keys(scores, seen, topk: int):
    """scores [Q, S], seen [Q, S] (the causal pairs) -> bool [Q, S]: each
    query's min(keys it sees, topk) keys of largest score, the lower key at
    a tie: jax.lax.top_k's choice among the keys it sees."""
    import jax
    import jax.numpy as jnp
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf),
                           min(topk, scores.shape[1]))
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return picked & seen


def reference_attention(a, n, config: Dict[str, Any]):
    """n [S, d], an attention layer's normed input -> (what attention adds
    [S, d], the indexer's loss summed over the S queries, the selected
    pairs)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim, eps = config["head_dim"], float(config["rms_norm_eps"])
    theta, topk = float(config["rope_theta"]), config["sa_config"]["topk"]
    s = n.shape[0]

    q = (n @ a["wq"].astype(f32)).reshape(s, heads, dim)
    k = (n @ a["wk"].astype(f32)).reshape(s, kv_heads, dim)
    v = (n @ a["wv"].astype(f32)).reshape(s, kv_heads, dim)
    q = _rotated(_norm(q, a["q_head_norm"]["scale"], eps), theta)
    k = _rotated(_norm(k, a["k_head_norm"]["scale"], eps), theta)
    # each query head's own key/value head, written out
    reads = _kv_head_of(heads, kv_heads)
    k, v = k[:, reads], v[:, reads]
    qi, ki, w = reference_index(a["index"], jax.lax.stop_gradient(n), config)

    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} positions are not whole blocks of {block}")
    at = jnp.arange(s)

    def queries(start):
        def rows(t):
            return jax.lax.dynamic_slice_in_dim(t, start, block)
        index = index_scores(rows(qi), ki, rows(w))             # [Q, S]
        seen = (start + jnp.arange(block))[:, None] >= at[None, :]
        chosen = chosen_keys(index, seen, topk)
        scores = jnp.einsum("qhd,khd->hqk", rows(q), k) / math.sqrt(dim)
        weights = jax.nn.softmax(jnp.where(chosen, scores, -jnp.inf), -1)
        mixed = jnp.einsum("hqk,khd->qhd", weights, v)
        # the indexer's KL from the heads' mean, detached
        p = jax.lax.stop_gradient(jnp.mean(weights, axis=0))
        log_r = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(chosen & (p > 0),
                               p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                    - jnp.where(chosen, log_r, 0.0)), 0.0))
        return mixed, kl, jnp.sum(chosen)
    mixed, kl, pairs = jax.lax.map(queries, jnp.arange(0, s, block))
    return (mixed.reshape(s, heads * dim) @ a["wo"].astype(f32),
            jnp.sum(kl), jnp.sum(pairs))


def reference_routing(m, h, config: Dict[str, Any]):
    """h [S, d] -> (w [S, E] float32: w_e where expert e is among the
    token's chosen, 0 elsewhere, over ALL the experts; tokens that chose
    each expert [E]; the summed probabilities [E])."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    n_all = share(config)[2]
    probs = jax.nn.softmax(h @ m["router"].astype(f32), -1)
    _, chosen = jax.lax.top_k(probs, config["num_experts_per_tok"])
    picked = jax.nn.one_hot(chosen, n_all, dtype=f32).sum(axis=1)
    kept = picked * probs
    if config["norm_topk_prob"]:
        kept = kept / jnp.sum(kept, -1, keepdims=True)
    return kept, jnp.sum(picked, 0), jnp.sum(probs, 0)


def reference_experts(m, h, config: Dict[str, Any]):
    """h [S, d], a layer's second normed input -> (what the layer adds: the
    weighted sum over each token's chosen experts THAT ARE HELD (m's
    matrices: experts first .. first + held - 1), the router's two sums)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    first, held, _ = share(config)
    weights, chose, probs = reference_routing(m, h, config)

    def expert(y, e):
        out = (jax.nn.silu(h @ m["w_gate"][e].astype(f32))
               * (h @ m["w_up"][e].astype(f32))) @ m["w_down"][e].astype(f32)
        return y + weights[:, first + e, None] * out, None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(held))
    return y, chose, probs


def _sequence(params, tokens, config):
    """tokens [S] -> (final-normed hidden states [S, d], a layer: (the
    indexer's loss summed over the queries, the selected pairs, tokens that
    chose each expert [E], the summed router probabilities [E]))."""
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = float(config["rms_norm_eps"])
    x = params["embed"]["table"].astype(f32)[tokens]
    sums = []
    for layer in params["layers"]:
        n = _norm(x, layer["ln1"]["scale"], eps)
        mixed, kl, pairs = reference_attention(layer["attn"], n, config)
        x = x + mixed
        h = _norm(x, layer["ln2"]["scale"], eps)
        y, chose, probs = reference_experts(layer["moe"], h, config)
        x = x + y
        sums.append((kl, pairs, chose, probs))
    return _norm(x, params["final_norm"]["scale"], eps), sums


def _head(params, config):
    import jax.numpy as jnp
    if not config["tie_word_embeddings"]:
        return params["lm_head"].astype(jnp.float32)
    return params["embed"]["table"].astype(jnp.float32).T


def reference_logits(params, tokens, config: Dict[str, Any]):
    """tokens [B, S] -> float32 logits [B, S, vocab held]."""
    import jax
    x = jax.lax.map(lambda row: _sequence(params, row, config)[0], tokens)
    return x @ _head(params, config)


def _logprobs_and_sums(params, tokens, config):
    """[B, S] -> ([B, S-1] log-probability of each token after the first
    given those before it, the layers' sums of `_sequence` over the
    positions that predict one, each [B, ...])."""
    import jax
    import jax.numpy as jnp

    def sequence(row):
        x, sums = _sequence(params, row[:-1], config)
        z = x @ _head(params, config)
        picked = jnp.take_along_axis(z, row[1:, None], axis=-1)[:, 0]
        return picked - jax.nn.logsumexp(z, axis=-1), sums
    return jax.lax.map(sequence, tokens)


def reference_logprobs(params, tokens, config: Dict[str, Any]):
    return _logprobs_and_sums(params, tokens, config)[0]


def reference_losses(params, tokens, config: Dict[str, Any]):
    """tokens [B, S+1] -> (log-probabilities [B, S], cross-entropy, the
    load-balancing loss averaged over the layers, the indexers' KL summed
    over the layers, the selected pairs over the causal pairs averaged over
    the layers), the router's f and P and the KL's mean over B x S."""
    import jax.numpy as jnp
    logp, layers = _logprobs_and_sums(params, tokens, config)
    n_tokens, seq = logp.size, logp.shape[1]
    n_all = share(config)[2]
    balance = kl = selected = 0.0
    for kl_sum, pairs, chose, probs in layers:
        f, p = jnp.sum(chose, 0) / n_tokens, jnp.sum(probs, 0) / n_tokens
        balance += n_all * jnp.sum(f * p) / len(layers)
        kl += jnp.sum(kl_sum) / n_tokens
        selected += jnp.sum(pairs) / (logp.shape[0] * seq * (seq + 1) / 2.0
                                      ) / len(layers)
    return logp, -jnp.mean(logp), balance, kl, selected


def reference_loss(params, tokens, config: Dict[str, Any]):
    """tokens [B, S+1] -> the training loss over B x S: cross-entropy +
    `router_aux_loss_coef` x the load-balancing loss + `indexer_loss_coef`
    x the layers' KL.

    Where the configuration has a `program_check`, the number comes back
    only if the program's own forward agrees with the reference token by
    token (program_logprob_gap below), and is nan otherwise: the harness
    (train_cell.py) holds a run to this one number, and nan is within no
    tolerance of any first loss."""
    import jax.numpy as jnp
    logp, xent, balance, kl, _ = reference_losses(params, tokens, config)
    loss = (xent + config["router_aux_loss_coef"] * balance
            + config["indexer_loss_coef"] * kl)
    check = config.get("program_check")
    if check is None:
        return loss
    median, rms = program_logprob_gap(params, tokens, config, logp)
    held = (median <= check["logprob_median_tol"]) \
        & (rms <= check["logprob_rms_tol"])
    return jnp.where(held, loss, jnp.nan)


def program_logprob_gap(params, tokens, config: Dict[str, Any], reference):
    """The sharper half of `correct`, as families/olmoe.py, kanana.py,
    lfm2.py and laguna.py have it: over the B x S predicted tokens, the
    program's log-probability less the reference's, as (median of the
    absolute gap, root mean square). The first loss at random weights is
    log V plus half the logits' variance plus the KLs whatever the block
    computes; the tokens' own log-probabilities tell a selection that is
    not the indexer's best `topk`, an indexer without its relu, weights,
    norm or rotation, the wrong key/value head, a missing norm a head, a
    top-k not renormalised and fp8 weights from bf16 rounding (the readings
    behind both bounds are in the configuration file). The program is the
    forward the step was built from, on one device, at the default matmul
    precision whatever the caller's."""
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
    """Elements of each group of matrices of a layer: attention's four, the
    indexer's three, one expert, the router."""
    d, dim = config["hidden_size"], config["head_dim"]
    sa = config["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {"attention": 2 * d * dim * (config["num_attention_heads"]
                                        + config["num_key_value_heads"]),
            "indexer": d * hi * di + d * di + d * hi,
            "expert": 3 * d * config["moe_intermediate_size"],
            "router": d * share(config)[2]}


def param_count(config: Dict[str, Any]) -> int:
    """Every parameter resident on the device: a layer's four attention
    matrices and two head norms, the indexer's three matrices and its
    LayerNorm, two layer norms, the router at its published width and the
    experts HELD; embedding and head over the vocabulary held, the final
    norm."""
    m = _matrices(config)
    d, v = config["hidden_size"], config["vocab_size"]
    layer = (m["attention"] + 2 * config["head_dim"] + m["indexer"]
             + 2 * config["sa_config"]["indexer_head_dim"] + 2 * d
             + m["router"] + share(config)[1] * m["expert"])
    return (config["num_hidden_layers"] * layer + v * d + d
            + (0 if config["tie_word_embeddings"] else d * v))


def active_param_count(config: Dict[str, Any]) -> int:
    """param_count with, of a layer's experts, the `num_experts_per_tok` a
    token goes through: the "A3B" of the name."""
    idle = share(config)[1] - config["num_experts_per_tok"]
    return param_count(config) - config["num_hidden_layers"] * idle * \
        _matrices(config)["expert"]


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs one head keeps under the selection: topk keys a
    query, every causal key for the first topk - 1."""
    k = min(topk, seq)
    return seq * k - k * (k - 1) // 2


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """The MODEL's arithmetic: 6 x the matrix parameters a token activates
    HERE (attention's four, the indexer's three, the router, the routed
    slots expected on this chip: experts a token x held / all, the head
    over the vocabulary held) + the main attention's two products over the
    SELECTED pairs alone (`selected_pairs` / S keys a query head: 3 x 4
    head_dim each, the backward's two for one, kanana's convention) + the
    indexer's one product over the causal pairs, forward and its two
    backward (3 x 2 indexer_head_dim a pair and index head). Left out: the
    embedding lookup, the norms, the softmaxes, RoPE, the relu and the
    weighted sum of the index heads, the search for a row's topk-th
    largest, the KL and its target (the attention's own probabilities, a
    second time in the program), the routing's sorts and gathers, and
    recomputation (remat)."""
    m = _matrices(config)
    _, held, of = share(config)
    sa = config["sa_config"]
    layers = config["num_hidden_layers"]
    slots = config["num_experts_per_tok"] * held / of
    active = (layers * (m["attention"] + m["indexer"] + m["router"]
                        + slots * m["expert"])
              + config["hidden_size"] * config["vocab_size"])
    main = (3.0 * 4.0 * config["head_dim"] * config["num_attention_heads"]
            * selected_pairs(seq, sa["topk"]) / seq)
    index = (3.0 * 2.0 * sa["indexer_head_dim"] * sa["indexer_num_heads"]
             * (seq + 1) / 2.0)
    return 6.0 * active + layers * (main + index)


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0


def attention_call(config: Dict[str, Any], mix: Dict[str, Any]
                   ) -> Dict[str, int]:
    """One call of the selected-attention kernels (`flash_sel_fwd`,
    `flash_sel_bwd_dq`, `flash_sel_bwd_dkv`) on one chip under a training
    mix: q and the output [batch, heads, seq, head_dim], k and v [batch,
    kv_heads, seq, head_dim], the selection [batch, seq, seq] with `topk`
    keys a query ([2, 32 on 4, 8192, 128], 2048 at keye2_train_1chip).
    benchmark/kernels/selected_attention.py counts it."""
    mesh = mix["mesh"]
    tensor = mesh.get("tensor", 1)
    return {"batch": mix["global_batch"] // (mesh.get("data", 1)
                                             * mesh.get("fsdp", 1)),
            "heads": config["num_attention_heads"] // tensor,
            "kv_heads": config["num_key_value_heads"] // tensor,
            "seq": mix["seq"], "head_dim": config["head_dim"],
            "topk": config["sa_config"]["topk"]}
