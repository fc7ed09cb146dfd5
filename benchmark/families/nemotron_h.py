"""The family `nemotron_h`: the decoder stack of NVIDIA-Nemotron-3-Super-
120B-A12B (`model_type` `nemotron_h`): a stack in which every layer is ONE
norm and ONE of three halves, named by a letter of `hybrid_override_pattern`:
`M` a Mamba-2 mixer alone, `E` a feed-forward of latent experts alone, `*`
grouped-query softmax attention alone; and a multi-token prediction module of
one depth (`num_nextn_predict_layers`, `mtp_hybrid_override_pattern`). What a
family module holds is listed in gpt_dense.py.

The layers, as the reference below writes them out. x is [S, d]; RMSNorm with
`norm_eps` everywhere; no bias but the filter's; untied embedding and head; no
position enters but through the causal mask and the state. Every layer:
  x <- x + f(n),  n = RMSNorm(x),  f by the layer's letter.

`M`, Mamba-2 (`expand`: d_inner = H x P, H `mamba_num_heads` heads of P
`mamba_head_dim`; G `n_groups`; N `ssm_state_size`; `conv_kernel` taps with a
bias, `use_conv_bias`; `chunk_size` sizes the program's chunks only):
  [z | xBC | dt] = n W_in, widths H P | H P + 2 G N | H (the parameters hold
        the three parts: w_z, w_xbc, w_dt)
  xBC <- silu(filter(xBC) + bias): a causal depthwise filter a channel (zeros
        before the start, the last tap on the token itself); split into
        x [H, P], B [G, N], C [G, N]; head h reads group h // (H / G)
  dt = softplus(dt + dt_bias) a head (no upper clamp: `time_step_limit` is
        absent),  a_t = exp(-exp(A_log_h) dt_t), a number a head
  a state S [P, N] a head, float32, S_0 = 0, a token at a time:
        S_t = a_t S_{t-1} + dt_t x_t B_t^T,   y_t = S_t C_t + D_h x_t
  f = [RMSNorm_group(y * silu(z)) * w] W_out, the mean square over each
        group's H P / G columns (the gate BEFORE the norm)
`*`: H `num_attention_heads` query heads on `num_key_value_heads` at
  `head_dim`, q = n Wq, k = n Wk, v = n Wv, no bias, no q/k norm, NO rotation
  (`assumed.no_rotation`); query head h reads key/value head h // (H / Hkv);
  softmax of q k^T / sqrt(head_dim) in float32 over j <= i; f = o Wo
`E`, latent experts (`mlp_hidden_act` relu2: act(v) = relu(v)^2, no gate
  matrix):
  s = sigmoid(n W_r) over ALL `n_routed_experts`, float32
  chosen: the `num_experts_per_tok` largest s_e + bias_e (the bias enters the
        choice alone; `n_group` 1 / `topk_group` 1: no group limit)
  w_e = s_e / (sum of the chosen s) (`norm_topk_prob`) x
        `routed_scaling_factor`
  u = n W_latent_in [d, `moe_latent_size`]
  f = (sum over chosen e of w_e down_e(act(up_e u))) W_latent_out + Shared(n),
        up_e [latent, `moe_intermediate_size`], down_e its transpose's shape;
        Shared(n) = W_d act(W_u n) at `moe_shared_expert_intermediate_size`
        on the FULL hidden size; the router reads the full hidden size. No
        router loss: cross-entropy alone
Final RMSNorm, then the head.
Prediction module (one depth; DeepSeek-V3's form, arXiv:2412.19437 section
2.2, `assumed.mtp`): with h the stream BEFORE the final norm and e the
embedding,
  g_i = [RMSNorm(e(t_{i+1})) ; RMSNorm(h_i)] W_mtp [2 d, d]
  then the layers of `mtp_hybrid_override_pattern` on g, a norm of its own,
  the SAME head;  L = CE(t_{i+1} | h_i) + lambda CE(t_{i+2} | g_i), the
  second mean over the positions that have a token two ahead.

The chip's share (`share` in the configuration file; model-configs guide,
section 4): the file's `n_routed_experts`, `vocab_size`,
`num_attention_heads`, `num_key_value_heads`, `mamba_num_heads` and `n_groups`
are what is HELD here: experts rank * held .. + held - 1 of
`share.n_routed_experts`, and of the mixers the heads (with their groups)
whose matrices the parameters hold: a mixer's head needs nothing of another's
before the output projection, whose rows are summed, and the gated norm is a
group's own. The router keeps its published width and its experts a token;
what the experts and the heads that are not held would have added is left
out, here and in the program alike; the shared expert, both latent
projections and the norms are whole on every chip. A file without `share` is
the whole layer (tests/test_state_space.py adds the shares up to it).

Departures and assumptions, each also in the configuration file: the program
runs the scan in chunks (ops/state_space.py), the reference a token at a
time; k and v of a `*` layer stay at Hkv heads in the program; the router's
matmul is float32 in program and reference alike; the selection bias's update
rule is not built; seeded random weights, the embedding's rows at the spread
`embedding_init_std`.

`FAULTS`: the reference computed WRONG on purpose, one fault a name, for the
controls that show the comparison tells each apart (`config["fault"]`, which
no configuration file sets).
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict, Tuple

# queries a block of the reference's attention
QUERY_BLOCK = 512

# the spread models/gpt.py:gpt_init draws the embedding's rows at
GPT_INIT_EMBEDDING_STD = 0.02

# a letter of the pattern -> models/gpt.py's kind of layer
KINDS = {"M": "ssm", "E": "ff", "*": "attention_alone"}

FAULTS = ("no_decay", "dt_raw", "no_skip", "gate_after_norm", "relu",
          "unscaled", "no_mtp")


def _fault(config: Dict[str, Any], name: str) -> bool:
    fault = config.get("fault")
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: expected one of {FAULTS}")
    return fault == name


def share(config: Dict[str, Any]) -> Tuple[int, int, int]:
    """(first expert held here, how many, of how many experts)."""
    held = config["n_routed_experts"]
    s = config.get("share")
    if s is None:
        return 0, held, held
    return s["rank"] * held, held, s["n_routed_experts"]


def _letters(config: Dict[str, Any], key: str) -> Tuple[str, ...]:
    pattern = config[key]
    if set(pattern) - set(KINDS):
        raise ValueError(f"{key} {pattern!r}: expected letters of "
                         f"{sorted(KINDS)}")
    return tuple(KINDS[letter] for letter in pattern)


def _kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    kinds = _letters(config, "hybrid_override_pattern")
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern is not num_hidden_layers "
                         "long")
    return kinds


def _module_kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    """The prediction module's layers; () where the model has none."""
    if not config["num_nextn_predict_layers"]:
        return ()
    if config["num_nextn_predict_layers"] != 1:
        raise ValueError("the family builds a prediction module of one depth")
    return _letters(config, "mtp_hybrid_override_pattern")


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    from ray_tpu.models.gpt import ExpertForm, PredictionModule, StateSpace
    if config["mlp_hidden_act"] != "relu2" or config["mamba_hidden_act"] != "silu":
        raise ValueError("the family writes relu2 experts and a SiLU mixer")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("the family builds no group-limited routing")
    if not config["use_conv_bias"] or config["use_bias"] \
            or config["mamba_proj_bias"] or config["mlp_bias"] \
            or config["attention_bias"]:
        raise ValueError("the family has a bias on the filter and nowhere "
                         "else")
    heads = (config.get("share") or {}).get("mamba_num_heads",
                                            config["mamba_num_heads"])
    if config["expand"] * config["hidden_size"] != (
            heads * config["mamba_head_dim"]):
        raise ValueError("expand x hidden_size is not the mixer's heads x "
                         "mamba_head_dim")
    if (config["time_step_min"], config["time_step_max"],
            config["time_step_floor"]) != (1e-3, 0.1, 1e-4):
        raise ValueError("models/gpt.py seeds a state-space layer's step "
                         "log-uniform over 1e-3..0.1, floored at 1e-4")
    first, held, of = share(config)
    module = _module_kinds(config)
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "layer_kinds": _kinds(config),
        "use_rope": False,                       # assumed.no_rotation
        "d_ff": config["moe_intermediate_size"],    # the width of ONE expert
        "max_seq": config["max_position_embeddings"],
        "rmsnorm_eps": float(config["norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "n_experts": of,
        "expert_top_k": config["num_experts_per_tok"],
        "experts_held": None if held == of else (first, held),
        "router_score": "sigmoid",
        "router_bias_scale": float(config["selection_bias_init_std"]),
        "router_renormalise": bool(config["norm_topk_prob"]),
        "router_scale": float(config["routed_scaling_factor"]),
        "n_shared_experts": config["n_shared_experts"],
        "ssm": StateSpace(
            heads=config["mamba_num_heads"], head_dim=config["mamba_head_dim"],
            groups=config["n_groups"], state=config["ssm_state_size"],
            chunk=config["chunk_size"]),
        "conv_filter": config["conv_kernel"],
        "expert_form": ExpertForm(
            matrices=2, activation="relu2",
            latent_dim=config["moe_latent_size"],
            shared_d_ff=config["moe_shared_expert_intermediate_size"]),
        "mtp": PredictionModule(
            layer_kinds=module,
            loss_coef=float(config["mtp_loss_coef"])) if module else None,
    }


def _train_config(config: Dict[str, Any]):
    """The GPTConfig the step is built from (bf16 activations, flash
    attention, the chunked scan, the filter and grouped-matmul kernels, remat
    of the whole layer)."""
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(**gpt_config_kwargs(config), attention="flash",
                     remat_policy="full")


def program(config: Dict[str, Any], serving: bool = False):
    """The system under test at these sizes, as gpt_dense.program: the same
    models/gpt.py, told the kind of each layer, the state-space mixer's
    sizes, the experts' form and latent width, the heads and experts held,
    and the prediction module."""
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
# time (lax.map over the batch), the state-space recurrence a token at a time,
# attention a block of queries at a time, the routed sum one expert at a
# time. Call it under jax.default_matmul_precision("highest").
# (program_logprob_gap, below the reference, is not part of it: it runs the
# program, to hold it to the reference where the harness cannot.)
# ---------------------------------------------------------------------------

def _norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _act(v, config):
    import jax
    r = jax.nn.relu(v)
    return r if _fault(config, "relu") else r * r


def _relu2_mlp(m, h, config, index=None):
    """down(act(up h)) through m's two matrices (expert `index` of a stack)."""
    import jax.numpy as jnp
    f32 = jnp.float32
    up, down = m["w_up"], m["w_down"]
    if index is not None:
        up, down = up[index], down[index]
    return _act(h @ up.astype(f32), config) @ down.astype(f32)


def reference_scan(x, dt, a_log, b, c, d, config):
    """x [S, H, P], dt [S, H], a_log and d [H], b and c [S, G, N] -> y
    [S, H, P]: the recurrence, a token a step."""
    import jax
    import jax.numpy as jnp
    heads, width = x.shape[1:]
    per_group = heads // b.shape[1]
    rate = jnp.exp(a_log.astype(jnp.float32))

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        decay = jnp.ones_like(dt_t) if _fault(config, "no_decay") \
            else jnp.exp(-rate * dt_t)
        b_t = jnp.repeat(b_t, per_group, axis=0)              # [H, N]
        c_t = jnp.repeat(c_t, per_group, axis=0)
        state = decay[:, None, None] * state + jnp.einsum(
            "hp,hn->hpn", dt_t[:, None] * x_t, b_t)
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(
        token, jnp.zeros((heads, width, b.shape[-1]), jnp.float32),
        (x, dt, b, c))
    if _fault(config, "no_skip"):
        return y
    return y + d.astype(jnp.float32)[:, None] * x


def reference_mamba(m, n, config: Dict[str, Any]):
    """n [S, d], an `M` layer's normed input -> what the layer adds, over the
    heads (and their groups) m's matrices hold."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    s = n.shape[0]
    width, state = config["mamba_head_dim"], config["ssm_state_size"]
    z = n @ m["w_z"].astype(f32)
    inner = z.shape[1]
    xbc = n @ m["w_xbc"].astype(f32)
    taps = m["conv"].astype(f32)
    padded = jnp.pad(xbc, ((taps.shape[1] - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(taps[:, j] * padded[j:j + s]
                          for j in range(taps.shape[1]))
                      + m["conv_bias"].astype(f32))
    directions = (xbc.shape[1] - inner) // 2
    groups = directions // state
    dt = n @ m["w_dt"].astype(f32) + m["dt_bias"].astype(f32)
    if not _fault(config, "dt_raw"):
        dt = jax.nn.softplus(dt)
    y = reference_scan(
        xbc[:, :inner].reshape(s, -1, width), dt, m["a_log"],
        xbc[:, inner:inner + directions].reshape(s, groups, state),
        xbc[:, inner + directions:].reshape(s, groups, state), m["d"],
        config).reshape(s, inner)
    gate = jax.nn.silu(z)
    scale = m["norm"]["scale"].astype(f32)
    eps = float(config["norm_eps"])

    def group_norm(t):
        t = t.reshape(s, groups, -1)
        t = t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + eps)
        return t.reshape(s, inner) * scale
    y = group_norm(y) * gate if _fault(config, "gate_after_norm") \
        else group_norm(y * gate)
    return y @ m["w_out"].astype(f32)


def reference_attention(a, n, config: Dict[str, Any]):
    """n [S, d], a `*` layer's normed input -> what attention adds, over the
    heads a's matrices hold: no rotation, the causal mask."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    dim = config["head_dim"]
    s = n.shape[0]
    q = (n @ a["wq"].astype(f32)).reshape(s, -1, dim)
    k = (n @ a["wk"].astype(f32)).reshape(s, -1, dim)
    v = (n @ a["wv"].astype(f32)).reshape(s, -1, dim)
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
    if _fault(config, "unscaled"):
        return kept
    return kept * float(config["routed_scaling_factor"])


def reference_experts(m, h, config: Dict[str, Any]):
    """h [S, d], an `E` layer's normed input -> what the layer adds: the
    weighted sum over each token's chosen experts THAT ARE HELD (m's
    matrices: experts first .. first + held - 1) in the latent width, back
    through the latent projection, and the shared expert on the full width."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    first, held, _ = share(config)
    weights = reference_routing(m, h, config)
    u = h @ m["w_latent_in"].astype(f32)

    def expert(y, e):
        return y + weights[:, first + e, None] * _relu2_mlp(m, u, config,
                                                            e), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(u), jnp.arange(held))
    return y @ m["w_latent_out"].astype(f32) + _relu2_mlp(m["shared"], h,
                                                           config)


def reference_layer(layer, x, config: Dict[str, Any]):
    """x [S, d] -> x + f(RMSNorm(x)), f by what the layer's parameters hold."""
    n = _norm(x, layer["ln1"]["scale"], float(config["norm_eps"]))
    if "ssm" in layer:
        return x + reference_mamba(layer["ssm"], n, config)
    if "moe" in layer:
        return x + reference_experts(layer["moe"], n, config)
    return x + reference_attention(layer["attn"], n, config)


def _letter(layer) -> str:
    return "M" if "ssm" in layer else "E" if "moe" in layer else "*"


def _stream(params, tokens, config):
    """tokens [S] -> the residual stream before the final norm [S, d]."""
    import jax.numpy as jnp
    x = params["embed"]["table"].astype(jnp.float32)[tokens]
    if "".join(map(_letter, params["layers"])) != \
            config["hybrid_override_pattern"]:
        raise ValueError("the parameters' layers are not the pattern's")
    for layer in params["layers"]:
        x = reference_layer(layer, x, config)
    return x


def _ahead(params, h, targets, config):
    """The prediction module: h [S, d] the stream before the final norm,
    targets [S] the token after each position -> what the head reads to
    predict the token two after each position [S, d]."""
    import jax.numpy as jnp
    m, eps = params["mtp"], float(config["norm_eps"])
    e = params["embed"]["table"].astype(jnp.float32)[targets]
    g = jnp.concatenate([_norm(e, m["norm_e"]["scale"], eps),
                         _norm(h, m["norm_h"]["scale"], eps)], -1) \
        @ m["proj"].astype(jnp.float32)
    for layer in m["layers"]:
        g = reference_layer(layer, g, config)
    return _norm(g, m["norm"]["scale"], eps)


def _head(params, config):
    import jax.numpy as jnp
    if not config["tie_word_embeddings"]:
        return params["lm_head"].astype(jnp.float32)
    return params["embed"]["table"].astype(jnp.float32).T


def _picked(z, tokens):
    import jax
    import jax.numpy as jnp
    return jnp.take_along_axis(z, tokens[:, None], axis=-1)[:, 0] \
        - jax.nn.logsumexp(z, axis=-1)


def _next_logprobs(params, h, targets, config):
    """h [S, d], the stream before the final norm -> log-probability of
    targets [S], the token after each position."""
    x = _norm(h, params["final_norm"]["scale"], float(config["norm_eps"]))
    return _picked(x @ _head(params, config), targets)


def reference_logits(params, tokens, config: Dict[str, Any]):
    """tokens [B, S] -> float32 logits [B, S, vocab held] (the next token's)."""
    import jax
    eps = float(config["norm_eps"])
    x = jax.lax.map(
        lambda row: _norm(_stream(params, row, config),
                          params["final_norm"]["scale"], eps), tokens)
    return x @ _head(params, config)


def reference_both_logprobs(params, tokens, config: Dict[str, Any]):
    """tokens [B, S + 1] -> (log-probability of each token after the first
    given the tokens before it [B, S], and through the prediction module of
    each token after the second given the tokens up to TWO before it and the
    one before it [B, S - 1]; None where the model has no module), over the
    vocabulary held."""
    import jax
    module = "mtp" in params

    def sequence(row):
        h = _stream(params, row[:-1], config)
        first = _next_logprobs(params, h, row[1:], config)
        if not module:
            return first, first[:-1]
        g = _ahead(params, h, row[1:], config)
        return first, _picked(g[:-1] @ _head(params, config), row[2:])
    first, second = jax.lax.map(sequence, tokens)
    return first, second if module else None


def reference_logprobs(params, tokens, config: Dict[str, Any]):
    """[B, S] -> [B, S-1]: log-probability of each token after the first
    given the tokens before it, over the vocabulary held."""
    import jax
    return jax.lax.map(
        lambda row: _next_logprobs(
            params, _stream(params, row[:-1], config), row[1:], config),
        tokens)


def reference_loss(params, tokens, config: Dict[str, Any]):
    """tokens [B, S+1] -> the training loss over B x S: the mean next-token
    cross-entropy plus `mtp_loss_coef` times the mean cross-entropy of the
    token two ahead through the prediction module (over the B x (S - 1)
    positions that have one), and nothing else.

    Where the configuration has a `program_check`, the number comes back
    only if the program's own forward agrees with the reference token by
    token (program_logprob_gap below), and is nan otherwise: the harness
    (train_cell.py) holds a run to this one number, and nan is within no
    tolerance of any first loss."""
    import jax.numpy as jnp
    first, second = reference_both_logprobs(params, tokens, config)
    loss = -jnp.mean(first)
    if second is not None and not _fault(config, "no_mtp"):
        loss = loss - float(config["mtp_loss_coef"]) * jnp.mean(second)
    check = config.get("program_check")
    if check is None:
        return loss
    median, rms, tail = program_logprob_gap(params, tokens, config, first,
                                            second)
    held = (median <= check["logprob_median_tol"]) \
        & (rms <= check["logprob_rms_tol"]) \
        & (tail <= check["logprob_p99_tol"])
    return jnp.where(held, loss, jnp.nan)


def program_logprob_gap(params, tokens, config: Dict[str, Any], first,
                        second=None):
    """The sharper half of `correct`, as the other share families have it:
    over the predicted tokens of BOTH heads' passes (B x S next tokens and,
    through the prediction module, B x (S - 1) tokens two ahead), the
    program's log-probability less the reference's (`first`, `second`:
    reference_both_logprobs'), as (median of the absolute gap, root mean
    square, 99th percentile of the absolute gap). The first loss at random
    weights is log V plus half the logits' variance whatever the block
    computes; the tokens' own log-probabilities tell a decay left out, a
    step not through its softplus, a skip left out, a gate after the norm,
    relu for relu^2, an unscaled routed sum and fp8 weights from bf16
    rounding (the readings behind the bounds are in the configuration
    file). The program is the forward the step was built from, on one
    device, at the default matmul precision whatever the caller's (the
    scan's own products ask for full precision themselves)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import gpt_forward_both
    with jax.default_matmul_precision("default"):
        logits, ahead = gpt_forward_both(params, tokens, _train_config(config))

    def logprob(z, picked):
        z = z.astype(jnp.float32)
        return jnp.take_along_axis(z, picked[..., None], axis=-1)[..., 0] \
            - jax.nn.logsumexp(z, axis=-1)
    gap = (logprob(logits, tokens[:, 1:]) - first).reshape(-1)
    if second is not None:
        gap = jnp.concatenate([gap, (logprob(ahead[:, :-1], tokens[:, 2:])
                                     - second).reshape(-1)])
    return (jnp.median(jnp.abs(gap)), jnp.sqrt(jnp.mean(gap * gap)),
            jnp.quantile(jnp.abs(gap), 0.99))


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

def _matrices(config: Dict[str, Any]) -> Dict[str, int]:
    """Elements of each group of matrices, at the heads and groups held."""
    d, dim = config["hidden_size"], config["head_dim"]
    wide = dim * config["num_attention_heads"]
    kv = dim * config["num_key_value_heads"]
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    directions = 2 * config["n_groups"] * config["ssm_state_size"]
    latent = config["moe_latent_size"]
    return {
        "attention": 2 * d * wide + 2 * d * kv,
        # the gate, what the filter reads, the step a head; the output
        "ssm": d * (2 * inner + directions + config["mamba_num_heads"])
        + inner * d,
        "expert": 2 * latent * config["moe_intermediate_size"],
        "latent": 2 * d * latent,
        "shared": (2 * d * config["moe_shared_expert_intermediate_size"]
                   * config["n_shared_experts"]),
        "router": d * share(config)[2],
        "module": 2 * d * d}


def _layers(config: Dict[str, Any]) -> Dict[str, int]:
    """How many layers of each kind run a step: the stack's and the
    prediction module's."""
    kinds = _kinds(config) + _module_kinds(config)
    return {kind: kinds.count(kind) for kind in KINDS.values()}


def _ssm_small(config: Dict[str, Any]) -> int:
    """An `M` layer's parameters that are no matrix: the taps and their
    bias, a_log, dt_bias and d a head, the gated norm's scale."""
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    channels = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    return (channels * (config["conv_kernel"] + 1)
            + 3 * config["mamba_num_heads"] + inner)


def param_count(config: Dict[str, Any]) -> int:
    """Every parameter resident on the device: a mixer's matrices at the
    heads held (an `M` layer's taps, biases, rates, skips and norm scale
    beside them), one norm a layer; in an `E` layer the router at its
    published width with its bias, the experts HELD, both latent projections
    and the shared expert; embedding and head over the vocabulary held, the
    final norm; the prediction module's projection, three norms and layers."""
    m = _matrices(config)
    d, v = config["hidden_size"], config["vocab_size"]
    n = _layers(config)
    _, held, of = share(config)
    module = (m["module"] + 3 * d) if _module_kinds(config) else 0
    return (n["attention_alone"] * (m["attention"] + d)
            + n["ssm"] * (m["ssm"] + _ssm_small(config) + d)
            + n["ff"] * (d + m["router"] + of + held * m["expert"]
                         + m["latent"] + m["shared"])
            + module
            + v * d + d + (0 if config["tie_word_embeddings"] else d * v))


def active_param_count(config: Dict[str, Any]) -> int:
    """param_count with, of a layer's experts, the `num_experts_per_tok` a
    token goes through: the "A12B" of the name, at the published sizes."""
    idle = share(config)[1] - config["num_experts_per_tok"]
    return param_count(config) - _layers(config)["ff"] * idle * _matrices(
        config)["expert"]


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 x the matrix parameters a token activates HERE + 3 x the mixers' own
    products: the `M` layers' projections, the `*` layers', in an `E` layer
    the router, both latent projections, the shared expert and the routed
    slots expected on this chip (experts a token x held / all: the true count
    moves with the routing); the head over the vocabulary held, TWICE where
    the model has a prediction module, whose projection and layers count like
    the stack's; in a `*` layer q.k and p.v at head_dim under the causal mask
    (S / 2 keys a query: 4 head_dim x S / 2 a query head forward); in an `M`
    layer the scan's products a token at the chunk the program runs
    (benchmark/kernels/ssd.py:ssd_flops_per_token). The backward's two for
    one. Left out: the embedding lookups, the norms, the filters, the decays'
    exponentials, the softmax, the routing's sorts and gathers, and
    recomputation (remat)."""
    from benchmark.kernels.ssd import ssd_flops_per_token
    m = _matrices(config)
    _, held, of = share(config)
    n = _layers(config)
    module = bool(_module_kinds(config))
    slots = config["num_experts_per_tok"] * held / of
    active = (n["attention_alone"] * m["attention"] + n["ssm"] * m["ssm"]
              + n["ff"] * (m["router"] + m["latent"] + m["shared"]
                           + slots * m["expert"])
              + (1 + module) * config["hidden_size"] * config["vocab_size"]
              + module * m["module"])
    products = (n["attention_alone"] * config["num_attention_heads"] * 4.0
                * config["head_dim"] * seq / 2.0
                + n["ssm"] * config["mamba_num_heads"] * ssd_flops_per_token(
                    config["chunk_size"], config["mamba_head_dim"],
                    config["ssm_state_size"],
                    config["mamba_num_heads"] // config["n_groups"]))
    return 6.0 * active + 3.0 * products


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0


def _batch(mix: Dict[str, Any]) -> int:
    mesh = mix["mesh"]
    return mix["global_batch"] // (mesh.get("data", 1) * mesh.get("fsdp", 1))


def attention_call(config: Dict[str, Any], mix: Dict[str, Any]
                   ) -> Dict[str, int]:
    """One call of a `*` layer's flash kernels (`flash_fwd`, `flash_bwd_dq`,
    `flash_bwd_dkv`) on one chip under a training mix: q and the output
    [batch, heads, seq, head_dim], k and v [batch, kv_heads, seq, head_dim]
    ([1, 4 on 1, 8192, 128] at nemotron3s_train_1chip, the stack's layer and
    the prediction module's alike). benchmark/kernels/gqa_attention.py counts
    it."""
    tensor = mix["mesh"].get("tensor", 1)
    return {"batch": _batch(mix),
            "heads": config["num_attention_heads"] // tensor,
            "kv_heads": config["num_key_value_heads"] // tensor,
            "seq": mix["seq"], "head_dim": config["head_dim"]}


def ssd_call(config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, int]:
    """One `M` layer's scan on one chip under a training mix: x and y [batch,
    seq, heads, head_dim], B and C [batch, seq, groups, state] ([1, 8192, 16,
    64] and [1, 8192, 1, 128] at nemotron3s_train_1chip).
    benchmark/kernels/ssd.py counts it."""
    tensor = mix["mesh"].get("tensor", 1)
    return {"batch": _batch(mix), "seq": mix["seq"],
            "heads": config["mamba_num_heads"] // tensor,
            "head_dim": config["mamba_head_dim"],
            "groups": max(config["n_groups"] // tensor, 1),
            "state": config["ssm_state_size"], "chunk": config["chunk_size"]}


def kda_call(config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, int]:
    """The shape of an `M` layer's ONE filter call (`conv_silu_fwd`,
    `conv_silu_bwd` over [x | B | C]: [batch, seq, H P + 2 G N], [1, 8192,
    1280] at nemotron3s_train_1chip) under the keys
    benchmark/kernels/kda.py's filter arithmetic reads a delta-rule layer's
    from: heads x head_dim is the channels of the call, whatever a head is."""
    c = ssd_call(config, mix)
    channels = c["heads"] * c["head_dim"] + 2 * c["groups"] * c["state"]
    return {"batch": c["batch"], "heads": channels // c["head_dim"],
            "seq": c["seq"], "head_dim": c["head_dim"],
            "taps": config["conv_kernel"], "chunk": c["chunk"]}
