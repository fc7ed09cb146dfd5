"""The family `granite_hybrid`: the decoder stack of IBM's granite-4.0-h-micro
(`model_type` `granitemoehybrid`, dense: `num_local_experts` 0): every layer
is a mixer AND then a gated MLP, the mixer a Mamba-2 scan or, where
`layer_types` says "attention", grouped-query softmax attention without any
position encoding, under four scalar multipliers and a tied head. What a
family module holds is listed in gpt_dense.py.

The layers, as the reference below writes them out. x is [S, d]; RMSNorm with
`rms_norm_eps` everywhere; no bias but the filter's (`attention_bias`,
`mamba_proj_bias` false, `mamba_conv_bias` true); no position enters but
through the causal mask and the state (`position_embedding_type` "nope":
`rope_theta` is read by nothing). With E the embedding and m
`residual_multiplier`:
  h_0 = `embedding_multiplier` E[t]
  every layer: h <- h + m mixer(RMSNorm(h)), then h <- h + m MLP(RMSNorm(h))
  after the last layer a final RMSNorm; logits = (h E^T) / `logits_scaling`
        (`tie_word_embeddings`: the one matrix is embedding and head)

MLP (`shared_intermediate_size`, `hidden_act` silu):
  out = (silu(n W_gate) * (n W_up)) W_down: the published `input_linear`
        [d, 2 x 8192] as its two halves, `output_linear` [8192, d]
Mamba-2 (`mamba_expand`: d_inner = H x P, H `mamba_n_heads` heads of P
  `mamba_d_head`; G `mamba_n_groups`; N `mamba_d_state`; `mamba_d_conv` taps
  with a bias; `mamba_chunk_size` sizes the program's chunks only):
  [z | xBC | dt] = n W_in, widths H P | H P + 2 G N | H (the parameters hold
        the three parts: w_z, w_xbc, w_dt)
  xBC <- silu(filter(xBC) + bias): a causal depthwise filter a channel (zeros
        before the start, the last tap on the token itself); split into
        x [H, P], B [G, N], C [G, N]; head h reads group h // (H / G)
  dt = softplus(dt + dt_bias) a head (no clamp: the default time-step limit
        is (0, inf)),  a_t = exp(-exp(A_log_h) dt_t), a number a head
  a state S [P, N] a head, float32, S_0 = 0, a token at a time:
        S_t = a_t S_{t-1} + dt_t x_t B_t^T,   y_t = S_t C_t + D_h x_t
  f = [RMSNorm_group(y * silu(z)) * w] W_out, the mean square over each
        group's H P / G columns (one group here: all 4096; the gate BEFORE
        the norm)
Attention: H `num_attention_heads` query heads on `num_key_value_heads` at
  head_dim = hidden_size / H (`assumed.head_dim`), q = n Wq, k = n Wk, v = n
  Wv; query head h reads key/value head h // (H / Hkv);
  softmax(`attention_multiplier` q k^T + causal mask) v in float32, then Wo.
  The multiplier is 1 / 64 at head 64, NOT 1 / sqrt(64).

The cut (`reduced` in the configuration file; model-configs guide, section
4): layers whole; depth one pipeline stage (the first `num_hidden_layers` of
`layer_types`, the stage that holds the embedding, whose tied matrix is also
this stage's head); the vocabulary's rows over `share.vocab_parallel` chips
(ids, logits and the loss over the rows held). `param_count` of the file
with its `published` values put back counts the published model.

Departures, each also in the configuration file: the program runs the scan in
chunks (ops/state_space.py), the reference a token at a time; k and v stay at
Hkv heads in the program; seeded random weights.

`FAULTS`: the reference computed WRONG on purpose, one fault a name, for the
controls that show the comparison tells each apart (`config["fault"]`, which
no configuration file sets). The four multipliers' controls need none: they
are keys of the configuration.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict, Tuple

# queries a block of the reference's attention
QUERY_BLOCK = 512

# `layer_types` entry -> models/gpt.py's kind of layer
KINDS = {"mamba": "ssm_ff", "attention": "attention"}

FAULTS = ("gate_after_norm", "bf16_state", "no_mlp", "no_decay", "no_skip")


def _fault(config: Dict[str, Any], name: str) -> bool:
    fault = config.get("fault")
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: expected one of {FAULTS}")
    return fault == name


def _kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    types = config["layer_types"]
    if set(types) - set(KINDS):
        raise ValueError(f"layer_types {types!r}: expected entries of "
                         f"{sorted(KINDS)}")
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("layer_types is not num_hidden_layers long")
    return tuple(KINDS[t] for t in types)


def _head_dim(config: Dict[str, Any]) -> int:
    """`assumed.head_dim`: the config has no such key."""
    return config["hidden_size"] // config["num_attention_heads"]


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    from ray_tpu.models.gpt import Multipliers, StateSpace
    if config["num_local_experts"] or config["num_experts_per_tok"]:
        raise ValueError("the family builds the dense model: "
                         "num_local_experts 0")
    if config["hidden_act"] != "silu" \
            or config["normalization_function"] != "rmsnorm":
        raise ValueError("the family writes a SiLU-gated MLP under RMSNorm")
    if config["position_embedding_type"] != "nope":
        raise ValueError("the family's attention layers carry no position "
                         "(position_embedding_type 'nope')")
    if not config["mamba_conv_bias"] or config["mamba_proj_bias"] \
            or config["attention_bias"]:
        raise ValueError("the family has a bias on the filter and nowhere "
                         "else")
    if config["mamba_expand"] * config["hidden_size"] != (
            config["mamba_n_heads"] * config["mamba_d_head"]):
        raise ValueError("mamba_expand x hidden_size is not mamba_n_heads x "
                         "mamba_d_head")
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": _head_dim(config),
        "layer_kinds": _kinds(config),
        "use_rope": False,
        "d_ff": config["shared_intermediate_size"],
        "max_seq": config["max_position_embeddings"],
        "rmsnorm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "ssm": StateSpace(
            heads=config["mamba_n_heads"], head_dim=config["mamba_d_head"],
            groups=config["mamba_n_groups"], state=config["mamba_d_state"],
            chunk=config["mamba_chunk_size"]),
        "conv_filter": config["mamba_d_conv"],
        "multipliers": Multipliers(
            embedding=float(config["embedding_multiplier"]),
            residual=float(config["residual_multiplier"]),
            attention=float(config["attention_multiplier"]),
            logits=float(config["logits_scaling"])),
    }


def _train_config(config: Dict[str, Any]):
    """The GPTConfig the step is built from (bf16 activations, flash
    attention, the chunked scan's and the filter's kernels, remat of the
    whole layer)."""
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(**gpt_config_kwargs(config), attention="flash",
                     remat_policy="full")


def program(config: Dict[str, Any], serving: bool = False):
    """The system under test at these sizes, as gpt_dense.program: the same
    models/gpt.py, told the kind of each layer, the state-space mixer's
    sizes and the four multipliers."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init, gpt_loss

    if serving:
        cfg = GPTConfig(**gpt_config_kwargs(config), attention="flash")
    else:
        cfg = _train_config(config)

    def init(key):
        params = gpt_init(key, cfg)
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
# attention a block of queries at a time. Call it under
# jax.default_matmul_precision("highest").
# (program_logprob_gap, below the reference, is not part of it: it runs the
# program, to hold it to the reference where the harness cannot.)
# ---------------------------------------------------------------------------

def _norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def reference_mlp(m, n):
    """n [S, d] -> (silu(n W_gate) * (n W_up)) W_down."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    return (jax.nn.silu(n @ m["w_gate"].astype(f32))
            * (n @ m["w_up"].astype(f32))) @ m["w_down"].astype(f32)


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
        if _fault(config, "bf16_state"):
            # (a cast there and back is one XLA removes: excess precision)
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(
        token, jnp.zeros((heads, width, b.shape[-1]), jnp.float32),
        (x, dt, b, c))
    if _fault(config, "no_skip"):
        return y
    return y + d.astype(jnp.float32)[:, None] * x


def reference_mamba(m, n, config: Dict[str, Any]):
    """n [S, d], a Mamba layer's normed input -> what its mixer gives."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    s = n.shape[0]
    width, state = config["mamba_d_head"], config["mamba_d_state"]
    groups = config["mamba_n_groups"]
    z = n @ m["w_z"].astype(f32)
    inner = z.shape[1]
    xbc = n @ m["w_xbc"].astype(f32)
    taps = m["conv"].astype(f32)
    padded = jnp.pad(xbc, ((taps.shape[1] - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(taps[:, j] * padded[j:j + s]
                          for j in range(taps.shape[1]))
                      + m["conv_bias"].astype(f32))
    directions = groups * state
    dt = jax.nn.softplus(n @ m["w_dt"].astype(f32) + m["dt_bias"].astype(f32))
    y = reference_scan(
        xbc[:, :inner].reshape(s, -1, width), dt, m["a_log"],
        xbc[:, inner:inner + directions].reshape(s, groups, state),
        xbc[:, inner + directions:].reshape(s, groups, state), m["d"],
        config).reshape(s, inner)
    gate = jax.nn.silu(z)
    scale = m["norm"]["scale"].astype(f32)
    eps = float(config["rms_norm_eps"])

    def group_norm(t):
        t = t.reshape(s, groups, -1)
        t = t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + eps)
        return t.reshape(s, inner) * scale
    y = group_norm(y) * gate if _fault(config, "gate_after_norm") \
        else group_norm(y * gate)
    return y @ m["w_out"].astype(f32)


def reference_attention(a, n, config: Dict[str, Any]):
    """n [S, d], an attention layer's normed input -> what its mixer gives:
    no rotation, the causal mask, the scores times `attention_multiplier`."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    dim = _head_dim(config)
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
        scores = jnp.einsum("qhd,khd->hqk", qb, k) \
            * float(config["attention_multiplier"])
        seen = (start + jnp.arange(block))[:, None] >= at[None, :]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", weights, v)
    mixed = jax.lax.map(queries, jnp.arange(0, s, block)).reshape(s, -1)
    return mixed @ a["wo"].astype(f32)


def reference_layer(layer, x, config: Dict[str, Any]):
    """x [S, d] -> x + m mixer(RMSNorm(x)), then + m MLP(RMSNorm(.)): the
    mixer by what the layer's parameters hold."""
    eps, m = float(config["rms_norm_eps"]), float(config["residual_multiplier"])
    n = _norm(x, layer["ln1"]["scale"], eps)
    if "ssm" in layer:
        x = x + m * reference_mamba(layer["ssm"], n, config)
    else:
        x = x + m * reference_attention(layer["attn"], n, config)
    if _fault(config, "no_mlp"):
        return x
    return x + m * reference_mlp(layer["mlp"],
                                 _norm(x, layer["ln2"]["scale"], eps))


def _stream(params, tokens, config):
    """tokens [S] -> the residual stream before the final norm [S, d]."""
    import jax.numpy as jnp
    x = float(config["embedding_multiplier"]) \
        * params["embed"]["table"].astype(jnp.float32)[tokens]
    types = ["mamba" if "ssm" in layer else "attention"
             for layer in params["layers"]]
    if types != list(config["layer_types"]):
        raise ValueError("the parameters' layers are not layer_types'")
    for layer in params["layers"]:
        x = reference_layer(layer, x, config)
    return x


def _logits(params, h, config):
    """h [S, d], the stream before the final norm -> [S, vocab held]."""
    import jax.numpy as jnp
    if not config["tie_word_embeddings"]:
        raise ValueError("the family ties the head to the embedding")
    x = _norm(h, params["final_norm"]["scale"], float(config["rms_norm_eps"]))
    return x @ params["embed"]["table"].astype(jnp.float32).T \
        / float(config["logits_scaling"])


def _picked(z, tokens):
    import jax
    import jax.numpy as jnp
    return jnp.take_along_axis(z, tokens[:, None], axis=-1)[:, 0] \
        - jax.nn.logsumexp(z, axis=-1)


def reference_logits(params, tokens, config: Dict[str, Any]):
    """tokens [B, S] -> float32 logits [B, S, vocab held]."""
    import jax
    return jax.lax.map(
        lambda row: _logits(params, _stream(params, row, config), config),
        tokens)


def reference_logprobs(params, tokens, config: Dict[str, Any]):
    """[B, S] -> [B, S-1]: log-probability of each token after the first
    given the tokens before it, over the vocabulary held."""
    import jax
    return jax.lax.map(
        lambda row: _picked(
            _logits(params, _stream(params, row[:-1], config), config),
            row[1:]), tokens)


def reference_loss(params, tokens, config: Dict[str, Any]):
    """tokens [B, S+1] -> the mean next-token cross-entropy over B x S.

    Where the configuration has a `program_check`, the number comes back
    only if the program's own forward agrees with the reference token by
    token (program_logprob_gap below), and is nan otherwise: the harness
    (train_cell.py) holds a run to this one number, and nan is within no
    tolerance of any first loss."""
    import jax.numpy as jnp
    logprobs = reference_logprobs(params, tokens, config)
    loss = -jnp.mean(logprobs)
    check = config.get("program_check")
    if check is None:
        return loss
    median, rms, tail = program_logprob_gap(params, tokens, config, logprobs)
    held = (median <= check["logprob_median_tol"]) \
        & (rms <= check["logprob_rms_tol"]) \
        & (tail <= check["logprob_p99_tol"])
    return jnp.where(held, loss, jnp.nan)


def program_logprob_gap(params, tokens, config: Dict[str, Any], logprobs):
    """The sharper half of `correct`, as the other families have it: over
    the B x S predicted tokens, the program's log-probability less the
    reference's (`logprobs`: reference_logprobs'), as (median of the absolute
    gap, root mean square, 99th percentile of the absolute gap). The first
    loss at random weights is log V plus half the logits' variance whatever
    the block computes; the tokens' own log-probabilities tell a softmax
    scale of head_dim^-1/2, a multiplier left at 1, a gate after the norm
    and a rounded state from bf16 rounding (the readings behind the bounds
    are in the configuration file). The program is the forward the step was
    built from, on one device, at the default matmul precision whatever the
    caller's (the scan's own products ask for full precision themselves)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import gpt_forward_both
    with jax.default_matmul_precision("default"):
        logits, _ = gpt_forward_both(params, tokens, _train_config(config))
    z = logits.astype(jnp.float32)
    gap = (jnp.take_along_axis(z, tokens[:, 1:, None], axis=-1)[..., 0]
           - jax.nn.logsumexp(z, axis=-1) - logprobs).reshape(-1)
    return (jnp.median(jnp.abs(gap)), jnp.sqrt(jnp.mean(gap * gap)),
            jnp.quantile(jnp.abs(gap), 0.99))


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

def _matrices(config: Dict[str, Any]) -> Dict[str, int]:
    """Elements of each group of matrices of a layer."""
    d, dim = config["hidden_size"], _head_dim(config)
    wide = dim * config["num_attention_heads"]
    kv = dim * config["num_key_value_heads"]
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    directions = 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    return {
        "attention": 2 * d * wide + 2 * d * kv,
        # the gate, what the filter reads, the step a head; the output
        "ssm": d * (2 * inner + directions + config["mamba_n_heads"])
        + inner * d,
        "mlp": 3 * d * config["shared_intermediate_size"]}


def _layers(config: Dict[str, Any]) -> Dict[str, int]:
    """How many layers of each entry of `layer_types` the stack holds."""
    _kinds(config)
    return {t: list(config["layer_types"]).count(t) for t in KINDS}


def _ssm_small(config: Dict[str, Any]) -> int:
    """A Mamba mixer's parameters that are no matrix: the taps and their
    bias, a_log, dt_bias and d a head, the gated norm's scale."""
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    channels = inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    return (channels * (config["mamba_d_conv"] + 1)
            + 3 * config["mamba_n_heads"] + inner)


def param_count(config: Dict[str, Any]) -> int:
    """Every parameter resident on the device: a layer's mixer, its MLP and
    two norms; the embedding over the vocabulary held (tied: once) and the
    final norm. 772 160 448 at the cell, 3 191 396 096 at the published
    depth and vocabulary."""
    m, n = _matrices(config), _layers(config)
    d, v = config["hidden_size"], config["vocab_size"]
    layer = m["mlp"] + 2 * d
    return (n["mamba"] * (m["ssm"] + _ssm_small(config) + layer)
            + n["attention"] * (m["attention"] + layer)
            + v * d + d + (0 if config["tie_word_embeddings"] else d * v))


def head_flops_share(config: Dict[str, Any], seq: int) -> float:
    """The head's share of train_flops_per_token."""
    return 6.0 * config["hidden_size"] * config["vocab_size"] \
        / train_flops_per_token(config, seq)


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 x the matrix parameters a token goes through + 3 x the mixers' own
    products: every layer's mixer and MLP, the tied matrix counted ONCE, as
    the head (the lookup is no matmul); in an attention layer q.k and p.v at
    head_dim under the causal mask (S / 2 keys a query: 4 head_dim x S / 2 a
    query head forward); in a Mamba layer the scan's products a token at the
    chunk the program runs (benchmark/kernels/ssd.py:ssd_flops_per_token).
    The backward's two for one. Left out: the lookup, the norms, the filters,
    the decays' exponentials, the softmax, and recomputation (remat)."""
    from benchmark.kernels.ssd import ssd_flops_per_token
    m, n = _matrices(config), _layers(config)
    active = (n["attention"] * m["attention"] + n["mamba"] * m["ssm"]
              + (n["attention"] + n["mamba"]) * m["mlp"]
              + config["hidden_size"] * config["vocab_size"])
    products = (n["attention"] * config["num_attention_heads"] * 4.0
                * _head_dim(config) * seq / 2.0
                + n["mamba"] * config["mamba_n_heads"] * ssd_flops_per_token(
                    config["mamba_chunk_size"], config["mamba_d_head"],
                    config["mamba_d_state"],
                    config["mamba_n_heads"] // config["mamba_n_groups"]))
    return 6.0 * active + 3.0 * products


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0


def _batch(mix: Dict[str, Any]) -> int:
    mesh = mix["mesh"]
    return mix["global_batch"] // (mesh.get("data", 1) * mesh.get("fsdp", 1))


def attention_call(config: Dict[str, Any], mix: Dict[str, Any]
                   ) -> Dict[str, int]:
    """One call of an attention layer's flash kernels (`flash_fwd`,
    `flash_bwd_dq`, `flash_bwd_dkv`) on one chip under a training mix: q and
    the output [batch, seq, heads x head_dim], k and v [batch, seq, kv_heads
    x head_dim], heads of 64 in pairs ([1, 8192, 32 on 8, 64] at
    granite4hm_train_1chip). benchmark/kernels/gqa_attention.py counts it."""
    tensor = mix["mesh"].get("tensor", 1)
    return {"batch": _batch(mix),
            "heads": config["num_attention_heads"] // tensor,
            "kv_heads": config["num_key_value_heads"] // tensor,
            "seq": mix["seq"], "head_dim": _head_dim(config)}


def ssd_call(config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, int]:
    """One Mamba layer's scan on one chip under a training mix: x and y
    [batch, seq, heads, head_dim], B and C [batch, seq, groups, state] ([1,
    8192, 64, 64] and [1, 8192, 1, 128] at granite4hm_train_1chip).
    benchmark/kernels/ssd.py and ssd_bwd.py count it."""
    tensor = mix["mesh"].get("tensor", 1)
    return {"batch": _batch(mix), "seq": mix["seq"],
            "heads": config["mamba_n_heads"] // tensor,
            "head_dim": config["mamba_d_head"],
            "groups": max(config["mamba_n_groups"] // tensor, 1),
            "state": config["mamba_d_state"],
            "chunk": config["mamba_chunk_size"]}


def kda_call(config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, int]:
    """The shape of a Mamba layer's ONE filter call (`conv_silu_fwd`,
    `conv_silu_bwd` over [x | B | C]: [batch, seq, H P + 2 G N], [1, 8192,
    4352] at granite4hm_train_1chip) under the keys
    benchmark/kernels/kda.py's filter arithmetic reads a delta-rule layer's
    from: heads x head_dim is the channels of the call, whatever a head is."""
    c = ssd_call(config, mix)
    channels = c["heads"] * c["head_dim"] + 2 * c["groups"] * c["state"]
    return {"batch": c["batch"], "heads": channels // c["head_dim"],
            "seq": c["seq"], "head_dim": c["head_dim"],
            "taps": config["mamba_d_conv"], "chunk": c["chunk"]}
