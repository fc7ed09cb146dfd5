"""The family `ouro`: the looped decoder of Ouro-2.6B (`model_type` `ouro`;
arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language Models", and
the model code published beside the config): ONE stack of dense layers whose
weights are shared by `total_ut_steps` passes a step, a norm either side of
each half of a layer, the final norm inside the loop, every pass read by the
same head, and an exit gate that weights the passes' cross-entropies a
token. What a family module holds is listed in gpt_dense.py.

The model, as the reference below writes it out. x is [S, d]; RMSNorm N with
`rms_norm_eps` everywhere; no bias but the gate's. A layer has FOUR norm
scales:
  a = x + N2(Attn(N1(x)));   y = a + N4(MLP(N3(a)))
Attn: causal multi-head attention (`num_key_value_heads` = the heads: no
  grouping), q and k rotated as halves over all `head_dim` columns at
  `rope_theta` (`rope_scaling` null), softmax of q k^T / sqrt(head_dim) in
  float32 over j <= i, no q/k norm;  MLP: Wdown(silu(Wgate h) * Wup h).
The stack L_1..L_N runs T = `total_ut_steps` times over the SAME weights:
  h_0 = E[tokens];   h_t = Nf(L_N(.. L_1(h_{t-1}))),  t = 1..T
with the final norm Nf INSIDE the loop (the normed output of a pass is what
the next pass reads) and the same positions in every pass. Every pass is
read by the one untied head, z_t = h_t W_head; l_{t,i} is token i's
next-token cross-entropy under z_t. The exit gate is a linear map to one
number with a bias: lam_{t,i} = sigmoid(h_{t,i} . w_g + b_g), and the exit
distribution a token
  p_{t,i} = lam_{t,i} prod_{j<t} (1 - lam_{j,i})  for t < T,
  p_{T,i} = prod_{j<T} (1 - lam_{j,i}),  the rest.
Training loss (the paper's first-stage objective, a uniform prior over the
exit steps): mean_i [ sum_t p_{t,i} l_{t,i} - beta H(p_{.,i}) ], H the
entropy of the T numbers, the gradient through p as well as through l; beta
is `exit_entropy_coef` (in no key of the published config: `assumed`).
`early_exit_threshold` 1.0 decides nothing in training and means "never
leave early" at inference: reference_logits and the program's `score` give
pass T's.

A tree that holds T x N layers is an UNTIED stack (pass t runs layers
t N .. t N + N - 1): the control tests/test_ouro_model.py differentiates, to
hold the program's gradient of a shared weight to the sum over the passes.

Departures and assumptions, each also in the configuration file: the
paper's second stage (the gate alone trained against the detached
improvement of the loss from pass to pass) and early exit at inference are
not built; the gate's form and bias, beta, the four norms a layer and the
final norm inside the loop are the paper's and the model code's, in no key
of the config; seeded random weights.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict

# one flash call's [batch, heads, seq, head_dim] follows from the same keys
# (head_dim = hidden_size / heads here too): [2, 16, 4096, 128] at the cell,
# olmoe's call
from benchmark.families.gpt_dense import attention_call  # noqa: F401

# queries a block of the reference's attention: [heads, block, S] float32
# scores are 0.13 GB at 16 heads and 4096 positions
QUERY_BLOCK = 512


def passes(config: Dict[str, Any]) -> int:
    """T: how often a step runs the stack."""
    return int(config["total_ut_steps"])


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    from ray_tpu.models.gpt import Loop
    heads = config["num_attention_heads"]
    unbuilt = {"hidden_act": "silu", "num_key_value_heads": heads,
               "rope_scaling": None, "use_sliding_window": False,
               "head_dim": config["hidden_size"] // heads,
               "layer_types": ["full_attention"]
               * config["num_hidden_layers"]}
    for key, built in unbuilt.items():
        if config[key] != built:
            raise ValueError(f"models/gpt.py is built for {key} = {built!r} "
                             f"here, the configuration has {config[key]!r}")
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": heads,
        "d_ff": config["intermediate_size"],
        "max_seq": config["max_position_embeddings"],
        "rope_theta": float(config["rope_theta"]),
        "rmsnorm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "norm_after": "both",
        "loop": Loop(passes(config), float(config["exit_entropy_coef"])),
    }


def _train_config(config: Dict[str, Any]):
    """The GPTConfig the step is built from (bf16 activations, flash
    attention, remat of the whole layer)."""
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(**gpt_config_kwargs(config), attention="flash",
                     remat_policy="full")


def program(config: Dict[str, Any], serving: bool = False):
    """The system under test at these sizes, as gpt_dense.program: the same
    models/gpt.py, told to loop its stack, where a half's norms sit and the
    entropy's weight. `score` reads the LAST pass's logits."""
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
# time (lax.map over the batch), the loop as T plain Python passes over the
# same weights, attention a block of queries at a time. Call it under
# jax.default_matmul_precision("highest"). (program_gap, below the
# reference, is not part of it: it runs the program, to hold it to the
# reference where the harness cannot.) The functions named reference_* are
# the mechanisms a control replaces one at a time.
# ---------------------------------------------------------------------------

def _norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def reference_attention(a, x, config: Dict[str, Any]):
    """x [S, d], a half's normed input -> causal multi-head attention's
    output projection of it."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    heads = config["num_attention_heads"]
    dim, theta = config["head_dim"], float(config["rope_theta"])
    s, half = x.shape[0], dim // 2
    angles = (jnp.arange(s, dtype=f32)[:, None]
              * theta ** (-jnp.arange(half, dtype=f32) / half)[None, :])
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]

    def rotate(t):                       # [S, heads, dim]
        u, w = t[..., :half], t[..., half:]
        return jnp.concatenate([u * cos - w * sin, u * sin + w * cos], -1)

    q = rotate((x @ a["wq"].astype(f32)).reshape(s, heads, dim))
    k = rotate((x @ a["wk"].astype(f32)).reshape(s, heads, dim))
    v = (x @ a["wv"].astype(f32)).reshape(s, heads, dim)
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


def reference_block(layer, x, config: Dict[str, Any]):
    """One layer on the stream x [S, d]: a norm either side of each half."""
    import jax
    import jax.numpy as jnp
    f32, eps = jnp.float32, float(config["rms_norm_eps"])
    a = x + _norm(reference_attention(
        layer["attn"], _norm(x, layer["ln1"]["scale"], eps), config),
        layer["ln1_after"]["scale"], eps)
    m, h = layer["mlp"], _norm(a, layer["ln2"]["scale"], eps)
    mlp = (jax.nn.silu(h @ m["w_gate"].astype(f32))
           * (h @ m["w_up"].astype(f32))) @ m["w_down"].astype(f32)
    return a + _norm(mlp, layer["ln2_after"]["scale"], eps)


def reference_layers(params, t: int, config: Dict[str, Any]):
    """The layers pass t (from 0) runs: the tree's N layers, every pass the
    same ones; of a tree of T x N layers (an untied stack, the tests'
    control) its own N."""
    layers, n = params["layers"], config["num_hidden_layers"]
    if len(layers) == n:
        return layers
    if len(layers) != n * passes(config):
        raise ValueError("the parameters' layers are not the configuration's")
    return layers[t * n:(t + 1) * n]


def reference_next_input(normed, stream):
    """What the next pass reads of a pass's output (`stream`, and `normed`,
    the final norm of it): the final norm sits inside the loop."""
    return normed


def reference_exit(lam):
    """lam [T, S], the gate's sigmoid a pass -> the exit distribution p
    [T, S]: lam_t times the share that has not left before t; the last
    pass takes the rest."""
    import jax.numpy as jnp
    stayed = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stayed[:-1]])
    return jnp.concatenate([lam[:-1] * before[:-1], before[-1:]])


def reference_entropy(p):
    """H(p) [S] of p [T, S]."""
    import jax.numpy as jnp
    return -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-37)), axis=0)


def _sequence(params, tokens, config):
    """tokens [S] -> (the passes' final-normed hidden states [T, S, d], the
    exit distribution p [T, S])."""
    import jax
    import jax.numpy as jnp
    f32, eps = jnp.float32, float(config["rms_norm_eps"])
    x = params["embed"]["table"].astype(f32)[tokens]
    streams = []
    for t in range(passes(config)):
        for layer in reference_layers(params, t, config):
            x = reference_block(layer, x, config)
        streams.append(_norm(x, params["final_norm"]["scale"], eps))
        x = reference_next_input(streams[-1], x)
    h = jnp.stack(streams)
    gate = params["exit_gate"]
    lam = jax.nn.sigmoid(h @ gate["w"].astype(f32)[:, 0]
                         + gate["b"].astype(f32))
    return h, reference_exit(lam)


def _head(params):
    import jax.numpy as jnp
    return params["lm_head"].astype(jnp.float32)


def reference_logits_passes(params, tokens, config: Dict[str, Any]):
    """tokens [B, S] -> (float32 logits [T, B, S, vocab], every pass's under
    the one head; the exit distribution p [T, B, S])."""
    import jax
    h, p = jax.lax.map(lambda row: _sequence(params, row, config), tokens)
    return (h @ _head(params)).transpose(1, 0, 2, 3), p.transpose(1, 0, 2)


def reference_logits(params, tokens, config: Dict[str, Any]):
    """tokens [B, S] -> float32 logits [B, S, vocab] of the LAST pass: no
    token leaves early."""
    import jax
    h = jax.lax.map(lambda row: _sequence(params, row, config)[0][-1], tokens)
    return h @ _head(params)


def _logprobs_and_exit(params, tokens, config):
    """[B, S + 1] -> ([T, B, S] log-probability under each pass of each
    token after the first given those before it, p [T, B, S])."""
    import jax
    import jax.numpy as jnp

    def sequence(row):
        h, p = _sequence(params, row[:-1], config)
        z = h @ _head(params)                              # [T, S, vocab]
        picked = jnp.take_along_axis(z, row[None, 1:, None], axis=-1)[..., 0]
        return picked - jax.nn.logsumexp(z, axis=-1), p
    logp, p = jax.lax.map(sequence, tokens)
    return logp.transpose(1, 0, 2), p.transpose(1, 0, 2)


def reference_logprobs(params, tokens, config: Dict[str, Any]):
    """[B, S] -> [B, S-1]: the LAST pass's log-probabilities."""
    return _logprobs_and_exit(params, tokens, config)[0][-1]


def reference_loss(params, tokens, config: Dict[str, Any]):
    """tokens [B, S+1] -> the training loss over B x S: the mean over the
    tokens of sum_t p_t l_t - beta H(p).

    Where the configuration has a `program_check`, the number comes back
    only if the program's own forward agrees with the reference token by
    token in EVERY pass and in the exit distribution (program_gap below),
    and is nan otherwise: the harness (train_cell.py) holds a run to this
    one number, and nan is within no tolerance of any first loss."""
    import jax.numpy as jnp
    logp, p = _logprobs_and_exit(params, tokens, config)
    loss = jnp.mean(jnp.sum(-p * logp, axis=0)
                    - float(config["exit_entropy_coef"])
                    * reference_entropy(p))
    check = config.get("program_check")
    if check is None:
        return loss
    median, rms, tail, exit_gap = program_gap(params, tokens, config, logp, p)
    held = (jnp.max(median) <= check["logprob_median_tol"]) \
        & (jnp.max(rms) <= check["logprob_rms_tol"]) \
        & (jnp.max(tail) <= check["logprob_p99_tol"]) \
        & (exit_gap <= check["exit_p_tol"])
    return jnp.where(held, loss, jnp.nan)


def program_gap(params, tokens, config: Dict[str, Any], logp, p):
    """The sharper half of `correct`, as the other families have it, for
    every pass: over the B x S predicted tokens, the program's
    log-probability under pass t less the reference's `logp` [T, B, S], as
    (median of the absolute gap [T], root mean square [T], 99th percentile
    of the absolute gap [T]), and the largest |program's p_t - reference's|
    over tokens and passes. The first loss at random weights is log V plus
    half the logits' variance whatever the stack computes, and one number
    over four passes; the tokens' own log-probabilities a pass and their
    exit weights tell a pass left out, a norm left out or misplaced, a gate
    without its running product and rounded weights from the step's own
    rounding (the readings behind the bounds are in the configuration
    file). The program is the forward the step was built from, on one
    device, at the default matmul precision whatever the caller's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import gpt_forward_passes
    with jax.default_matmul_precision("default"):
        logits, exit_p = gpt_forward_passes(params, tokens[:, :-1],
                                            _train_config(config))
    gaps = []
    for z, want in zip(logits, logp):        # a pass at a time: [B, S, V]
        z = z.astype(jnp.float32)
        picked = jnp.take_along_axis(z, tokens[:, 1:, None], axis=-1)[..., 0]
        gaps.append((picked - jax.nn.logsumexp(z, axis=-1) - want).reshape(-1))
    gap = jnp.abs(jnp.stack(gaps))                           # [T, B S]
    return (jnp.median(gap, axis=1), jnp.sqrt(jnp.mean(gap * gap, axis=1)),
            jnp.quantile(gap, 0.99, axis=1), jnp.max(jnp.abs(exit_p - p)))


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

def _layer_matrices(config: Dict[str, Any]) -> int:
    """Elements of one layer's matrices: attention's four and the MLP's
    three."""
    d = config["hidden_size"]
    return 4 * d * d + 3 * d * config["intermediate_size"]


def param_count(config: Dict[str, Any]) -> int:
    """Every parameter resident on the device, each ONCE however often a
    step runs it: a layer's matrices and its four norm scales, the embedding
    and the untied head over the vocabulary held, the final norm, the exit
    gate's column and bias."""
    d, v = config["hidden_size"], config["vocab_size"]
    head = 0 if config["tie_word_embeddings"] else d * v
    return (config["num_hidden_layers"] * (_layer_matrices(config) + 4 * d)
            + v * d + head + d + d + 1)


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """T x (6 x the matrix parameters a token goes through in ONE pass + 3 x
    attention's own products): a token passes every held layer, attention
    and the head `total_ut_steps` times a step, so each counts that often:
    the layers' matrices and the head over the vocabulary held; q.k and p.v
    at head_dim under the causal mask (S / 2 keys a query); the backward's
    two for one. Left out: the embedding lookup, the norms, the gate (a
    product of one column), the softmaxes, and recomputation (remat)."""
    heads, dim = config["num_attention_heads"], config["head_dim"]
    layers = config["num_hidden_layers"]
    active = (layers * _layer_matrices(config)
              + config["hidden_size"] * config["vocab_size"])
    products = layers * heads * 2.0 * 2 * dim * seq / 2.0
    return passes(config) * (6.0 * active + 3.0 * products)


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0
