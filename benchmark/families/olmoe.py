"""The family `olmoe`: OLMoE's decoder block (arXiv:2409.02060, and the
`olmoe` model code its config.json names), every MLP replaced by sparse
experts. What a family module holds is listed in gpt_dense.py.

The layer, as the reference below writes it out. Pre-norm block:
  h = x + Wo attention(rope(norm_q(Wq n1(x))), rope(norm_k(Wk n1(x))), Wv n1(x))
  y = h + sum over the k experts e with the largest p_e(n2(h)) of
          p_e * down_e(silu(gate_e n2(h)) * up_e n2(h))
with RMSNorm everywhere; norm_q and norm_k run over the WHOLE hidden-wide
projection before it is split into heads and rotated; p = softmax over all
the experts of the router's logits n2(h) Wr in float32, and the k kept
probabilities are used as they are (`norm_topk_prob` false: they do not
sum to one). Training loss = cross-entropy
  + router_aux_loss_coef * E * sum_e f_e P_e   (f_e: the share of tokens
        with e among their k choices, P_e: the mean of p_e over tokens)
  + router_z_loss_coef * mean over tokens of logsumexp(logits)^2.

Departures from the published description, each also in the configuration
file: the two router losses are taken layer by layer and averaged over the
layers (the model code concatenates the layers' tokens before it takes f
and P; with one layer, as the cell runs, the two are the same number); the
router's matmul is float32 in program and reference alike (the model code
runs it in the activations' type and casts to float32 for the softmax);
rotary angles are in the half-split layout of benchmark/reference.py,
which is the model code's `rotate_half`.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict

# one flash call's [batch, heads, seq, head_dim] follows from the same keys
# (head_dim = hidden_size / heads here too): [2, 16, 4096, 128] at the cell
from benchmark.families.gpt_dense import attention_call  # noqa: F401


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("models/gpt.py has no grouped-query attention")
    if config["norm_topk_prob"]:
        raise ValueError("models/gpt.py does not renormalise the top-k")
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "d_ff": config["intermediate_size"],      # the width of ONE expert
        "max_seq": config["max_position_embeddings"],
        "rope_theta": float(config["rope_theta"]),
        "rmsnorm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "qk_norm": bool(config["qk_norm"]),
        "n_experts": config["num_experts"],
        "expert_top_k": config["num_experts_per_tok"],
        "router_aux_loss_coef": float(config["router_aux_loss_coef"]),
        "router_z_loss_coef": float(config["router_z_loss_coef"]),
    }


def _train_config(config: Dict[str, Any]):
    """The GPTConfig the step is built from (bf16 activations, flash
    attention, the grouped-matmul kernels, remat of the whole layer)."""
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(**gpt_config_kwargs(config), attention="flash",
                     remat_policy="full")


def program(config: Dict[str, Any], serving: bool = False):
    """The system under test at these sizes, as gpt_dense.program: the same
    models/gpt.py, with its sparse block, q/k norm and router losses on."""
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
# time (lax.map over the batch) and one expert at a time (lax.scan over the
# experts), so that neither [B, heads, S, S] nor [S, experts, width] has to
# exist at once at the published widths. Call it under
# jax.default_matmul_precision("highest"). (program_logprob_gap, below the
# reference, is not part of it: it runs the program, to hold it to the
# reference where the harness cannot.)
# ---------------------------------------------------------------------------

def _sequence(params, tokens, config):
    """tokens [S] -> (final-normed hidden states [S, d], per layer the
    router's sums over this sequence: (tokens that chose each expert [E],
    summed probabilities [E], summed logsumexp(logits)^2))."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    heads, top_k = config["num_attention_heads"], config["num_experts_per_tok"]
    n_experts = config["num_experts"]
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    x = params["embed"]["table"].astype(f32)[tokens]
    s, d = x.shape
    hd = d // heads
    half = hd // 2
    angles = (jnp.arange(s, dtype=f32)[:, None]
              * theta ** (-jnp.arange(half, dtype=f32) / half)[None, :])
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale.astype(f32)

    def rotate(t):                       # [S, heads, hd]
        a, c = t[..., :half], t[..., half:]
        return jnp.concatenate([a * cos - c * sin, a * sin + c * cos], -1)

    sums = []
    for layer in params["layers"]:
        a = layer["attn"]
        h = norm(x, layer["ln1"]["scale"])
        q = norm(h @ a["wq"].astype(f32), a["q_norm"]["scale"])
        k = norm(h @ a["wk"].astype(f32), a["k_norm"]["scale"])
        q = rotate(q.reshape(s, heads, hd))
        k = rotate(k.reshape(s, heads, hd))
        v = (h @ a["wv"].astype(f32)).reshape(s, heads, hd)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        x = x + jnp.einsum("hqk,khd->qhd", weights, v).reshape(s, d) \
            @ a["wo"].astype(f32)

        m = layer["moe"]
        h = norm(x, layer["ln2"]["scale"])
        logits = h @ m["router"].astype(f32)               # [S, E]
        probs = jax.nn.softmax(logits, -1)
        kept, chosen = jax.lax.top_k(probs, top_k)
        picked = jax.nn.one_hot(chosen, n_experts, dtype=f32)   # [S, k, E]
        # p_e where e is among the k chosen, 0 elsewhere; not renormalised
        mask = jnp.sum(picked * kept[..., None], axis=1)        # [S, E]

        def expert(y, e, h=h, m=m, mask=mask):
            out = (jax.nn.silu(h @ m["w_gate"][e].astype(f32))
                   * (h @ m["w_up"][e].astype(f32))) \
                @ m["w_down"][e].astype(f32)
            return y + mask[:, e, None] * out, None
        y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(n_experts))
        x = x + y
        sums.append((jnp.sum(picked, axis=(0, 1)), jnp.sum(probs, axis=0),
                     jnp.sum(jax.nn.logsumexp(logits, -1) ** 2)))
    return norm(x, params["final_norm"]["scale"]), sums


def _head(params):
    import jax.numpy as jnp
    return (params["lm_head"] if "lm_head" in params
            else params["embed"]["table"].T).astype(jnp.float32)


def reference_logits(params, tokens, config: Dict[str, Any]):
    """tokens [B, S] -> float32 logits [B, S, vocab]."""
    import jax
    x = jax.lax.map(lambda row: _sequence(params, row, config)[0], tokens)
    return x @ _head(params)


def _logprobs_and_router(params, tokens, config):
    """[B, S] -> ([B, S-1] log-probability of each token after the first
    given those before it, the router's per-layer sums over the positions
    that predict one, each [B, ...])."""
    import jax
    import jax.numpy as jnp

    def sequence(row):
        x, sums = _sequence(params, row[:-1], config)
        z = x @ _head(params)
        picked = jnp.take_along_axis(z, row[1:, None], axis=-1)[:, 0]
        return picked - jax.nn.logsumexp(z, axis=-1), sums
    return jax.lax.map(sequence, tokens)


def reference_logprobs(params, tokens, config: Dict[str, Any]):
    return _logprobs_and_router(params, tokens, config)[0]


def reference_loss(params, tokens, config: Dict[str, Any]):
    """tokens [B, S+1] -> the training loss over B x S: cross-entropy plus
    the two router losses, whose f, P and z are means over the whole batch
    (so a mean of per-row losses is another number).

    Where the configuration has a `program_check`, the number comes back
    only if the program's own forward agrees with the reference token by
    token (program_logprob_gap below), and is nan otherwise: the harness
    (train_cell.py) holds a run to this one number, and nan is within no
    tolerance of any first loss."""
    import jax.numpy as jnp
    logp, layers = _logprobs_and_router(params, tokens, config)
    n_tokens = logp.size
    balance = z = 0.0
    for chose, probs, lse2 in layers:
        f, p = jnp.sum(chose, 0) / n_tokens, jnp.sum(probs, 0) / n_tokens
        balance += config["num_experts"] * jnp.sum(f * p) / len(layers)
        z += jnp.sum(lse2) / n_tokens / len(layers)
    loss = (-jnp.mean(logp) + config["router_aux_loss_coef"] * balance
            + config["router_z_loss_coef"] * z)
    check = config.get("program_check")
    if check is None:
        return loss
    median, rms = program_logprob_gap(params, tokens, config, logp)
    held = (median <= check["logprob_median_tol"]) \
        & (rms <= check["logprob_rms_tol"])
    return jnp.where(held, loss, jnp.nan)


def program_logprob_gap(params, tokens, config: Dict[str, Any], reference):
    """The sharper half of `correct`, which the harness has no place for
    yet: over the B x S predicted tokens, the program's log-probability less
    the reference's [B, S], as (median of the absolute gap, root mean
    square). A first loss at random weights is log V plus half the logits'
    variance whatever the block computes, and cannot tell fp8 experts or a
    renormalised top-k from bf16 rounding; the tokens' own log-probabilities
    can. The median is the typical token's rounding, which a lower precision
    widens; the root mean square also weighs the tail, where a fault that
    reaches only some tokens shows (the readings behind both bounds are in
    the configuration file). The program is the forward the step was built
    from, on one device, at the default matmul precision whatever the
    caller's."""
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

def param_count(config: Dict[str, Any]) -> int:
    """Every parameter resident on the device: four d x d attention
    matrices, the q and k norms, two layer norms, the router and ALL the
    experts' three matrices a layer; embedding, final norm, untied head."""
    k = gpt_config_kwargs(config)
    d, f, v, e = k["d_model"], k["d_ff"], k["vocab_size"], k["n_experts"]
    layer = 4 * d * d + 4 * d + d * e + e * 3 * d * f
    head = 0 if k["tie_embeddings"] else d * v
    return k["n_layers"] * layer + v * d + d + head


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 x the matrix parameters a token ACTIVATES + 12 L d S: attention's
    four matrices, the router, expert_top_k of the experts, the head; causal
    attention counted as full, as gpt_dense counts it. Left out: the
    embedding lookup (a gather), the norms, the softmaxes, the routing's
    sorts and gathers, the two router losses, and recomputation (remat)."""
    k = gpt_config_kwargs(config)
    d, f, v = k["d_model"], k["d_ff"], k["vocab_size"]
    layer = 4 * d * d + d * k["n_experts"] + k["expert_top_k"] * 3 * d * f
    return 6.0 * (k["n_layers"] * layer + d * v) \
        + 12.0 * k["n_layers"] * d * seq


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0
