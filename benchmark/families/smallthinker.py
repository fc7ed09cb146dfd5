"""The family `smallthinker`: the decoder stack of SmallThinker-21BA3B-Instruct
(`model_name` smallthinker_21b_instruct; arXiv:2507.20984): full causal
layers that rotate nothing beside sliding-window layers that rotate
(`sliding_window_layout`, `rope_layout`), sparse ReLU-gated experts in every
layer, and a router that reads the layer's normed INPUT, ahead of the mixer.
What a family module holds is listed in gpt_dense.py.

The layer, as the reference below writes it out. x is [S, d]; RMSNorm with
`rms_norm_eps` everywhere; no bias anywhere, no q/k norm, no shared expert,
no dense layer. Layer l, H = `num_attention_heads` query heads on Hkv =
`num_key_value_heads`, D = `head_dim` (H x D is not d):
  n1 = norm1(x)
  r  = n1 Wr over ALL `moe_num_primary_experts`, float32     <- ahead of
        the mixer: the router never sees what attention adds
  I  = the `moe_num_active_primary_experts` largest r_e;  p = softmax(r[I])
        (`moe_primary_router_apply_softmax`; under `norm_topk_prob` a
        softmax over all the experts, its chosen ones divided by their sum,
        is the same numbers)
  q = n1 Wq as H heads, k = n1 Wk and v = n1 Wv as Hkv heads
  `rope_layout`[l] 1: q and k rotated as halves of the whole head,
        frequencies `rope_theta`^(-2i / D); 0: no rotation (NoPE)
  query head h reads key/value head h // (H / Hkv); softmax of
        q k^T / sqrt(D) in float32 over the keys query i keeps:
        `sliding_window_layout`[l] 0: j <= i;  1: 0 <= i - j <
        `sliding_window_size` (itself and the window - 1 before it)
  h = x + concat_h(P_h v) Wo
  n2 = norm2(h)
  y = h + sum over e in I of p_e down_e(relu(gate_e n2) * up_e n2),
        experts `moe_ffn_hidden_size` wide ("sparse ReGLU": relu's
        derivative at 0 is 0); cross-entropy alone
Final RMSNorm, then an untied head.

The chip's share (`share` in the configuration file; model-configs guide,
section 4): the file's `moe_num_primary_experts` and `vocab_size` are what
is HELD here, experts rank * held .. + held - 1 of `share.
moe_num_primary_experts`. The router keeps its published width and its
experts a token (the softmax is over the chosen of ALL the experts); the
sum above runs over the chosen experts that are held, and what the others
would have added is left out, here and in the program alike. A file without
`share` is the whole layer (tests/test_smallthinker.py adds the shares up to
it).

Departures and assumptions, each also in the configuration file: k and v
stay at Hkv heads in the program; the router's matmul is float32 in program
and reference alike; the program takes the softmax over all the experts and
divides the chosen by their sum; seeded random weights, the embedding's rows
at the spread `embedding_init_std`.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict, Tuple

# queries a block of the reference's attention: [heads, block, S] float32
# scores are 0.9 GB at 28 heads, 512 queries and 16 384 positions
QUERY_BLOCK = 512

# the spread models/gpt.py:gpt_init draws the embedding's rows at
GPT_INIT_EMBEDDING_STD = 0.02

KINDS = {0: "attention", 1: "window"}


def share(config: Dict[str, Any]) -> Tuple[int, int, int]:
    """(first expert held here, how many, of how many experts)."""
    held = config["moe_num_primary_experts"]
    s = config.get("share")
    if s is None:
        return 0, held, held
    return s["rank"] * held, held, s["moe_num_primary_experts"]


def _by_layer(config: Dict[str, Any]):
    """(`sliding_window_layout`, `rope_layout`), checked against the depth
    and against what models/gpt.py can be told: one rotation a kind of
    attention layer."""
    n = config["num_hidden_layers"]
    windows, ropes = config["sliding_window_layout"], config["rope_layout"]
    if not len(windows) == len(ropes) == n:
        raise ValueError("sliding_window_layout and rope_layout have to "
                         f"list {n} layers each")
    for kind in set(windows):
        rotates = {r for w, r in zip(windows, ropes) if w == kind}
        if len(rotates) != 1:
            raise ValueError(f"{KINDS[kind]} layers that rotate and that do "
                             "not: models/gpt.py keeps one rotation a kind")
    return windows, ropes


def _rotates(config: Dict[str, Any], window: int) -> bool:
    """Whether the layers of a kind (0 full, 1 sliding) rotate q and k; a
    kind the stack does not have, as the published pattern has it."""
    windows, ropes = _by_layer(config)
    return bool(next((r for w, r in zip(windows, ropes) if w == window),
                     window))


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    from ray_tpu.ops.rope import RopeSpec
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise ValueError("the family routes by a softmax over the chosen "
                         "experts' logits, normalised")
    if config["rope_scaling"] is not None:
        raise ValueError("the family rotates at rope_theta, unscaled")
    windows, _ = _by_layer(config)
    first, held, of = share(config)
    theta = float(config["rope_theta"])
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "layer_kinds": tuple(KINDS[w] for w in windows),
        "attention_window": config["sliding_window_size"],
        # a kind that rotates nothing rotates no column of its heads
        "rope": RopeSpec(theta=theta, rotated=float(_rotates(config, 0))),
        "window_rope": RopeSpec(theta=theta,
                                rotated=float(_rotates(config, 1))),
        "d_ff": config["moe_ffn_hidden_size"],      # the width of ONE expert
        "max_seq": config["max_position_embeddings"],
        "rmsnorm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "n_experts": of,
        "expert_top_k": config["moe_num_active_primary_experts"],
        "experts_held": None if held == of else (first, held),
        "router_score": "softmax",
        "router_renormalise": True,
        # no key gives a router loss a weight: cross-entropy alone
        "router_aux_loss_coef": 0.0,
        "router_z_loss_coef": 0.0,
        "route_from": "input",
        "gate_activation": "relu",
    }


def _train_config(config: Dict[str, Any]):
    """The GPTConfig the step is built from (bf16 activations, flash
    attention with the window kernels, the grouped-matmul kernels, remat of
    the whole layer)."""
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(**gpt_config_kwargs(config), attention="flash",
                     remat_policy="full")


def program(config: Dict[str, Any], serving: bool = False):
    """The system under test at these sizes, as gpt_dense.program: the same
    models/gpt.py, told the kinds of its layers and which of them rotates,
    the window, where the router reads, the gate's activation and the share
    of the experts."""
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
# time (lax.map over the batch), attention a block of queries at a time, the
# window as a mask, the routed sum one expert at a time. Call it under
# jax.default_matmul_precision("highest"). (program_logprob_gap, below the
# reference, is not part of it: it runs the program, to hold it to the
# reference where the harness cannot.)
# ---------------------------------------------------------------------------

def _norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _gate_activation(g):
    """relu, with a derivative of 0 at 0."""
    import jax.numpy as jnp
    return jnp.where(g > 0, g, 0.0)


def _reglu(m, e, h, f32):
    """Expert e of m's stacked matrices over h [S, d]."""
    return (_gate_activation(h @ m["w_gate"][e].astype(f32))
            * (h @ m["w_up"][e].astype(f32))) @ m["w_down"][e].astype(f32)


def _rotated(t, theta: float):
    """t [S, heads, D]: every column rotated as halves of the head by the
    token's position."""
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


def _seen(queries, keys, window):
    """[Q, K] bool: which keys (positions) each query (position) keeps;
    window None: every key up to the query's own."""
    behind = queries[:, None] - keys[None, :]
    if window is None:
        return behind >= 0
    return (behind >= 0) & (behind < window)


def reference_attention(a, n, config: Dict[str, Any], window: int,
                        rotates: int):
    """n [S, d], a layer's normed input -> what attention adds: under the
    sliding window or the whole causal mask (`window` 1 | 0), rotated or
    not (`rotates` 1 | 0)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim = config["head_dim"]
    s = n.shape[0]
    q = (n @ a["wq"].astype(f32)).reshape(s, heads, dim)
    k = (n @ a["wk"].astype(f32)).reshape(s, kv_heads, dim)
    v = (n @ a["wv"].astype(f32)).reshape(s, kv_heads, dim)
    if rotates:
        theta = float(config["rope_theta"])
        q, k = _rotated(q, theta), _rotated(k, theta)
    # each query head's own key/value head, written out
    reads = _kv_head_of(heads, kv_heads)
    k, v = k[:, reads], v[:, reads]

    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} positions are not whole blocks of {block}")
    at = jnp.arange(s)
    band = config["sliding_window_size"] if window else None

    def queries(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dim)
        seen = _seen(start + jnp.arange(block), at, band)
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", weights, v)
    mixed = jax.lax.map(queries, jnp.arange(0, s, block)).reshape(
        s, heads * dim)
    return mixed @ a["wo"].astype(f32)


def reference_routing(m, n, config: Dict[str, Any]):
    """n [S, d], the tensor the router reads -> [S, E] float32: p_e where
    expert e is among the token's chosen, 0 elsewhere, over ALL the
    experts: the chosen logits' softmax."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    n_all = share(config)[2]
    logits = n @ m["router"].astype(f32)
    top, chosen = jax.lax.top_k(logits,
                                config["moe_num_active_primary_experts"])
    p = jax.nn.softmax(top, axis=-1)
    return jnp.einsum("sk,ske->se", p,
                      jax.nn.one_hot(chosen, n_all, dtype=f32))


def reference_experts(m, h, weights, config: Dict[str, Any]):
    """h [S, d], the normed stream after the mixer; weights [S, E] from
    reference_routing -> what the layer adds: the weighted sum over each
    token's chosen experts THAT ARE HELD (m's matrices: experts first ..
    first + held - 1)."""
    import jax
    import jax.numpy as jnp
    first, held, _ = share(config)

    def expert(y, e):
        return y + weights[:, first + e, None] * _reglu(m, e, h,
                                                        jnp.float32), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(held))
    return y


def reference_layer(layer, x, config: Dict[str, Any], window: int,
                    rotates: int):
    """x [S, d] -> the layer's output: the routing from the normed INPUT,
    the mixer, the experts over the normed stream after it."""
    eps = float(config["rms_norm_eps"])
    n1 = _norm(x, layer["ln1"]["scale"], eps)
    weights = reference_routing(layer["moe"], n1, config)
    a = layer["window_attn" if window else "attn"]
    h = x + reference_attention(a, n1, config, window, rotates)
    n2 = _norm(h, layer["ln2"]["scale"], eps)
    return h + reference_experts(layer["moe"], n2, weights, config)


def _sequence(params, tokens, config):
    """tokens [S] -> final-normed hidden states [S, d]."""
    import jax.numpy as jnp
    x = params["embed"]["table"].astype(jnp.float32)[tokens]
    for layer, window, rotates in zip(params["layers"], *_by_layer(config)):
        x = reference_layer(layer, x, config, window, rotates)
    return _norm(x, params["final_norm"]["scale"],
                 float(config["rms_norm_eps"]))


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
    percentile of the absolute gap). The first loss at random weights is
    log V plus half the logits' variance whatever the block computes; the
    tokens' own log-probabilities tell a router fed the mixed stream, a
    SiLU for the ReLU, a rotation on the wrong kind of layer, another
    window, weights left unnormalised and fp8 weights from bf16 rounding
    (the readings behind the bounds are in the configuration file). The
    program is the forward the step was built from, on one device, at the
    default matmul precision whatever the caller's."""
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
    """Elements of each group of matrices: an attention layer's four (both
    kinds alike), one expert, the router."""
    d, dim = config["hidden_size"], config["head_dim"]
    wide = dim * config["num_attention_heads"]
    kv = dim * config["num_key_value_heads"]
    return {"attention": 2 * d * wide + 2 * d * kv,
            "expert": 3 * d * config["moe_ffn_hidden_size"],
            "router": d * share(config)[2]}


def _layers(config: Dict[str, Any]) -> Tuple[int, int]:
    """(full layers, sliding layers)."""
    windows, _ = _by_layer(config)
    return windows.count(0), windows.count(1)


def param_count(config: Dict[str, Any]) -> int:
    """Every parameter resident on the device: a layer's four attention
    matrices, its two norms, the router at its published width and the
    experts HELD; embedding and head over the vocabulary held, the final
    norm."""
    m = _matrices(config)
    d, v = config["hidden_size"], config["vocab_size"]
    n = config["num_hidden_layers"]
    return (n * (m["attention"] + 2 * d + m["router"]
                 + share(config)[1] * m["expert"])
            + v * d + d + (0 if config["tie_word_embeddings"] else d * v))


def active_param_count(config: Dict[str, Any]) -> int:
    """param_count with, of a layer's experts, the
    `moe_num_active_primary_experts` a token goes through: the "A3B" of the
    name."""
    idle = share(config)[1] - config["moe_num_active_primary_experts"]
    return (param_count(config)
            - config["num_hidden_layers"] * idle * _matrices(config)["expert"])


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 x the matrix parameters a token activates HERE + attention's two
    products: the projections, the router, the routed slots expected on
    this chip (experts a token x held / all: the true count moves with the
    routing), the head over the vocabulary held; q.k and p.v at head_dim,
    in a full layer under the causal mask (S / 2 keys a query), in a
    sliding layer over the band's pairs (benchmark/kernels/
    window_attention.py: `band_pairs` / S keys a query): 3 x 4 head_dim x
    keys a query head and layer, the backward's two for one (kanana's
    convention). Left out: the embedding lookup, the norms, the softmaxes,
    RoPE, the routing's sorts and gathers, and recomputation (remat)."""
    from benchmark.kernels.window_attention import band_pairs
    m = _matrices(config)
    _, held, of = share(config)
    full, sliding = _layers(config)
    slots = config["moe_num_active_primary_experts"] * held / of
    active = ((full + sliding) * (m["attention"] + m["router"]
                                  + slots * m["expert"])
              + config["hidden_size"] * config["vocab_size"])
    keys = config["num_attention_heads"] * (
        full * seq / 2.0
        + sliding * band_pairs(seq, config["sliding_window_size"]) / seq)
    return 6.0 * active + 3.0 * 4.0 * config["head_dim"] * keys


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0


def attention_call(config: Dict[str, Any], mix: Dict[str, Any]
                   ) -> Dict[str, int]:
    """One call of the FULL layers' flash kernels (`flash_fwd`,
    `flash_bwd_dq`, `flash_bwd_dkv`) on one chip under a training mix: q
    and the output [batch, heads, seq, head_dim], k and v [batch, kv_heads,
    seq, head_dim] ([1, 28 on 4, 16384, 128] at smallthinker_train_1chip).
    benchmark/kernels/gqa_attention.py counts it."""
    mesh = mix["mesh"]
    tensor = mesh.get("tensor", 1)
    return {"batch": mix["global_batch"] // (mesh.get("data", 1)
                                             * mesh.get("fsdp", 1)),
            "heads": config["num_attention_heads"] // tensor,
            "kv_heads": config["num_key_value_heads"] // tensor,
            "seq": mix["seq"], "head_dim": config["head_dim"]}


def window_call(config: Dict[str, Any], mix: Dict[str, Any]
                ) -> Dict[str, int]:
    """One call of the sliding layers' kernels (`flash_win_*`): as
    attention_call, with the window (4096 there).
    benchmark/kernels/window_attention.py counts it."""
    return dict(attention_call(config, mix),
                window=config["sliding_window_size"])
