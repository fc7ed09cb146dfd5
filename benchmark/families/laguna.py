"""The family `laguna`: the decoder stack of Laguna-XS.2 (the `laguna` model
code its config.json names): sliding-window and full attention layers in one
stack (`layer_types`), each kind with a head count (`num_attention_heads_per_
layer`), a rotation and a rope table of its own (`rope_parameters`), a gate a
head on attention's output (`gating`), a dense SwiGLU in the leading layer and
sparse experts beside a shared one in the others (`mlp_layer_types`). What a
family module holds is listed in gpt_dense.py.

The layer, as the reference below writes it out. x is [S, d]; RMSNorm with
`rms_norm_eps` everywhere; no bias anywhere. Layer l:
  h = x + Attn_l(norm1(x));   y = h + FF_l(norm2(h))
Attn_l, n the normed input, H_l = `num_attention_heads_per_layer`[l] query
heads on Hkv = `num_key_value_heads`, D = `head_dim` (H_l x D is not d):
  q = n Wq as H_l heads, k = n Wk and v = n Wv as Hkv heads
  rotation, by `rope_parameters`[`layer_types`[l]]: a head's FIRST D x
        `partial_rotary_factor` columns as halves, the others as they are;
        frequencies theta^(-2i / columns), under `rope_type` yarn blended
        with themselves / `factor` by how many turns a pair makes over
        `original_max_position_embeddings` (`beta_fast`, `beta_slow`; the
        Hugging Face `_compute_yarn_parameters`, `truncate` at its default),
        and cos and sin both times `attention_factor`
  query head h reads key/value head h // (H_l / Hkv); softmax of
        q k^T / sqrt(D) in float32 over the keys query i keeps:
        "full_attention": j <= i;  "sliding_attention": 0 <= i - j <
        `sliding_window` (itself and the window - 1 before it)
  `gating`: g = sigmoid(n Wg), Wg [d, H_l], one number a head and token;
        Attn = concat_h(g_h . P_h v_{h // (H_l/Hkv)}) Wo
FF_l, `mlp_layer_types`[l] "dense": Wdown(silu(Wgate m) * Wup m),
  `intermediate_size` wide. "sparse":
  s = sigmoid(m Wr) over ALL the experts, float32
  chosen: the `num_experts_per_tok` largest s_e
  w_e = s_e / (sum of the chosen s) times `moe_routed_scaling_factor`, on
        the expert's OUTPUT (`moe_apply_router_weight_on_input` false)
  FF = sum over chosen e of w_e down_e(silu(gate_e m) * up_e m) + Shared(m),
        experts `moe_intermediate_size` wide, the shared one
        `shared_expert_intermediate_size`; cross-entropy alone
Final RMSNorm, then an untied head.

The chip's share (`share` in the configuration file; model-configs guide,
section 4): the file's `num_experts` and `vocab_size` are what is HELD here,
experts rank * held .. + held - 1 of `share.num_experts`. The router keeps
its published width and its experts a token; the sum above runs over the
chosen experts that are held, and what the others would have added is left
out, here and in the program alike; the shared expert is whole on every
chip. A file without `share` is the whole layer (tests/
test_window_attention.py adds the shares up to it).

Departures and assumptions, each also in the configuration file: the program
permutes the full layers' q and k columns in the weights so that the rotated
halves lie half a head apart (a dot product does not change with its
columns' order); k and v stay at Hkv heads; the router's matmul is float32
in program and reference alike; seeded random weights.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict, Tuple

# queries a block of the reference's attention: [heads, block, S] float32
# scores are 1 GB at 64 heads, 512 queries and 8192 positions
QUERY_BLOCK = 512

KINDS = {"full_attention": "attention", "sliding_attention": "window"}


def share(config: Dict[str, Any]) -> Tuple[int, int, int]:
    """(first expert held here, how many, of how many experts)."""
    held = config["num_experts"]
    s = config.get("share")
    if s is None:
        return 0, held, held
    return s["rank"] * held, held, s["num_experts"]


def _by_layer(config: Dict[str, Any]):
    """(layer_types, mlp_layer_types, heads a layer), checked against the
    depth and against what models/gpt.py can be told: one head count a kind
    of attention layer, the dense layers leading."""
    n = config["num_hidden_layers"]
    types, mlps, heads = (config["layer_types"], config["mlp_layer_types"],
                          config["num_attention_heads_per_layer"])
    if not len(types) == len(mlps) == len(heads) == n:
        raise ValueError(f"layer_types, mlp_layer_types and num_attention_"
                         f"heads_per_layer have to list {n} layers each")
    for kind in set(types):
        counts = {h for t, h in zip(types, heads) if t == kind}
        if len(counts) != 1:
            raise ValueError(f"{kind} layers with head counts {counts}")
    dense = mlps.count("dense")
    if mlps != ["dense"] * dense + ["sparse"] * (n - dense):
        raise ValueError("models/gpt.py keeps its dense layers leading")
    return types, mlps, heads


def _heads_of(config: Dict[str, Any], kind: str) -> int:
    """Query heads of the layers of `kind`; where the stack has none, the
    model's `num_attention_heads`."""
    types, _, heads = _by_layer(config)
    return next((h for t, h in zip(types, heads) if t == kind),
                config["num_attention_heads"])


def _rope_spec(parameters: Dict[str, Any]):
    from ray_tpu.ops.rope import RopeSpec
    yarn = None
    if parameters["rope_type"] == "yarn":
        yarn = (float(parameters["factor"]),
                int(parameters["original_max_position_embeddings"]),
                float(parameters["beta_fast"]), float(parameters["beta_slow"]))
    elif parameters["rope_type"] != "default":
        raise ValueError("models/gpt.py rotates with rope_type 'default' or "
                         f"'yarn', not {parameters['rope_type']!r}")
    return RopeSpec(theta=float(parameters["rope_theta"]),
                    rotated=float(parameters.get("partial_rotary_factor", 1)),
                    yarn=yarn,
                    attention_factor=float(
                        parameters.get("attention_factor", 1.0)))


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    if config["attention_bias"]:
        raise ValueError("models/gpt.py's projections have no bias, the "
                         "configuration has attention_bias true")
    if config["moe_apply_router_weight_on_input"]:
        raise ValueError("models/gpt.py weighs an expert's output")
    if config["shared_expert_intermediate_size"] % config[
            "moe_intermediate_size"]:
        raise ValueError("the shared expert is whole experts' widths")
    types, mlps, _ = _by_layer(config)
    first, held, of = share(config)
    rope = config["rope_parameters"]
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": _heads_of(config, "full_attention"),
        "window_heads": _heads_of(config, "sliding_attention"),
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "layer_kinds": tuple(KINDS[t] for t in types),
        "attention_window": config["sliding_window"],
        "rope": _rope_spec(rope["full_attention"]),
        "window_rope": _rope_spec(rope["sliding_attention"]),
        "attention_gate": bool(config["gating"]),
        "d_ff": config["moe_intermediate_size"],    # the width of ONE expert
        "max_seq": config["max_position_embeddings"],
        "rmsnorm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "n_experts": of,
        "expert_top_k": config["num_experts_per_tok"],
        "experts_held": None if held == of else (first, held),
        "router_score": "sigmoid",
        "router_renormalise": True,
        "router_scale": float(config["moe_routed_scaling_factor"]),
        "n_shared_experts": (config["shared_expert_intermediate_size"]
                             // config["moe_intermediate_size"]),
        "dense_layers": mlps.count("dense"),
        "dense_d_ff": config["intermediate_size"],
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
    models/gpt.py, told the kinds of its layers, each kind's heads and
    rotation, the window, the gate, the routing rule and the share of the
    experts."""
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
# routed sum one expert at a time. Call it under
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


def _frequencies(parameters: Dict[str, Any], columns: int):
    """The columns / 2 rotation frequencies of one kind of layer, numpy
    float32: theta^(-2i / columns), and under yarn each blended with itself
    / factor. Pair i makes original x theta^(-2i/columns) / 2 pi turns over
    the original positions; solved for i, `beta_fast` turns is the pair
    below which (floored) nothing changes, `beta_slow` the one above which
    (ceiled) the frequency is divided by the factor, a linear ramp
    between."""
    import numpy as np
    theta = float(parameters["rope_theta"])
    plain = theta ** (-np.arange(0, columns, 2, dtype=np.float32) / columns)
    if parameters["rope_type"] == "default":
        return plain.astype(np.float32)

    def pair_turning(turns):
        original = parameters["original_max_position_embeddings"]
        return (columns * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(pair_turning(parameters["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(parameters["beta_slow"])), columns - 1)
    if low == high:
        high = high + 0.001
    scaled = np.clip((np.arange(columns // 2, dtype=np.float32) - low)
                     / (high - low), 0.0, 1.0)
    return (plain * (1.0 - scaled)
            + plain / float(parameters["factor"]) * scaled).astype(np.float32)


def _rotation(parameters: Dict[str, Any], positions: int, dim: int):
    """(cos, sin [positions, 1, columns / 2], columns): what one kind of
    layer rotates a head's first `columns` columns by."""
    import jax.numpy as jnp
    columns = int(dim * parameters.get("partial_rotary_factor", 1)) // 2 * 2
    angles = (jnp.arange(positions, dtype=jnp.float32)[:, None]
              * jnp.asarray(_frequencies(parameters, columns)))
    scale = float(parameters.get("attention_factor", 1.0))
    return (scale * jnp.cos(angles)[:, None, :],
            scale * jnp.sin(angles)[:, None, :], columns)


def _rotated(t, cos, sin, columns: int):
    """t [S, heads, D]: the first `columns` columns rotated as halves, the
    others as they are."""
    import jax.numpy as jnp
    half = columns // 2
    a, c = t[..., :half], t[..., half:columns]
    return jnp.concatenate(
        [a * cos - c * sin, a * sin + c * cos, t[..., columns:]], -1)


def _kv_head_of(heads: int, kv_heads: int):
    """The key/value head each query head reads."""
    import jax.numpy as jnp
    return jnp.arange(heads) // (heads // kv_heads)


def _seen(queries, keys, layer_type: str, window: int):
    """[Q, K] bool: which keys (positions) each query (position) keeps."""
    behind = queries[:, None] - keys[None, :]
    if layer_type == "sliding_attention":
        return (behind >= 0) & (behind < window)
    return behind >= 0


def _gated(mixed, n, a):
    """mixed [S, heads, D], the heads' outputs, each times its gate."""
    import jax
    import jax.numpy as jnp
    gate = jax.nn.sigmoid(n @ a["wg"].astype(jnp.float32))      # [S, heads]
    return mixed * gate[:, :, None]


def reference_attention(a, n, config: Dict[str, Any], layer_type: str,
                        heads: int):
    """n [S, d], an attention layer's normed input -> what attention adds:
    `heads` query heads, the mask and the rotation of `layer_type`."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    kv_heads, dim = config["num_key_value_heads"], config["head_dim"]
    s = n.shape[0]
    cos, sin, columns = _rotation(
        config["rope_parameters"][layer_type], s, dim)

    q = (n @ a["wq"].astype(f32)).reshape(s, heads, dim)
    k = (n @ a["wk"].astype(f32)).reshape(s, kv_heads, dim)
    v = (n @ a["wv"].astype(f32)).reshape(s, kv_heads, dim)
    q, k = _rotated(q, cos, sin, columns), _rotated(k, cos, sin, columns)
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
        seen = _seen(start + jnp.arange(block), at, layer_type,
                     config["sliding_window"])
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", weights, v)
    mixed = jax.lax.map(queries, jnp.arange(0, s, block)).reshape(
        s, heads, dim)
    if config["gating"]:
        mixed = _gated(mixed, n, a)
    return mixed.reshape(s, heads * dim) @ a["wo"].astype(f32)


def reference_routing(m, h, config: Dict[str, Any]):
    """h [S, d] -> [S, E] float32: w_e where expert e is among the token's
    chosen, 0 elsewhere, over ALL the experts."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    n_all = share(config)[2]
    scores = jax.nn.sigmoid(h @ m["router"].astype(f32))
    _, chosen = jax.lax.top_k(scores, config["num_experts_per_tok"])
    kept = jax.nn.one_hot(chosen, n_all, dtype=f32).sum(axis=1) * scores
    kept = kept / jnp.sum(kept, -1, keepdims=True)
    return kept * float(config["moe_routed_scaling_factor"])


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
    return y + _swiglu(m["shared"], h, f32)


def _sequence(params, tokens, config):
    """tokens [S] -> final-normed hidden states [S, d]."""
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = float(config["rms_norm_eps"])
    x = params["embed"]["table"].astype(f32)[tokens]
    for layer, layer_type, mlp, heads in zip(params["layers"],
                                             *_by_layer(config)):
        n = _norm(x, layer["ln1"]["scale"], eps)
        a = layer["window_attn" if layer_type == "sliding_attention"
                  else "attn"]
        x = x + reference_attention(a, n, config, layer_type, heads)
        h = _norm(x, layer["ln2"]["scale"], eps)
        if mlp == "dense":
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
    """The sharper half of `correct`, as families/olmoe.py, kanana.py and
    lfm2.py have it: over the B x S predicted tokens, the program's
    log-probability less the reference's, as (median of the absolute gap,
    root mean square). The first loss at random weights is log V plus half
    the logits' variance whatever the block computes; the tokens' own
    log-probabilities tell a window that is not the configuration's, a
    rotation of the wrong columns or by the other kind's table, a missing
    attention factor, a dropped gate, the wrong key/value head and fp8
    weights from bf16 rounding (the readings behind both bounds are in the
    configuration file). The program is the forward the step was built
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

def _matrices(config: Dict[str, Any]) -> Dict[str, int]:
    """Elements of each group of matrices: an attention layer's four and its
    gate by kind, the dense MLP, one expert, the shared one, the router."""
    d, dim = config["hidden_size"], config["head_dim"]
    kv = dim * config["num_key_value_heads"]
    out = {"dense": 3 * d * config["intermediate_size"],
           "expert": 3 * d * config["moe_intermediate_size"],
           "shared": 3 * d * config["shared_expert_intermediate_size"],
           "router": d * share(config)[2]}
    for kind in KINDS:
        heads = _heads_of(config, kind)
        out[kind] = (2 * d * heads * dim + 2 * d * kv
                     + (d * heads if config["gating"] else 0))
    return out


def _layers(config: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """(full layers, sliding layers, dense layers, sparse layers)."""
    types, mlps, _ = _by_layer(config)
    return (types.count("full_attention"), types.count("sliding_attention"),
            mlps.count("dense"), mlps.count("sparse"))


def param_count(config: Dict[str, Any]) -> int:
    """Every parameter resident on the device: an attention layer's four
    matrices and its gate at its kind's head count, two layer norms a
    layer; the dense layers' MLP; in a sparse layer the router at its
    published width, the experts HELD and the shared expert; embedding and
    head over the vocabulary held, the final norm."""
    m = _matrices(config)
    d, v = config["hidden_size"], config["vocab_size"]
    full, sliding, dense, sparse = _layers(config)
    return (full * m["full_attention"] + sliding * m["sliding_attention"]
            + (full + sliding) * 2 * d + dense * m["dense"]
            + sparse * (m["router"] + share(config)[1] * m["expert"]
                        + m["shared"])
            + v * d + d + (0 if config["tie_word_embeddings"] else d * v))


def active_param_count(config: Dict[str, Any]) -> int:
    """param_count with, of a sparse layer's experts, the
    `num_experts_per_tok` a token goes through: the "A3B" of the name."""
    idle = share(config)[1] - config["num_experts_per_tok"]
    return param_count(config) - _layers(config)[3] * idle * _matrices(
        config)["expert"]


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 x the matrix parameters a token activates HERE + attention's two
    products: both kinds' projections and gates, the dense MLP or the
    router, the shared expert and the routed slots expected on this chip
    (experts a token x held / all: the true count moves with the routing),
    the head over the vocabulary held; q.k and p.v at head_dim, in a full
    layer under the causal mask (S / 2 keys a query), in a sliding layer
    over the band's pairs (benchmark/kernels/window_attention.py:
    `band_pairs` / S keys a query): 3 x 4 head_dim x
    keys a query head and layer, the backward's two for one (kanana's
    convention). Left out: the embedding lookup, the norms, the softmaxes,
    RoPE, the gate's product, the routing's sorts and gathers, and
    recomputation (remat)."""
    from benchmark.kernels.window_attention import band_pairs
    m = _matrices(config)
    _, held, of = share(config)
    full, sliding, dense, sparse = _layers(config)
    slots = config["num_experts_per_tok"] * held / of
    active = (full * m["full_attention"] + sliding * m["sliding_attention"]
              + dense * m["dense"]
              + sparse * (m["router"] + m["shared"] + slots * m["expert"])
              + config["hidden_size"] * config["vocab_size"])
    dim = config["head_dim"]
    keys = (full * _heads_of(config, "full_attention") * seq / 2.0
            + sliding * _heads_of(config, "sliding_attention")
            * band_pairs(seq, config["sliding_window"]) / seq)
    return 6.0 * active + 3.0 * 4.0 * dim * keys


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0


def _call(config: Dict[str, Any], mix: Dict[str, Any], kind: str
          ) -> Dict[str, int]:
    mesh = mix["mesh"]
    tensor = mesh.get("tensor", 1)
    return {"batch": mix["global_batch"] // (mesh.get("data", 1)
                                             * mesh.get("fsdp", 1)),
            "heads": _heads_of(config, kind) // tensor,
            "kv_heads": config["num_key_value_heads"] // tensor,
            "seq": mix["seq"], "head_dim": config["head_dim"]}


def attention_call(config: Dict[str, Any], mix: Dict[str, Any]
                   ) -> Dict[str, int]:
    """One call of the FULL layers' flash kernels (`flash_fwd`,
    `flash_bwd_dq`, `flash_bwd_dkv`) on one chip under a training mix: q
    and the output [batch, heads, seq, head_dim], k and v [batch, kv_heads,
    seq, head_dim] ([2, 48 on 8, 8192, 128] at laguna_train_1chip).
    benchmark/kernels/gqa_attention.py counts it."""
    return _call(config, mix, "full_attention")


def window_call(config: Dict[str, Any], mix: Dict[str, Any]
                ) -> Dict[str, int]:
    """One call of the sliding layers' kernels (`flash_win_*`): as
    attention_call at their head count ([2, 64 on 8, 8192, 128]), with the
    window. benchmark/kernels/window_attention.py counts it."""
    return dict(_call(config, mix, "sliding_attention"),
                window=config["sliding_window"])
