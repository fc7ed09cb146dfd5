"""The family `olmo_hybrid`: the decoder stack of Olmo-Hybrid-7B
(`model_type` `olmo_hybrid`): three gated delta-rule layers (Gated DeltaNet,
arXiv:2412.06464; `layer_types` `linear_attention`, the `linear_*` keys, which
are Qwen3-Next's) to one full-attention layer that rotates nothing
(`full_attention`; `rope_parameters.rope_theta` null), a dense gated MLP in
every layer, and the Olmo 2 / 3 block: the norm of a half sits AFTER it. What
a family module holds is listed in gpt_dense.py.

The layer, as the reference below writes it out. x is [S, d]; RMSNorm with
`rms_norm_eps` everywhere; no bias anywhere. Layer l:
  h = x + norm1(Mix_l(x));   y = h + norm2(MLP(h))
Mix_l and the MLP read the stream itself; the norm is on what each half ADDS.

`linear_attention` (H = `linear_num_key_heads` = `linear_num_value_heads`
heads, dk = `linear_key_head_dim` for q and k, dv = `linear_value_head_dim`
for v, a state [dk, dv] a head; Qwen3NextGatedDeltaNet and
torch_recurrent_gated_delta_rule of transformers' qwen3_next):
  [q | k | v] = silu(conv(x Wqkv)): conv a causal depthwise filter of
        `linear_conv_kernel_dim` taps a channel (zeros before the start, the
        last tap on the token itself); ONE matrix and one filter as the
        published in_proj_qkvz and conv1d are, its columns ordered a head at a
        time, [q_h | k_h | v_h] (whole heads are whole columns: the order a
        tensor-parallel cut needs; a permutation of the checkpoint's)
  q = l2norm_h(q) dk^-1/2,  k = l2norm_h(k): a head's dk columns divided by
        sqrt(their squares' sum + 1e-6)
  g_t = -exp(A_h) softplus(x Wa + b_dt)_h: ONE log-decay a head and token
  beta_t = sigmoid(x Wb)_h, doubled under `linear_allow_neg_eigval`: (0, 2)
  S_0 = 0, a token at a time:
        S_t = (I - beta_t k_t k_t^T) e^{g_t} S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
  Mix = [RMSNorm_dv(o_t) * silu(x Wz)] Wo, Wz a full matrix [d, H dv], the
        norm's scale one of dv for every head (Qwen3NextRMSNormGated)
`full_attention` (H = `num_attention_heads` on `num_key_value_heads` alike, D
  = `head_dim`, 128 = hidden_size / the published 30 heads):
  q = RMSNorm(x Wq), k = RMSNorm(x Wk) over the WHOLE projection, a scale a
        column (Olmo's q/k norm); v = x Wv
  NO rotation (`rope_theta` null: the mask and the delta-rule layers carry
        position; a number there, which no published model of the family
        has and a control uses: every column as halves at that theta)
  softmax of q k^T / sqrt(D) in float32 over j <= i;  Mix = concat_h(P_h v_h) Wo
MLP: Wdown(silu(Wgate h) * Wup h), `intermediate_size` wide.
Final RMSNorm, then an untied head.

The chip's share (`share` in the configuration file; model-configs guide,
section 4): the file's four head counts and `vocab_size` are what is HELD
here: heads rank * held .. + held - 1 of `share.num_attention_heads` in both
mixers (column-parallel in-projections, a head's filters, A, b_dt, its rows
of Wo), the vocabulary's rows of this slice. A mixer's head needs nothing of
another's before Wo, whose rows are summed, so a share's mixer output is its
heads' part of that sum, and what the other heads would have added is left
out, here and in the program alike. TWO statistics would be all-reduced over
the tensor-parallel pair in a deployment and are taken over what is held
here, in both: the q/k norm's mean square (over the held 15 x 128 columns)
and the norm after the mixer (over the held heads' sum). The MLP, the norms'
scales of d and the stream are whole on every chip. A file without `share`
is the whole layer (tests/test_olmo_hybrid_model.py runs the two shares on a
mesh of tensor = 2, where both sums are real, against it).

Departures and assumptions, each also in the configuration file: the program
runs the delta rule in chunks of 64 tokens (ops/linear_attention.py), the
reference a token at a time; the fused projection's columns a head at a time
and the gate z a matrix of its own; `head_dim` is stated (128) because hidden_size / the held head
count is not it; seeded random weights, the decay's initialisation solar's
and kimi's.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict, Tuple

# queries a block of the reference's attention: [heads, block, S] float32
# scores are 0.25 GB at 15 heads and 8192 positions
QUERY_BLOCK = 512

# the chunk ops/linear_attention.py runs the delta rule in: the arithmetic
# of benchmark/kernels/gated_delta.py is stated at it
KDA_CHUNK = 64

# the spread models/gpt.py:gpt_init draws the embedding's rows at
GPT_INIT_EMBEDDING_STD = 0.02

_HEAD_KEYS = ("num_attention_heads", "num_key_value_heads",
              "linear_num_key_heads", "linear_num_value_heads")


def share(config: Dict[str, Any]) -> Tuple[int, int, int]:
    """(first head held here, how many, of how many), in both mixers."""
    held = config["num_attention_heads"]
    s = config.get("share")
    if s is None:
        return 0, held, held
    return s["rank"] * held, held, s["num_attention_heads"]


def _kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    """"kda" | "attention" a layer, from `layer_types`."""
    names = {"linear_attention": "kda", "full_attention": "attention"}
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"] or set(types) - set(names):
        raise ValueError(
            f"layer_types {types!r}: expected num_hidden_layers="
            f"{config['num_hidden_layers']} of "
            + " | ".join(map(repr, names)))
    return tuple(names[t] for t in types)


def _theta(config: Dict[str, Any]):
    """`rope_parameters.rope_theta`: None, nothing rotates."""
    return (config.get("rope_parameters") or {}).get("rope_theta")


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    from ray_tpu.models.gpt import DeltaRule
    heads = config["num_attention_heads"]
    unbuilt = {"hidden_act": "silu", "attention_bias": False,
               "num_key_value_heads": heads, "linear_num_key_heads": heads,
               "linear_num_value_heads": heads}
    for key, built in unbuilt.items():
        if config[key] != built:
            raise ValueError(f"models/gpt.py is built for {key} = {built!r} "
                             f"here, the configuration has {config[key]!r}")
    theta = _theta(config)
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": heads,
        # the full layer's; stated where a share's head count hides it
        "head_dim": config.get("head_dim") or config["hidden_size"] // heads,
        "layer_kinds": _kinds(config),
        "use_rope": theta is not None,
        **({} if theta is None else {"rope_theta": float(theta)}),
        "qk_norm": True,
        "norm_after": True,
        "conv_filter": config["linear_conv_kernel_dim"],
        "kda_neg_eigval": bool(config["linear_allow_neg_eigval"]),
        "delta": DeltaRule(config["linear_key_head_dim"],
                           config["linear_value_head_dim"],
                           decay="head", gate="silu"),
        "d_ff": config["intermediate_size"],
        "max_seq": config["max_position_embeddings"],
        "rmsnorm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
    }


def _train_config(config: Dict[str, Any]):
    """The GPTConfig the step is built from (bf16 activations, flash
    attention, the delta rule's and the filters' kernels, remat of the whole
    layer)."""
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(**gpt_config_kwargs(config), attention="flash",
                     remat_policy="full")


def program(config: Dict[str, Any], serving: bool = False):
    """The system under test at these sizes, as gpt_dense.program: the same
    models/gpt.py, told the kinds of its layers, the delta rule's widths and
    forms, where the norm of a half sits and that nothing rotates."""
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
# a block of queries at a time. Call it under
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


def reference_delta_rule(q, k, v, g, beta):
    """q, k [S, H, dk], v [S, H, dv], g and beta [S, H] -> o [S, H, dv]: the
    recurrence, a token a step, as torch_recurrent_gated_delta_rule writes
    it (the state decayed, read along k, overwritten, read along q)."""
    import jax
    import jax.numpy as jnp

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, None, None]
        kv_mem = jnp.einsum("hkv,hk->hv", state, k_t)
        delta = (v_t - kv_mem) * b_t[:, None]
        state = state + jnp.einsum("hk,hv->hkv", k_t, delta)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, heads, dk = q.shape
    _, o = jax.lax.scan(token,
                        jnp.zeros((heads, dk, v.shape[-1]), jnp.float32),
                        (q, k, v, g, beta))
    return o


def reference_gate(o, z):
    """The gated norm's second half: the normed heads' outputs times
    silu(z), an element."""
    import jax
    return o * jax.nn.silu(z)


def reference_gdn(m, x, config: Dict[str, Any]):
    """x [S, d], the stream -> what a Gated DeltaNet mixer makes of it,
    before the block's norm."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    s = x.shape[0]
    qkv = _filtered(x @ m["w_qkv"].astype(f32), m["qkv_conv"]).reshape(
        s, -1, 2 * dk + dv)                 # a head's columns [q | k | v]
    q, k, v = qkv[..., :dk], qkv[..., dk:2 * dk], qkv[..., 2 * dk:]
    q, k = _unit(q) / math.sqrt(dk), _unit(k)
    g = -jnp.exp(m["a_log"].astype(f32)) * jax.nn.softplus(
        x @ m["w_decay"].astype(f32) + m["dt_bias"])
    beta = jax.nn.sigmoid(x @ m["w_beta"].astype(f32))
    if config["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    o = reference_delta_rule(q, k, v, g, beta)
    o = _norm(o, m["o_norm"]["scale"], float(config["rms_norm_eps"]))
    return reference_gate(o.reshape(s, -1), x @ m["wg"].astype(f32)) \
        @ m["wo"].astype(f32)


def _rotated(t, theta: float):
    """t [S, heads, D]: every column rotated as halves at theta (the form a
    `rope_theta` that is a number takes: a control's, no published model's)."""
    import jax.numpy as jnp
    s, _, dim = t.shape
    angles = (jnp.arange(s, dtype=jnp.float32)[:, None]
              * theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, c = t[..., :dim // 2], t[..., dim // 2:]
    return jnp.concatenate([a * cos - c * sin, a * sin + c * cos], -1)


def reference_qk_norm(y, scale, eps):
    """Olmo's q/k norm: RMSNorm over the whole projection [S, H D] (what is
    held of it), a scale a column."""
    return _norm(y, scale, eps)


def reference_attention(a, x, config: Dict[str, Any]):
    """x [S, d], the stream -> what a full-attention mixer makes of it,
    before the block's norm: no rotation, the causal mask alone."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    heads = config["num_attention_heads"]
    dim = config.get("head_dim") or config["hidden_size"] // heads
    eps = float(config["rms_norm_eps"])
    s = x.shape[0]

    q = reference_qk_norm(x @ a["wq"].astype(f32), a["q_norm"]["scale"], eps)
    k = reference_qk_norm(x @ a["wk"].astype(f32), a["k_norm"]["scale"], eps)
    q, k, v = (t.reshape(s, heads, dim)
               for t in (q, k, x @ a["wv"].astype(f32)))
    theta = _theta(config)
    if theta is not None:
        q, k = _rotated(q, float(theta)), _rotated(k, float(theta))

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


def reference_mixer(layer, x, config: Dict[str, Any]):
    """A layer's mixer, by what its parameters hold."""
    if "kda" in layer:
        return reference_gdn(layer["kda"], x, config)
    return reference_attention(layer["attn"], x, config)


def reference_block(layer, x, config: Dict[str, Any]):
    """One layer on the stream x [S, d]: the norm AFTER each half."""
    import jax.numpy as jnp
    eps = float(config["rms_norm_eps"])
    h = x + _norm(reference_mixer(layer, x, config), layer["ln1"]["scale"],
                  eps)
    return h + _norm(_swiglu(layer["mlp"], h, jnp.float32),
                     layer["ln2"]["scale"], eps)


def _sequence(params, tokens, config):
    """tokens [S] -> final-normed hidden states [S, d]."""
    import jax.numpy as jnp
    x = params["embed"]["table"].astype(jnp.float32)[tokens]
    for layer, kind in zip(params["layers"], _kinds(config)):
        if ("kda" in layer) != (kind == "kda"):
            raise ValueError("the parameters' layers are not the "
                             "configuration's")
        x = reference_block(layer, x, config)
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
    percentile of the absolute gap). The first loss at random weights is log
    V plus half the logits' variance whatever the block computes; the tokens'
    own log-probabilities tell a decay left out, a beta not doubled, the norm
    on the wrong side of a half, the full layer rotated, another gate, a q/k
    norm left out and rounded weights from the step's own rounding (the
    readings behind the bounds are in the configuration file). The program
    is the forward the step was built from, on one device, at the default
    matmul precision whatever the caller's (the delta rule's own products
    ask for full precision themselves)."""
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

def _widths(config: Dict[str, Any]) -> Tuple[int, int, int, int, int]:
    """(d, heads held, the full layer's head width, H dk, H dv)."""
    heads = config["num_attention_heads"]
    return (config["hidden_size"], heads,
            config.get("head_dim") or config["hidden_size"] // heads,
            heads * config["linear_key_head_dim"],
            heads * config["linear_value_head_dim"])


def _matrices(config: Dict[str, Any]) -> Dict[str, int]:
    """Elements of each group of matrices HELD: the two mixers, the MLP."""
    d, heads, dim, keys, values = _widths(config)
    return {
        # q, k, v, the gate z, the output; the decay's and beta's a head
        "gdn": d * (2 * keys + 2 * values) + values * d + 2 * d * heads,
        "attention": 4 * d * heads * dim,
        "mlp": 3 * d * config["intermediate_size"]}


def _layers(config: Dict[str, Any]) -> Tuple[int, int]:
    """(full-attention layers, delta-rule layers)."""
    kinds = _kinds(config)
    return kinds.count("attention"), kinds.count("kda")


def param_count(config: Dict[str, Any]) -> int:
    """Every parameter resident on the device: a mixer's matrices (a
    delta-rule layer's three filters, its decay rate and step bias a head
    and its norm's scale of dv beside them; the full layer's two q/k norm
    scales over the held projection), two norms and the MLP a layer; embedding
    and head over the vocabulary held, the final norm."""
    m = _matrices(config)
    d, heads, dim, keys, values = _widths(config)
    v = config["vocab_size"]
    small = ((2 * keys + values) * config["linear_conv_kernel_dim"]
             + 2 * heads + config["linear_value_head_dim"])
    full, gdn = _layers(config)
    return (full * (m["attention"] + 2 * heads * dim)
            + gdn * (m["gdn"] + small) + (full + gdn) * (m["mlp"] + 2 * d)
            + v * d + d + (0 if config["tie_word_embeddings"] else d * v))


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 x the matrix parameters a token goes through HERE + 3 x the two
    mixers' own products: both mixers' projections, the MLP, the head over
    the vocabulary held; in the full layer q.k and p.v at head_dim under the
    causal mask (S / 2 keys a query); in a delta-rule layer the rule's
    products a token and head at the chunk the program runs
    (benchmark/kernels/gated_delta.py:flops_per_token). The backward's two
    for one. Left out: the embedding lookup, the norms, the filters, the
    decays' exponentials, the softmax, and recomputation (remat)."""
    from benchmark.kernels.gated_delta import flops_per_token
    m = _matrices(config)
    _, heads, dim, _, _ = _widths(config)
    full, gdn = _layers(config)
    active = (full * m["attention"] + gdn * m["gdn"] + (full + gdn) * m["mlp"]
              + config["hidden_size"] * config["vocab_size"])
    products = (full * heads * 2.0 * 2 * dim * seq / 2.0
                + gdn * heads * flops_per_token(
                    KDA_CHUNK, config["linear_key_head_dim"],
                    config["linear_value_head_dim"]))
    return 6.0 * active + 3.0 * products


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0


def _a_chips_rows(mix: Dict[str, Any]) -> int:
    mesh = mix["mesh"]
    return mix["global_batch"] // (mesh.get("data", 1) * mesh.get("fsdp", 1))


def attention_call(config: Dict[str, Any], mix: Dict[str, Any]
                   ) -> Dict[str, int]:
    """One call of the full layer's flash kernels on one chip under a
    training mix: q, k, v and the output [batch, heads, seq, head_dim]
    ([1, 15, 8192, 128] at olmohybrid_train_1chip).
    benchmark/kernels/flash_attention.py counts it."""
    _, heads, dim, _, _ = _widths(config)
    return {"batch": _a_chips_rows(mix),
            "heads": heads // mix["mesh"].get("tensor", 1),
            "seq": mix["seq"], "head_dim": dim}


def kda_call(config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, int]:
    """One delta-rule layer's tensors on one chip under a training mix: q
    and k [batch, heads, seq, key_dim], v and the output [.., value_dim]
    ([1, 15, 8192, 96 / 192] at olmohybrid_train_1chip), the filter's taps.
    benchmark/kernels/gated_delta.py counts the rule's two kernels from
    `key_dim` and `value_dim`. `head_dim` is what benchmark/kernels/kda.py
    reads it as, the columns a head of the ONE filtered tensor: the program
    filters [q | k | v] in one call over heads x (2 key_dim + value_dim)
    columns ([1, 8192, 15 x 384])."""
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    return {"batch": _a_chips_rows(mix),
            "heads": config["linear_num_value_heads"]
            // mix["mesh"].get("tensor", 1),
            "seq": mix["seq"], "key_dim": dk, "value_dim": dv,
            "head_dim": 2 * dk + dv,
            "taps": config["linear_conv_kernel_dim"], "chunk": KDA_CHUNK}
