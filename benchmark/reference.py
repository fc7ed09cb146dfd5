"""The configurations' plain reference: a decoder block written out in
float32 with nothing of the program in it — no kernel, no remat, no
sharding rule, no chunking, none of ray_tpu's model code. It reads the same
parameter tree (embed.table, layers[i].{ln1, ln2, attn.{wq, wk, wv, wo},
mlp.{w_gate, w_up, w_down}}, final_norm, lm_head unless the head is tied)
and the sizes of the configuration's file. `correct` compares the program's
answers with these. Call it under jax.default_matmul_precision("highest").
"""

from __future__ import annotations

import math
from typing import Any, Dict


def logits(params, tokens, config: Dict[str, Any]):
    """tokens [B, S] -> float32 logits [B, S, vocab]: RMSNorm, rotary
    positions on halves of the head, causal softmax attention over all
    heads, SwiGLU, residuals, final norm, head."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    heads = config["num_attention_heads"]
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    x = params["embed"]["table"].astype(f32)[tokens]
    b, s, d = x.shape
    hd = d // heads
    half = hd // 2
    angles = (jnp.arange(s, dtype=f32)[:, None]
              * theta ** (-jnp.arange(half, dtype=f32) / half)[None, :])
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale.astype(f32)

    def rotate(t):                       # [B, S, heads, hd]
        a, c = t[..., :half], t[..., half:]
        return jnp.concatenate([a * cos - c * sin, a * sin + c * cos], -1)

    for layer in params["layers"]:
        w = {k: v.astype(f32) for k, v in layer["attn"].items()}
        h = norm(x, layer["ln1"]["scale"])
        q = rotate((h @ w["wq"]).reshape(b, s, heads, hd))
        k = rotate((h @ w["wk"]).reshape(b, s, heads, hd))
        v = (h @ w["wv"]).reshape(b, s, heads, hd)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        mixed = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, s, d)
        x = x + mixed @ w["wo"]
        m = {k: v.astype(f32) for k, v in layer["mlp"].items()}
        h = norm(x, layer["ln2"]["scale"])
        x = x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
    x = norm(x, params["final_norm"]["scale"])
    head = (params["lm_head"] if "lm_head" in params
            else params["embed"]["table"].T).astype(f32)
    return x @ head


def logprobs(params, tokens, config: Dict[str, Any]):
    """[B, S] -> [B, S-1]: log-probability of each token after the first
    given the tokens before it."""
    import jax
    import jax.numpy as jnp
    z = logits(params, tokens, config)[:, :-1]
    picked = jnp.take_along_axis(z, tokens[:, 1:, None], axis=-1)[..., 0]
    return picked - jax.nn.logsumexp(z, axis=-1)


def loss(params, tokens, config: Dict[str, Any]):
    """tokens [B, S+1] -> mean next-token cross-entropy over B x S."""
    import jax.numpy as jnp
    return -jnp.mean(logprobs(params, tokens, config))
