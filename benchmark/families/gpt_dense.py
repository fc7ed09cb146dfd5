"""The family `gpt_dense`: the repo's dense decoder block (RoPE, RMSNorm,
SwiGLU, multi-head attention), and the one place in the benchmark that
names it. A configuration file picks its family with "family": "<name>"
(none: this one); benchmark/model.py:family() finds the module, and the
cells, the readers and the kernels' arithmetic reach an architecture only
through what a family module holds:

  program(config, serving)   the program's side, as the cells call it
  reference_logits / reference_logprobs / reference_loss
                             the plain reference (benchmark/reference.py is
                             this family's; a new family brings its own)
  param_count, train_flops_per_token, forward_flops_per_token
                             the arithmetic behind train_mfu_pct
  attention_call(config, mix)
                             the shape of one flash-kernel call on one chip,
                             which benchmark/kernels/flash_attention.py
                             turns into FLOPs and bytes

A new architecture is a new file here with these names, and a
configuration file that names it.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict

from benchmark.reference import (  # noqa: F401
    logits as reference_logits, logprobs as reference_logprobs,
    loss as reference_loss)


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The config file's sizes as GPTConfig's keyword arguments."""
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("models/gpt.py has no grouped-query attention")
    return {
        "vocab_size": config.get("padded_vocab_size", config["vocab_size"]),
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "d_ff": config["intermediate_size"],
        "max_seq": config["max_position_embeddings"],
        "rope_theta": float(config["rope_theta"]),
        "rmsnorm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config["tie_word_embeddings"]),
    }


def program(config: Dict[str, Any], serving: bool = False):
    """The system under test at these sizes: init(key) -> parameters (fp32
    masters for training, the served type for serving), loss(params, batch,
    mesh, act_sharding) for the train step, score(params, tokens) ->
    [B, S-1] log-probabilities for a scoring forward. Flash attention in
    both; full remat in training."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init, gpt_loss

    if serving:
        cfg = GPTConfig(**gpt_config_kwargs(config), attention="flash")
    else:
        cfg = GPTConfig(**gpt_config_kwargs(config), attention="flash",
                        remat_policy="full")

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


def param_count(config: Dict[str, Any]) -> int:
    """Parameters of the program's block at these sizes: four d x d
    attention matrices, a three-matrix SwiGLU MLP, two norms a layer, the
    embedding, the final norm, and the head unless it is tied."""
    k = gpt_config_kwargs(config)
    d, ff, v = k["d_model"], k["d_ff"], k["vocab_size"]
    layer = 4 * d * d + 3 * d * ff + 2 * d
    head = 0 if k["tie_embeddings"] else d * v
    return k["n_layers"] * layer + v * d + d + head


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 N + 12 L d S: forward and backward of the matrices and of causal
    attention counted as full (bench.py's form). Recomputation (remat) is
    not counted: it is work the model does not require."""
    k = gpt_config_kwargs(config)
    return 6.0 * param_count(config) + 12.0 * k["n_layers"] * k["d_model"] * seq


def forward_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """A scoring forward is a third of the training arithmetic."""
    return train_flops_per_token(config, seq) / 3.0


def attention_call(config: Dict[str, Any], mix: Dict[str, Any]
                   ) -> Dict[str, int]:
    """[batch, heads, seq, head_dim] of one flash-kernel call on one chip
    under a training mix: the batch divided over the mesh's `data` and
    `fsdp` axes, the heads over `tensor` ([64, 12, 1024, 64] at
    gpt2s_train_1chip, [16, 16, 2048, 64] at smollm17_train_4chip)."""
    mesh = mix["mesh"]
    heads = config["num_attention_heads"]
    return {"batch": mix["global_batch"] // (mesh.get("data", 1)
                                             * mesh.get("fsdp", 1)),
            "heads": heads // mesh.get("tensor", 1),
            "seq": mix["seq"],
            "head_dim": config["hidden_size"] // heads}
