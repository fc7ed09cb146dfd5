"""Configurations, the FLOPs arithmetic and the table of peaks.

A configuration file (benchmark/configs/<name>.json) holds the model's sizes
under the keys of the Hugging Face `config.json` convention, whatever its
source calls them; `gpt_config` maps them onto the program's `GPTConfig`.
The arithmetic and the peak are copies of bench.py's (`bench_model`,
`PEAK_BF16_FLOPS`), kept here so that no later PR can move the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict

# One chip's published peaks, keyed by jax's device_kind. Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s, 16 GB of HBM.
# A device kind that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device kind {device_kind!r} is not in the benchmark's table of "
            f"peaks ({sorted(PEAKS)}); add it with its source")
    return PEAKS[device_kind]


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


def mfu_pct(tokens_per_s: float, flops_per_token: float, chips: int,
            device_kind: str) -> float:
    """Model FLOP/s utilization: arithmetic on an end-to-end rate, not a
    kernel's roofline share."""
    return 100.0 * tokens_per_s * flops_per_token / (
        chips * peak(device_kind)["bf16_flops"])
