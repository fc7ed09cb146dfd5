"""The table of peaks, the utilization arithmetic on it, and the lookup of
a configuration's family.

A configuration file (benchmark/configs/<name>.json) holds the model's sizes
under the keys of the Hugging Face `config.json` convention, whatever its
source calls them, and may name its family; benchmark/families/<family>.py
maps the keys onto the program and holds the reference and the FLOPs
arithmetic. The peak is a copy of bench.py's `PEAK_BF16_FLOPS`, kept here so
that no later PR can move the yardstick.
"""

from __future__ import annotations

import importlib
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


def family(config: Dict[str, Any]):
    """The module under benchmark/families/ that knows this configuration's
    architecture (its "family" key; gpt_dense where the file has none): the
    program's side, the plain reference, the FLOPs arithmetic and the shapes
    of its kernels' calls. The one lookup through which the cells, the
    readers and the selftest reach an architecture."""
    return importlib.import_module(
        "benchmark.families." + config.get("family", "gpt_dense"))


def mfu_pct(tokens_per_s: float, flops_per_token: float, chips: int,
            device_kind: str) -> float:
    """Model FLOP/s utilization: arithmetic on an end-to-end rate, not a
    kernel's roofline share."""
    return 100.0 * tokens_per_s * flops_per_token / (
        chips * peak(device_kind)["bf16_flops"])
