"""FLOPs and HBM bytes of ONE call on ONE chip of the two kernels of
ops/short_conv.py, by the names they carry in the trace (`name=` on its
pallas_calls). The yardstick of `short_conv_*_roofline`: what the operator
between a short-convolution layer's projections needs, C * filter(B * X) on
[batch, seq, channels] with a filter of L taps a channel.

Each function takes (configuration, traffic mix) and returns (FLOPs, bytes).
Both kernels are bound by bytes (a few operations an element moved), so the
FLOPs beside them only say so. Forward: reads B, C, X and writes the result,
4 tensors in the activations' two-byte type (268 MB at [2, 8192, 2048]);
L + 1 multiplies and L - 1 adds an element. Backward: reads B, C, X and the
cotangent, writes dB, dC, dX: 7 tensors (470 MB); it computes u = B * X and
the filtered u again (L + 1 operations), the filtered cotangent (2 L), the
three gates' products and the filter's gradient (2 L): about 5 L + 6 an
element. The filter itself, its gradient and the rows read a second time
beside a block (the halo, 16 in 512) are left out on both sides.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

ELEMENT_BYTES = 2          # bf16 activations


def _elements_and_taps(config: Dict[str, Any], mix: Dict[str, Any]
                       ) -> Tuple[float, int]:
    """(elements of one [batch, seq, channels] tensor on one chip, L)."""
    mesh = mix["mesh"]
    batch = mix["global_batch"] // (mesh.get("data", 1) * mesh.get("fsdp", 1))
    channels = config["hidden_size"] // mesh.get("tensor", 1)
    return float(batch * mix["seq"] * channels), config["conv_L_cache"]


def short_conv_fwd(config, mix) -> Tuple[float, float]:
    elements, taps = _elements_and_taps(config, mix)
    return 2 * taps * elements, 4 * elements * ELEMENT_BYTES


def short_conv_bwd(config, mix) -> Tuple[float, float]:
    elements, taps = _elements_and_taps(config, mix)
    return (5 * taps + 6) * elements, 7 * elements * ELEMENT_BYTES
