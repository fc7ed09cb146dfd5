"""FLOPs and HBM bytes of ONE call on ONE chip of the three flash-attention
kernels, by the names they carry in the trace (`name=` on the pallas_calls
of ops/attention.py). The yardstick of `<kernel>_roofline`: what the
algorithm needs, not what an implementation happens to run.

Each function takes (configuration, traffic mix) and returns (FLOPs, bytes).
The call's shape [B, H, S, D] comes from the configuration's family
(`attention_call`), so a family with other head widths or another sharding
brings no arithmetic of its own. A metric file names this module and the
kernel: {"reader": "roofline", "args": {"arithmetic": "flash_attention",
"kernel": "flash_fwd"}}; a new kernel is a new module here.

Causal attention over S positions: two matmuls forward (Q K^T, P V), each
2 B H S^2 D, half of it under the mask: 4 B H S^2 D / 2. Backward needs five
(S again, dP, dV, dK, dQ): 2.5 x the forward. The program runs them as two
kernels, `flash_bwd_dq` with three matmuls and `flash_bwd_dkv` with four
(both recompute S and dP), so the 2.5 x is divided 3 : 4 between them; a
fused backward would be held to the whole 2.5 x. Bytes: every tensor a
kernel reads or writes, once, in the activations' two-byte type: Q, K, V, O
forward; Q, K, V, dO and dQ; Q, K, V, dO and dK, dV. The row statistics
(B H S floats) are left out on both sides.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import model

ELEMENT_BYTES = 2          # bf16 activations
BACKWARD_OVER_FORWARD = 2.5


def _forward_flops_and_tensor_bytes(config: Dict[str, Any],
                                    mix: Dict[str, Any]
                                    ) -> Tuple[float, float]:
    c = model.family(config).attention_call(config, mix)
    b, h, s, d = c["batch"], c["heads"], c["seq"], c["head_dim"]
    return 4.0 * b * h * s * s * d / 2.0, float(b * h * s * d * ELEMENT_BYTES)


def flash_fwd(config, mix) -> Tuple[float, float]:
    flops, tensor = _forward_flops_and_tensor_bytes(config, mix)
    return flops, 4 * tensor


def flash_bwd_dq(config, mix) -> Tuple[float, float]:
    flops, tensor = _forward_flops_and_tensor_bytes(config, mix)
    return BACKWARD_OVER_FORWARD * flops * 3.0 / 7.0, 5 * tensor


def flash_bwd_dkv(config, mix) -> Tuple[float, float]:
    flops, tensor = _forward_flops_and_tensor_bytes(config, mix)
    return BACKWARD_OVER_FORWARD * flops * 4.0 / 7.0, 6 * tensor
