"""FLOPs and HBM bytes of ONE call on ONE chip of the three flash-attention
kernels under grouped-query attention (H query heads on Hkv key/value
heads), by the names they carry in the trace (`name=` on the pallas_calls
of ops/attention.py). The yardstick of `gqa_*_roofline`: what the algorithm
needs, K and V read once a key/value head and never repeated to the query
heads' count; a kernel that reads a repeated copy reads a lower share.

Each function takes (configuration, traffic mix) and returns (FLOPs, bytes).
The call's shape comes from the configuration's family (`attention_call`:
batch, heads, kv_heads, seq, head_dim).

Causal attention over S positions, half of every product under the mask:
one product costs B H S^2 D (every QUERY head runs its own). Forward: S =
Q K^T and P V. Backward needs five: S again, dP = dO V^T, dV = P^T dO, dK =
dS^T Q, dQ = dS K. The program runs them as two kernels that both recompute
S and dP: `flash_bwd_dq` runs S, dP, dQ and `flash_bwd_dkv` runs S, dP, dV,
dK, seven where five are needed. Each product of the five is divided
between the kernels that run it in equal parts (S and dP halved, dQ whole
to the first, dV and dK whole to the second: benchmark/kernels/
mla_attention.py's division), so that the two shares add up to the five.
Bytes: every tensor a kernel reads or writes, once, in the activations'
two-byte type, Q, O, dO, dQ at H heads and K, V, dK, dV at Hkv: Q, K, V, O
forward; Q, K, V, dO and dQ; Q, K, V, dO and dK, dV. The row statistics are
left out on both sides.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import model

ELEMENT_BYTES = 2          # bf16 activations


def _product_and_tensors(config: Dict[str, Any], mix: Dict[str, Any]
                         ) -> Tuple[float, float, float]:
    """(FLOPs of one causal product, bytes of one tensor at the query
    heads' count, of one at the key/value heads')."""
    c = model.family(config).attention_call(config, mix)
    positions = c["batch"] * c["seq"] * c["head_dim"]
    product = float(positions) * c["heads"] * c["seq"]   # 2 S^2 / 2, masked
    return (product, float(positions * c["heads"] * ELEMENT_BYTES),
            float(positions * c["kv_heads"] * ELEMENT_BYTES))


def flash_fwd(config, mix) -> Tuple[float, float]:
    product, wide, narrow = _product_and_tensors(config, mix)
    return 2 * product, 2 * wide + 2 * narrow


def flash_bwd_dq(config, mix) -> Tuple[float, float]:
    product, wide, narrow = _product_and_tensors(config, mix)
    return 2 * product, 3 * wide + 2 * narrow


def flash_bwd_dkv(config, mix) -> Tuple[float, float]:
    product, wide, narrow = _product_and_tensors(config, mix)
    return 3 * product, 2 * wide + 4 * narrow
