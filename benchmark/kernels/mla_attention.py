"""FLOPs and HBM bytes of ONE call on ONE chip of the three flash-attention
kernels where q.k and v differ in width (latent attention), by the names
they carry in the trace (`name=` on the pallas_calls of ops/attention.py).
The yardstick of `mla_*_roofline`: what the algorithm needs at the
PUBLISHED widths, not what an implementation pads; a kernel that pads the
q.k width reads a lower share, never a higher one.

Each function takes (configuration, traffic mix) and returns (FLOPs, bytes).
The call's shape comes from the configuration's family (`attention_call`:
batch, heads, seq, qk_dim, v_dim).

Causal attention over S positions, half of every product under the mask. A
product over the q.k width costs B H S^2 Dqk, one over the v width
B H S^2 Dv. Forward: S = Q K^T (Dqk) and P V (Dv). Backward needs five:
S again (Dqk), dP = dO V^T (Dv), dV = P^T dO (Dv), dK = dS^T Q (Dqk),
dQ = dS K (Dqk). The program runs them as two kernels that both recompute
S and dP: `flash_bwd_dq` runs S, dP, dQ and `flash_bwd_dkv` runs S, dP, dV,
dK, seven where five are needed. Each product of the five is divided
between the kernels that run it in equal parts (S and dP halved, dQ whole
to the first, dV and dK whole to the second), so that the two shares add
up to the five; a fused backward would be held to the whole. Bytes: every
tensor a kernel reads or writes, once, in the activations' two-byte type,
K as the kernel reads it (every head's own copy of the shared rotated
part: the kernels take k [B, H, S, Dqk]): Q, K, V, O forward; Q, K, V, dO
and dQ; Q, K, V, dO and dK, dV. The row statistics are left out on both
sides.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import model

ELEMENT_BYTES = 2          # bf16 activations


def _products_and_tensors(config: Dict[str, Any], mix: Dict[str, Any]
                          ) -> Tuple[float, float, float, float]:
    """(FLOPs of one causal product over the q.k width, over the v width,
    bytes of one q.k-wide tensor, of one v-wide tensor)."""
    c = model.family(config).attention_call(config, mix)
    positions = c["batch"] * c["heads"] * c["seq"]
    square = float(positions) * c["seq"]           # 2 S^2 / 2 under the mask
    return (square * c["qk_dim"], square * c["v_dim"],
            float(positions * c["qk_dim"] * ELEMENT_BYTES),
            float(positions * c["v_dim"] * ELEMENT_BYTES))


def flash_fwd(config, mix) -> Tuple[float, float]:
    qk, pv, wide, narrow = _products_and_tensors(config, mix)
    return qk + pv, 2 * wide + 2 * narrow


def flash_bwd_dq(config, mix) -> Tuple[float, float]:
    qk, pv, wide, narrow = _products_and_tensors(config, mix)
    return 0.5 * qk + 0.5 * pv + qk, 3 * wide + 2 * narrow


def flash_bwd_dkv(config, mix) -> Tuple[float, float]:
    qk, pv, wide, narrow = _products_and_tensors(config, mix)
    return 0.5 * qk + 0.5 * pv + pv + qk, 3 * wide + 3 * narrow
