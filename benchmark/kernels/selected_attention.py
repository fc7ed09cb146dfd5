"""FLOPs and HBM bytes of ONE call on ONE chip of the three flash-attention
kernels under a learned selection and grouped queries (H query heads on Hkv
key/value heads), by the names they carry in the trace (`name=` on the
pallas_calls of ops/attention.py: `flash_sel_fwd`, `flash_sel_bwd_dq`,
`flash_sel_bwd_dkv`). The yardstick of `sel_*_roofline`: what the algorithm
needs, the SELECTED pairs alone, K and V read once a key/value head and the
selection once a batch row; a kernel that computes every causal tile and
masks it reads at most selected / causal pairs of its dense share (44 % at
8192 positions and 2048 keys a query), one that fetches the selection's
tile again for every head a little less.

Each function takes (configuration, traffic mix) and returns (FLOPs, bytes).
The call's shape comes from the configuration's family (`attention_call`:
batch, heads, kv_heads, seq, head_dim, topk).

Query i keeps min(i + 1, K) keys: S K - K (K - 1) / 2 pairs a head
(`selected_pairs`), whichever keys they are. One product costs 2 x pairs x
D a query head and batch row. Forward: S = Q K^T and P V. Backward needs
five: S again, dP = dO V^T, dV = P^T dO, dK = dS^T Q, dQ = dS K, run as two
kernels that both recompute S and dP; each of the five is divided between
the kernels that run it in equal parts (S and dP halved, dQ whole to the
first, dV and dK whole to the second: benchmark/kernels/gqa_attention.py's
division), so that the two shares add up to the five. Bytes: every tensor a
kernel reads or writes, once, in the activations' two-byte type, Q, O, dO,
dQ at H heads and K, V, dK, dV at Hkv: Q, K, V, O forward; Q, K, V, dO and
dQ; Q, K, V, dO and dK, dV; and the selection as the kernels read it, one
byte a pair, its causal half (nothing above the diagonal is ever selected)
once a batch row. The row statistics are left out on both sides.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import model

ELEMENT_BYTES = 2          # bf16 activations
SELECTION_BYTES = 1        # int8, a pair


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs one head keeps."""
    k = min(topk, seq)
    return seq * k - k * (k - 1) // 2


def _product_and_tensors(config: Dict[str, Any], mix: Dict[str, Any]
                         ) -> Tuple[float, float, float, float]:
    """(FLOPs of one product over the selected pairs, bytes of one tensor
    at the query heads' count, of one at the key/value heads', of the
    selection's causal half)."""
    c = model.family(config).attention_call(config, mix)
    product = (2.0 * selected_pairs(c["seq"], c["topk"]) * c["head_dim"]
               * c["batch"] * c["heads"])
    positions = c["batch"] * c["seq"] * c["head_dim"]
    return (product, float(positions * c["heads"] * ELEMENT_BYTES),
            float(positions * c["kv_heads"] * ELEMENT_BYTES),
            float(c["batch"] * c["seq"] * (c["seq"] + 1) // 2
                  * SELECTION_BYTES))


def flash_sel_fwd(config, mix) -> Tuple[float, float]:
    product, wide, narrow, selection = _product_and_tensors(config, mix)
    return 2 * product, 2 * wide + 2 * narrow + selection


def flash_sel_bwd_dq(config, mix) -> Tuple[float, float]:
    product, wide, narrow, selection = _product_and_tensors(config, mix)
    return 2 * product, 3 * wide + 2 * narrow + selection


def flash_sel_bwd_dkv(config, mix) -> Tuple[float, float]:
    product, wide, narrow, selection = _product_and_tensors(config, mix)
    return 3 * product, 2 * wide + 4 * narrow + selection
