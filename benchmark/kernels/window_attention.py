"""FLOPs and HBM bytes of ONE call on ONE chip of the three flash-attention
kernels under a sliding window and grouped queries (H query heads on Hkv
key/value heads), by the names they carry in the trace (`name=` on the
pallas_calls of ops/attention.py: `flash_win_fwd`, `flash_win_bwd_dq`,
`flash_win_bwd_dkv`). The yardstick of `swa_*_roofline`: what the algorithm
needs, the band's pairs alone, K and V read once a key/value head; a kernel
that walks the whole causal triangle and masks it, or fetches a block of
keys for every query block that touches it, reads a lower share.

Each function takes (configuration, traffic mix) and returns (FLOPs, bytes).
The call's shape comes from the configuration's family (`window_call`:
batch, heads, kv_heads, seq, head_dim, window).

Query i keeps keys j with 0 <= i - j < W: W keys a query, fewer for the
first W - 1 queries: S W - W (W - 1) / 2 pairs a head (`band_pairs`), counted
exactly. One product costs 2 x pairs x D a query head and batch row. Forward:
S = Q K^T and P V. Backward needs five: S again, dP = dO V^T, dV = P^T dO,
dK = dS^T Q, dQ = dS K, run as two kernels that both recompute S and dP;
each of the five is divided between the kernels that run it in equal parts
(S and dP halved, dQ whole to the first, dV and dK whole to the second:
benchmark/kernels/gqa_attention.py's division), so that the two shares add
up to the five. Bytes: every tensor a kernel reads or writes, once, in the
activations' two-byte type, Q, O, dO, dQ at H heads and K, V, dK, dV at Hkv:
Q, K, V, O forward; Q, K, V, dO and dQ; Q, K, V, dO and dK, dV. The row
statistics are left out on both sides.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import model

ELEMENT_BYTES = 2          # bf16 activations


def band_pairs(seq: int, window: int) -> int:
    """(query, key) pairs one head keeps."""
    w = min(window, seq)
    return seq * w - w * (w - 1) // 2


def _product_and_tensors(config: Dict[str, Any], mix: Dict[str, Any]
                         ) -> Tuple[float, float, float]:
    """(FLOPs of one product over the band, bytes of one tensor at the
    query heads' count, of one at the key/value heads')."""
    c = model.family(config).window_call(config, mix)
    product = (2.0 * band_pairs(c["seq"], c["window"]) * c["head_dim"]
               * c["batch"] * c["heads"])
    positions = c["batch"] * c["seq"] * c["head_dim"]
    return (product, float(positions * c["heads"] * ELEMENT_BYTES),
            float(positions * c["kv_heads"] * ELEMENT_BYTES))


def flash_win_fwd(config, mix) -> Tuple[float, float]:
    product, wide, narrow = _product_and_tensors(config, mix)
    return 2 * product, 2 * wide + 2 * narrow


def flash_win_bwd_dq(config, mix) -> Tuple[float, float]:
    product, wide, narrow = _product_and_tensors(config, mix)
    return 2 * product, 3 * wide + 2 * narrow


def flash_win_bwd_dkv(config, mix) -> Tuple[float, float]:
    product, wide, narrow = _product_and_tensors(config, mix)
    return 3 * product, 2 * wide + 4 * narrow
