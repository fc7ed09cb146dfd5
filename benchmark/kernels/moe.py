"""FLOPs and HBM bytes of ONE call on ONE chip of the two grouped-matmul
kernels of the sparse-expert block, by the names they carry in the trace
(`name=` on the pallas_calls of ops/moe.py). What the algorithm needs, not
what the implementation pads: M = the chip's tokens x experts a token rows
exactly, every expert's matrix read or written once.

`moe_gmm` is one name for five call shapes of one cost: gate and up
([M, d] x [E, d, f] -> [M, f]), down ([M, f] x [E, f, d] -> [M, d]) and
the gradients of their rows (the same with the matrix transposed). Each is
2 M d f FLOPs and moves M (d + f) elements of rows and E d f of matrices.
`moe_tgmm` is the gradient of a matrix, rows^T x rows over each expert's
group ([M, d]^T [M, f] -> [E, d, f] or its transpose): the same FLOPs and
the same bytes, the matrices written instead of read. All in the
activations' two-byte type. The tile-to-expert table and the padding rows
of the implementation are left out on both sides.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

ELEMENT_BYTES = 2          # bf16 activations, bf16 copies of the matrices


def _one_grouped_matmul(config: Dict[str, Any], mix: Dict[str, Any]
                        ) -> Tuple[float, float]:
    mesh = mix["mesh"]
    tokens = (mix["global_batch"] // (mesh.get("data", 1)
                                      * mesh.get("fsdp", 1))) * mix["seq"]
    m = tokens * config["num_experts_per_tok"]
    d, f, e = (config["hidden_size"], config["intermediate_size"],
               config["num_experts"])
    return 2.0 * m * d * f, float((m * (d + f) + e * d * f) * ELEMENT_BYTES)


def moe_gmm(config, mix) -> Tuple[float, float]:
    return _one_grouped_matmul(config, mix)


def moe_tgmm(config, mix) -> Tuple[float, float]:
    return _one_grouped_matmul(config, mix)
