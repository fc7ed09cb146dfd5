"""FLOPs and HBM bytes of ONE call on ONE chip of the two kernels between
the attention projections and the flash kernels, by the names they carry in
the trace (`name=` on the pallas_calls of ops/rope.py). What the algorithm
needs, not what an implementation pads.

`rope_split` takes one projection's output [B, S, H * D] to the flash
kernels' [B, H, S, D], rotating q and k on the way (v is split only);
`rope_merge` is its transpose on the way back. Either way a call reads
B H S D elements once and writes them once, in the activations' two-byte
type: logical bytes, so the 128 lanes a 64-wide head owns in memory (twice
its bytes, on the [B, H, S, D] side) are left out, as is the table of
cosines and sines (S x 128 floats, read once a batch row and sequence
block). The rotation is two multiplies and an add an element for two calls
of three: nothing beside the bytes. The shape comes from the
configuration's family (`attention_call`), as for the flash kernels.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import model

ELEMENT_BYTES = 2          # bf16 activations


def _one_pass(config: Dict[str, Any], mix: Dict[str, Any]
              ) -> Tuple[float, float]:
    c = model.family(config).attention_call(config, mix)
    elements = c["batch"] * c["heads"] * c["seq"] * c["head_dim"]
    return 2.0 * elements, float(2 * elements * ELEMENT_BYTES)


def rope_split(config, mix) -> Tuple[float, float]:
    return _one_pass(config, mix)


def rope_merge(config, mix) -> Tuple[float, float]:
    return _one_pass(config, mix)
