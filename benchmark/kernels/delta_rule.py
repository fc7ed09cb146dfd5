"""FLOPs and HBM bytes of ONE call on ONE chip of the two kernels of the
chunked gated delta rule, by the names they carry in the trace (`name=` on
the pallas_calls of ops/linear_attention.py: `kda_fwd`, `kda_bwd`), FROM THE
MATHEMATICS and not from the kernels' passes. The shapes come from the
configuration's family (`kda_call`: batch, heads, seq, head_dim, chunk), the
forward's products from benchmark/kernels/kda.py, which stated them before
there was a kernel.

- `kda_fwd` is the whole forward of `kda.py:delta_rule`: with C the chunk and
  D = dk = dv, A and Aqk under the decays, the triangular solve against [V |
  Kbar] and the three products against the state, 5 C D + 6 D^2 a token and
  head; q, k, v (two bytes), the log-decay and beta (four) read once, o
  written once, and the state each chunk starts from (D^2 float32) written
  once for the backward. That is `delta_rule`'s count less the states' read.
- `kda_bwd` transposes it: every product of the forward has two transposes
  of its own size, 2 x the forward's; q, k, v, the cotangent of o, the
  log-decay, beta and the chunks' states read once, the five gradients
  written once in their inputs' types. That it computes a chunk again before
  it transposes it is the kernel's way and not in the count.

Both are bound by bytes on this count (0.21 and 0.31 ms a call at [1, 8,
8192, 128]) while the kernels are bound by the matrix unit's passes: every
product is float32 at full precision, six two-byte passes on operands of 64
rows. They read a low share by that, as `index_*_roofline` do.

Each function takes (configuration, traffic mix) and returns (FLOPs, bytes).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import model
from benchmark.kernels.kda import ELEMENT_BYTES, delta_rule_flops_per_token

FLOAT_BYTES = 4            # the log-decay, beta, a chunk's state


def _shape(config: Dict[str, Any], mix: Dict[str, Any]
           ) -> Tuple[float, int, float, float]:
    """(tokens x heads, the head's width, the forward's FLOPs a token and
    head, the bytes a token and head of one pass over the chunks' states)."""
    c = model.family(config).kda_call(config, mix)
    dim, chunk = c["head_dim"], c["chunk"]
    return (float(c["batch"] * c["heads"] * c["seq"]), dim,
            delta_rule_flops_per_token(chunk, dim, dim),
            FLOAT_BYTES * dim * dim / chunk)


def kda_fwd(config, mix) -> Tuple[float, float]:
    tokens, dim, flops, states = _shape(config, mix)
    per_token = (4 * dim * ELEMENT_BYTES              # q, k, v read, o written
                 + FLOAT_BYTES * (dim + 1)            # the log-decay, beta
                 + states)                            # written for the backward
    return tokens * flops, tokens * per_token


def kda_bwd(config, mix) -> Tuple[float, float]:
    tokens, dim, flops, states = _shape(config, mix)
    per_token = (7 * dim * ELEMENT_BYTES      # q, k, v, do read; dq, dk, dv
                 + 2 * FLOAT_BYTES * (dim + 1)        # a, beta and theirs
                 + states)                            # read once
    return 2.0 * tokens * flops, tokens * per_token
