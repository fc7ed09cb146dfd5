"""FLOPs and HBM bytes of ONE call on ONE chip of the two kernels of the
chunked gated delta rule where the key's and the value's widths differ and
the decay is ONE number a head and token (Gated DeltaNet, arXiv:2412.06464;
`name=` on the pallas_calls of ops/linear_attention.py: `kda_fwd`,
`kda_bwd`), FROM THE MATHEMATICS and not from the kernels' passes: the same
count whether the program runs the decay a head as such or broadcast to the
key's channels, and whatever the kernels pad the widths to. The shapes come
from the configuration's family (`kda_call`: batch, heads, seq, key_dim,
value_dim, chunk). benchmark/kernels/delta_rule.py is the same count at dk =
dv with a decay a channel.

With C the chunk, dk the key's width and dv the value's, a chunk of one head
needs: A = K K^T and Aqk = Q K^T under the decays, below and on the diagonal
(C^2 dk each: half of 2 C^2 dk), the triangular solve of (I + Diag(beta) A)
against [V | Kbar] (C^2 (dk + dv): half of 2 C^2 (dk + dv)), U = Wv - Wk S
(2 C dk dv), O = Qbar S + Aqk U (2 C dk dv + C^2 dv) and the next state
e^{g_C} S + Ktilde^T U (2 C dk dv): C (3 dk + 2 dv) + 6 dk dv a token and
head.

- `kda_fwd`: q, k (dk two-byte elements each) and v (dv) read once, o (dv)
  written once; the log-decay and beta, one float32 each a token and head; the
  state each chunk starts from (dk dv float32 a chunk) written once for the
  backward.
- `kda_bwd` transposes it: every product of the forward has two transposes
  of its own size, 2 x the forward's; q, k, v, the cotangent of o, the
  log-decay, beta and the chunks' states read once, the five gradients
  written once in their inputs' types. That it computes a chunk again before
  it transposes it, and reads the forward's kept matrices, is the kernel's
  way and not in the count.

Each function takes (configuration, traffic mix) and returns (FLOPs, bytes).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import model
# (kda.py stated the products at any dk and dv before there was a kernel)
from benchmark.kernels.kda import ELEMENT_BYTES
from benchmark.kernels.kda import delta_rule_flops_per_token as flops_per_token

FLOAT_BYTES = 4            # the log-decay, beta, a chunk's state


def _shape(config: Dict[str, Any], mix: Dict[str, Any]
           ) -> Tuple[float, int, int, float, float]:
    """(tokens x heads, dk, dv, the forward's FLOPs a token and head, the
    bytes a token and head of one pass over the chunks' states)."""
    c = model.family(config).kda_call(config, mix)
    dk, dv, chunk = c["key_dim"], c["value_dim"], c["chunk"]
    return (float(c["batch"] * c["heads"] * c["seq"]), dk, dv,
            flops_per_token(chunk, dk, dv), FLOAT_BYTES * dk * dv / chunk)


def kda_fwd(config, mix) -> Tuple[float, float]:
    tokens, dk, dv, flops, states = _shape(config, mix)
    per_token = ((2 * dk + 2 * dv) * ELEMENT_BYTES    # q, k, v read, o written
                 + 2 * FLOAT_BYTES                    # the log-decay, beta
                 + states)                            # written for the backward
    return tokens * flops, tokens * per_token


def kda_bwd(config, mix) -> Tuple[float, float]:
    tokens, dk, dv, flops, states = _shape(config, mix)
    per_token = ((4 * dk + 3 * dv) * ELEMENT_BYTES    # q, k, v, do; dq, dk, dv
                 + 4 * FLOAT_BYTES                    # g, beta and theirs
                 + states)                            # read once
    return 2.0 * tokens * flops, tokens * per_token
