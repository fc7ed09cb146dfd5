"""FLOPs and HBM bytes of what a gated delta-rule linear-attention layer
(KDA: ops/linear_attention.py, ops/short_conv.py:silu_conv) runs between its
projections, ONE call on ONE chip, FROM THE MATHEMATICS and not from the
implementation's passes, so that a later fusion is read against the same
work. The shapes come from the configuration's family (`kda_call`: batch,
heads, seq, head_dim, taps, chunk).

The filter kernels, by the names they carry in the trace (`name=` on the
pallas_calls), the yardstick of `conv_silu_*_roofline`: silu(filter(x)) on
one projection's [batch, seq, heads x head_dim] with a filter of L taps a
channel. Both are bound by bytes. Forward: reads x, writes the result: 2
tensors in the activations' two-byte type (34 MB at [1, 8192, 1024]); L
multiplies, L - 1 adds and the SiLU (about 4) an element. Backward: reads x
and the cotangent, writes dx: 3 tensors; it filters x again (2 L), takes the
SiLU's slope (about 8), filters the cotangent the other way (2 L) and sums
the filter's gradient (2 L): about 6 L + 8 an element. The filter, its
gradient and the rows read a second time beside a block are left out on
both sides.

The delta rule itself runs as XLA today and has no kernel name; its
arithmetic is stated here at the chunk the program runs, for
`train_flops_per_token` now and for a kernel's roofline later
(`delta_rule`). With C the chunk and D = dk = dv, a chunk of one head needs:
A = K K^T under the decays below the diagonal and Aqk = Q K^T on and below
it (C^2 D each: half of 2 C^2 D), the triangular solve of (I + Diag(beta) A)
against [V | Kbar] (2 C^2 D), U = Wv - Wk S (2 C D^2), O = Qbar S + Aqk U
(2 C D^2 + C^2 D) and the next state Diag(e^g) S + Ktilde^T U (2 C D^2): 5
C^2 D + 6 C D^2 a chunk, 5 C D + 6 D^2 a token and head. Bytes: q, k, v,
the log-decay (float32) and beta read once, o written once, and a chunk
state (D^2 float32) written and read once.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import model

ELEMENT_BYTES = 2          # bf16 activations


def delta_rule_flops_per_token(chunk: int, dk: int, dv: int) -> float:
    """The chunked form's products, forward, a token and head."""
    return (3.0 * chunk * dk + 2.0 * chunk * dv      # A, Aqk, the solve, Aqk U
            + 6.0 * dk * dv)                          # the three against S


def _projection(config: Dict[str, Any], mix: Dict[str, Any]
                ) -> Tuple[float, int]:
    """(elements of one [batch, seq, heads x head_dim] tensor, L)."""
    c = model.family(config).kda_call(config, mix)
    return (float(c["batch"] * c["seq"] * c["heads"] * c["head_dim"]),
            c["taps"])


def conv_silu_fwd(config, mix) -> Tuple[float, float]:
    elements, taps = _projection(config, mix)
    return (2 * taps + 3) * elements, 2 * elements * ELEMENT_BYTES


def conv_silu_bwd(config, mix) -> Tuple[float, float]:
    elements, taps = _projection(config, mix)
    return (6 * taps + 8) * elements, 3 * elements * ELEMENT_BYTES


def delta_rule(config, mix) -> Tuple[float, float]:
    """One layer's delta rule, forward, on one chip."""
    c = model.family(config).kda_call(config, mix)
    dim, chunk = c["head_dim"], c["chunk"]
    tokens = float(c["batch"] * c["heads"] * c["seq"])
    per_token = (4 * dim * ELEMENT_BYTES             # q, k, v read, o written
                 + 4 * dim + 4                        # the log-decay, beta
                 + 2 * 4 * dim * dim / chunk)        # a chunk state, both ways
    return (tokens * delta_rule_flops_per_token(chunk, dim, dim),
            tokens * per_token)
