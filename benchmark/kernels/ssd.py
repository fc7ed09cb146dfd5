"""FLOPs and HBM bytes of what a state-space layer's scan (Mamba-2's SSD:
ops/state_space.py) runs between its filter and its gated norm, ONE call on
ONE chip, FROM THE MATHEMATICS and not from the implementation's passes, so
that a later kernel is read against the same work. The shapes come from the
configuration's family (`ssd_call`: batch, seq, heads, head_dim, groups,
state, chunk).

The scan runs as XLA today and has no kernel name; its arithmetic is stated
here at the chunk the program runs, for `train_flops_per_token` now and for a
kernel's roofline later (`ssd`). With C the chunk, P a head's channels, N the
state and R = heads / groups the heads that share a pair of directions, a
token and head needs: its row of C B^T on and below the diagonal (C / 2 keys
of 2 N, shared by R heads: N C / R), that row under the decays against the
chunk's dt x (2 P a key, C / 2 keys: P C), what it adds to the state the
chunk hands on (2 P N) and what the state it started from gives it (2 P N):
P C + 4 P N + N C / R. The carry from chunk to chunk is P N a head and CHUNK,
under a hundredth of that. Bytes: x read and y written (two bytes an
element), B and C read once a group, dt (float32) a head, and a chunk state
(P N float32) written and read once.
"""

from __future__ import annotations

from typing import Tuple

from benchmark import model

ELEMENT_BYTES = 2          # bf16 activations
FLOAT_BYTES = 4            # dt, a chunk's state


def ssd_flops_per_token(chunk: int, width: int, state: int,
                        heads_per_group: int) -> float:
    """The chunked form's products, forward, a token and head."""
    return (float(width) * chunk + 4.0 * width * state
            + float(state) * chunk / heads_per_group)


def ssd(config, mix) -> Tuple[float, float]:
    """One layer's scan, forward, on one chip."""
    c = model.family(config).ssd_call(config, mix)
    per_group = c["heads"] // c["groups"]
    tokens = float(c["batch"] * c["seq"] * c["heads"])
    per_token = (2 * c["head_dim"] * ELEMENT_BYTES        # x read, y written
                 + 2 * c["state"] * ELEMENT_BYTES / per_group     # B and C
                 + FLOAT_BYTES                            # dt
                 + 2 * FLOAT_BYTES * c["head_dim"] * c["state"] / c["chunk"])
    return (tokens * ssd_flops_per_token(c["chunk"], c["head_dim"],
                                         c["state"], per_group),
            tokens * per_token)
