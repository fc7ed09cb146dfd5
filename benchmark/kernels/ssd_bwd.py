"""FLOPs and HBM bytes of ONE call on ONE chip of the two kernels of the
chunked state-space scan, by the names they carry in the trace (`name=` on
the pallas_calls of ops/state_space.py: `ssd_fwd`, `ssd_bwd`), FROM THE
MATHEMATICS and not from the kernels' passes. The shapes come from the
configuration's family (`ssd_call`: batch, seq, heads, head_dim, groups,
state, chunk). The roofline reader looks a function up by its kernel's name,
so both names live here.

- `ssd_fwd` is benchmark/kernels/ssd.py:ssd as it stands, which stated the
  scan's work before there was a kernel: P C + 4 P N + N C / R a token and
  head; x read and y written, B and C once a group, dt, and a chunk's state
  written and read once (the kernel only writes it: the count is a little
  generous to the kernel's time, 128 of 524 bytes a token and head at
  granite's [64 heads of 64 on one group of 128, chunks of 256]).
- `ssd_bwd` transposes it. The [C, C] scores under their decays are not
  kept, so the backward needs the forward's products AGAIN, and every
  product has two transposes of its own size: 3 x the forward's FLOPs. Bytes:
  x, the cotangent of y, B, C and dt read, the chunks' states read once; dx
  written in x's type, dB and dC once a group in theirs, ddt (float32). That
  the kernel writes dB and dC a block of heads in float32, reads g beside dt
  and writes dg is its way and not in the count.

On this count the forward is bound by bytes (0.34 ms a call at granite's
shape against 0.13 ms of FLOPs) and the backward by FLOPs (0.40 ms against
0.34 ms of bytes), while the kernels are bound by the matrix unit's passes:
every product is float32 at full precision, three to six bfloat16 passes on
operands of 64 rows a head, and the decays' exponentials over [h, C, C] are
vector work no count holds. They read low by that, as `kda_*_roofline` do.

Each function takes (configuration, traffic mix) and returns (FLOPs, bytes).
"""

from __future__ import annotations

from typing import Tuple

from benchmark import model
from benchmark.kernels.ssd import ELEMENT_BYTES, FLOAT_BYTES
from benchmark.kernels.ssd import ssd as ssd_fwd  # noqa: F401 — by its name


def ssd_bwd(config, mix) -> Tuple[float, float]:
    """One layer's scan, backward, on one chip."""
    c = model.family(config).ssd_call(config, mix)
    per_group = c["heads"] // c["groups"]
    tokens = float(c["batch"] * c["seq"] * c["heads"])
    per_token = (3 * c["head_dim"] * ELEMENT_BYTES        # x, dy read; dx
                 + 4 * c["state"] * ELEMENT_BYTES / per_group   # B, C, dB, dC
                 + 2 * FLOAT_BYTES                        # dt read, ddt
                 + FLOAT_BYTES * c["head_dim"] * c["state"] / c["chunk"])
    return 3.0 * ssd_fwd(config, mix)[0], tokens * per_token
