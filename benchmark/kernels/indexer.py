"""FLOPs and HBM bytes of ONE call on ONE chip of the five kernels of a
learned sparse-attention indexer's walk, by the names they carry in the
trace (`name=` on the pallas_calls of ops/indexer.py: `index_scores`,
`index_search`, `index_kl`, `index_grad_q`, `index_grad_k`). What the
algorithm needs, the least of it, every tensor once:

- `index_scores`: I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) over the
  CAUSAL pairs, S (S + 1) / 2 a batch row (every causal key is scored to
  be chosen or not): one product of Hi heads Di wide; qI, kI (two bytes),
  w (four) read, I written (four bytes a causal pair).
- `index_search`: each row's topk-th largest and the selection. No
  product: I read once and the selection written, a byte a causal pair
  (the 32 counting passes run over a row held on the chip).
- `index_kl`: the target p = mean_h softmax_S(q_h . k_h) over the SELECTED
  pairs alone, S K - K (K - 1) / 2 a batch row (selected_attention.py's
  count): one product of H heads D wide; q at H heads, k at Hkv, each
  head's log-sum-exp (four bytes a query), I read and the KL's gradient g
  written on the selected pairs (four bytes each), the selection's causal
  half (a byte a pair).
- `index_grad_q`, `index_grad_k`: g is 0 off the selected pairs, so the
  gradient needs the SELECTED pairs alone: the index heads' scores again
  and one more product of the same size each (d qI = d_s kI; d kI = d_s^T
  qI); qI, kI, w and g read, d qI and d w, or d kI, written.

The relu, the weights, the sum over the index heads, the exponentials and
the counting are vector work and not in the count, so a kernel they bound
reads a low share. The kernels compute every tile at or under the diagonal:
those over the selected pairs read at most selected / causal pairs (44 % at
8192 positions and 2048 keys a query) of what they would over all.

Each function takes (configuration, traffic mix) and returns (FLOPs, bytes).
The main heads' shape comes from the configuration's family
(`attention_call`), the indexer's from the configuration's `sa_config`.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import model
from benchmark.kernels.selected_attention import selected_pairs

ELEMENT_BYTES = 2          # bf16 activations
FLOAT_BYTES = 4            # I, g, w, the rows' statistics
SELECTION_BYTES = 1        # int8, a pair


def _shape(config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, int]:
    c = dict(model.family(config).attention_call(config, mix))
    c["index_heads"] = config["sa_config"]["indexer_num_heads"]
    c["index_dim"] = config["sa_config"]["indexer_head_dim"]
    c["causal"] = c["batch"] * (c["seq"] * (c["seq"] + 1) // 2)
    c["selected"] = c["batch"] * selected_pairs(c["seq"], c["topk"])
    return c


def _index_product(c, pairs: int) -> float:
    """One product of the index heads over `pairs` (query, key) pairs."""
    return 2.0 * pairs * c["index_dim"] * c["index_heads"]


def _index_operands(c) -> float:
    """qI, kI and w, read once."""
    positions = c["batch"] * c["seq"]
    return float(positions * c["index_dim"] * (c["index_heads"] + 1)
                 * ELEMENT_BYTES + positions * c["index_heads"] * FLOAT_BYTES)


def index_scores(config, mix) -> Tuple[float, float]:
    c = _shape(config, mix)
    return (_index_product(c, c["causal"]),
            _index_operands(c) + c["causal"] * FLOAT_BYTES)


def index_search(config, mix) -> Tuple[float, float]:
    c = _shape(config, mix)
    return 0.0, float(c["causal"] * (FLOAT_BYTES + SELECTION_BYTES))


def index_kl(config, mix) -> Tuple[float, float]:
    c = _shape(config, mix)
    positions = c["batch"] * c["seq"]
    tensors = (positions * c["head_dim"] * (c["heads"] + c["kv_heads"])
               * ELEMENT_BYTES + positions * c["heads"] * FLOAT_BYTES)
    return (2.0 * c["selected"] * c["head_dim"] * c["heads"],
            float(tensors + 2 * c["selected"] * FLOAT_BYTES
                  + c["causal"] * SELECTION_BYTES))


def index_grad_q(config, mix) -> Tuple[float, float]:
    c = _shape(config, mix)
    positions = c["batch"] * c["seq"]
    written = (positions * c["index_dim"] * c["index_heads"] * ELEMENT_BYTES
               + positions * c["index_heads"] * FLOAT_BYTES)
    return (2 * _index_product(c, c["selected"]),
            _index_operands(c) + c["selected"] * FLOAT_BYTES + written)


def index_grad_k(config, mix) -> Tuple[float, float]:
    c = _shape(config, mix)
    written = c["batch"] * c["seq"] * c["index_dim"] * ELEMENT_BYTES
    return (2 * _index_product(c, c["selected"]),
            _index_operands(c) + c["selected"] * FLOAT_BYTES + written)
