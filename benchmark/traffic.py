"""The one traffic generator. A mix is a data file of parameters
(benchmark/traffic/<name>.json); this file turns it and a seed into requests.

Every seed gets the SAME multiset of prompt lengths and inter-arrival gaps —
the quantiles of the distribution the file names, at evenly spaced
probabilities — in another order, with other token values. So two seeds do
the same work, and a difference between runs is the system's, not the draw's.

Keys of a serving mix:
  loop       "closed" (clients, each sends its next when the last is answered)
             or "open" (arrivals on a schedule, whatever the server does)
  clients    closed loop: number of callers
  rate_per_s open loop: mean arrivals per second, a number fixed in the file
  arrivals   open loop: {"dist": "poisson"}; the order of the gaps comes
             from --seed
  lengths    list of parts {"share", "dist": "uniform"|"loguniform", "min", "max"}
  pool       how many distinct prompts the run draws from
  serve      deployment parameters: pad_to (tokens a row), max_batch_size
             (rows a forward), batch_wait_timeout_s
"""

from __future__ import annotations

import math
import random
from typing import Any, Dict, List


def _mid_probabilities(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def length_set(parts: List[Dict[str, Any]], n: int) -> List[int]:
    """n prompt lengths: each part's share of them, at its quantiles."""
    out: List[int] = []
    for j, part in enumerate(parts):
        k = (n - len(out) if j == len(parts) - 1
             else int(round(part["share"] * n)))
        lo, hi = part["min"], part["max"]
        for p in _mid_probabilities(max(k, 0)):
            if part["dist"] == "uniform":
                x = lo + (hi - lo) * p
            elif part["dist"] == "loguniform":
                x = math.exp(math.log(lo) + (math.log(hi) - math.log(lo)) * p)
            else:
                raise ValueError(f"unknown length dist {part['dist']!r}")
            out.append(int(min(max(round(x), lo), hi)))
    return out


def gap_set(arrivals: Dict[str, Any], rate_per_s: float, n: int) -> List[float]:
    """n inter-arrival gaps with mean exactly 1 / rate_per_s."""
    if arrivals["dist"] != "poisson":
        raise ValueError(f"unknown arrival dist {arrivals['dist']!r}")
    raw = [-math.log(1.0 - p) for p in _mid_probabilities(n)]
    scale = n / (rate_per_s * sum(raw))
    return [g * scale for g in raw]


def prompts(mix: Dict[str, Any], vocab: int, seed: int) -> List[List[int]]:
    """The pool of prompts: the mix's length set in a seeded order, filled
    with seeded token ids."""
    rng = random.Random(seed)
    lengths = length_set(mix["lengths"], mix["pool"])
    rng.shuffle(lengths)
    return [[rng.randrange(vocab) for _ in range(n)] for n in lengths]


def due_times(mix: Dict[str, Any], seconds: float, seed: int) -> List[float]:
    """Open loop: when each request is due, from 0, covering `seconds`."""
    n = max(int(math.ceil(mix["rate_per_s"] * seconds)), 1)
    gaps = gap_set(mix["arrivals"], mix["rate_per_s"], n)
    random.Random(seed + 1).shuffle(gaps)
    t, out = 0.0, []
    for g in gaps:
        t += g
        out.append(t)
    return out
