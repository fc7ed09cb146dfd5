"""The arithmetic that turns readings taken inside a run into metrics.

No JAX, no clocks: every function takes numbers and returns numbers, so
selftest.py can feed it synthetic runs. An end-to-end rate counts all the
work of the window over all its time, between events and not between the
window's edges: PR 22 was refused because "completions in 15 s / 15" moved
by 2 % with where the edges fell against bursts of 8 completions. A stall
inside the window is lost work and does move the rate; the medians that a
stall leaves alone stand beside it as per-layer metrics.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple


def quantile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between order statistics (q in [0, 1])."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of no readings")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return quantile(values, 0.5)


def samples_beyond(n: int, q: float) -> int:
    """How many of n readings lie beyond the q-quantile."""
    return int(math.floor(n * (1.0 - q)))


def in_window(times: Sequence[float], start: float, end: float) -> List[float]:
    return [t for t in times if start <= t <= end]


def turn_rates(completions: Sequence[float], clients: int) -> List[float]:
    """Closed loop with `clients` callers: one reading per completion i,
    clients / (t[i+clients] - t[i]) — the rate over one full turn of the
    loop, in which every caller is answered once."""
    ts = sorted(completions)
    return [clients / (ts[i + clients] - ts[i])
            for i in range(len(ts) - clients)
            if ts[i + clients] > ts[i]]


def median_turn_rate(completions: Sequence[float],
                     clients: int) -> Optional[float]:
    """The per-layer reading: a stall spoils `clients` turn readings of some
    hundreds and leaves their median alone, so this says what the steady
    loop delivers where loop_rate says what the window delivered."""
    rates = turn_rates(completions, clients)
    return median(rates) if rates else None


def loop_rate(completions: Sequence[float],
              clients: int) -> Tuple[Optional[float], int]:
    """(requests per second over the window's whole turns, number of
    turns). With n completions, N = (n - 1) // clients whole turns fit;
    N x clients completions take t[i + N x clients] - t[i] from completion
    i, and the rate is N x clients over the mean of that span over every i
    that fits. N turns span a whole number of batches wherever they start,
    so the window's edges do not move the reading, while every completion
    and every stall between the first and the last lies inside the spans
    and does."""
    ts = sorted(completions)
    turns = (len(ts) - 1) // clients
    if turns < 1:
        return None, 0
    k = turns * clients
    spans = [ts[i + k] - ts[i] for i in range(len(ts) - k)]
    return k * len(spans) / sum(spans), turns


def count_rate(completions: Sequence[float], start: float,
               end: float) -> float:
    """The estimator that was refused, kept to be printed beside the other:
    completions inside [start, end] over its length."""
    return len(in_window(completions, start, end)) / (end - start)


def stall_share(completions: Sequence[float], interval: float, start: float,
                end: float, factor: float = 3.0) -> float:
    """Share of [start, end] inside gaps between consecutive completions
    longer than `factor` times `interval`, the median time between two
    batches ending."""
    ts = sorted(in_window(completions, start, end))
    return sum(b - a for a, b in zip(ts, ts[1:])
               if b - a > factor * interval) / (end - start)


def whole_steps(steps: Sequence[Tuple[float, float]], start: float,
                end: float) -> List[Tuple[float, float]]:
    """The (dispatch, done) pairs that lie wholly inside [start, end]."""
    return [(a, b) for a, b in steps if a >= start and b <= end]


def step_times(steps: Sequence[Tuple[float, float]], start: float,
               end: float) -> List[float]:
    """One reading a whole step after the first: from the step before it
    being done to its own being done, so the host's work between two steps
    is inside it, as it is inside a training run."""
    inside = whole_steps(steps, start, end)
    return [inside[i][1] - inside[i - 1][1] for i in range(1, len(inside))]


def step_rate(steps: Sequence[Tuple[float, float]], start: float,
              end: float) -> Tuple[Optional[float], int]:
    """(steps per second, number of steps counted): every whole step of the
    window after the first over the time from the first being done to the
    last being done — all the work over all the time, a slow step
    included."""
    times = step_times(steps, start, end)
    return (len(times) / sum(times) if times else None), len(times)


def host_gap_share(steps: Sequence[Tuple[float, float]], start: float,
                   end: float) -> Optional[float]:
    """Share of the stepped time between one step's block_until_ready
    returning and the next step's dispatch."""
    inside = whole_steps(steps, start, end)
    if len(inside) < 2:
        return None
    gaps = sum(inside[i][0] - inside[i - 1][1] for i in range(1, len(inside)))
    return gaps / (inside[-1][1] - inside[0][1])
