"""A float32 product at full precision as the sum of its bfloat16 terms'
products, with the terms an operand HAS: what ops/state_space.py's and
ops/linear_attention.py's kernel bodies multiply with.

Precision.HIGHEST splits each float32 operand into three bfloat16 terms (hi
+ mid + lo add up to every bit of it) and sums, in a float32 accumulator,
the six pairs whose orders add to at most two. An operand that holds a
bfloat16 VALUE (it arrived as bfloat16 and nothing has scaled it since) is
its own first term and the other two are zeros: their pairs are left out
and the sum is the same sum, in three passes of the matrix unit (or one,
where both sides are exact) instead of six. The caller says how many terms
each operand has; that is a statement about the values, never a choice of
precision."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def split(a, count: int):
    """float32 a as `count` bfloat16 terms, the largest first: one where a
    holds a bfloat16 value (the caller's word), else the three that add up
    to every bit of it."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    terms = [a.astype(bf16)]          # a bfloat16 a is its own one term
    for _ in range(count - 1):
        a = a - terms[-1].astype(f32)
        terms.append(a.astype(bf16))
    return terms


def dot(a, b, contract, terms):
    """a . b over `contract` (one dimension of each; operands of three
    dimensions are batched over their first), float32 at full precision:
    the bfloat16 terms' products whose orders add to at most two (with
    three terms a side Precision.HIGHEST's six), summed in float32."""
    batch = ((0,), (0,)) if a.ndim == 3 else ((), ())
    dims = ((contract[:1], contract[1:]), batch)
    pairs = [(i + j, s, t) for i, s in enumerate(split(a, terms[0]))
             for j, t in enumerate(split(b, terms[1])) if i + j <= 2]
    pairs.sort(key=lambda pair: -pair[0])                 # the smallest first
    return functools.reduce(jnp.add, (
        jax.lax.dot_general(s, t, dims, preferred_element_type=jnp.float32)
        for _, s, t in pairs))
