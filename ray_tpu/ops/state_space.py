"""A state-space mixer's scan: Mamba-2's selective state space in its dual
form (SSD, arXiv:2405.21060). A head of P channels keeps S [P, N] and, a
token,

    S_t = a_t S_{t-1} + dt_t x_t B_t^T,   a_t = exp(-exp(a_log) dt_t)
    y_t = S_t C_t + d x_t,                                        S_0 = 0

a_t in (0, 1] is ONE number a head and token (a scalar decay: the state
fades as a whole and is never overwritten along a key, so there is no
delta-rule solve as in ops/linear_attention.py), dt_t > 0 the step the
caller has already put through its softplus, B_t and C_t [N] the input and
output directions, shared by the H / G heads of a group.

x [B, S, H, P], dt [B, S, H] (float32), a_log and d [H], b and c [B, S, G,
N] in; y [B, S, H, P] out, in x's type; every sum and the state float32.

Two formulations. `ssd_reference` is the recurrence as it stands, a token a
step of a `lax.scan` (the oracle, never the timed path). `ssd` is its
chunked form as two Pallas kernels under a custom_vjp. With g_t the
cumulative log-decay inside a chunk of C tokens (g <= 0, falling) and S_0
the state the chunk starts from,

    y_t = sum_{s<=t} (C_t . B_s) e^{g_t - g_s} dt_s x_s  +  e^{g_t} S_0 C_t
    next S_0 = e^{g_C} S_0 + sum_s e^{g_C - g_s} dt_s x_s B_s^T

The first sum is a lower-triangular [C, C] matrix a head and chunk, (C B^T)
times the decays between the two tokens, against the chunk's x: matmuls.
Only the last line is sequential. No product divides by a decay
(ops/linear_attention.py's rule): e^{g_t - g_s} is the exponential of a
difference that is <= 0 wherever it is used, taken after the subtraction,
and masked above the diagonal before it. A sequence that is no whole number
of chunks is padded with tokens of dt = 0, which leave the state as it is.

`_chunk` states all of that once, for one chunk of a few heads of one
group, as a jnp function of values that live in VMEM (a head's x is 4 P C
bytes in float32, a [C, C] tile 4 C^2, a state 4 P N: at nemotron's [128
tokens, 64 channels, 128 directions] 32 KB, 64 KB and 32 KB; at granite's
chunks of 256 a head's x is 64 KB and a [C, C] tile 256 KB, four times
nemotron's, so a grid step takes fewer heads there: `_heads_a_step`). The
tokens lie along the lanes (x a head
is [P, C]), so a head of 64 channels fills its tiles, what a token and head
scales by (dt, the decays) is a row that broadcasts down the sublanes, and
a state is [P, N] as the recurrence keeps it. `ssd_fwd` runs it over a grid
(heads / h, chunks), h heads of one group a step (a group of more heads than
h is several blocks), the chunks in order, the state in VMEM scratch: a
chunk's x, B, C, dt and g cross HBM once and C B^T, the decays, the scores
and both state products stay on the chip. `ssd_bwd` runs the same
function's transpose, written out (`_bwd_kernel`), over the chunks from the
last to the first and, under each, a group's blocks of heads one after
another, the state's cotangent of every head in scratch: it computes again,
from a chunk's inputs and the state it started from, which the forward
keeps ([heads, chunks, P, N] float32: 34 MB a layer at [1, 8192, 16, 64] on
128 in chunks of 128, 67 MB at [1, 8192, 64, 64] in chunks of 256), only
what the transposes read, and writes every gradient as its owner takes it:
dx in x's type, B's and C's once a group in theirs (summed over the
group's blocks of heads in VMEM: one block at 16 heads a group, four at
64), dt's with g's transpose applied. g is the caller's cumulative sum
(`chunk_log_decay`), forward; backward the kernel sums g's cotangent back
over the later tokens of the chunk (one product against a triangle of
ones) onto dt, and onto a_log a chunk. The output and the kept states carry
the name SSD_OUT, so that a remat policy that saves it runs the forward
once a layer and step, as KDA_OUT does for the delta rule.

Every product is the float32 one at full precision (`_product`, over
ops/terms.py's `dot`, which ops/linear_attention.py shares): the sum,
in a float32 accumulator, of the bfloat16 terms' products that
Precision.HIGHEST keeps (an operand is three terms, hi + mid + lo, and the
six pairs whose orders add to at most two are multiplied). What is open is
how many terms an operand HAS: x, B and C that arrive as bfloat16 are their
own first term and the other two are zeros, so their pairs are left out
and the sum is the same sum. That is why dt rides on the scores' columns
and on the decays to the chunk's end, never on x: in y = scores x and in
the state's x^T B the x and B operands are exact (three passes instead of
six), C B^T is exact on both sides (one), and C S_0 is exact in C (three).
Backward the same holds of dy: the cotangent of a bfloat16 y arrives as
bfloat16 and is one term (dy^T x one pass, dy W three), while a cotangent
that is float32 by nature (the state's, e^g dy, the scores') is three.
Float32 inputs, and a float32 dy, take all six passes everywhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.ops.attention import LANES
from ray_tpu.ops.terms import dot as _dot

# The name the output and the chunks' states carry
# (jax.ad_checkpoint.checkpoint_name).
SSD_OUT = "ssd_out"

# The recurrence's products: float32 operands at full precision (the state
# is summed over the whole sequence). The kernels' are `_product`'s.
_PRECISION = jax.lax.Precision.HIGHEST


def _by_head(t, heads: int):
    """b or c [..., G, N] -> [..., H, N]: head h reads group h // (H / G)."""
    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def ssd_reference(x, dt, a_log, b, c, d):
    """The recurrence, a token a step, float32."""
    f32 = jnp.float32
    batch, _, heads, width = x.shape
    rate = jnp.exp(a_log.astype(f32))

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs               # [B, H, P], [B, H], [B, H, N]
        state = (jnp.exp(-rate * dt_t)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=_PRECISION)

    by_token = [jnp.moveaxis(t.astype(f32), 1, 0)
                for t in (x, dt, _by_head(b, heads), _by_head(c, heads))]
    _, y = jax.lax.scan(
        step, jnp.zeros((batch, heads, width, b.shape[-1]), f32),
        tuple(by_token))
    y = jnp.moveaxis(y, 0, 1) + d.astype(f32)[:, None] * x.astype(f32)
    return y.astype(x.dtype)


def chunk_log_decay(dt, a_log, chunk: int = 128):
    """dt [B, S, H] (the steps), a_log [H] -> the cumulative log-decay
    inside each chunk [B, S / chunk (rounded up), H, chunk], float32: g of
    the module's docstring (a token past the sequence's end decays
    nothing). Its smallest value is how far a chunk fades what it was
    handed (past float32's -87 the carried state is an exact 0 there)."""
    batch, seq, heads = dt.shape
    steps = jnp.pad(dt.astype(jnp.float32), ((0, 0), (0, -seq % chunk), (0, 0)))
    steps = steps.reshape(batch, -1, chunk, heads).transpose(0, 1, 3, 2)
    return jnp.cumsum(-jnp.exp(a_log.astype(jnp.float32))[:, None] * steps,
                      axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _product(a, b, contract, terms):
    """`_dot`, with the transposes that are the same kind of product: a
    cotangent is three terms, and an operand keeps the count it had (what
    JAX's own transpose of `_chunk` can know: the backward kernel's oracle
    in the tests)."""
    return _dot(a, b, contract, terms)


def _product_fwd(a, b, contract, terms):
    return _dot(a, b, contract, terms), (a, b)


def _product_bwd(contract, terms, operands, g):
    a, b = operands
    lead = a.ndim - 2                          # 1 where batched
    free = [2 * lead + 1 - c for c in contract]          # the other of two
    # g is [.., a's free, b's free]; a result's dimensions are its first
    # operand's free one, then its second's
    if contract[0] == a.ndim - 1:
        da = _dot(g, b, (g.ndim - 1, free[1]), (3, terms[1]))
    else:
        da = _dot(b, g, (free[1], g.ndim - 1), (terms[1], 3))
    if contract[1] == b.ndim - 1:
        db = _dot(g, a, (lead, free[0]), (3, terms[0]))
    else:
        db = _dot(a, g, (free[0], lead), (terms[0], 3))
    return da, db


_product.defvjp(_product_fwd, _product_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _spread(v, shape):
    """v [h, 1, 1], a number a head, over `shape`. Its transpose sums one
    dimension at a time and keeps all three: broadcasting's own reduces
    two at once to a vector of one dimension, which the chip's compiler
    does not lay out."""
    return jnp.broadcast_to(v, shape)


def _spread_fwd(v, shape):
    return jnp.broadcast_to(v, shape), None


def _spread_bwd(shape, _, g):
    return (jnp.sum(jnp.sum(g, axis=1, keepdims=True), axis=2,
                    keepdims=True),)


_spread.defvjp(_spread_fwd, _spread_bwd)


def _chunk(x, b, c, dt, g, d, state, exact):
    """One chunk of h heads of one group, on values that live in VMEM, all
    float32: x [h, P, C] (tokens along the lanes), b and c [C, N], the
    steps dt and the cumulative log-decay g [h, 1, C] (rows), the skip d
    [h, 1, 1] and the state the chunk starts from [h, P, N] -> (y [h, P,
    C], the state after the chunk). `exact` = (x, b, c): whether each holds
    bfloat16 values. The one statement of the chunked form: the forward
    kernel runs it, the backward kernel its transpose written out, which
    tests/test_state_space.py holds to this function's jax.vjp."""
    heads, width, chunk = x.shape
    of_x, of_b, of_c = (1 if e else 3 for e in exact)
    t = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, chunk), 1)
    s = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, chunk), 2)
    # g a row in, a column here: the diagonal's row sums
    down = jnp.sum(jnp.where(t == s, g, 0.0), axis=2, keepdims=True)
    between = jnp.exp(jnp.where(s <= t, down - g, -jnp.inf))   # [h, C, C]
    scores = _product(c, b, (1, 1), (of_c, of_b))              # [t, s]
    # dt on the scores' columns: x stays the operand it arrived as
    y = _product(x, between * scores * dt, (2, 2), (of_x, 3))
    last = jax.lax.broadcasted_iota(jnp.int32, g.shape, 2) == chunk - 1
    whole = jnp.sum(jnp.where(last, g, 0.0), axis=2, keepdims=True)  # g_C
    rows = lambda v: v.reshape(heads * width, -1)
    # what the state the chunk started from gives each token
    carried = _product(rows(state), c, (1, 1), (3, of_c))
    y = y + jnp.exp(g) * carried.reshape(x.shape) + _spread(d, x.shape) * x
    # what the chunk adds to the state it hands on
    to_end = jnp.exp(_spread(whole, g.shape) - g) * dt
    added = _product(rows(x * to_end), b, (1, 0), (3, of_b))
    return y, (_spread(jnp.exp(whole), state.shape) * state
               + added.reshape(state.shape))


def _fwd_kernel(exact, x_ref, b_ref, c_ref, dt_ref, g_ref, d_ref, y_ref,
                states_ref, state):
    """Grid (heads / h, chunks), the chunks in order: `state` [h, P, N]
    carries each head's state; states_ref keeps what a chunk started from
    for the backward."""
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
    states_ref[:, 0] = state[...]
    y, state[...] = _chunk(
        x_ref[...].astype(f32), b_ref[0].astype(f32), c_ref[0].astype(f32),
        dt_ref[:, 0], g_ref[:, 0], d_ref[...], state[...], exact)
    y_ref[...] = y.astype(y_ref.dtype)


def _tiles(x, dy, dt, g, scores, of_x: int, of_dy: int):
    """What a chunk's transpose does on [C, C] tiles, for k of the step's
    heads: x and dy [k, P, C] (in the type they arrived in), dt and g [k,
    1, C], scores = C B^T [C, C] -> (the weights' part of dx [k, P, C]; the
    scores' cotangent summed over the k heads [C, C]; as rows [k, 1, C]:
    dt's cotangent from the weights, and g's as the later token of a
    pair). The decays and the weights W = between scores dt are computed
    again as `_chunk` states them; y is not."""
    chunk = g.shape[-1]
    t = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, chunk), 1)
    s = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, chunk), 2)
    diagonal = t == s
    down = jnp.sum(jnp.where(diagonal, g, 0.0), axis=2, keepdims=True)
    between = jnp.exp(jnp.where(s <= t, down - g, -jnp.inf))   # [k, C, C]
    unstepped = between * scores
    # W's cotangent, dy^T x: both operands as they arrived
    dw = _dot(dy, x, (1, 1), (of_dy, of_x))                    # [t, s]
    dx = _dot(dy, unstepped * dt, (2, 1), (of_dy, 3))
    stepped = dw * dt
    # the exponent's cotangent is dW W: its row sums are g's as t (a
    # column, turned to a row on the diagonal as g was turned down), its
    # column sums dt times what dt's own cotangent sums
    as_first = jnp.sum(stepped * unstepped, axis=2, keepdims=True)
    return (dx, jnp.sum(stepped * between, axis=0),
            jnp.sum(dw * unstepped, axis=1, keepdims=True),
            jnp.sum(jnp.where(diagonal, as_first, 0.0), axis=1,
                    keepdims=True))


def _bwd_kernel(exact, blocks, x_ref, b_ref, c_ref, dt_ref, g_ref, d_ref,
                a_ref, states_ref, dy_ref, dx_ref, db_ref, dc_ref, ddt_ref,
                da_ref, dd_ref, dstate, dx_tiles, rows, dscores, *of_group):
    """Grid (chunks, heads / h), the chunks from the last to the first and
    under each the blocks of heads, a group's `blocks` one after another:
    `dstate` [heads, P, N] carries, for every head of the call, the
    cotangent of the state a chunk hands on; B's and C's blocks stay where
    they are over a group's blocks of heads, and their cotangents are
    summed in `of_group` [2, C, N] (float32) and written, in their type, at
    the group's last block (one block a group writes them as they come).
    `_chunk`'s transpose, written out: of the chunk only what the
    transposes read is computed again (C B^T, the decays, the weights and
    what the starting state gives each token, which g's cotangent reads),
    and every product is `_dot`'s sum with the terms its operands HAVE: dy
    that arrives as bfloat16 is one term (dy^T x one pass at exact x, dy W
    three), a cotangent that is float32 by nature (e^g dy, the state's, the
    scores') three. The [C, C] tiles are walked `_tile_heads` heads at a
    time (`_tiles`), their results through scratch: `dx_tiles` [h, P, C],
    `rows` [2, h, 1, C], `dscores` [C, C]."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    heads, width, chunk = x_ref.shape
    of_x, of_b, of_c = (1 if e else 3 for e in exact)
    of_dy = 1 if dy_ref.dtype == bf16 else 3
    rows_of = lambda v: v.reshape(heads * width, -1)

    block = pl.program_id(1)
    mine = pl.ds(block * heads, heads)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dstate[mine] = jnp.zeros((heads,) + dstate.shape[1:], f32)
    b, c = b_ref[0], c_ref[0]
    scores = _dot(c, b, (1, 1), (of_c, of_b))                  # [t, s]
    some = _tile_heads(chunk, heads)
    dscores[...] = jnp.zeros_like(dscores)

    def walk(i, carry):
        at = pl.ds(i * some, some)
        dx_tiles[at], ds, rows[0, at], rows[1, at] = _tiles(
            x_ref[at], dy_ref[at], dt_ref[at, 0], g_ref[at, 0], scores,
            of_x, of_dy)
        dscores[...] += ds
        return carry
    jax.lax.fori_loop(0, heads // some, walk, None)

    x, dy = x_ref[...].astype(f32), dy_ref[...].astype(f32)
    dt, g, state, handed = dt_ref[:, 0], g_ref[:, 0], states_ref[:, 0], \
        dstate[mine]
    last = jax.lax.broadcasted_iota(jnp.int32, g.shape, 2) == chunk - 1
    whole = jnp.sum(jnp.where(last, g, 0.0), axis=2, keepdims=True)  # g_C
    decay, to_end = jnp.exp(g), jnp.exp(whole - g)                # [h, 1, C]
    reach = to_end * dt                  # `_chunk`'s to_end: x's way to S
    kept = jnp.exp(whole)                                         # [h, 1, 1]
    over = lambda v: jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=2,
                             keepdims=True)
    carried = _dot(rows_of(state), c, (1, 1), (3, of_c)).reshape(x.shape)
    dcarried = decay * dy
    # the cotangent of x reach, the operand of the state's x^T B, and reach's
    dadded = _dot(rows_of(handed), b, (1, 1), (3, of_b)).reshape(x.shape)
    dreach = jnp.sum(x * dadded, axis=1, keepdims=True)           # [h, 1, C]
    dx_ref[...] = (dx_tiles[...] + d_ref[...] * dy
                   + reach * dadded).astype(dx_ref.dtype)
    dd_ref[:, 0] = over(dy * x)
    dwhole = (jnp.sum(reach * dreach, axis=2, keepdims=True)
              + kept * over(handed * state))
    dg = (rows[1] - dt * rows[0]
          + decay * jnp.sum(dy * carried, axis=1, keepdims=True)
          - reach * dreach + jnp.where(last, dwhole, 0.0))
    # g_u = -e^{a_log} sum_{s <= u} dt_s, `chunk_log_decay`'s: a_log's
    # cotangent is sum_u dg_u g_u and dt_s's -e^{a_log} sum_{u >= s} dg_u,
    # every head's sums in ONE product against a triangle of ones (exact
    # in one term)
    da_ref[:, 0] = jnp.sum(dg * g, axis=2, keepdims=True)
    later = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    after = _dot(dg.reshape(heads, chunk), later.astype(bf16), (1, 0),
                 (3, 1)).reshape(g.shape)
    ddt_ref[:, 0] = rows[0] + to_end * dreach - jnp.exp(a_ref[...]) * after
    dstate[mine] = kept * handed + _dot(
        rows_of(dcarried), c, (1, 0), (3, of_c)).reshape(handed.shape)
    db = (_dot(dscores[...], c, (0, 0), (3, of_c))
          + _dot(rows_of(x * reach), rows_of(handed), (0, 0), (3, 3)))
    dc = (_dot(dscores[...], b, (1, 0), (3, of_b))
          + _dot(rows_of(dcarried), rows_of(state), (0, 0), (3, 3)))
    if blocks == 1:
        db_ref[0], dc_ref[0] = db.astype(db_ref.dtype), dc.astype(dc_ref.dtype)
        return
    summed, = of_group
    at = jax.lax.rem(block, blocks)

    @pl.when(at == 0)
    def _():
        summed[0], summed[1] = db, dc

    @pl.when(at > 0)
    def _():
        summed[0] += db
        summed[1] += dc

    @pl.when(at == blocks - 1)
    def _():
        db_ref[0] = summed[0].astype(db_ref.dtype)
        dc_ref[0] = summed[1].astype(dc_ref.dtype)


# Heads a grid step, at most (and all of one group): their products are
# batched, and the state's two run over all their rows at once (at [1, 8192,
# 16, 64] on one group of 128, a v5e: 0.16 / 0.51 ms forward / backward at
# 16, 0.19 / 0.54 at 8, 0.25 / 0.57 at 4; with all of a group's heads in
# one step B, C and their gradients cross HBM once a group).
_HEADS = 16

# The bytes ONE [h, C, C] float32 tile of a grid step may take (the decays
# between two tokens, the scores under them and their bfloat16 terms; the
# forward holds a few at once, beside x and the state at h P (C + N) floats
# each, and the backward walks its own a megabyte at a time: _WALK_BYTES).
# A tile is 4 C^2 a head: 16 heads of chunks of 128 are 1 MB, of chunks of
# 256 4 MB, and at chunks of 512 a step takes 4. At [1, 8192, 64, 64] on
# one group of 128 in chunks of 256, a v5e read 0.81 / 3.65 ms forward /
# forward and gradient at 16 heads a step, 0.88 / 3.76 at 8, 1.02 / 4.09
# at 4 (and 0.67 / 2.83 in chunks of 128 at 16), with `jax.vjp(_chunk)` as
# the backward's body.
_TILE_BYTES = 4 << 20


def _most_heads(of: int, most: int, chunk: int, tile_bytes: int) -> int:
    """The most heads that divide `of`, stay within `most` and keep a
    chunk's [n, C, C] float32 tile within tile_bytes (never none)."""
    most = max(1, min(most, tile_bytes // (4 * chunk * chunk)))
    return max(n for n in range(1, most + 1) if of % n == 0)


def _heads_a_step(chunk: int, per_group: int) -> int:
    """The block of heads of one grid step: the most that divide a group's
    heads, stay within _HEADS and keep a chunk's [h, C, C] tiles within
    _TILE_BYTES each."""
    return _most_heads(per_group, _HEADS, chunk, _TILE_BYTES)


# The bytes of ONE [k, C, C] float32 tile of the heads the backward walks at
# a time (`_tiles`): the tiles' elementwise work is a pass over VMEM an
# operation, so a walk of few heads stores and loads less between two
# operations, and a walk of many batches more matrix passes. At [1, 8192,
# 64, 64] on one group of 128 in chunks of 256 (16 heads a grid step), a
# v5e read `ssd_bwd` 2.42 / 2.21 / 2.08 / 2.11 ms a call at 1 / 2 / 4 / 16
# heads a walk, and at [1, 8192, 16, 64] in chunks of 128 0.77 / 0.55 /
# 0.53 / 0.47: 1 MB either way.
_WALK_BYTES = 1 << 20


def _tile_heads(chunk: int, heads: int) -> int:
    """The heads of a grid step's `heads` that `_tiles` takes at a time:
    the most that divide them and keep a [k, C, C] tile in _WALK_BYTES."""
    return _most_heads(heads, heads, chunk, _WALK_BYTES)


# What a call may take of VMEM. XLA keeps that much free ACROSS the call, so
# what its neighbours hold there (an MLP's operands, prefetched) is evicted
# by a limit the body does not need: with 64 MB granite's step read 398.5
# ms, with 32 MB 395.2 (`mlp` 189.6 -> 186.3 ms a step). At 16 heads of
# chunks of 256 the forward's body takes 15 MB and the backward's 19, and
# _TILE_BYTES / _WALK_BYTES bound both at any chunk.
_VMEM_LIMIT = 32 << 20
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)
# the backward sums over a group's blocks of heads, its inner axis
_BWD_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


@functools.lru_cache(maxsize=None)
def _make_ssd_fn(chunk: int, per_group: int, exact, interpret: bool):
    """ssd_fwd with ssd_bwd as its backward, on x [heads, P, tokens] of
    whole chunks, b and c [groups, tokens, N], dt and g [heads, chunks, 1,
    chunk], d [heads, 1, 1] (heads and groups times the batch). The
    residuals are the six inputs and the chunks' states."""
    h = _heads_a_step(chunk, per_group)

    def specs(width, n, at):
        """The blocks of a grid whose two indices `at` turns into (block of
        heads, chunk)."""
        def spec(shape, where):
            return pl.BlockSpec(shape, lambda *ids: where(*at(*ids)))
        x = spec((h, width, chunk), lambda i, j: (i, 0, j))
        shared = spec((1, chunk, n),
                      lambda i, j: (jax.lax.div(i * h, per_group), j, 0))
        row = spec((h, 1, 1, chunk), lambda i, j: (i, j, 0, 0))
        skip = spec((h, 1, 1), lambda i, j: (i, 0, 0))
        states = spec((h, 1, width, n), lambda i, j: (i, j, 0, 0))
        per_chunk = spec((h, 1, 1, 1), lambda i, j: (i, j, 0, 0))
        return x, shared, row, skip, states, per_chunk

    def forward(x, b, c, dt, g, d):
        heads, width, tokens = x.shape
        n, chunks = b.shape[-1], tokens // chunk
        wide, shared, row, skip, states, _ = specs(width, n,
                                                   lambda i, j: (i, j))
        return pl.pallas_call(
            functools.partial(_fwd_kernel, exact),
            grid=(heads // h, chunks),
            in_specs=[wide, shared, shared, row, row, skip],
            out_specs=[wide, states],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((heads, chunks, width, n),
                                            jnp.float32)],
            scratch_shapes=[pltpu.VMEM((h, width, n), jnp.float32)],
            compiler_params=_PARAMS,
            interpret=interpret,
            name="ssd_fwd",
        )(x, b, c, dt, g, d)

    @jax.custom_vjp
    def f(x, b, c, dt, g, d, a_log):
        return forward(x, b, c, dt, g, d)[0]

    def fwd(x, b, c, dt, g, d, a_log):
        y, states = forward(x, b, c, dt, g, d)
        # both kept by a remat policy that saves the name: the forward runs
        # once a layer and step
        return checkpoint_name(y, SSD_OUT), (
            x, b, c, dt, g, d, a_log, checkpoint_name(states, SSD_OUT))

    def bwd(residuals, dy):
        x, b, c, dt, g, d, a_log, kept = residuals
        heads, width, tokens = x.shape
        n, chunks = b.shape[-1], tokens // chunk
        # the chunks from the last, outside; a group's blocks of heads inside
        wide, shared, row, skip, states, per_chunk = specs(
            width, n, lambda j, i: (i, chunks - 1 - j))
        f32 = jnp.float32
        blocks = per_group // h
        dx, db, dc, ddt, da, dd = pl.pallas_call(
            functools.partial(_bwd_kernel, exact, blocks),
            grid=(chunks, heads // h),
            in_specs=[wide, shared, shared, row, row, skip, skip, states,
                      wide],
            out_specs=[wide, shared, shared, row, per_chunk, per_chunk],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct(b.shape, b.dtype),
                       jax.ShapeDtypeStruct(c.shape, c.dtype),
                       jax.ShapeDtypeStruct(dt.shape, f32),
                       jax.ShapeDtypeStruct((heads, chunks, 1, 1), f32),
                       jax.ShapeDtypeStruct((heads, chunks, 1, 1), f32)],
            scratch_shapes=[pltpu.VMEM((heads, width, n), f32),
                            pltpu.VMEM((h, width, chunk), f32),
                            pltpu.VMEM((2, h, 1, chunk), f32),
                            pltpu.VMEM((chunk, chunk), f32)]
            + [pltpu.VMEM((2, chunk, n), f32)] * (blocks > 1),
            compiler_params=_BWD_PARAMS,
            interpret=interpret,
            name="ssd_bwd",
        )(x, b, c, dt, g, d, a_log, kept, dy)
        of_head = lambda t: t.sum(1, keepdims=True)[..., 0]
        # g's cotangent went onto dt and a_log inside the kernel
        return (dx, db, dc, ddt, jnp.zeros_like(g), of_head(dd), of_head(da))

    f.defvjp(fwd, bwd)
    return f


def ssd(x, dt, a_log, b, c, d, *, chunk: int = 128,
        interpret: Optional[bool] = None):
    """The chunked form of the module's docstring, `ssd_fwd` / `ssd_bwd`.
    S need not be whole chunks. x, b and c that arrive as bfloat16 are
    exact operands of their products (three passes where float32 takes
    six). What the chip's tiles cannot hold is refused: tokens lie along
    the lanes, so a compiled chunk is whole lane tiles, and a head's
    channels whole sublane tiles of bfloat16."""
    if interpret is None:
        interpret = attention._default_interpret()
    heads, width = x.shape[2:]
    if heads % b.shape[2]:
        raise ValueError(f"{b.shape[2]} groups do not divide {heads} heads")
    if not interpret and (chunk % LANES or width % 16):
        raise ValueError(
            f"chunk={chunk} is not whole lane tiles ({LANES} tokens) or "
            f"head_dim={width} not whole sublane tiles (16 channels): the "
            f"scan's kernels hold a chunk's tokens along the lanes")
    exact = tuple(t.dtype == jnp.bfloat16 for t in (x, b, c))
    return _scan(x, dt, a_log, b, c, d, chunk, exact, interpret)


def _scan(x, dt, a_log, b, c, d, chunk: int, exact, interpret: bool):
    """`ssd` once it has decided: the operands laid out for the kernels
    (tokens last), `exact` = whether x, b, c hold bfloat16 values."""
    f32 = jnp.float32
    batch, seq, heads, width = x.shape
    groups, n = b.shape[-2:]
    pad = -seq % chunk
    chunks = (seq + pad) // chunk

    def whole_chunks(t):               # [B, S, ...] -> [B, S + pad, ...]
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

    def rows(t):                   # [B, chunks, H, C] -> [B H, chunks, 1, C]
        return t.transpose(0, 2, 1, 3).reshape(batch * heads, chunks, 1, chunk)

    def shared(t):                     # [B, S, G, N] -> [B G, S + pad, N]
        return whole_chunks(t).transpose(0, 2, 1, 3).reshape(
            batch * groups, chunks * chunk, n)
    steps = whole_chunks(dt.astype(f32)).reshape(batch, chunks, chunk, heads)

    def a_head(t):                                  # [H] -> [B H, 1, 1]
        return jnp.broadcast_to(t.astype(f32), (batch, heads)).reshape(
            -1, 1, 1)
    # g is the caller's cumulative sum, forward; its transpose onto dt and
    # a_log is the backward kernel's, so nothing flows back through it here
    # and a_log rides along for its cotangent
    g = jax.lax.stop_gradient(chunk_log_decay(dt, a_log, chunk))
    y = _make_ssd_fn(chunk, heads // groups, exact, interpret)(
        whole_chunks(x).transpose(0, 2, 3, 1).reshape(
            batch * heads, width, chunks * chunk),
        shared(b), shared(c), rows(steps.transpose(0, 1, 3, 2)), rows(g),
        a_head(d), a_head(a_log))
    return y.reshape(batch, heads, width, -1).transpose(0, 3, 1, 2)[:, :seq]
