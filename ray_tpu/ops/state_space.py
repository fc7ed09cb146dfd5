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
and both state products stay on the chip. `ssd_bwd` runs `jax.vjp` of the
same function over the chunks from the last to the first, the state's
cotangent in scratch: it computes the chunk again from its inputs and the
state it started from, which the forward keeps ([heads, chunks, P, N]
float32: 34 MB a layer at [1, 8192, 16, 64] on 128 in chunks of 128, 67 MB
at [1, 8192, 64, 64] in chunks of 256), and writes the gradients, B's and
C's a block of heads, float32 (XLA sums a group's blocks outside the
kernel: one block at 16 heads a group, four or more at 64). g is the caller's cumulative sum (`chunk_log_decay`), so its
transpose back onto dt and a_log is XLA's, over [B, S, H] numbers. The
output and the kept states carry the name SSD_OUT, so that a remat policy
that saves it runs the forward once a layer and step, as KDA_OUT does for
the delta rule.

Every product is the float32 one at full precision (`_product`): the sum,
in a float32 accumulator, of the bfloat16 terms' products that
Precision.HIGHEST keeps (an operand is three terms, hi + mid + lo, and the
six pairs whose orders add to at most two are multiplied). What is open is
how many terms an operand HAS: x, B and C that arrive as bfloat16 are their
own first term and the other two are zeros, so their pairs are left out
and the sum is the same sum. That is why dt rides on the scores' columns
and on the decays to the chunk's end, never on x: in y = scores x and in
the state's x^T B the x and B operands are exact (three passes instead of
six), C B^T is exact on both sides (one), and C S_0 is exact in C (three).
A cotangent is float32 and always three terms. Float32 inputs take all six
passes everywhere.
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


def _terms(a, count: int):
    """float32 a as `count` bfloat16 terms, the largest first: one where a
    holds a bfloat16 value (the caller's word), else the three that add up
    to every bit of it."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    terms = [a.astype(bf16)]
    for _ in range(count - 1):
        a = a - terms[-1].astype(f32)
        terms.append(a.astype(bf16))
    return terms


def _dot(a, b, contract, terms):
    """a . b over `contract` (one dimension of each; operands of three
    dimensions are batched over their first), float32 at full precision:
    the bfloat16 terms' products whose orders add to at most two (with
    three terms a side Precision.HIGHEST's six), summed in float32."""
    batch = ((0,), (0,)) if a.ndim == 3 else ((), ())
    dims = ((contract[:1], contract[1:]), batch)
    pairs = [(i + j, s, t) for i, s in enumerate(_terms(a, terms[0]))
             for j, t in enumerate(_terms(b, terms[1])) if i + j <= 2]
    pairs.sort(key=lambda pair: -pair[0])                 # the smallest first
    return functools.reduce(jnp.add, (
        jax.lax.dot_general(s, t, dims, preferred_element_type=jnp.float32)
        for _, s, t in pairs))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _product(a, b, contract, terms):
    """`_dot`, with the transposes that are the same kind of product: a
    cotangent is three terms, and an operand keeps the count it had."""
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
    kernel runs it and the backward kernel runs its jax.vjp."""
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


def _bwd_kernel(exact, x_ref, b_ref, c_ref, dt_ref, g_ref, d_ref, states_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dg_ref, dd_ref,
                dstate):
    """Grid (heads / h, chunks), the chunks from the last to the first:
    `dstate` [h, P, N] carries the cotangent of the state a chunk hands on.
    A chunk is computed again from its inputs and the state it started
    from, and transposed."""
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
    _, pull = jax.vjp(
        functools.partial(_chunk, exact=exact),
        x_ref[...].astype(f32), b_ref[0].astype(f32), c_ref[0].astype(f32),
        dt_ref[:, 0], g_ref[:, 0], d_ref[...], states_ref[:, 0])
    dx, db, dc, ddt, dg, dd, dstate[...] = pull(
        (dy_ref[...].astype(f32), dstate[...]))
    dx_ref[...] = dx.astype(dx_ref.dtype)
    db_ref[0] = db
    dc_ref[0] = dc
    ddt_ref[:, 0] = ddt
    dg_ref[:, 0] = dg
    dd_ref[:, 0] = dd


# Heads a grid step, at most (and all of one group): their products are
# batched, and the state's two run over all their rows at once (at [1, 8192,
# 16, 64] on one group of 128, a v5e: 0.16 / 0.51 ms forward / backward at
# 16, 0.19 / 0.54 at 8, 0.25 / 0.57 at 4; with all of a group's heads in
# one step B, C and their gradients cross HBM once a group).
_HEADS = 16

# The bytes ONE [h, C, C] float32 tile of a grid step may take (the decays
# between two tokens, the scores under them, their bfloat16 terms and, in
# the backward, the cotangent of each: `jax.vjp` of `_chunk` holds about a
# dozen at once, beside x, the state and their cotangents at h P (C + N)
# floats each, inside the 64 MB the call may use). A tile is 4 C^2 a head:
# 16 heads of chunks of 128 are 1 MB, of chunks of 256 4 MB, and at chunks
# of 512 a step takes 4. At [1, 8192, 64, 64] on one group of 128 in chunks
# of 256, a v5e read 0.81 / 3.65 ms forward / forward and gradient at 16
# heads a step, 0.88 / 3.76 at 8, 1.02 / 4.09 at 4 (and 0.67 / 2.83 in
# chunks of 128 at 16).
_TILE_BYTES = 4 << 20


def _heads_a_step(chunk: int, per_group: int) -> int:
    """The block of heads of one grid step: the most that divide a group's
    heads, stay within _HEADS and keep a chunk's [h, C, C] tiles within
    _TILE_BYTES each."""
    most = max(1, min(_HEADS, _TILE_BYTES // (4 * chunk * chunk)))
    return max(n for n in range(1, most + 1) if per_group % n == 0)

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=64 << 20)


@functools.lru_cache(maxsize=None)
def _make_ssd_fn(chunk: int, per_group: int, exact, interpret: bool):
    """ssd_fwd with ssd_bwd as its backward, on x [heads, P, tokens] of
    whole chunks, b and c [groups, tokens, N], dt and g [heads, chunks, 1,
    chunk], d [heads, 1, 1] (heads and groups times the batch). The
    residuals are the six inputs and the chunks' states."""
    h = _heads_a_step(chunk, per_group)

    def specs(width, n, order):
        x = pl.BlockSpec((h, width, chunk), lambda i, j: (i, 0, order(j)))
        shared = pl.BlockSpec(
            (1, chunk, n),
            lambda i, j: (jax.lax.div(i * h, per_group), order(j), 0))
        row = pl.BlockSpec((h, 1, 1, chunk), lambda i, j: (i, order(j), 0, 0))
        skip = pl.BlockSpec((h, 1, 1), lambda i, j: (i, 0, 0))
        states = pl.BlockSpec((h, 1, width, n),
                              lambda i, j: (i, order(j), 0, 0))
        return x, shared, row, skip, states

    def forward(x, b, c, dt, g, d):
        heads, width, tokens = x.shape
        n, chunks = b.shape[-1], tokens // chunk
        wide, shared, row, skip, states = specs(width, n, lambda j: j)
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
    def f(x, b, c, dt, g, d):
        return forward(x, b, c, dt, g, d)[0]

    def fwd(x, b, c, dt, g, d):
        y, states = forward(x, b, c, dt, g, d)
        # both kept by a remat policy that saves the name: the forward runs
        # once a layer and step
        return checkpoint_name(y, SSD_OUT), (
            x, b, c, dt, g, d, checkpoint_name(states, SSD_OUT))

    def bwd(residuals, dy):
        x, b, c, dt, g, d, kept = residuals
        heads, width, tokens = x.shape
        n, chunks = b.shape[-1], tokens // chunk
        wide, shared, row, skip, states = specs(width, n,
                                                lambda j: chunks - 1 - j)
        f32 = jnp.float32
        # B's and C's gradients a block of heads, d's a chunk
        block = pl.BlockSpec((1, chunk, n),
                             lambda i, j: (i, chunks - 1 - j, 0))
        dx, db, dc, ddt, dg, dd = pl.pallas_call(
            functools.partial(_bwd_kernel, exact),
            grid=(heads // h, chunks),
            in_specs=[wide, shared, shared, row, row, skip, states, wide],
            out_specs=[wide, block, block, row, row,
                       pl.BlockSpec((h, 1, 1, 1),
                                    lambda i, j: (i, chunks - 1 - j, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((heads // h, tokens, n), f32),
                       jax.ShapeDtypeStruct((heads // h, tokens, n), f32),
                       jax.ShapeDtypeStruct(dt.shape, f32),
                       jax.ShapeDtypeStruct(g.shape, f32),
                       jax.ShapeDtypeStruct((heads, chunks, 1, 1), f32)],
            scratch_shapes=[pltpu.VMEM((h, width, n), f32)],
            compiler_params=_PARAMS,
            interpret=interpret,
            name="ssd_bwd",
        )(x, b, c, dt, g, d, kept, dy)

        def of_group(t, like):
            return t.reshape(like.shape[0], per_group // h, tokens, n).sum(
                1).astype(like.dtype)
        return (dx, of_group(db, b), of_group(dc, c), ddt, dg,
                dd.sum(1, keepdims=True)[..., 0])

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
    y = _make_ssd_fn(chunk, heads // groups, exact, interpret)(
        whole_chunks(x).transpose(0, 2, 3, 1).reshape(
            batch * heads, width, chunks * chunk),
        shared(b), shared(c), rows(steps.transpose(0, 1, 3, 2)),
        rows(chunk_log_decay(dt, a_log, chunk)),
        jnp.broadcast_to(d.astype(f32), (batch, heads)).reshape(-1, 1, 1))
    return y.reshape(batch, heads, width, -1).transpose(0, 3, 1, 2)[:, :seq]
