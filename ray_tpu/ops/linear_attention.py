"""Linear attention that carries a state: the gated delta rule with a decay
a channel (KDA, the form arXiv:2510.26692 publishes for Kimi Delta
Attention) or ONE decay a head (Gated DeltaNet, arXiv:2412.06464: the same
recurrence with every channel of alpha_t the same number). A head keeps S
[dk, dv] and, a token,

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,                                           S_0 = 0

alpha_t = exp(log_decay_t) in (0, 1]^dk, beta_t a number (up to 2: the
transition then has a negative eigenvalue along k_t). The data decays the
state a channel at a time and overwrites what it holds along k_t.

The operands lie by token, as a layer's projections write them and its
output projection reads them: q, k [B, S, H dk], v [B, S, H dv], log_decay
[B, S, H dk] (<= 0, float32; [B, S, H] for a decay a head) and beta [B, S, H]
in, o [B, S, H dv] out, in q's type; every sum and the state float32. Where a
head's dk and dv are whole lane tiles (128 / 128) nothing ever turns them by
head: the kernels' block maps place a grid step's heads among a token's
columns (`_make_kda_fn`), and a head is a static slice of the block (`_heads`,
`_place`). The chip tiles [.., H, 128] as 8 heads x 128 lanes of one token and
[.., H 128] as 8 tokens x 128 lanes, so every [B, H, S, w] view of such a
tensor was a relayout pass of its own, in float32 where its consumer was
(PERF.md, PRs 48 and 68). Or they lie by head, q, k [B, H, S, dk], v [B, H, S,
dv], log_decay [B, H, S, dk] or [B, H, S, 1], beta [B, H, S] in, o [B, H, S,
dv] out: then dk and dv need not be whole lane tiles (padded: 96 / 192 run as
128 / 256), and operands by token at such widths are turned by head on the way
in (`kda`). The same two kernel bodies run both. dk and dv need not be alike.
The caller normalises and scales q and k.

Two formulations. `kda_reference` is the recurrence as it stands, a token a
step of a `lax.scan` (the oracle, never the timed path). `kda` is its
chunked form as two Pallas kernels under a custom_vjp. With g_t the
cumulative log-decay inside a chunk of C tokens (g <= 0, falling) and S_0
the state the chunk starts from,

    S_t = Diag(e^{g_t}) S_0 + sum_{s<=t} Diag(e^{g_t - g_s}) k_s u_s^T,
    u_t = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t)

so (I + Diag(beta) A) U = Diag(beta) (V - Kbar S_0), with A[t, s] = sum_c
k_t[c] k_s[c] e^{g_t[c] - g_s[c]} below the diagonal and Kbar_t = e^{g_t} k_t
(the WY / UT form: U = Wv - Wk S_0 with [Wv | Wk] = (I + Diag(beta) A)^-1
Diag(beta) [V | Kbar], which needs no state), O = Qbar S_0 + Aqk U with Aqk
the same products of q with k on and below the diagonal, and the next
chunk's S_0 = Diag(e^{g_C}) S_0 + Ktilde^T U, Ktilde_s = e^{g_C - g_s} k_s.
Only the last line is sequential.

No product divides by a decay. e^{g_t - g_s} does not factor into e^{g_t}
e^{-g_s} safely (e^{-g_s} overflows where the decay underflows), so A is
built over a binary tree of the chunk: at the level of blocks of 2b tokens,
the pairs (t in a block's right half, s in its left) take the left half's
last token r as their reference, e^{g_t - g_s} = e^{g_t - g_r} e^{g_r -
g_s}, both exponents <= 0, and are ONE matmul of rows scaled by the first
with columns scaled by the second, masked to the pairs of one block; log2 C
levels cover every pair below the diagonal once. The same tree inverts I +
Diag(beta) A, which is unit lower triangular: with X the inverse of its
diagonal blocks of b tokens and M the level's pairs, X - X M X is the
inverse of the blocks of 2b (block forward substitution, as matmuls).

ONE decay a head needs no tree for A: g_t is a number, e^{g_t - g_s} a [C, C]
matrix of differences (all <= 0 on and below the diagonal: nothing divides),
A and Aqk are one masked product each, K K^T and Q K^T, times that matrix, and
Kbar, Ktilde and Qbar are rows times a number. `kda` hands the kernels each
chunk's cumulative log-decay as a row of C numbers (as beta's), `_chunk` and
`_bwd_kernel` read off the decay's shape which form they run, and the inverse's
doubling and everything after it are the same lines for both. A v5e, ms a call
at [1, 15, 8192, 96 / 192]: `kda_fwd` 3.07 and `kda_bwd` 4.05, against 4.58 and
6.16 for the same decay broadcast to the key's channels through the tree.

`_chunk` states all of that once, for one chunk of a few heads, as a jnp
function of values that live in VMEM (a chunk's q, k, v, g are 32 KB each
at [64, 128], its A and inverse 16 KB, a state 64 KB). `kda_fwd` runs it
over a grid (heads / h, chunks), the chunks in order, the state in VMEM
scratch (h neighbouring heads of a batch row a step): a chunk's operands
cross HBM once and every intermediate stays on the chip. `kda_bwd` runs
the same function's transpose, written out (`_bwd_kernel`), over the chunks from the last to the first, the state's
cotangent in scratch. What the transposes read of the forward it READS:
the state a chunk started from ([heads, chunks, dv, dk] float32, 67 MB a
layer at [1, 8, 8192, 128]) and the chunk's A, Aqk and inverse T, which the
forward hands out packed into one [C, 2 C] float32 block (`_packed`: A
below the diagonal of the left half and T's transpose above it, Aqk the
right half; 32 KB a chunk and head, 34 MB a layer there and 134 MB at
kimi's 32 heads). What it makes AGAIN is the decays of every level (adds,
rotations and exponentials, no product) and Wv, Wk and U, three products
from T. T's cotangent goes back through the inverse in closed form, dM =
-T^T dT T^T below the diagonal (two products where the doubling's
mechanical transpose took twenty), and the tree is walked once, from the
chunk's whole down to the pairs of neighbours, a level's two products on A's
and Aqk's cotangents stacked (128 rows against one operand). It writes the
five gradients, the log-decay's among them (the doubling of `_chunk` is
linear in it: `_undoubled`). The output, the kept states and the kept
matrices carry the name KDA_OUT, so that a remat policy that saves it runs
the forward once a layer and step, as FLASH_OUT does for the flash kernels.

Every product is the float32 one at full precision: `Precision.HIGHEST` on
float32 operands, six bfloat16 passes of the matrix unit. The backward
leaves out the passes that multiply zeros (`_product`, over ops/terms.py):
dO arrives in o's type and v in its own, and as bfloat16 they are their own
one term of the three HIGHEST would split them into (three passes in place
of six), so beta rides on T's columns and never on v, and dO is multiplied
before anything scales it. Whatever is float32 by nature (a decayed q or k,
the states, A, T, every other cotangent) is three terms, and float32 inputs
take all six passes everywhere. That is the same sum, not a lower
precision: the triangular system amplifies a rounding of A, and the state
is summed over the chunks.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention, terms
from ray_tpu.ops.attention import LANES

# The name the output carries (jax.ad_checkpoint.checkpoint_name).
KDA_OUT = "kda_out"

# Every product of the recurrence and of the chunked form, float32 operands:
# the triangular system amplifies a rounding of A, and the state is summed
# over the chunks.
_PRECISION = jax.lax.Precision.HIGHEST


def kda_reference(q, k, v, log_decay, beta):
    """The recurrence, a token a step, float32."""
    f32 = jnp.float32

    def step(state, x):
        q_t, k_t, v_t, a_t, b_t = x
        state = state * jnp.exp(a_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", state, k_t, precision=_PRECISION))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=_PRECISION)

    b, h, _, dk = q.shape
    by_token = [jnp.moveaxis(x.astype(f32), 2, 0)
                for x in (q, k, v, log_decay, beta)]
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), f32),
                        tuple(by_token))
    return jnp.moveaxis(o, 0, 2).astype(q.dtype)


def chunk_log_decay(log_decay, chunk: int = 64):
    """log_decay [.., S, w] (by token [B, S, H dk] or [B, S, H]; by head [B,
    H, S, dk] or [B, H, S, 1]: the tokens are the last dimension but one
    either way) -> the cumulative log-decay inside each chunk [.., S / chunk
    (rounded up), chunk, w], float32: g of the module's docstring. Its
    smallest value is what the chunked form's range depends on (where it
    passes float32's -87 a decay underflows to an exact 0)."""
    *lead, s, w = log_decay.shape
    pad = -s % chunk
    a = jnp.pad(log_decay.astype(jnp.float32),
                ((0, 0),) * len(lead) + ((0, pad), (0, 0)))
    return jnp.cumsum(a.reshape(*lead, (s + pad) // chunk, chunk, w),
                      axis=len(lead) + 1)


def _dot(x, y, contract):
    """x . y over `contract` (one dimension of each) a head, the heads
    leading both: float32, full precision."""
    return jax.lax.dot_general(
        x, y, ((contract[:1], contract[1:]), ((0,), (0,))),
        precision=_PRECISION, preferred_element_type=jnp.float32)


def _product(x, y, contract, of):
    """`_dot` where `of`, the bfloat16 terms each operand HAS (ops/terms.py),
    is three a side; else the same sum without the pairs of the terms that
    are zero: three passes of the matrix unit in place of six."""
    if of == (3, 3):
        return _dot(x, y, contract)
    return terms.dot(x, y, contract, of)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rows_down(x, shift: int):
    """x [h, C, d] with row t holding x[t - shift], around the end (a
    sublane rotation; its transpose is the rotation back)."""
    return pltpu.roll(x, shift, 1)


def _rows_down_fwd(x, shift):
    return _rows_down(x, shift), None


def _rows_down_bwd(shift, _, g):
    return (pltpu.roll(g, g.shape[1] - shift, 1),)


_rows_down.defvjp(_rows_down_fwd, _rows_down_bwd)


def _pairs(chunk: int):
    """(t, s): the row's and the column's token of a [1, C, C] tile."""
    return (jax.lax.broadcasted_iota(jnp.int32, (1, chunk, chunk), 1),
            jax.lax.broadcasted_iota(jnp.int32, (1, chunk, chunk), 2))


def _in_right_half(level: int, chunk: int):
    """[1, C, 1]: whether a token lies in the right half of its block of
    2 ** (level + 1)."""
    at = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, 1), 1)
    return ((at >> level) & 1) == 1


def _doubled(level: int, since, until, whole):
    """The decays of blocks of 2b tokens from those of blocks of b = 2 **
    level, [h, C, dk] each (`_chunk`'s docstring): a block's whole is added
    to its sibling's tokens, the left block's to the right's `since` and
    the right's to the left's `until`."""
    chunk, half = whole.shape[1], 1 << level
    right = _in_right_half(level, chunk)
    before = _rows_down(whole, half)
    after = _rows_down(whole, chunk - half)
    return (since + jnp.where(right, before, 0.0),
            until + jnp.where(right, 0.0, after),
            whole + jnp.where(right, before, after))


def _undoubled(level: int, dsince, duntil, dwhole):
    """`_doubled`'s transpose: the cotangents of the decays of blocks of 2b
    tokens -> what they hand the decays of blocks of b."""
    chunk, half = dwhole.shape[1], 1 << level
    right = _in_right_half(level, chunk)
    dbefore = jnp.where(right, dsince + dwhole, 0.0)
    dafter = jnp.where(right, 0.0, duntil + dwhole)
    return dsince, duntil, (dwhole + pltpu.roll(dbefore, chunk - half, 1)
                            + pltpu.roll(dafter, half, 1))


def _column(row):
    """[h, 1, C], tokens along the lanes -> [h, C, 1]: the diagonal's row
    sums."""
    t, s = _pairs(row.shape[2])
    return jnp.sum(jnp.where(t == s, row, 0.0), axis=2, keepdims=True)


def _last(row):
    """[h, 1, C] -> [h, 1, 1]: the row's last token's."""
    chunk = row.shape[2]
    at = jax.lax.broadcasted_iota(jnp.int32, (1, 1, chunk), 2)
    return jnp.sum(jnp.where(at == chunk - 1, row, 0.0), axis=2,
                   keepdims=True)


def _a_heads_decays(g):
    """ONE decay a head: g [h, 1, C], the cumulative log-decay inside the
    chunk, a row -> (e^{g_t - g_s} [h, C, C] on and below the diagonal and
    0 above it, e^{g_t}, e^{g_C - g_t} [h, C, 1] and e^{g_C} [h, 1, 1]).
    The pairs' decays are a matrix of differences, every exponent <= 0:
    nothing divides and no tree is walked."""
    t, s = _pairs(g.shape[2])
    column, last = _column(g), _last(g)
    between = jnp.where(t >= s, jnp.exp(jnp.minimum(column - g, 0.0)), 0.0)
    return between, jnp.exp(column), jnp.exp(last - column), jnp.exp(last)


def _chunk(q, k, v, a, beta, state, exact: int = 3):
    """One chunk of h heads, on values that live in VMEM: q, k, the
    log-decay a [h, C, dk], v [h, C, dv], beta [h, 1, C] (a row: tokens
    along the lanes) and the state the chunk starts from, transposed [h,
    dv, dk], all float32 -> (o [h, C, dv], the state after the chunk, and
    what the backward reads of it: A, Aqk and the inverse, [h, C, C] each).
    The one statement of the chunked form: the forward kernel runs it, the
    backward kernel its transpose written out, which
    tests/test_linear_attention.py holds to this function's jax.vjp.

    The decays between the pairs of a level need no cumulative sum and no
    reference row: with F_b[t] the log-decay summed from the start of t's
    block of b tokens up to t, B_b[t] from after t to the block's end and
    T_b their sum (the block's whole), e^{g_t - g_r} = e^{F_b[t]} for t in a
    right half and e^{g_r - g_s} = e^{B_b[s]} for s in a left half, and a
    level doubles them by adding the sibling block's whole, T_b moved b
    rows (F_1 = T_1 = a, B_1 = 0; at the top F = g, B = g_C - g, T =
    g_C). Every exponent is <= 0.

    The function adapts to the decay it is handed. ONE decay a head arrives
    as a [h, 1, C], a row like beta, and is g itself, the cumulative
    log-decay inside the chunk: e^{g_t - g_s} is then a [C, C] matrix of
    differences (`_a_heads_decays`), A and Aqk are ONE masked product each
    times it where the decay a channel walks the tree's log2 C levels, and
    Kbar, Ktilde, Qbar are rows times a number; the inverse's doubling and
    everything after it are the same lines. exact: the bfloat16 terms q and
    k have (1 where they arrived as bfloat16: their plain products are one
    pass)."""
    chunk = q.shape[1]
    t, s = _pairs(chunk)
    eye = t == s
    # beta a row in, a column here: the diagonal's row sums
    beta = jnp.sum(jnp.where(eye, beta, 0.0), axis=2, keepdims=True)
    inverse = jnp.broadcast_to(eye.astype(jnp.float32),
                               (q.shape[0], chunk, chunk))
    if a.shape[1] == 1 and chunk > 1:
        # ONE decay a head: a is g [h, 1, C], and A and Aqk are one masked
        # product each times the matrix of the pairs' decays
        between, decayed, ending, fade = _a_heads_decays(a)
        akk = jnp.where(eye, 0.0,
                        _product(k, k, (2, 2), (exact, exact)) * between)
        aqk = _product(q, k, (2, 2), (exact, exact)) * between
        for level in range(chunk.bit_length() - 1):
            pairs = (((t ^ s) >> level) == 1) & (t > s)
            m = jnp.where(pairs, beta * akk, 0.0)
            inverse = inverse - (m if level == 0 else _dot(
                _dot(inverse, m, (2, 1)), inverse, (2, 1)))
    else:
        since, until, whole = a, jnp.zeros_like(a), a
        aqk = jnp.where(eye, jnp.sum(q * k, axis=2, keepdims=True), 0.0)
        akk = jnp.zeros_like(aqk)
        for level in range(chunk.bit_length() - 1):
            # (t in a block's right half, s in its left), zero elsewhere
            pairs = (((t ^ s) >> level) == 1) & (t > s)
            rows, cols = jnp.exp(since), k * jnp.exp(until)
            between = _dot(k * rows, cols, (2, 2))
            akk = akk + jnp.where(pairs, between, 0.0)
            m = jnp.where(pairs, beta * between, 0.0)
            aqk = aqk + jnp.where(pairs, _dot(q * rows, cols, (2, 2)), 0.0)
            inverse = inverse - (m if level == 0 else _dot(
                _dot(inverse, m, (2, 1)), inverse, (2, 1)))
            since, until, whole = _doubled(level, since, until, whole)
        # e^g, the chunk's own; e^{g_C - g}; e^{g_C} [h, 1, dk]
        decayed, ending, fade = (jnp.exp(since), jnp.exp(until),
                                 jnp.exp(whole[:, :1]))
    wv = _dot(inverse, beta * v, (2, 1))
    wk = _dot(inverse, beta * (k * decayed), (2, 1))
    u = wv - _dot(wk, state, (2, 2))
    o = _dot(q * decayed, state, (2, 2)) + _dot(aqk, u, (2, 1))
    after = state * fade + _dot(u, k * ending, (1, 1))
    return o, after, (akk, aqk, inverse)


def _packed(akk, aqk, inverse):
    """A, Aqk and the inverse [h, C, C] as ONE [h, C, 2 C] block (at chunks
    of 64 whole lane tiles of 128: a [C, C] array of its own is laid out
    in HBM at twice its bytes). A is zero on and above its diagonal and the
    inverse is 1 on its own and zero above it, so the left half holds A
    below the diagonal and the inverse's TRANSPOSE above it; the right half
    is Aqk."""
    t, s = _pairs(akk.shape[1])
    return jnp.concatenate(
        [akk + jnp.where(t < s, jnp.swapaxes(inverse, 1, 2), 0.0), aqk],
        axis=2)


def _unpacked(kept):
    """`_packed`'s [h, C, 2 C] -> (A, Aqk, the inverse's transpose)."""
    chunk = kept.shape[1]
    t, s = _pairs(chunk)
    left = kept[:, :, :chunk]
    return (jnp.where(t > s, left, 0.0), kept[:, :, chunk:],
            jnp.where(t < s, left, 0.0) + (t == s).astype(jnp.float32))


def _heads(ref, h: int):
    """A grid step's block of q, k, v, the decay a channel or dO as `_chunk`
    takes it, [h, C, w]: the block itself where the operands lie by head,
    [h, C, w]; each head's columns, a static slice of whole lane tiles, of a
    block [1, C, h w] of the operands as the projections wrote them."""
    if ref.shape[0] == h:
        return ref[...]
    w = ref.shape[2] // h
    return jnp.stack([ref[0, :, i * w:(i + 1) * w] for i in range(h)])


def _place(ref, x):
    """x [h, C, w] into the step's block of o or of a gradient: `_heads`'
    way back, in the block's type."""
    h, _, w = x.shape
    if ref.shape[0] == h:
        ref[...] = x.astype(ref.dtype)
        return
    for i in range(h):
        ref[0, :, i * w:(i + 1) * w] = x[i].astype(ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, o_ref, states_ref,
                kept_ref, state):
    """Grid (heads / h, chunks), the chunks in order: `state` [h, dv, dk]
    carries each head's state; states_ref keeps what a chunk started from
    and kept_ref its A, Aqk and inverse (`_packed`) for the backward. The
    wide blocks are by head or a token's columns (`_heads`, `_place`)."""
    f32, h = jnp.float32, state.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
    states_ref[:, 0] = state[...]
    # a decay a head is a row a chunk, as beta is
    a = a_ref[:, 0] if len(a_ref.shape) == 4 else _heads(a_ref, h)
    o, state[...], kept = _chunk(
        _heads(q_ref, h).astype(f32), _heads(k_ref, h).astype(f32),
        _heads(v_ref, h).astype(f32), a, beta_ref[:, 0], state[...],
        exact=1 if q_ref.dtype == jnp.bfloat16 else 3)
    _place(o_ref, o)
    kept_ref[:, 0] = _packed(*kept)


def _bwd_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, states_ref, kept_ref,
                do_ref, dq_ref, dk_ref, dv_ref, da_ref, dbeta_ref, dstate):
    """Grid (heads / h, chunks), the chunks from the last to the first:
    `dstate` [h, dv, dk] carries the cotangent of the state a chunk hands
    on. `_chunk`'s transpose, written out. Read, not made again: the state
    the chunk started from, A, Aqk and the inverse T (`_unpacked`). Made
    again: the decays of every level (adds, rotations and exponentials, no
    product) and Wv, Wk, U from T. T's cotangent goes back through the
    inverse in closed form, dM = -T^T dT T^T below the diagonal (M =
    Diag(beta) A), and the tree is walked once, from the chunk's whole down
    to the pairs of neighbours, a level's two products on its stacked
    cotangents [dA ; dAqk]. Every product is `_product`'s, with the terms
    its operands HAVE: dO and v that arrive as bfloat16 are one term (beta
    rides on T's columns, not on v), whatever is float32 by nature (a
    decayed q or k, the states, T, every other cotangent) three. The wide
    blocks are by head or a token's columns, as the forward's."""
    f32, bf16, h = jnp.float32, jnp.bfloat16, dstate.shape[0]
    of_v, of_do = (1 if ref.dtype == bf16 else 3 for ref in (v_ref, do_ref))

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
    q, k, v, do = (_heads(ref, h).astype(f32)
                   for ref in (q_ref, k_ref, v_ref, do_ref))
    state, handed = states_ref[:, 0], dstate[...]
    akk, aqk, inverse_t = _unpacked(kept_ref[:, 0])
    chunk = q.shape[1]
    levels = chunk.bit_length() - 1
    t, s = _pairs(chunk)
    eye = t == s
    row = beta_ref[:, 0]                            # beta [h, 1, C]
    beta = jnp.sum(jnp.where(eye, row, 0.0), axis=2, keepdims=True)

    a_head = len(a_ref.shape) == 4                  # ONE decay a head
    if a_head:
        between, decayed, ending, fade = _a_heads_decays(a_ref[:, 0])
    else:
        # e^F and e^B of every level, as `_chunk` makes them
        a = _heads(a_ref, h)
        since, until, whole = a, jnp.zeros(q.shape, f32), a
        from_start, to_end = [], []
        for level in range(levels):
            from_start.append(jnp.exp(since))
            to_end.append(jnp.exp(until))
            since, until, whole = _doubled(level, since, until, whole)
        decayed, ending = jnp.exp(since), jnp.exp(until)
        fade = jnp.exp(whole[:, :1])                # e^{g_C} [h, 1, dk]
    qbar, kbar, ktilde = q * decayed, k * decayed, k * ending

    # the tail of the chunk again: [Wv | Wk] = T Diag(beta) [V | Kbar]
    wv = _product(inverse_t * beta, v, (1, 1), (3, of_v))
    wk = _product(inverse_t, beta * kbar, (1, 1), (3, 3))
    u = wv - _product(wk, state, (2, 2), (3, 3))
    # its transposes: O = Qbar S^T + Aqk U, S' = e^{g_C} S + U^T Ktilde
    du = (_product(aqk, do, (1, 1), (3, of_do))
          + _product(ktilde, handed, (2, 2), (3, 3)))
    daqk = jnp.where(t >= s, _product(do, u, (2, 2), (of_do, 3)), 0.0)
    dqbar = _product(do, state, (2, 1), (of_do, 3))
    dktilde = _product(u, handed, (2, 1), (3, 3))
    dwk = -_product(du, state, (2, 1), (3, 3))      # U = Wv - Wk S^T
    dstate[...] = (handed * fade + _product(do, qbar, (1, 1), (of_do, 3))
                   - _product(du, wk, (1, 1), (3, 3)))
    # through T: its operands' cotangents T^T dW, its own dW (operands)^T
    drv = _product(inverse_t, du, (2, 1), (3, 3))
    drk = _product(inverse_t, dwk, (2, 1), (3, 3))
    dinverse = row * (_product(du, v, (2, 2), (3, of_v))
                      + _product(dwk, kbar, (2, 2), (3, 3)))
    # T = (I + M)^-1: dM = -T^T dT T^T, and M has the pairs below the
    # diagonal alone
    dm = -_product(_product(inverse_t, dinverse, (2, 1), (3, 3)), inverse_t,
                   (2, 1), (3, 3))
    dm = jnp.where(t > s, dm, 0.0)
    dbeta = (jnp.sum(dm * akk, axis=2, keepdims=True)
             + jnp.sum(drv * v, axis=2, keepdims=True)
             + jnp.sum(drk * kbar, axis=2, keepdims=True))
    dbeta_ref[:, 0] = jnp.sum(jnp.where(eye, dbeta, 0.0), axis=1,
                              keepdims=True)
    _place(dv_ref, beta * drv)
    dakk, dkbar = beta * dm, beta * drk
    if a_head:
        # A = (K K^T) D and Aqk = (Q K^T) D under the pairs' decays D, the
        # diagonal of Aqk like any pair: their cotangents times D go back
        # through the two plain products, stacked (q and k with the terms
        # they have), and D's own is dA A + dAqk Aqk, e^x's slope being e^x
        of_k = 1 if k_ref.dtype == bf16 else 3
        both = jnp.concatenate([dakk * between, daqk * between], axis=1)
        dplain = _product(both, k, (2, 1), (3, of_k))         # [h, 2 C, dk]
        dcols = _product(both, jnp.concatenate([k, q], axis=1), (1, 1),
                         (3, of_k))                           # [h, C, dk]
        _place(dq_ref, dqbar * decayed + dplain[:, chunk:])
        _place(dk_ref, dkbar * decayed + dktilde * ending + dplain[:, :chunk]
               + dcols)
        # g's: a column from e^{g_t} (Qbar, Kbar), e^{g_C - g_t} (Ktilde)
        # and the rows of D's cotangent, less a row from its columns; the
        # last token's from e^{g_C} (Ktilde, the state's fade)
        dbetween = dakk * akk + daqk * aqk
        leaving = jnp.sum(dktilde * ktilde, axis=2, keepdims=True)
        dg = (jnp.sum(dqbar * qbar + dkbar * kbar, axis=2, keepdims=True)
              - leaving + jnp.sum(dbetween, axis=2, keepdims=True))
        dlast = (jnp.sum(leaving, axis=1, keepdims=True)
                 + fade * jnp.sum(jnp.sum(handed * state, axis=2,
                                          keepdims=True), axis=1,
                                  keepdims=True))
        at_end = jax.lax.broadcasted_iota(jnp.int32, (1, 1, chunk),
                                          2) == chunk - 1
        da_ref[:, 0] = (jnp.sum(jnp.where(eye, dg, 0.0), axis=1,
                                keepdims=True)
                        - jnp.sum(dbetween, axis=1, keepdims=True)
                        + jnp.where(at_end, dlast, 0.0))
        return
    on_diagonal = jnp.sum(jnp.where(eye, daqk, 0.0), axis=2, keepdims=True)
    dq = dqbar * decayed + on_diagonal * k
    dk = dkbar * decayed + dktilde * ending + on_diagonal * q
    # the log-decay's: of e^F, e^B and (its first row) e^{g_C} at the top
    dsince = dqbar * qbar + dkbar * kbar
    duntil = dktilde * ktilde
    first = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, 1), 1) == 0
    dwhole = jnp.where(
        first, fade * jnp.sum(handed * state, axis=1, keepdims=True), 0.0)
    for level in reversed(range(levels)):
        dsince, duntil, dwhole = _undoubled(level, dsince, duntil, dwhole)
        pairs = (((t ^ s) >> level) == 1) & (t > s)
        rows = from_start[level]
        cols = k * to_end[level]
        # A's and Aqk's cotangents on the level's pairs, stacked: their
        # products share cols, and its own cotangent is one product
        both = jnp.concatenate([jnp.where(pairs, dakk, 0.0),
                                jnp.where(pairs, daqk, 0.0)], axis=1)
        scaled = jnp.concatenate([k * rows, q * rows], axis=1)
        dscaled = _product(both, cols, (2, 1), (3, 3))       # [h, 2 C, dk]
        dcols = _product(both, scaled, (1, 1), (3, 3))       # [h, C, dk]
        dk = dk + dscaled[:, :chunk] * rows + dcols * to_end[level]
        dq = dq + dscaled[:, chunk:] * rows
        product = dscaled * scaled
        dsince = dsince + product[:, :chunk] + product[:, chunk:]
        duntil = duntil + dcols * cols
    _place(dq_ref, dq)
    _place(dk_ref, dk)
    # F_1 = T_1 = a; B_1 = 0 is no function of it
    _place(da_ref, dsince + dwhole)


# Heads a grid step, at most: their products are batched, and the chains of
# dependent ones interleave (the forward's longest is the inverse's
# doubling), while every head more is more [h, C, dk] values held at once
# (backward: the decays of every level, the running gradients). A v5e, ms a
# call at [1, 8, 8192, 128] / [1, 32, 8192, 128]: `kda_fwd` 2.11 / 8.42 at
# 2 heads, 2.04 / 8.15 at 4, 2.01 / 8.04 at 8; `kda_bwd` 3.09 / 12.36 at 1,
# 2.63 / 10.51 at 2, 2.52 / 10.08 at 4, 2.54 / 10.16 at 8. A count they do
# not divide takes its largest divisor under them: 15 heads of 96 / 192
# under ONE decay a head run 5 forward and 3 backward, `kda_fwd` 3.20 at 3
# heads, 3.07 at 5, 3.02 at 15; `kda_bwd` 5.26 at 1, 4.05 at 3, 3.92 at 5
# (which asks for more VMEM than the limit below: 0.13 ms a call is not
# worth moving solar's and kimi's settings for).
_FWD_HEADS = 8
_BWD_HEADS = 4


def _params(vmem_limit_bytes: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes)


# What a call may take of VMEM. XLA keeps that much free ACROSS the call, so
# a limit the body does not need evicts what its neighbours hold there
# (ops/state_space.py's finding). Compiled for the described v5e the
# forward's body takes 9.3 MB at 8 heads a step and the backward's 8.7 at 4
# (8.4 at 5 heads of 96 / 192 padded and 8.7 at 3 under a decay a head).
_FWD_PARAMS = _params(16 << 20)
_BWD_PARAMS = _params(12 << 20)


@functools.lru_cache(maxsize=None)
def _make_kda_fn(chunk: int, interpret: bool):
    """kda_fwd with kda_bwd as its backward, on operands of whole chunks
    and whole lane tiles. The wide ones (q, k, v, the log-decay a channel,
    o, and dO and the gradients backward) lie by token, [rows, tokens,
    heads a row x width], a row's heads side by side as the projections
    wrote them, or by head, [heads, tokens, width]; beta is [heads, chunks,
    1, chunk] either way, and how many times its heads are q's rows says
    which (by head: once). ONE decay a head is the cumulative log-decay
    inside each chunk as rows like beta's (the kernels read which off its
    rank). A grid step's h heads are h neighbours of one row's columns, a
    block [1, chunk, h x width] at (row, chunk, step of the row), or h
    heads' [h, chunk, width]: the kernels' edges read which off the block
    (`_heads`). The residuals are the five inputs, the chunks' states and
    their kept matrices (`_packed`), both [heads, chunks, ..]."""

    def specs(most, q, v, a, beta, order):
        heads, a_row = beta.shape[0], beta.shape[0] // q.shape[0]
        dk, dv = q.shape[2] // a_row, v.shape[2] // a_row
        h = max(d for d in range(1, most + 1)
                if (heads if a_row == 1 else a_row) % d == 0)
        if a_row == 1:
            wide = lambda d: pl.BlockSpec((h, chunk, d),
                                          lambda i, j: (i, order(j), 0))
        else:
            steps = a_row // h
            wide = lambda d: pl.BlockSpec(
                (1, chunk, h * d),
                lambda i, j: (i // steps, order(j), i % steps))
        a_chunk = lambda *dims: pl.BlockSpec(
            (h, 1) + dims, lambda i, j: (i, order(j), 0, 0))
        row = a_chunk(1, chunk)
        return (h, heads, dk, dv, wide(dk), wide(dv),
                wide(dk) if a.ndim == 3 else row, row, a_chunk(dv, dk),
                a_chunk(chunk, 2 * chunk))

    def forward(q, k, v, a, beta):
        n = q.shape[1] // chunk
        h, heads, dk, dv, key, value, decay, row, states, kept = specs(
            _FWD_HEADS, q, v, a, beta, lambda j: j)
        f32 = jnp.float32
        return pl.pallas_call(
            _fwd_kernel,
            grid=(heads // h, n),
            in_specs=[key, key, value, decay, row],
            out_specs=[value, states, kept],
            out_shape=[jax.ShapeDtypeStruct(v.shape, q.dtype),
                       jax.ShapeDtypeStruct((heads, n, dv, dk), f32),
                       jax.ShapeDtypeStruct((heads, n, chunk, 2 * chunk),
                                            f32)],
            scratch_shapes=[pltpu.VMEM((h, dv, dk), f32)],
            compiler_params=_FWD_PARAMS,
            interpret=interpret,
            name="kda_fwd",
        )(q, k, v, a, beta)

    @jax.custom_vjp
    def f(q, k, v, a, beta):
        return forward(q, k, v, a, beta)[0]

    def fwd(q, k, v, a, beta):
        o, states, kept = forward(q, k, v, a, beta)
        # all three kept by a remat policy that saves the name: the forward
        # runs once a layer and step
        return checkpoint_name(o, KDA_OUT), (
            q, k, v, a, beta, checkpoint_name(states, KDA_OUT),
            checkpoint_name(kept, KDA_OUT))

    def bwd(residuals, g):
        q, k, v, a, beta, states_kept, matrices = residuals
        n = q.shape[1] // chunk
        h, heads, dk, dv, key, value, decay, row, states, kept = specs(
            _BWD_HEADS, q, v, a, beta, lambda j: n - 1 - j)
        return tuple(pl.pallas_call(
            _bwd_kernel,
            grid=(heads // h, n),
            in_specs=[key, key, value, decay, row, states, kept, value],
            out_specs=[key, key, value, decay, row],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in (q, k, v, a, beta)],
            scratch_shapes=[pltpu.VMEM((h, dv, dk), jnp.float32)],
            compiler_params=_BWD_PARAMS,
            interpret=interpret,
            name="kda_bwd",
        )(q, k, v, a, beta, states_kept, matrices, g))

    f.defvjp(fwd, bwd)
    return f


def by_token(dk: int, dv: int) -> bool:
    """Whether `kda` runs operands that lie by token as they are: a head's
    columns of q, k and v are whole lane tiles, so a grid step's heads are a
    block of a token's columns that the block maps place. At any other
    width (96 / 192) the operands go by head, padded to whole tiles."""
    return dk % LANES == 0 and dv % LANES == 0


def kda(q, k, v, log_decay, beta, *, chunk: int = 64,
        interpret: Optional[bool] = None):
    """The gated delta rule, chunked: see the module's docstring. The
    operands lie by token, as a layer's projections write them and its
    output projection reads them: q, k [B, S, H dk], v [B, S, H dv], beta
    [B, S, H] -> o [B, S, H dv]; log_decay [B, S, H dk], a decay a channel,
    or [B, S, H], ONE a head and token (Gated DeltaNet's: the kernels then
    take the cumulative log-decay of each chunk, a number a token, and build
    the pairs' decays as one matrix of differences). At heads of whole lane
    tiles (`by_token`) nothing turns them: the kernels' block maps place a
    grid step's heads among a token's columns. Or they lie by head: q, k [B,
    H, S, dk], v [B, H, S, dv], beta [B, H, S] -> o [B, H, S, dv]; log_decay
    [B, H, S, dk] or [B, H, S, 1]; the widths then need not be whole lane
    tiles (padded with channels that hold nothing), and operands by token at
    such widths are turned by head here, and o back. S need not be whole
    chunks (the tail is padded with tokens that leave the state alone)."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk} is not a power of two")
    if interpret is None:
        interpret = attention._default_interpret()
    columns = q.ndim == 3
    if columns:
        b, s, h = beta.shape
        dk, dv = q.shape[-1] // h, v.shape[-1] // h
        if not by_token(dk, dv):
            turned = lambda x: x.reshape(b, s, h, -1).transpose(0, 2, 1, 3)
            o = kda(turned(q), turned(k), turned(v), turned(log_decay),
                    beta.transpose(0, 2, 1), chunk=chunk, interpret=interpret)
            return o.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
        a_head = log_decay.shape[-1] == h
    else:
        b, h, s, dk = q.shape
        dv = v.shape[-1]
        a_head = log_decay.shape[-1] == 1 and dk > 1
    n = -(-s // chunk)
    f32 = jnp.float32

    def laid_out(x):
        # whole chunks, and by head [B, H, S, d] -> [B * H, S, whole lane
        # tiles]. A padded token has k = 0, beta = 0, no decay: the state
        # passes it
        lanes = 0 if columns else -x.shape[3] % LANES
        pad = ((0, 0),) * (x.ndim - 2) + ((0, n * chunk - s), (0, lanes))
        if any(p for _, p in pad):
            x = jnp.pad(x, pad)
        return x if columns else x.reshape((b * h,) + x.shape[2:])

    def whole_chunks(x):
        # a number a head and token -> [B, H, S], float32, the tail padded
        if columns:
            x = x.transpose(0, 2, 1)
        return jnp.pad(x.astype(f32), ((0, 0), (0, 0), (0, n * chunk - s)))
    rows = whole_chunks(beta)
    q, k, v = laid_out(q), laid_out(k), laid_out(v)
    if a_head:
        # ONE decay a head: each chunk's cumulative log-decay, rows as beta's
        decay = jnp.cumsum(whole_chunks(
            log_decay if columns else log_decay[..., 0]).reshape(
                b * h, n, 1, chunk), axis=3)
    else:
        decay = laid_out(log_decay.astype(f32))
    o = _make_kda_fn(chunk, interpret)(
        q, k, v, decay, rows.reshape(b * h, n, 1, chunk))
    if columns:
        return o[:, :s]
    return o.reshape(b, h, n * chunk, -1)[:, :, :s, :dv]
