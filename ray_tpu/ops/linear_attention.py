"""Linear attention that carries a state: the gated delta rule with a decay
a channel (KDA, the form arXiv:2510.26692 publishes for Kimi Delta
Attention). A head keeps S [dk, dv] and, a token,

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,                                           S_0 = 0

alpha_t = exp(log_decay_t) in (0, 1]^dk, beta_t a number (up to 2: the
transition then has a negative eigenvalue along k_t). The data decays the
state a channel at a time and overwrites what it holds along k_t.

q, k [B, H, S, dk], v [B, H, S, dv], log_decay [B, H, S, dk] (<= 0, float32)
and beta [B, H, S] in, o [B, H, S, dv] out, in q's type; every sum and the
state float32. The caller normalises and scales q and k.

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

`_chunk` states all of that once, for one chunk of a few heads, as a jnp
function of values that live in VMEM (a chunk's q, k, v, g are 32 KB each
at [64, 128], its A and inverse 16 KB, a state 64 KB). `kda_fwd` runs it
over a grid (heads / h, chunks), the chunks in order, the state in VMEM
scratch: a chunk's operands cross HBM once and every intermediate stays on
the chip. `kda_bwd` runs `jax.vjp` of the same function over the chunks
from the last to the first, the state's cotangent in scratch: it computes
the chunk again from its inputs and the state it started from, which the
forward keeps ([heads, chunks, dv, dk] float32, 67 MB a layer at [1, 8,
8192, 128]), and writes the five gradients, the log-decay's among them
(the doubling of `_chunk` is linear in it). The output and the kept states
carry the name KDA_OUT, so that a remat policy that saves it runs the
forward once a layer and step, as FLASH_OUT does for the flash kernels.
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

# The name the output carries (jax.ad_checkpoint.checkpoint_name).
KDA_OUT = "kda_out"

# Every product of the chunked form, float32 operands: the triangular
# system amplifies a rounding of A, and the state is summed over the chunks.
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
    """log_decay [B, H, S, dk] -> the cumulative log-decay inside each chunk
    [B, H, S / chunk (rounded up), chunk, dk], float32: g of the module's
    docstring. Its smallest value is what the chunked form's range depends
    on (where it passes float32's -87 a decay underflows to an exact 0)."""
    b, h, s, dk = log_decay.shape
    pad = -s % chunk
    a = jnp.pad(log_decay.astype(jnp.float32),
                ((0, 0), (0, 0), (0, pad), (0, 0)))
    return jnp.cumsum(a.reshape(b, h, (s + pad) // chunk, chunk, dk), axis=3)


def _dot(x, y, contract):
    """x . y over `contract` (one dimension of each) a head, the heads
    leading both: float32, full precision."""
    return jax.lax.dot_general(
        x, y, ((contract[:1], contract[1:]), ((0,), (0,))),
        precision=_PRECISION, preferred_element_type=jnp.float32)


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


def _chunk(q, k, v, a, beta, state):
    """One chunk of h heads, on values that live in VMEM: q, k, the
    log-decay a [h, C, dk], v [h, C, dv], beta [h, 1, C] (a row: tokens
    along the lanes) and the state the chunk starts from, transposed [h,
    dv, dk], all float32 -> (o [h, C, dv], the state after the chunk). The
    one statement of the chunked form: the forward kernel runs it and the
    backward kernel runs its jax.vjp.

    The decays between the pairs of a level need no cumulative sum and no
    reference row: with F_b[t] the log-decay summed from the start of t's
    block of b tokens up to t, B_b[t] from after t to the block's end and
    T_b their sum (the block's whole), e^{g_t - g_r} = e^{F_b[t]} for t in a
    right half and e^{g_r - g_s} = e^{B_b[s]} for s in a left half, and a
    level doubles them by adding the sibling block's whole, T_b moved b
    rows (F_1 = T_1 = a, B_1 = 0; at the top F = g, B = g_C - g, T =
    g_C). Every exponent is <= 0."""
    chunk = q.shape[1]
    at = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, 1), 1)
    t = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, chunk), 1)
    s = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, chunk), 2)
    eye = t == s
    # beta a row in, a column here: the diagonal's row sums
    beta = jnp.sum(jnp.where(eye, beta, 0.0), axis=2, keepdims=True)
    since, until, whole = a, jnp.zeros_like(a), a
    aqk = jnp.where(eye, jnp.sum(q * k, axis=2, keepdims=True), 0.0)
    inverse = jnp.broadcast_to(eye.astype(jnp.float32), aqk.shape)
    for level in range(chunk.bit_length() - 1):
        half = 1 << level
        # (t in a block's right half, s in its left), zero elsewhere
        pairs = (((t ^ s) >> level) == 1) & (t > s)
        rows, cols = jnp.exp(since), k * jnp.exp(until)
        m = jnp.where(pairs, beta * _dot(k * rows, cols, (2, 2)), 0.0)
        aqk = aqk + jnp.where(pairs, _dot(q * rows, cols, (2, 2)), 0.0)
        inverse = inverse - (m if level == 0 else _dot(
            _dot(inverse, m, (2, 1)), inverse, (2, 1)))
        right = ((at >> level) & 1) == 1
        before = _rows_down(whole, half)
        after = _rows_down(whole, chunk - half)
        since = since + jnp.where(right, before, 0.0)
        until = until + jnp.where(right, 0.0, after)
        whole = whole + jnp.where(right, before, after)
    decayed = jnp.exp(since)                        # e^g, the chunk's own
    wv = _dot(inverse, beta * v, (2, 1))
    wk = _dot(inverse, beta * (k * decayed), (2, 1))
    u = wv - _dot(wk, state, (2, 2))
    o = _dot(q * decayed, state, (2, 2)) + _dot(aqk, u, (2, 1))
    after = (state * jnp.exp(whole[:, :1])
             + _dot(u, k * jnp.exp(until), (1, 1)))
    return o, after


def _fwd_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, o_ref, states_ref,
                state):
    """Grid (heads / h, chunks), the chunks in order: `state` [h, dv, dk]
    carries each head's state; states_ref keeps what a chunk started from
    for the backward."""
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
    states_ref[:, 0] = state[...]
    o, state[...] = _chunk(q_ref[...].astype(f32), k_ref[...].astype(f32),
                           v_ref[...].astype(f32), a_ref[...],
                           beta_ref[:, 0], state[...])
    o_ref[...] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, da_ref, dbeta_ref, dstate):
    """Grid (heads / h, chunks), the chunks from the last to the first:
    `dstate` [h, dv, dk] carries the cotangent of the state a chunk hands
    on. A chunk is computed again from its inputs and the state it started
    from, and transposed."""
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
    _, pull = jax.vjp(_chunk, q_ref[...].astype(f32), k_ref[...].astype(f32),
                      v_ref[...].astype(f32), a_ref[...], beta_ref[:, 0],
                      states_ref[:, 0])
    dq, dk, dv, da, dbeta, dstate[...] = pull(
        (do_ref[...].astype(f32), dstate[...]))
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    da_ref[...] = da
    dbeta_ref[:, 0] = dbeta


# Heads a grid step, at most: their products are batched, and the chains of
# dependent ones interleave (at [1, 8, 8192, 128] on a v5e 2.03 / 6.29 ms
# forward / backward at 4, 3.05 / 9.19 at 1, 2.01 / 7.49 at 8).
_HEADS = 4

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=64 << 20)


@functools.lru_cache(maxsize=None)
def _make_kda_fn(chunk: int, interpret: bool):
    """kda_fwd with kda_bwd as its backward, on [heads, tokens, width]
    operands of whole chunks and whole lane tiles; beta [heads, chunks, 1,
    chunk]. The residuals are the five inputs and the chunks' states."""

    def specs(heads, dk, dv, order):
        h = max(d for d in range(1, _HEADS + 1) if heads % d == 0)
        wide = lambda d: pl.BlockSpec((h, chunk, d),
                                      lambda i, j: (i, order(j), 0))
        beta = pl.BlockSpec((h, 1, 1, chunk),
                            lambda i, j: (i, order(j), 0, 0))
        states = pl.BlockSpec((h, 1, dv, dk),
                              lambda i, j: (i, order(j), 0, 0))
        return h, wide(dk), wide(dv), beta, states

    def forward(q, k, v, a, beta):
        heads, tokens, dk = q.shape
        dv, n = v.shape[-1], tokens // chunk
        h, key, value, row, states = specs(heads, dk, dv, lambda j: j)
        return pl.pallas_call(
            _fwd_kernel,
            grid=(heads // h, n),
            in_specs=[key, key, value, key, row],
            out_specs=[value, states],
            out_shape=[jax.ShapeDtypeStruct(v.shape, q.dtype),
                       jax.ShapeDtypeStruct((heads, n, dv, dk), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((h, dv, dk), jnp.float32)],
            compiler_params=_PARAMS,
            interpret=interpret,
            name="kda_fwd",
        )(q, k, v, a, beta)

    @jax.custom_vjp
    def f(q, k, v, a, beta):
        return forward(q, k, v, a, beta)[0]

    def fwd(q, k, v, a, beta):
        o, states = forward(q, k, v, a, beta)
        # both kept by a remat policy that saves the name: the forward runs
        # once a layer and step
        return checkpoint_name(o, KDA_OUT), (
            q, k, v, a, beta, checkpoint_name(states, KDA_OUT))

    def bwd(residuals, g):
        q, k, v, a, beta, kept = residuals
        heads, tokens, dk = q.shape
        dv, n = v.shape[-1], tokens // chunk
        h, key, value, row, states = specs(heads, dk, dv,
                                           lambda j: n - 1 - j)
        return tuple(pl.pallas_call(
            _bwd_kernel,
            grid=(heads // h, n),
            in_specs=[key, key, value, key, row, states, value],
            out_specs=[key, key, value, key, row],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in (q, k, v, a, beta)],
            scratch_shapes=[pltpu.VMEM((h, dv, dk), jnp.float32)],
            compiler_params=_PARAMS,
            interpret=interpret,
            name="kda_bwd",
        )(q, k, v, a, beta, kept, g))

    f.defvjp(fwd, bwd)
    return f


def kda(q, k, v, log_decay, beta, *, chunk: int = 64,
        interpret: Optional[bool] = None):
    """The gated delta rule with a decay a channel, chunked: see the
    module's docstring. q, k [B, H, S, dk], v [B, H, S, dv], log_decay
    [B, H, S, dk], beta [B, H, S] -> o [B, H, S, dv]. S need not be whole
    chunks (the tail is padded with tokens that leave the state alone), nor
    the widths whole lane tiles (padded with channels that hold nothing)."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk} is not a power of two")
    if interpret is None:
        interpret = attention._default_interpret()
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    n = -(-s // chunk)

    def laid_out(x):
        # [B, H, S, d] -> [B * H, whole chunks, whole lane tiles]. A padded
        # token has k = 0, beta = 0, no decay: the state passes it
        pad = ((0, 0), (0, 0), (0, n * chunk - s), (0, -x.shape[3] % LANES))
        if any(p for _, p in pad):
            x = jnp.pad(x, pad)
        return x.reshape((b * h,) + x.shape[2:])
    f32 = jnp.float32
    rows = jnp.pad(beta.astype(f32), ((0, 0), (0, 0), (0, n * chunk - s)))
    o = _make_kda_fn(chunk, interpret)(
        laid_out(q), laid_out(k), laid_out(v), laid_out(log_decay.astype(f32)),
        rows.reshape(b * h, n, 1, chunk))
    return o.reshape(b, h, n * chunk, -1)[:, :, :s, :dv]
