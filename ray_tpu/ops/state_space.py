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
chunked form, XLA einsums and one `lax.scan`. With g_t the cumulative
log-decay inside a chunk of C tokens (g <= 0, falling) and S_0 the state
the chunk starts from,

    y_t = sum_{s<=t} (C_t . B_s) e^{g_t - g_s} dt_s x_s  +  e^{g_t} S_0 C_t
    next S_0 = e^{g_C} S_0 + sum_s e^{g_C - g_s} dt_s x_s B_s^T

The first sum is a lower-triangular [C, C] matrix a head and chunk, (C B^T)
times the decays between the two tokens, against the chunk's dt x: matmuls.
Only the last line is sequential: one scan over the chunks' [H, P, N]
states. No product divides by a decay (ops/linear_attention.py's rule):
e^{g_t - g_s} is the exponential of a difference that is <= 0 wherever it
is used, taken after the subtraction, and masked above the diagonal before
it. A sequence that is no whole number of chunks is padded with tokens of
dt = 0, which leave the state as it is. The output and the chunks' states
carry the name SSD_OUT, so that a remat policy that saves it runs the scan
once a layer and step, as KDA_OUT does for the delta rule.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# The name the output and the chunks' states carry
# (jax.ad_checkpoint.checkpoint_name).
SSD_OUT = "ssd_out"

# Every product of both forms: float32 operands at full precision (the
# state is summed over the whole sequence).
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


def ssd(x, dt, a_log, b, c, d, *, chunk: int = 128):
    """The chunked form of the module's docstring. The heads of a group
    share its B and C: every product with them runs a group at a time over
    its heads' columns, and B and C are never written out a head."""
    f32 = jnp.float32
    batch, seq, heads, width = x.shape
    groups, n = b.shape[-2:]
    per = heads // groups
    pad = -seq % chunk
    chunks = (seq + pad) // chunk

    def chunked(t):                  # [B, S, ...] -> [B, chunks, C, ...] f32
        t = jnp.pad(t.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape(batch, chunks, chunk, *t.shape[2:])

    xc, dtc, bc, cc = chunked(x), chunked(dt), chunked(b), chunked(c)
    g = chunk_log_decay(dt, a_log, chunk)                     # [B,c,H,C]
    u = dtc[..., None] * xc                                  # dt x [B,c,C,H,P]
    at = jnp.arange(chunk)
    below = at[:, None] >= at[None, :]                        # s <= t
    between = jnp.exp(jnp.where(below, g[..., :, None] - g[..., None, :],
                                -jnp.inf))                    # [B,c,H,C,C]
    cb = jnp.einsum("bktgn,bksgn->bkgts", cc, bc, precision=_PRECISION)
    scores = between * jnp.repeat(cb, per, axis=2)
    y = jnp.einsum("bkhts,bkshp->bkthp", scores, u, precision=_PRECISION)

    # what a chunk adds to the state it hands on, and how far it decays it
    to_end = jnp.exp(g[..., -1:] - g).transpose(0, 1, 3, 2)   # [B,c,C,H]
    added = jnp.einsum(
        "bksgrp,bksgn->bkgrpn",
        (to_end[..., None] * u).reshape(batch, chunks, chunk, groups, per,
                                        width),
        bc, precision=_PRECISION).reshape(batch, chunks, heads, width, n)
    whole = jnp.exp(g[..., -1])                               # [B,c,H]

    def step(state, inputs):
        decay, add = inputs
        return decay[..., None, None] * state + add, state

    _, starts = jax.lax.scan(
        step, jnp.zeros((batch, heads, width, n), f32),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
    starts = checkpoint_name(jnp.moveaxis(starts, 0, 1), SSD_OUT)
    from_start = jnp.exp(g).transpose(0, 1, 3, 2)             # [B,c,C,H]
    carried = jnp.einsum(
        "bktgn,bkgrpn->bktgrp", cc,
        starts.reshape(batch, chunks, groups, per, width, n),
        precision=_PRECISION).reshape(batch, chunks, chunk, heads, width)
    y = y + from_start[..., None] * carried + d.astype(f32)[:, None] * xc
    y = y.reshape(batch, seq + pad, heads, width)[:, :seq]
    return checkpoint_name(y.astype(x.dtype), SSD_OUT)
