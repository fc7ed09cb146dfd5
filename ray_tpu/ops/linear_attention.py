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
chunked form under a custom_vjp. With g_t the cumulative log-decay inside a
chunk of C tokens (g <= 0, falling) and S_0 the state the chunk starts from,

    S_t = Diag(e^{g_t}) S_0 + sum_{s<=t} Diag(e^{g_t - g_s}) k_s u_s^T,
    u_t = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t)

so (I + Diag(beta) A) U = Diag(beta) (V - Kbar S_0), with A[t, s] = sum_c
k_t[c] k_s[c] e^{g_t[c] - g_s[c]} below the diagonal and Kbar_t = e^{g_t} k_t
(the WY / UT form: U = Wv - Wk S_0 with [Wv | Wk] = (I + Diag(beta) A)^-1
Diag(beta) [V | Kbar], which needs no state), O = Qbar S_0 + Aqk U with Aqk
the same products of q with k on and below the diagonal, and the next
chunk's S_0 = Diag(e^{g_C}) S_0 + Ktilde^T U, Ktilde_s = e^{g_C - g_s} k_s.
Only the last line is sequential: a `lax.scan` over the S / C chunk states.

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

The backward differentiates the chunked form again from the five inputs
(`jax.vjp` inside the rule: the chunk states are recomputed, none is kept),
each level's scaled copies under a `jax.checkpoint` of their own; the
levels are a `lax.scan`, one body for the compiler. The
output carries the name KDA_OUT, so that a remat policy that saves it runs
the forward once a layer and step, as FLASH_OUT does for the flash kernels.
All of it is XLA: einsums over [B, H, chunks] and one scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

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


def _tree(chunk: int):
    """The binary tree of a chunk, a row a level (blocks of 2b = 2 << level
    tokens): which tokens lie in their block's right half [L, C], which
    pairs share a block [L, C, C], and, as 0 / 1 rows, each token's
    reference, its block's last token of the left half [L, C, C]."""
    at = np.arange(chunk)
    half = 1 << np.arange(chunk.bit_length() - 1)[:, None]
    block = at[None] // (2 * half)
    reference = block * 2 * half + half - 1
    return ((at[None] // half) % 2 == 1,
            block[:, :, None] == block[:, None, :],
            (reference[:, :, None] == at[None, None, :]).astype(np.float32))


def _within_chunks(q, k, v, g, beta):
    """What needs no state, for every chunk at once: q, k [.., C, dk], v
    [.., C, dv], g the cumulative log-decay [.., C, dk], beta [.., C] ->
    (Wv [.., C, dv], Wk [.., C, dk], Aqk [.., C, C]). The levels of the
    tree are a scan (one body for the compiler, whatever the chunk), each
    under a jax.checkpoint: its scaled copies are made again backward."""
    chunk, dv = g.shape[-2], v.shape[-1]

    def level(carry, tree):
        inverse, aqk = carry
        right, same_block, reference = tree
        # (a 0 / 1 product at full precision picks the row to the bit)
        ref = jnp.einsum("ts,...sd->...td", reference, g,
                         precision=jax.lax.Precision.HIGHEST)
        rows = jnp.exp(jnp.where(right[:, None], g - ref, -jnp.inf))
        cols = k * jnp.exp(jnp.where(right[:, None], -jnp.inf, ref - g))

        def pairs(x):
            # (t in a block's right half, s in its left); zero elsewhere
            return jnp.where(same_block, jnp.einsum(
                "...td,...sd->...ts", x * rows, cols, precision=_PRECISION),
                0.0)
        m = beta[..., None] * pairs(k)
        inverse = inverse - jnp.einsum(
            "...ts,...su,...uw->...tw", inverse, m, inverse,
            precision=_PRECISION)
        return (inverse, aqk + pairs(q)), None

    eye = jnp.eye(chunk, dtype=g.dtype)
    on_diagonal = eye * jnp.sum(q * k, axis=-1)[..., None]
    (inverse, aqk), _ = jax.lax.scan(
        jax.checkpoint(level),
        (jnp.broadcast_to(eye, on_diagonal.shape), on_diagonal),
        tuple(map(jnp.asarray, _tree(chunk))))
    solved = jnp.einsum(
        "...ts,...sd->...td", inverse,
        beta[..., None] * jnp.concatenate([v, k * jnp.exp(g)], axis=-1),
        precision=_PRECISION)
    return solved[..., :dv], solved[..., dv:], aqk


def _across_chunks(wv, wk, k_end, decay):
    """The sequential part: wv [B, H, N, C, dv], wk and k_end (Ktilde) [B,
    H, N, C, dk], decay (a chunk's whole) [B, H, N, dk] -> (the state each
    chunk starts from [B, H, N, dk, dv], U [B, H, N, C, dv])."""
    def step(state, x):
        wv_n, wk_n, k_n, decay_n = x
        u = wv_n - jnp.einsum("bhck,bhkv->bhcv", wk_n, state,
                              precision=_PRECISION)
        after = state * decay_n[..., None] + jnp.einsum(
            "bhck,bhcv->bhkv", k_n, u, precision=_PRECISION)
        return after, (state, u)

    b, h, _, _, dv = wv.shape
    by_chunk = tuple(jnp.moveaxis(x, 2, 0) for x in (wv, wk, k_end, decay))
    _, (states, u) = jax.lax.scan(
        step, jnp.zeros((b, h, wk.shape[-1], dv), wv.dtype), by_chunk)
    return jnp.moveaxis(states, 0, 2), jnp.moveaxis(u, 0, 2)


def _kda_chunked(q, k, v, log_decay, beta, chunk: int):
    """The chunked form, forward."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk} is not a power of two")
    f32 = jnp.float32
    b, h, s, _ = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    n = (s + pad) // chunk

    def chunks(x):
        # a padded token has k = 0, beta = 0, no decay: the state passes it
        x = jnp.pad(x.astype(f32), ((0, 0), (0, 0), (0, pad))
                    + ((0, 0),) * (x.ndim - 3))
        return x.reshape((b, h, n, chunk) + x.shape[3:])
    out_dtype = q.dtype
    q, k, v, beta = chunks(q), chunks(k), chunks(v), chunks(beta)
    g = chunk_log_decay(log_decay, chunk)
    wv, wk, aqk = _within_chunks(q, k, v, g, beta)
    states, u = _across_chunks(wv, wk, k * jnp.exp(g[..., -1:, :] - g),
                               jnp.exp(g[..., -1, :]))
    o = (jnp.einsum("...tk,...kv->...tv", q * jnp.exp(g), states,
                    precision=_PRECISION)
         + jnp.einsum("...ts,...sv->...tv", aqk, u, precision=_PRECISION))
    return o.reshape(b, h, n * chunk, dv)[:, :, :s].astype(out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda(q, k, v, log_decay, beta, chunk):
    return _kda_chunked(q, k, v, log_decay, beta, chunk)


def _kda_fwd(q, k, v, log_decay, beta, chunk):
    out = checkpoint_name(_kda_chunked(q, k, v, log_decay, beta, chunk),
                          KDA_OUT)
    return out, (q, k, v, log_decay, beta)


def _kda_bwd(chunk, inputs, g):
    _, pull = jax.vjp(functools.partial(_kda_chunked, chunk=chunk), *inputs)
    return pull(g)


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda(q, k, v, log_decay, beta, *, chunk: int = 64):
    """The gated delta rule with a decay a channel, chunked: see the
    module's docstring. q, k [B, H, S, dk], v [B, H, S, dv], log_decay
    [B, H, S, dk], beta [B, H, S] -> o [B, H, S, dv]. S need not be whole
    chunks (the tail is padded with tokens that leave the state alone)."""
    return _kda(q, k, v, log_decay, beta, chunk)
