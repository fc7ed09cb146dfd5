"""A learned sparse-attention indexer (the form arXiv:2512.02556 publishes):
which keys a query's attention may see is chosen from the data, by scores of
the indexer's own thin heads on one key head,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])            (float32)

and S_t is the min(t + 1, topk) causal keys of largest I[t, s], the lower s
at a tie. The indexer is trained by its own loss, the KL divergence of its
softmax over S_t from the main attention's probabilities there, averaged
over the heads and detached:

    L = mean_t KL(p[t, .] || softmax_{s in S_t} I[t, s]),
    p[t, s] = (1 / H) sum_h softmax_{s in S_t}(q_h[t] . k_h[s] * scale)

`select_and_kl` does all of it in one walk over blocks of queries, a whole
row of keys each (a row's choice needs the row): the scores, the row's
topk-th largest EXACTLY (`top_k_mask`: a search on the scores' integer
order, 32 counting passes, the set `lax.top_k` would choose), the selection
as one byte a pair ([B, S, S] int8, what ops/attention.py's `flash_sel_*`
kernels read), the target from q and k, the KL, and, when differentiated,
the KL's gradient `softmax_S(I) - p` on the selected pairs carried into qI,
kI and w there and then, so that no [S, S] tensor outlives its block. The
selection and those three gradients carry checkpoint names (INDEX_MASK,
INDEX_GRADS): a remat policy that saves them runs the walk once a layer and
step. Plain XLA: the head's scores of a block are written and read
([B, heads, block, S] float32), which a kernel that keeps them in VMEM
would not (PERF.md section 7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import NEG_INF

INDEX_MASK = "index_mask"
INDEX_GRADS = "index_grads"

# Queries a block of the walk: [B, heads, block, S] float32 scores of the
# main attention are 537 MB at 2 x 32 heads, 256 queries and 8192 keys.
QUERY_BLOCK = 256


def sortable(x):
    """float32 -> uint32 of the same order (no NaN): a negative number's
    bits inverted, a positive one's sign bit set."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def top_k_mask(scores, k: int, valid):
    """scores [..., n] float32, valid [..., n] bool -> bool [..., n]: each
    row's k largest valid entries, the lower index at a tie (the set
    `jax.lax.top_k` chooses); every valid entry of a row that has at most k.

    The k-th largest is found exactly, in the scores' integer order: from
    the highest bit down, a bit stays set if at least k entries are still
    at or above the candidate (32 counting passes over the row). Entries
    above it are chosen; of those equal to it, the first k - (entries
    above) in index order, by a running count that is taken only when some
    row has more equal entries than it may keep."""
    order = jnp.where(valid, sortable(scores), jnp.uint32(0))

    def bit(i, kth):
        candidate = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(order >= candidate[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, candidate, kth)
    # 0 where the row has fewer than k valid entries (every valid one
    # orders above 0): the row keeps them all
    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(order.shape[:-1], jnp.uint32))
    kth = kth[..., None]
    above, equal = order > kth, order == kth
    allowed = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    untied = (kth == 0) | (
        jnp.sum(equal, axis=-1, keepdims=True, dtype=jnp.int32) == allowed)

    def lowest_first(_):
        return above | (equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
                                 <= allowed))
    chosen = jax.lax.cond(jnp.all(untied), lambda _: above | equal,
                          lowest_first, None)
    return chosen & valid


def index_scores(qi, ki, w):
    """qi [B, Hi, Q, Di], ki [B, K, Di] (the one key head), w [B, Q, Hi]
    float32 -> (I [B, Q, K] float32, the heads' scores before the relu
    [B, Hi, Q, K] float32). The products take their operands as they come
    (bf16 on the model path) and accumulate in float32; the weighted sum
    over the heads is float32 elementwise (a matmul would round to bf16)."""
    s = jnp.einsum("bhqd,bkd->bhqk", qi, ki,
                   preferred_element_type=jnp.float32)
    heads_first = jnp.swapaxes(w, 1, 2)[..., None]          # [B, Hi, Q, 1]
    return jnp.sum(jax.nn.relu(s) * heads_first, axis=1), s


def _row_stats(x):
    """(max, sum of exp(x - max)) over the last dimension, kept. Behind a
    barrier each: the chip's compiler otherwise fuses a row's reduction
    with its broadcast back over the row into ONE window reduction 2 K - 1
    wide, K^2 work a row (47 ms a [2, 32, 256, 8192] block where the two
    passes take 1.5: PERF.md, PR 40)."""
    top = jax.lax.optimization_barrier(jnp.max(x, axis=-1, keepdims=True))
    return top, jax.lax.optimization_barrier(
        jnp.sum(jnp.exp(x - top), axis=-1, keepdims=True))


def _row_softmax(x):
    top, total = _row_stats(x)
    return jnp.exp(x - top) / total


def _row_log_softmax(x):
    top, total = _row_stats(x)
    return x - (top + jnp.log(total))


def _walk(qi, ki, w, q, k, topk, sm_scale, block, with_grads):
    """The walk of the module's docstring. qi [B, Hi, S, Di], ki [B, S, Di],
    w [B, S, Hi] float32, q [B, H, S, D], k [B, Hkv, S, D] (Hkv divides H).
    -> (selection [B, S, S] int8, KL (mean over B x S), selected pairs /
    causal pairs, (d qi, d ki, d w) of the KL or None)."""
    batch, _, seq, _ = qi.shape
    heads, kv_heads, dim = q.shape[1], k.shape[1], q.shape[-1]
    block = min(block, seq)
    if seq % block:
        raise ValueError(f"{seq} positions are not whole blocks of {block}")
    at = jnp.arange(seq)
    tokens = batch * seq

    def queries(carry, start):
        kl_sum, pairs, d_ki = carry
        qib = jax.lax.dynamic_slice_in_dim(qi, start, block, axis=2)
        wb = jax.lax.dynamic_slice_in_dim(w, start, block, axis=1)
        scores, s = index_scores(qib, ki, wb)               # [B, block, S]
        causal = at[None, :] <= (start + jnp.arange(block))[:, None]
        chosen = top_k_mask(scores, topk, causal[None])
        # the target: the main attention's probabilities over the chosen
        # keys, a key/value head with the query heads that read it
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2).reshape(
            batch, kv_heads, heads // kv_heads, block, dim)
        logits = jnp.einsum("bgrqd,bgkd->bgrqk", qb, k,
                            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.mean(_row_softmax(
            jnp.where(chosen[:, None, None], logits, NEG_INF)), axis=(1, 2))
        log_r = _row_log_softmax(jnp.where(chosen, scores, NEG_INF))
        kl_sum = kl_sum + jnp.sum(jax.scipy.special.xlogy(p, p) - p * log_r)
        pairs = pairs + jnp.sum(chosen, dtype=jnp.float32)
        out = [chosen.astype(jnp.int8)]
        if with_grads:
            # d KL / d I = softmax_S(I) - p on the chosen pairs (p sums to
            # one over them), 0 elsewhere: exp(NEG_INF - lse) is 0
            g = (jnp.exp(log_r) - p) / tokens
            d_s = (g[:, None] * jnp.swapaxes(wb, 1, 2)[..., None]
                   * (s > 0)).astype(qi.dtype)
            out.append(jnp.einsum("bhqk,bkd->bhqd", d_s, ki,
                                  preferred_element_type=jnp.float32
                                  ).astype(qi.dtype))
            d_ki = d_ki + jnp.einsum("bhqk,bhqd->bkd", d_s, qib,
                                     preferred_element_type=jnp.float32)
            out.append(jnp.swapaxes(
                jnp.sum(g[:, None] * jax.nn.relu(s), axis=-1), 1, 2))
        return (kl_sum, pairs, d_ki), out

    carry = (jnp.float32(0), jnp.float32(0),
             jnp.zeros(ki.shape, jnp.float32))
    (kl_sum, pairs, d_ki), out = jax.lax.scan(
        queries, carry, jnp.arange(0, seq, block))

    def rows(x, axis):
        """[blocks, .., block, ..] with `block` at `axis` of the rest ->
        the blocks side by side there."""
        x = jnp.moveaxis(x, 0, axis)
        return x.reshape(x.shape[:axis] + (seq,) + x.shape[axis + 2:])
    share = pairs / (batch * seq * (seq + 1) / 2.0)
    grads = None
    if with_grads:
        grads = (rows(out[1], 2), d_ki.astype(ki.dtype),
                 rows(out[2], 1).astype(w.dtype))
    return rows(out[0], 1), kl_sum / tokens, share, grads


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _select_and_kl(qi, ki, w, q, k, topk, sm_scale, block):
    return _walk(qi, ki, w, q, k, topk, sm_scale, block, False)[:3]


def _select_and_kl_fwd(qi, ki, w, q, k, topk, sm_scale, block):
    selection, kl, share, grads = _walk(qi, ki, w, q, k, topk, sm_scale,
                                        block, True)
    # named, the selection and the KL's gradients are all the backward
    # pass needs of the walk: a policy that saves both runs it once
    selection = checkpoint_name(selection, INDEX_MASK)
    grads = tuple(checkpoint_name(g, INDEX_GRADS) for g in grads)
    return (selection, kl, share), grads


def _select_and_kl_bwd(topk, sm_scale, block, grads, cotangents):
    g_kl = cotangents[1]
    # the target is detached: nothing reaches q and k from here
    return tuple((g_kl * g).astype(g.dtype) for g in grads) + (None, None)


_select_and_kl.defvjp(_select_and_kl_fwd, _select_and_kl_bwd)


def select_and_kl(qi, ki, w, q, k, *, topk: int, sm_scale: float,
                  block: int = QUERY_BLOCK):
    """(selection [B, S, S] int8: 1 where query t may see key s, a subset
    of the causal pairs; the indexer's KL loss, a mean over B x S; the
    selected pairs over the causal pairs). Differentiable in qi, ki and w
    through the KL alone; q and k (the main attention's, rotated) are read
    as constants, and the selection carries no gradient."""
    return _select_and_kl(qi, ki, w, jax.lax.stop_gradient(q),
                          jax.lax.stop_gradient(k), int(topk),
                          float(sm_scale), int(block))
