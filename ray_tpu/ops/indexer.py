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

What runs where. `select_and_kl` is the plain form: one jnp walk over blocks
of queries, a whole row of keys each (a row's choice needs the row): the
scores, the row's topk-th largest EXACTLY (`top_k_mask`: a search on the
scores' integer order, 32 counting passes, the set `lax.top_k` would
choose), the selection as one byte a pair ([B, S, S] int8, what
ops/attention.py's `flash_sel_*` kernels read), the target from q and k,
the KL, and, when differentiated, the KL's gradient `softmax_S(I) - p` on
the selected pairs carried into qI, kI and w there and then. It writes and
reads every block's per-head scores ([B, heads, block, S] float32) in HBM:
the `reference` attention path runs it, a sequence of at most topk
positions on the flash path does, and it is the kernels' oracle in the
tests.

The flash path runs the same walk as five Pallas kernels on the tiles at or
under the diagonal, split around the attention call whose log-sum-exp the
target needs: `select` before it (`index_scores`: I a tile, the relu, the
weights and the heads' sum in float32 in the tile's epilogue;
`index_search`: `top_k_mask` on a block of rows' causal prefix held in
VMEM, ties by a second search for the last index that may stay, and the
rows' log-sum-exp of I over the chosen) and `kl` after it (`index_kl`: per
tile the main heads' exp(q_h . k_h * scale - lse_h) summed in VMEM, the KL
a row and its gradient g; `index_grad_q`, `index_grad_k`: g into qI and w
by query tile, into kI by key tile, the index heads' scores computed again
a tile). A tile of per-head scores lives and dies in VMEM; what crosses
HBM between kernels is one value a (query, key) pair and batch row: I, the
selection, g, and the rows' statistics.

Either way the selection and the three gradients carry checkpoint names
(INDEX_MASK, INDEX_GRADS): a remat policy that saves them runs the walk once
a layer and step.
"""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.ops.attention import LANES, NEG_INF, _dot, lane_divisor

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


# ---------------------------------------------------------------------------
# The same walk as five kernels (the flash path). A tile of per-head scores
# lives and dies in VMEM; between kernels crosses what is one value a
# (query, key) pair and batch row: I, the selection, the KL's gradient g.
# ---------------------------------------------------------------------------

_INT_MIN = -(1 << 31)
# chunks of columns a loop step of the search takes (as far as they divide
# a row's)
_UNROLL = 4

# (square tile of the pair-space kernels, rows of it one score tile covers,
# rows a step of the search, columns a pass of the search takes at a time;
# the rows' statistics ride lane-replicated, `chunk` wide)
_Tiles = collections.namedtuple("_Tiles", "block group rows chunk")
_BLOCK, _GROUP = 512, 256


def _tiles(seq: int, block=None) -> _Tiles:
    """The kernels' tiles, from the shape alone; `block` is for tests that
    want several tiles of a short sequence."""
    if block is not None:
        block = min(block, seq)
        if seq % block:
            raise ValueError(f"{seq} positions are not whole tiles of {block}")
        return _Tiles(block, block, block, min(block, LANES))
    if seq > LANES and seq % LANES:
        raise ValueError(f"the indexer's kernels need up to {LANES} positions "
                         f"or a multiple of {LANES}, got {seq}")
    block = lane_divisor(seq, _BLOCK)
    return _Tiles(block, lane_divisor(block, _GROUP), lane_divisor(seq, LANES),
                  min(seq, LANES))


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=64 << 20)


def _groups(block: int, group: int):
    return [slice(r, r + group) for r in range(0, block, group)]


def _fold(x, lanes: int):
    """[rows, n * lanes] -> [rows, lanes]: the lane tiles summed (vector
    adds, no reduction across lanes)."""
    parts = [x[:, c:c + lanes] for c in range(0, x.shape[1], lanes)]
    return functools.reduce(jnp.add, parts)


def _under(i, j):
    """Index of the key tile step j of query tile i holds: past the
    diagonal the diagonal's again, which Pallas does not fetch twice."""
    return jnp.minimum(j, i)


def _scores_kernel(qi_ref, ki_ref, w_ref, out_ref, *, group):
    """Grid (batch, query tile, key tile): I over one tile at or under the
    diagonal. The relu, the weights and the sum over the index heads are
    float32 elementwise, as `index_scores` has them."""
    block = out_ref.shape[1]

    @pl.when(pl.program_id(2) <= pl.program_id(1))
    def _tile():
        ki = ki_ref[0]
        for rows in _groups(block, group):
            total = None
            for h in range(qi_ref.shape[1]):
                s = _dot(qi_ref[0, h, rows, :], ki, (1, 1))
                s = jnp.maximum(s, 0.0) * w_ref[0, rows, h:h + 1]
                total = s if total is None else total + s
            out_ref[0, rows, :] = total


def _search_kernel(scores_ref, sel_ref, lse_ref, count_ref, order_ref, *,
                   topk, chunk, unroll):
    """Grid (batch, block of rows): `top_k_mask` over the rows' causal
    prefix, held in VMEM in the scores' integer order (signed here: the
    unsigned order of `sortable` with the top bit turned, so that int32
    comparisons do), `chunk` columns at a time. Out: the selection's bytes,
    the rows' log-sum-exp of I over the chosen keys and how many were
    chosen, lane-replicated.

    Among the keys equal to the topk-th largest the lower ones are kept by
    a second search, for the last index that may stay, run only when some
    row of the step has more equal keys than it may keep."""
    rows, seq = scores_ref.shape[1:]
    first = pl.program_id(1) * rows
    # loop steps of `unroll` chunks that hold a causal key (the last may
    # reach past the diagonal: what lies there orders below every key)
    held = (first + rows + chunk * unroll - 1) // (chunk * unroll)
    shape = (rows, chunk)
    row = first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    def at(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def over_row(x, reduce):
        return jnp.broadcast_to(reduce(x, axis=1, keepdims=True), shape)

    def chunks(first_step, last_step, visit, carry):
        """carry = visit(chunk's number, carry) over the steps' chunks."""
        def step(i, carry):
            for u in range(unroll):
                carry = visit(i * unroll + u, carry)
            return carry
        return jax.lax.fori_loop(first_step, last_step, step, carry)

    def counted(test):
        """How many held entries of each row pass `test(order, index)`."""
        def visit(c, acc):
            return acc + test(order_ref[:, at(c)],
                              lane + c * chunk).astype(jnp.int32)
        return over_row(chunks(0, held, visit, jnp.zeros(shape, jnp.int32)),
                        jnp.sum)

    def build(c, top):
        x = scores_ref[0, :, at(c)]
        valid = lane + c * chunk <= row
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        order_ref[:, at(c)] = jnp.where(
            valid, jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits),
            jnp.int32(_INT_MIN))
        return jnp.maximum(top, jnp.where(valid, x, NEG_INF))
    top = over_row(chunks(0, held, build,
                          jnp.full(shape, NEG_INF, jnp.float32)), jnp.max)

    def bit(i, kth):
        candidate = kth | jax.lax.shift_right_logical(jnp.int32(_INT_MIN), i)
        turned = candidate ^ jnp.int32(_INT_MIN)
        enough = counted(lambda order, _: order >= turned) >= topk
        return jnp.where(enough, candidate, kth)
    # 0 where the row has fewer than topk causal keys: it keeps them all
    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(shape, jnp.int32))
    turned = kth ^ jnp.int32(_INT_MIN)
    allowed = topk - counted(lambda order, _: order > turned)
    tied = (kth != 0) & (counted(lambda order, _: order == turned)
                         != allowed)

    def lowest_first():
        """The last index an equal key may have."""
        bits = max(seq - 1, 1).bit_length()

        def index_bit(i, last):
            candidate = last | jax.lax.shift_left(jnp.int32(1), bits - 1 - i)
            before = counted(lambda order, index:
                             (order == turned) & (index < candidate))
            return jnp.where(before < allowed, candidate, last)
        return jax.lax.fori_loop(0, bits, index_bit,
                                 jnp.zeros(shape, jnp.int32))
    # (any index where no row of the step is tied)
    last = jax.lax.cond(jnp.max(tied.astype(jnp.float32)) > 0, lowest_first,
                        lambda: jnp.full(shape, seq, jnp.int32))

    def emit(c, carry):
        total, count = carry
        order, index = order_ref[:, at(c)], lane + c * chunk
        chosen = ((order > turned) | ((order == turned) & (index <= last))
                  ) & (index <= row)
        sel_ref[0, :, at(c)] = chosen.astype(jnp.int8)
        return (total + jnp.where(chosen,
                                  jnp.exp(scores_ref[0, :, at(c)] - top), 0.0),
                count + chosen.astype(jnp.float32))
    total, count = chunks(0, held, emit,
                          (jnp.zeros(shape, jnp.float32),) * 2)

    def nothing(c, _):
        sel_ref[0, :, at(c)] = jnp.zeros(shape, jnp.int8)
        return 0
    chunks(held, seq // (chunk * unroll), nothing, 0)
    lse_ref[0] = top + jnp.log(over_row(total, jnp.sum))
    count_ref[0] = over_row(count, jnp.sum)


def _kl_kernel(q_ref, k_ref, lse_ref, scores_ref, sel_ref, row_ref, g_ref,
               kl_ref, acc_ref, *, sm_scale, group, tokens):
    """Grid (batch, query tile, key tile): the target p = mean_h exp(q_h .
    k_h * scale - lse_h) on the selected pairs of one tile (lse_h the main
    attention's log-sum-exp over the query's selected keys, as the flash
    kernel under the selection left it; q scaled as that kernel scales it),
    the heads' sum kept in VMEM; then the KL's terms, summed a row, and its
    gradient g = (softmax_S(I) - p) / tokens."""
    block, lanes = kl_ref.shape[1:]
    heads, rep = q_ref.shape[1], q_ref.shape[1] // k_ref.shape[1]
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= i)
    def _tile():
        for rows in _groups(block, group):
            total = None
            for h in range(heads):
                s = _dot(q_ref[0, h, rows, :] * sm_scale, k_ref[0, h // rep],
                         (1, 1))
                # (an unselected pair may overflow: it is dropped below)
                e = jnp.exp(s - lse_ref[0, rows, h:h + 1])
                total = e if total is None else total + e
            keep = sel_ref[0, rows, :].astype(jnp.int32) != 0
            p = jnp.where(keep, total * (1.0 / heads), 0.0)
            log_r = scores_ref[0, rows, :] - row_ref[0, rows, :1]
            r = jnp.where(keep, jnp.exp(log_r), 0.0)
            g_ref[0, rows, :] = (r - p) * (1.0 / tokens)
            seen = p > 0.0
            terms = jnp.where(
                seen, p * (jnp.log(jnp.where(seen, p, 1.0)) - log_r), 0.0)
            acc_ref[rows, :] += _fold(terms, lanes)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        kl_ref[0] = acc_ref[...]


def _grad_q_kernel(qi_ref, ki_ref, w_ref, g_ref, dqi_ref, dw_ref, acc_ref,
                   dw_acc_ref, *, group):
    """Grid (batch, query tile, key tile): d qI and d w of one query tile
    from g, the index heads' scores before the relu computed again a tile.
    d w leaves as its lane tiles' partial sums (the caller adds them)."""
    block, lanes = dw_ref.shape[2:]
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        dw_acc_ref[...] = jnp.zeros_like(dw_acc_ref)

    @pl.when(j <= i)
    def _tile():
        ki = ki_ref[0]
        for rows in _groups(block, group):
            g = g_ref[0, rows, :]
            for h in range(qi_ref.shape[1]):
                s = _dot(qi_ref[0, h, rows, :], ki, (1, 1))
                d_s = jnp.where(s > 0.0, g * w_ref[0, rows, h:h + 1], 0.0)
                acc_ref[h, rows, :] += _dot(d_s.astype(ki.dtype), ki, (1, 0))
                dw_acc_ref[h, rows, :] += _fold(g * jnp.maximum(s, 0.0),
                                                lanes)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dqi_ref[0] = acc_ref[...].astype(dqi_ref.dtype)
        dw_ref[0] = dw_acc_ref[...]


def _grad_k_kernel(ki_ref, qi_ref, wt_ref, g_ref, dki_ref, acc_ref, *,
                   group):
    """Grid (batch, key tile, query tile): d kI of one key tile, on
    transposed tiles [keys, queries] (g's tile turned once, the weights a
    row as they lie in w transposed), over the query tiles at or after the
    key tile's own."""
    block = dki_ref.shape[1]
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j >= i)
    def _tile():
        ki = ki_ref[0]
        for cols in _groups(block, group):
            gt = g_ref[0, cols, :].T                    # [keys, queries]
            for h in range(qi_ref.shape[1]):
                qh = qi_ref[0, h, cols, :]
                st = _dot(ki, qh, (1, 1))
                d_st = jnp.where(st > 0.0, gt * wt_ref[0, h:h + 1, cols], 0.0)
                acc_ref[...] += _dot(d_st.astype(qh.dtype), qh, (1, 0))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dki_ref[0] = acc_ref[...].astype(dki_ref.dtype)


def _interpret(interpret):
    return attention._default_interpret() if interpret is None else interpret


def _scores(qi, ki, w, t: _Tiles, interpret):
    """I [B, S, S] float32, written at and under the diagonal's tiles."""
    batch, heads, seq, dim = qi.shape
    n = seq // t.block
    return pl.pallas_call(
        functools.partial(_scores_kernel, group=t.group),
        grid=(batch, n, n),
        in_specs=[
            pl.BlockSpec((1, heads, t.block, dim),
                         lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, t.block, dim),
                         lambda b, i, j: (b, _under(i, j), 0)),
            pl.BlockSpec((1, t.block, heads), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, t.block, t.block),
                               lambda b, i, j: (b, i, _under(i, j))),
        out_shape=jax.ShapeDtypeStruct((batch, seq, seq), jnp.float32),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="index_scores",
    )(qi, ki, w.astype(jnp.float32))


def _search(scores, topk: int, t: _Tiles, interpret):
    """scores [B, S, S] float32 (what lies past a row's diagonal is not
    read) -> (selection [B, S, S] int8, the rows' log-sum-exp of the scores
    over the chosen keys and how many were chosen, [B, S, chunk] each)."""
    batch, seq, _ = scores.shape

    def rows(width):
        return pl.BlockSpec((1, t.rows, width), lambda b, i: (b, i, 0))
    return pl.pallas_call(
        functools.partial(_search_kernel, topk=topk, chunk=t.chunk,
                          unroll=math.gcd(seq // t.chunk, _UNROLL)),
        grid=(batch, seq // t.rows),
        in_specs=[rows(seq)],
        out_specs=[rows(seq), rows(t.chunk), rows(t.chunk)],
        out_shape=[jax.ShapeDtypeStruct((batch, seq, seq), jnp.int8),
                   jax.ShapeDtypeStruct((batch, seq, t.chunk), jnp.float32),
                   jax.ShapeDtypeStruct((batch, seq, t.chunk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t.rows, seq), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
        name="index_search",
    )(scores)


def select(qi, ki, w, *, topk: int, block=None, interpret=None):
    """The first half of `select_and_kl` as kernels `index_scores` and
    `index_search`: qi [B, Hi, S, Di], ki [B, S, Di], w [B, S, Hi] float32
    (read as constants) -> (selection [B, S, S] int8, named INDEX_MASK;
    what `kl` needs of the indexer's side: I [B, S, S] float32 and the
    rows' log-sum-exp of I over the chosen keys [B, S, lanes],
    lane-replicated; selected pairs over causal pairs)."""
    qi, ki, w = (jax.lax.stop_gradient(x) for x in (qi, ki, w))
    batch, _, seq, _ = qi.shape
    t = _tiles(seq, block)
    interpret = _interpret(interpret)
    scores = _scores(qi, ki, w, t, interpret)
    selection, row_lse, counts = _search(scores, int(topk), t, interpret)
    share = jnp.sum(counts[:, :, 0]) / (batch * seq * (seq + 1) / 2.0)
    return checkpoint_name(selection, INDEX_MASK), (scores, row_lse), share


def _kl_and_grads(qi, ki, w, q, k, lse, selection, scores, row_lse,
                  sm_scale, block, interpret, with_grads):
    batch, heads, seq, dim = qi.shape
    t = _tiles(seq, block)
    n = seq // t.block
    lanes = t.chunk
    tokens = batch * seq
    semantics = _params("parallel", "parallel", "arbitrary")

    def rows(*shape):       # a block of the query tile's rows
        return pl.BlockSpec((1,) + shape, lambda b, i, j: (b, i, 0))

    def heads_rows(count, width):
        return pl.BlockSpec((1, count, t.block, width),
                            lambda b, i, j: (b, 0, i, 0))
    pair = pl.BlockSpec((1, t.block, t.block),
                        lambda b, i, j: (b, i, _under(i, j)))
    g, kl_rows = pl.pallas_call(
        functools.partial(_kl_kernel, sm_scale=sm_scale, group=t.group,
                          tokens=tokens),
        grid=(batch, n, n),
        in_specs=[
            heads_rows(q.shape[1], q.shape[3]),
            pl.BlockSpec((1, k.shape[1], t.block, k.shape[3]),
                         lambda b, i, j: (b, 0, _under(i, j), 0)),
            rows(t.block, q.shape[1]), pair, pair,
            rows(t.block, row_lse.shape[2]),
        ],
        out_specs=[pair, rows(t.block, lanes)],
        out_shape=[jax.ShapeDtypeStruct((batch, seq, seq), jnp.float32),
                   jax.ShapeDtypeStruct((batch, seq, lanes), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t.block, lanes), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
        name="index_kl",
    )(q, k, lse, scores, selection, row_lse)
    kl = jnp.sum(kl_rows) / tokens
    if not with_grads:
        return kl, None
    w = w.astype(jnp.float32)
    d_qi, d_w = pl.pallas_call(
        functools.partial(_grad_q_kernel, group=t.group),
        grid=(batch, n, n),
        in_specs=[
            heads_rows(heads, dim),
            pl.BlockSpec((1, t.block, dim),
                         lambda b, i, j: (b, _under(i, j), 0)),
            rows(t.block, heads), pair,
        ],
        out_specs=[heads_rows(heads, dim), heads_rows(heads, lanes)],
        out_shape=[jax.ShapeDtypeStruct(qi.shape, qi.dtype),
                   jax.ShapeDtypeStruct((batch, heads, seq, lanes),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, t.block, dim), jnp.float32),
                        pltpu.VMEM((heads, t.block, lanes), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
        name="index_grad_q",
    )(qi, ki, w, g)

    def over(i, j):         # the query tile step j of key tile i holds
        return jnp.maximum(j, i)
    d_ki = pl.pallas_call(
        functools.partial(_grad_k_kernel, group=t.group),
        grid=(batch, n, n),
        in_specs=[
            pl.BlockSpec((1, t.block, dim), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, heads, t.block, dim),
                         lambda b, i, j: (b, 0, over(i, j), 0)),
            pl.BlockSpec((1, heads, t.block),
                         lambda b, i, j: (b, 0, over(i, j))),
            pl.BlockSpec((1, t.block, t.block),
                         lambda b, i, j: (b, over(i, j), i)),
        ],
        out_specs=pl.BlockSpec((1, t.block, dim), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(ki.shape, ki.dtype),
        scratch_shapes=[pltpu.VMEM((t.block, dim), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
        name="index_grad_k",
    )(ki, qi, jnp.swapaxes(w, 1, 2), g)
    d_w = jnp.swapaxes(jnp.sum(d_w, axis=-1), 1, 2)
    return kl, (d_qi, d_ki, d_w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _kl(qi, ki, w, q, k, lse, selection, scores, row_lse, sm_scale, block,
        interpret):
    return _kl_and_grads(qi, ki, w, q, k, lse, selection, scores, row_lse,
                         sm_scale, block, interpret, False)[0]


def _kl_fwd(qi, ki, w, q, k, lse, selection, scores, row_lse, sm_scale,
            block, interpret):
    kl, grads = _kl_and_grads(qi, ki, w, q, k, lse, selection, scores,
                              row_lse, sm_scale, block, interpret, True)
    # as the walk's: named, they are all the backward pass needs
    return kl, tuple(checkpoint_name(g, INDEX_GRADS) for g in grads)


def _kl_bwd(sm_scale, block, interpret, grads, g_kl):
    return tuple((g_kl * g).astype(g.dtype) for g in grads) + (None,) * 6


_kl.defvjp(_kl_fwd, _kl_bwd)


def kl(qi, ki, w, q, k, lse, selection, kept, *, sm_scale: float, block=None,
       interpret=None):
    """The second half of `select_and_kl` as kernels `index_kl`,
    `index_grad_q` and `index_grad_k`: the indexer's KL (a mean over B x S)
    from `select`'s selection and `kept`, q [B, H, S, D], k [B, Hkv, S, D]
    and lse [B, H, S] float32, each main head's log-sum-exp over the
    query's selected keys (what the flash kernel under the selection
    leaves: the target's softmax is that kernel's). Differentiable in qi,
    ki and w alone, as `select_and_kl` is."""
    scores, row_lse = kept
    return _kl(qi, ki, w, jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
               jnp.swapaxes(jax.lax.stop_gradient(lse), 1, 2), selection,
               scores, row_lse, float(sm_scale), block, _interpret(interpret))
