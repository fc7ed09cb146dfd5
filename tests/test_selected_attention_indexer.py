"""The indexer of attention under a learned selection (ops/indexer.py): the
topk-th largest with its tie rule, the KL and its gradient, the search
kernel and the five kernels of the walk against the jnp walk and plain
autodiff, on the CPU (the kernels in interpret mode). The kernels under the
selection it makes: tests/test_selected_attention.py."""

import math

import numpy as np
import pytest

from test_selected_attention import _selections  # noqa: F401


# ---------------------------------------------------------------------------
# (b) the indexer: the topk-th largest, the KL and its gradient
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 8, 63, 64, 100])
@pytest.mark.parametrize("quantum", [0.0, 0.25, 4.0],
                         ids=["no_ties", "some_ties", "mostly_ties"])
def test_top_k_mask_is_lax_top_k_ties_included(jax_cpu, k, quantum):
    """The search on the scores' integer order keeps the set jax.lax.top_k
    chooses among the causal keys: negative scores, zeros, equal scores
    (the lower key first), rows with fewer than k keys."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.indexer import top_k_mask
    n = 64
    scores = jax.random.normal(jax.random.PRNGKey(k), (3, n, n))
    if quantum:
        scores = jnp.round(scores / quantum) * quantum
    valid = jnp.tril(jnp.ones((n, n), bool))[None]
    got = top_k_mask(scores, k, valid)
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), min(k, n))
    want = jnp.zeros(scores.shape, bool).at[
        jnp.arange(3)[:, None, None], jnp.arange(n)[None, :, None],
        idx].set(True) & valid
    assert bool(jnp.all(got == want))
    counts = np.asarray(got.sum(-1))
    assert (counts == np.minimum(np.arange(1, n + 1), k)[None]).all()


def test_sortable_keeps_the_order_of_float32(jax_cpu):
    import jax.numpy as jnp
    from ray_tpu.ops.indexer import sortable
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, jnp.inf])
    keys = np.asarray(sortable(x))
    assert (np.diff(keys.astype(np.int64)) > 0).all() and keys[0] > 0


def _walk_operands(jax, dtype):
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    b, s = 2, 128
    qi = jax.random.normal(keys[0], (b, 4, s, 16), dtype)
    ki = jax.random.normal(keys[1], (b, s, 16), dtype)
    w = 0.1 * jax.random.normal(keys[2], (b, s, 4), jnp.float32)
    q = jax.random.normal(keys[3], (b, 4, s, 32), dtype)
    k = jax.random.normal(keys[4], (b, 2, s, 32), dtype)
    return qi, ki, w, q, k


def _plain_kl(jax, qi, ki, w, q, k, topk, sm_scale):
    """The indexer's loss written out: whole [S, S] tensors, jax.lax.top_k,
    every query head's key/value head repeated."""
    import jax.numpy as jnp
    s = qi.shape[2]
    scores = jnp.einsum("bqh,bhqk->bqk", w, jax.nn.relu(
        jnp.einsum("bhqd,bkd->bhqk", qi, ki)))
    causal = jnp.tril(jnp.ones((s, s), bool))
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, s))
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(qi.shape[0])[:, None, None], jnp.arange(s)[None, :, None],
        idx].set(True) & causal
    kk = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * sm_scale
    p = jax.lax.stop_gradient(jnp.mean(jax.nn.softmax(
        jnp.where(chosen[:, None], logits, -jnp.inf), -1), axis=1))
    log_r = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), -1)
    kl = jnp.where(chosen & (p > 0), p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                          - jnp.where(chosen, log_r, 0.0)), 0)
    return jnp.mean(jnp.sum(kl, -1)), chosen


@pytest.mark.parametrize("topk,block", [(24, 32), (128, 128), (200, 64)],
                         ids=["selects", "topk_is_the_sequence", "over_it"])
def test_the_kl_and_its_gradient_are_autodiffs_of_the_plain_form(
        jax_cpu, topk, block):
    """select_and_kl's selection, loss and hand-written gradient (softmax_S
    (I) - p on the selected pairs, into qI, kI and w) against jax.grad of
    the plain form; q and k are constants of it."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import indexer
    qi, ki, w, q, k = _walk_operands(jax, jnp.float32)
    scale = 1.0 / math.sqrt(32)

    def mine(qi, ki, w, q, k):
        selected, kl, share = indexer.select_and_kl(
            qi, ki, w, q, k, topk=topk, sm_scale=scale, block=block)
        return kl, (selected, share)
    with jax.default_matmul_precision("highest"):
        (kl, (selected, share)), grads = jax.value_and_grad(
            mine, (0, 1, 2, 3, 4), has_aux=True)(qi, ki, w, q, k)
        (want, chosen), want_grads = jax.value_and_grad(
            lambda *a: _plain_kl(jax, *a, q, k, topk, scale), (0, 1, 2),
            has_aux=True)(qi, ki, w)
    assert selected.dtype == jnp.int8
    assert bool(jnp.all((selected != 0) == chosen))
    np.testing.assert_allclose(kl, want, rtol=2e-6)
    pairs = sum(min(t + 1, topk) for t in range(128))
    np.testing.assert_allclose(share, pairs / (128 * 129 / 2), rtol=1e-6)
    for got, ref in zip(grads[:3], want_grads):
        assert float(jnp.abs(ref).max()) > 1e-5
        np.testing.assert_allclose(got, ref, atol=2e-8)
    assert not np.any(grads[3]) and not np.any(grads[4])


@pytest.mark.parametrize("k", [1, 8, 63, 64, 100])
@pytest.mark.parametrize("quantum", [0.0, 0.25, 4.0],
                         ids=["no_ties", "some_ties", "mostly_ties"])
def test_the_search_kernel_is_top_k_mask_byte_for_byte(jax_cpu, k, quantum):
    """`index_search` on given scores against `top_k_mask` over the causal
    keys: ties at the topk-th largest (the lower key stays: the second
    search, for the last index that may), rows with fewer than k causal
    keys, four blocks of rows and two passes' columns a row; and the rows'
    log-sum-exp and count over the chosen."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import indexer
    n = 64
    scores = jax.random.normal(jax.random.PRNGKey(k), (3, n, n))
    if quantum:
        scores = jnp.round(scores / quantum) * quantum
    valid = jnp.tril(jnp.ones((n, n), bool))[None]
    want = indexer.top_k_mask(scores, k, valid)
    tiles = indexer._Tiles(block=32, group=32, rows=16, chunk=32)
    # what lies past the diagonal is never read: NaN there
    got, lse, count = indexer._search(jnp.where(valid, scores, jnp.nan), k,
                                      tiles, True)
    assert got.dtype == jnp.int8 and bool(jnp.all((got != 0) == want))
    np.testing.assert_array_equal(count[:, :, 0], want.sum(-1))
    np.testing.assert_allclose(
        lse[:, :, 0], jax.scipy.special.logsumexp(
            jnp.where(want, scores, -jnp.inf), axis=-1), rtol=1e-6)
    assert bool(jnp.all(lse == lse[:, :, :1]))


def _whole_numbers(operands):
    """The indexer's operands as small whole numbers (w in eighths): every
    product and sum of the scores is exact in float32 in any order, and
    scores tie at the threshold."""
    import jax.numpy as jnp
    qi, ki, w, q, k = operands
    return (jnp.round(2 * qi), jnp.round(2 * ki), jnp.round(40 * w) / 8, q, k)


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (2, 2)],
                         ids=["grouped", "a_head_each"])
@pytest.mark.parametrize("whole", [False, True],
                         ids=["drawn", "whole_numbers"])
@pytest.mark.parametrize("topk,block", [(24, 32), (128, 128), (200, 64)],
                         ids=["selects", "topk_is_the_sequence", "over_it"])
def test_the_kernels_are_the_walk(jax_cpu, topk, block, whole, heads,
                                  kv_heads):
    """`select`, the flash kernel under the selection and `kl` (the five
    `index_*` kernels, interpreted) against `select_and_kl`'s jnp walk: the
    selection byte for byte, the KL, the selected share and the three
    gradients to float32 tolerance; q and k get none."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import indexer
    from ray_tpu.ops.attention import flash_attention
    qi, ki, w, q, k = _walk_operands(jax, jnp.float32)
    q, k = q[:, :heads], k[:, :kv_heads]
    if whole:
        qi, ki, w, q, k = _whole_numbers((qi, ki, w, q, k))
    scale = 1.0 / math.sqrt(32)

    def walk(qi, ki, w, q, k):
        selected, kl, share = indexer.select_and_kl(
            qi, ki, w, q, k, topk=topk, sm_scale=scale, block=block)
        return kl, (selected, share)

    def kernels(qi, ki, w, q, k):
        selected, kept, share = indexer.select(qi, ki, w, topk=topk,
                                               block=block)
        _, lse = flash_attention(q, k, k, causal=True, sm_scale=scale,
                                 selected=selected, with_lse=True,
                                 block_q=block, block_k=block)
        kl = indexer.kl(qi, ki, w, q, k, lse, selected, kept, sm_scale=scale,
                        block=block)
        return kl, (selected, share)
    with jax.default_matmul_precision("highest"):
        (want, (chosen, want_share)), want_grads = jax.value_and_grad(
            walk, (0, 1, 2, 3, 4), has_aux=True)(qi, ki, w, q, k)
        (kl, (selected, share)), grads = jax.value_and_grad(
            kernels, (0, 1, 2, 3, 4), has_aux=True)(qi, ki, w, q, k)
    assert selected.dtype == jnp.int8
    np.testing.assert_array_equal(selected, chosen)
    if whole:
        # some row's topk-th largest score is shared beyond what it keeps
        scores = indexer.index_scores(qi, ki, w)[0]
        kth = jnp.min(jnp.where(chosen != 0, scores, jnp.inf), -1)
        spare = jnp.tril(scores == kth[..., None]) & (chosen == 0)
        assert topk >= 128 or bool(spare.any())
    np.testing.assert_allclose(kl, want, rtol=3e-6)
    np.testing.assert_allclose(share, want_share, rtol=1e-6)
    for got, ref in zip(grads[:3], want_grads):
        assert float(jnp.abs(ref).max()) > 1e-5
        np.testing.assert_allclose(got, ref, rtol=1e-4,
                                   atol=2e-6 * float(jnp.abs(ref).max()))
    assert not np.any(grads[3]) and not np.any(grads[4])


@pytest.mark.parametrize("seq,tiles", [
    (8192, (512, 256, 128, 128)), (1024, (512, 256, 128, 128)),
    (384, (384, 128, 128, 128)), (128, (128, 128, 128, 128)),
    (64, (64, 64, 64, 64))], ids=lambda v: str(v) if isinstance(v, int) else "")
def test_the_walks_tiles_follow_from_the_shape(jax_cpu, seq, tiles):
    """(square tile of the pair-space kernels, rows of it a score tile
    covers, rows a step of the search, columns a pass takes at a time):
    whole lane tiles that divide the sequence, one tile below 128
    positions; a ragged sequence is refused by name, and a test's `block`
    tiles a short sequence by exactly that."""
    from ray_tpu.ops import indexer
    assert tuple(indexer._tiles(seq)) == tiles
    assert tuple(indexer._tiles(seq, 32)) == (32, 32, 32, 32)
    with pytest.raises(ValueError, match="multiple of 128"):
        indexer._tiles(seq + 200)
    with pytest.raises(ValueError, match="whole tiles"):
        indexer._tiles(seq, seq - 8)


def test_the_selected_call_hands_out_the_lse_its_backward_reads(jax_cpu):
    """flash_attention(selected=, with_lse=True): the lse [B, H, S] beside
    the output is the forward kernel's own (the backward's residual, named
    FLASH_LSE), each head's log-sum-exp over the query's selected keys; it
    carries no gradient and the output's gradients do not move."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import attention
    seq, dim = 256, 16
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (2, 4, seq, dim))
    k, v = (jax.random.normal(key, (2, 2, seq, dim)) for key in keys[1:])
    selected = _selections(jax, 2, seq)["a_random_set"]
    out, lse = attention.flash_attention(q, k, v, causal=True,
                                         selected=selected, with_lse=True)
    assert lse.shape == (2, 4, seq) and lse.dtype == jnp.float32
    blocks = attention._block_sizes(seq, seq, dim, dim)
    scale = 1.0 / math.sqrt(dim)
    _, vjp = jax.vjp(attention._make_flash_sel_fn(scale, blocks, True),
                     q, k, v, selected)
    residual = [x for x in jax.tree_util.tree_leaves(vjp)
                if getattr(x, "shape", None) == (2 * 4, 1, seq)]
    assert len(residual) == 1
    np.testing.assert_array_equal(lse, residual[0].reshape(2, 4, seq))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1))
    want = jax.scipy.special.logsumexp(
        jnp.where(selected[:, None] != 0, logits * scale, -jnp.inf), axis=-1)
    np.testing.assert_allclose(lse, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(out, attention.flash_attention(
        q, k, v, causal=True, selected=selected))

    def both(q, k, v):
        out, lse = attention.flash_attention(q, k, v, causal=True,
                                             selected=selected, with_lse=True)
        return jnp.sum(out * out) + jnp.sum(lse)
    grads = jax.grad(both, (0, 1, 2))(q, k, v)
    alone = jax.grad(lambda q, k, v: jnp.sum(attention.flash_attention(
        q, k, v, causal=True, selected=selected) ** 2), (0, 1, 2))(q, k, v)
    for got, ref in zip(grads, alone):
        np.testing.assert_array_equal(got, ref)
    with pytest.raises(ValueError, match="with_lse"):
        attention.flash_attention(q, k, v, causal=True, with_lse=True)
