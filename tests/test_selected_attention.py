"""Attention under a learned selection: the three `flash_sel_*` kernels
(ops/attention.py), the indexer's scores, exact top-k and KL
(ops/indexer.py), and a stack whose attention layers carry an indexer
(models/gpt.py) against the plain float32 reference of
benchmark/families/keye.py, at a small size on the CPU: seeded random
weights, the kernels in interpret mode."""

import copy
import json
import math
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    """benchmark/rehearsal/configs/tiny-keye.json: three layers, 4 query
    heads of 32 on 2 key/value heads with a norm a head, an indexer of 4
    heads of 16 on one key head that keeps 32 keys a query, experts 4..7 of
    16 held, 2 a token, renormalised."""
    return _read("benchmark", "rehearsal", "configs", "tiny-keye.json")


# ---------------------------------------------------------------------------
# (a) the three kernels under a selection
# ---------------------------------------------------------------------------

def _selections(jax, batch, seq):
    """name -> [batch, seq, seq] int8, each a subset of the causal pairs
    with at least one key a query."""
    import jax.numpy as jnp
    at = jnp.arange(seq)
    causal = at[:, None] >= at[None, :]
    band = causal & (at[:, None] - at[None, :] < 40)
    drawn = jax.random.uniform(jax.random.PRNGKey(seq), (batch, seq, seq))
    own = jnp.eye(seq, dtype=bool)
    random = ((drawn < 0.3) | own) & causal
    # no pair at all in the tile of queries 128.. and keys 0..127
    empty = random.at[:, 128:, :128].set(False)
    # a query that does NOT see itself: its latest key is two before it
    away = (((drawn < 0.2) & (at[:, None] - at[None, :] >= 2))
            | (at[None, :] == jnp.maximum(at[:, None] - 2, 0))) & causal
    every = jnp.broadcast_to(causal, (batch, seq, seq))
    return {name: jnp.broadcast_to(s, (batch, seq, seq)).astype(jnp.int8)
            for name, s in (("every_key", every), ("a_band", band),
                            ("a_random_set", random),
                            ("an_empty_tile", empty),
                            ("not_itself", away))}


@pytest.mark.parametrize("name", ["every_key", "a_band", "a_random_set",
                                  "an_empty_tile", "not_itself"])
@pytest.mark.parametrize("heads,kv_heads,block", [(4, 2, 128), (2, 2, 64)],
                         ids=["grouped", "a_head_each"])
def test_selected_kernels_match_the_reference(jax_cpu, name, heads, kv_heads,
                                              block):
    """flash_sel_fwd, flash_sel_bwd_dq and flash_sel_bwd_dkv against
    mha_reference(selected=): the output and all three gradients, dK and dV
    at the key/value heads' count, two blocks of 128 (four of 64) a row."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference
    seq, dim = 256, 16
    keys = jax.random.split(jax.random.PRNGKey(heads), 4)
    q, g = (jax.random.normal(k, (2, heads, seq, dim)) for k in keys[:2])
    k, v = (jax.random.normal(k, (2, kv_heads, seq, dim)) for k in keys[2:])
    selected = _selections(jax, 2, seq)[name]

    def flash(q, k, v):
        return flash_attention(q, k, v, selected=selected, block_q=block,
                               block_k=block)

    def oracle(q, k, v):
        return mha_reference(q, k, v, selected=selected)
    np.testing.assert_allclose(flash(q, k, v), oracle(q, k, v), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(oracle(*a) * g), (0, 1, 2))(q, k, v)
    assert got[1].shape == got[2].shape == (2, kv_heads, seq, dim)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)
    if name == "every_key":
        np.testing.assert_allclose(
            flash(q, k, v), flash_attention(q, k, v, block_q=block,
                                            block_k=block), atol=2e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_selected_kernels_write_heads_of_128_tokens_first(jax_cpu, dtype):
    """flash_sel_* at heads of 128 (6 on 2, two blocks a row, a random set
    with an empty tile): o leaves and dO arrives as [B, S, H * 128], and the
    lse handed out beside it stays [B, H, S]."""
    jax = jax_cpu
    import jax.numpy as jnp
    from helpers.flash_layout import check_tokens_first, operands
    from ray_tpu.ops.attention import (flash_attention,
                                       flash_attention_native)
    selected = _selections(jax, 2, 256)["an_empty_tile"]
    check_tokens_first(jax, jnp.dtype(dtype).type, selected=selected)
    q, k, v, _ = operands(jax, jnp.dtype(dtype).type, 6, 2, 256, 128, 128)
    out, lse = flash_attention_native(q, k, v, selected=selected,
                                      with_lse=True)
    assert out.shape == (2, 256, 6 * 128) and lse.shape == (2, 6, 256)
    turned, same = flash_attention(q, k, v, selected=selected, with_lse=True)
    np.testing.assert_array_equal(
        turned, out.reshape(2, 256, 6, 128).transpose(0, 2, 1, 3))
    np.testing.assert_array_equal(same, lse)


def test_the_rule_blocks_and_unequal_blocks_run_the_selection(jax_cpu):
    """The shape's own blocks (one of 512) and a test's unequal ones."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference
    seq = 512
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (1, 2, seq, 32))
    k, v = (jax.random.normal(x, (1, 1, seq, 32)) for x in keys[1:])
    selected = _selections(jax, 1, seq)["a_random_set"]
    want = mha_reference(q, k, v, selected=selected)
    for blocks in ({}, {"block_q": 128, "block_k": 256}):
        np.testing.assert_allclose(
            flash_attention(q, k, v, selected=selected, **blocks), want,
            atol=2e-6)


def test_kernel_names_under_a_selection_and_without(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.util import profiling
    q = jnp.zeros((1, 2, 128, 16))
    selected = jnp.ones((1, 128, 128), jnp.int8)

    def names(**kw):
        text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
            flash_attention(q, q, q, **kw))))(q))
        return set(re.findall(r"name=(flash_\w+)", text)) - {
            "flash_out", "flash_lse"}
    assert names(selected=selected) == {
        "flash_sel_fwd", "flash_sel_bwd_dq", "flash_sel_bwd_dkv"}
    assert names() == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert {"flash_sel_fwd", "flash_sel_bwd_dq", "flash_sel_bwd_dkv"} \
        <= set(profiling.KERNELS)
    assert "attn_index" in profiling.REGIONS


@pytest.mark.parametrize("kwargs,says", [
    ({"causal": False}, "selected needs causal"),
    ({"window": 8}, "selected needs causal"),
    ({"selected_shape": (1, 128, 64)}, "a selection \\[B, S, S\\]"),
], ids=["not_causal", "window", "shape"])
def test_flash_refuses_a_selection_it_cannot_run(jax_cpu, kwargs, says):
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 2, 128, 16))
    shape = kwargs.pop("selected_shape", (1, 128, 128))
    with pytest.raises(ValueError, match=says):
        flash_attention(q, q, q, selected=jnp.ones(shape, jnp.int8), **kwargs)


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


# ---------------------------------------------------------------------------
# (c) the whole model against the family's reference
# ---------------------------------------------------------------------------

def _program(jax, config, attention, **change):
    import jax.numpy as jnp
    from benchmark.families import keye
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    cfg = GPTConfig(**dict(keye.gpt_config_kwargs(config), **change),
                    dtype=jnp.float32, attention=attention)
    params = gpt_init(jax.random.PRNGKey(3), cfg)
    tokens = jnp.asarray(np.random.default_rng(4).integers(
        0, config["vocab_size"], (2, 129), dtype=np.int32))
    return cfg, params, tokens


@pytest.fixture(scope="module")
def reference(jax_cpu, tiny):
    jax = jax_cpu
    from benchmark.families import keye
    _cfg, params, tokens = _program(jax, tiny, "reference")
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: keye.reference_logits(
            p, t[:, :-1], tiny))(params, tokens)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: keye.reference_loss(p, t, tiny)))(params, tokens)
        parts = jax.jit(lambda p, t: keye.reference_losses(p, t, tiny)[1:])(
            params, tokens)
    return logits, loss, grads, parts


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_logits_loss_and_gradients_match_the_reference(jax_cpu, tiny,
                                                       reference, attention):
    """Grouped queries with a norm a head, the indexer (its LayerNorm, its
    rotation at its own width, its weights), the selection of 32 of up to
    128 keys, its KL in the loss beside the balance loss, experts on a
    share with a renormalised top-2, in float32: logits, the loss and its
    parts, and the whole tree of gradients, the indexer's among them."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_forward, gpt_loss_and_aux
    cfg, params, tokens = _program(jax, tiny, attention)
    assert [sorted(layer) for layer in params["layers"]] == [
        ["attn", "ln1", "ln2", "moe"]] * 3
    attn = params["layers"][0]["attn"]
    assert sorted(attn) == ["index", "k_head_norm", "q_head_norm", "wk", "wo",
                            "wq", "wv"]
    assert attn["wq"].shape == (128, 4 * 32)
    assert attn["wk"].shape == (128, 2 * 32)
    assert {n: x.shape for n, x in attn["index"].items() if n != "k_norm"} \
        == {"wq": (128, 4 * 16), "wk": (128, 16), "ww": (128, 4)}
    assert sorted(attn["index"]["k_norm"]) == ["bias", "scale"]
    assert params["layers"][1]["moe"]["w_up"].shape == (4, 128, 64)
    assert params["layers"][1]["moe"]["router"].shape == (128, 16)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, t: gpt_forward(p, t, cfg))(
            params, tokens[:, :-1])
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p, t: gpt_loss_and_aux(p, {"tokens": t}, cfg),
            has_aux=True))(params, tokens)
    ref_logits, ref_loss, ref_grads, (xent, balance, kl, share) = reference
    np.testing.assert_allclose(logits, ref_logits, atol=5e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    np.testing.assert_allclose(aux["xent"], xent, rtol=1e-6)
    np.testing.assert_allclose(aux["router_balance_loss"], balance, rtol=1e-5)
    # the statistic is the layers' mean, the loss takes their sum
    np.testing.assert_allclose(3 * aux["index_kl"], kl, rtol=1e-5)
    np.testing.assert_allclose(aux["index_selected_share"], share, rtol=1e-6)
    np.testing.assert_allclose(
        loss, aux["xent"] + 0.001 * aux["router_balance_loss"]
        + 3 * aux["index_kl"], rtol=1e-6)
    assert 0.05 < float(aux["index_kl"]) < 2.0
    assert 0.0 < float(aux["expert_slots_held_share"]) < 1.0
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(ref_grads)):
        assert np.any(np.asarray(r)), jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            g, r, atol=2e-5 * max(1.0, float(np.abs(r).max())),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_the_two_losses_reach_disjoint_parameters(jax_cpu, tiny, attention):
    """The cross-entropy's (and the balance loss's) gradient of every
    parameter of the indexer and the KL's gradient of every other
    parameter are exactly zero: one step on the sum is the two separate
    optimisations."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_loss_and_aux
    cfg, params, tokens = _program(jax, tiny, attention)

    def part(which):
        def loss(p):
            total, aux = gpt_loss_and_aux(p, {"tokens": tokens}, cfg)
            return aux["index_kl"] if which == "kl" \
                else total - 3 * aux["index_kl"]
        return jax.jit(jax.grad(loss))(params)
    for which, in_indexer in (("kl", True), ("rest", False)):
        grads = part(which)
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
            name = jax.tree_util.keystr(path)
            if ("index" in name) == in_indexer:
                if "bias" not in name or which == "kl":
                    assert np.any(np.asarray(g)), (which, name)
            else:
                assert not np.any(np.asarray(g)), (which, name)


def test_a_sequence_of_at_most_topk_is_plain_causal_attention(jax_cpu, tiny):
    """Every causal key is selected: the logits are those of the same
    weights without an indexer, the plain kernels run (no flash_sel_*),
    and the KL is still taken."""
    jax = jax_cpu
    import dataclasses
    from ray_tpu.models.gpt import gpt_forward, gpt_loss_and_aux
    cfg, params, tokens = _program(jax, tiny, "flash", index_topk=128)
    plain = dataclasses.replace(cfg, index_topk=0, index_heads=0,
                                index_head_dim=0)
    bare = copy.deepcopy(params)
    for layer in bare["layers"]:
        del layer["attn"]["index"]
    logits, stats = gpt_forward(params, tokens[:, :-1], cfg)
    want, _ = gpt_forward(bare, tokens[:, :-1], plain)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    assert float(stats["index_selected_share"]) == 1.0
    assert float(stats["index_kl"]) > 0.01
    text = str(jax.make_jaxpr(jax.grad(lambda p: gpt_loss_and_aux(
        p, {"tokens": tokens}, cfg)[0]))(params))
    assert "name=flash_fwd" in text and "flash_sel" not in text


def test_the_reference_tells_each_mechanism_apart(jax_cpu, tiny, reference):
    """What `program_check` rests on: the reference with one mechanism
    changed gives other logits. A topk of 31 and of 33 for 32 stand in for
    the cell's 2047 and 2049, which the chip's check cannot tell apart at
    seeded weights; the tie rule is held by test_top_k_mask_is_lax_top_k_
    ties_included and, here, by a reference that keeps the HIGHER key at a
    tie giving another selection on quantised scores."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import keye
    _cfg, params, tokens = _program(jax, tiny, "reference")
    sound = reference[0]

    def sa(**change):
        return dict(tiny, sa_config=dict(tiny["sa_config"], **change))

    def logits_of(config):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, t: keye.reference_logits(
                p, t[:, :-1], config))(params, tokens)
    configs = {"no_selection": sa(topk=10 ** 6), "topk_16": sa(topk=16),
               "topk_64": sa(topk=64), "topk_31": sa(topk=31),
               "topk_33": sa(topk=33),
               "not_renormalised": dict(tiny, norm_topk_prob=False),
               "other_theta": dict(tiny, rope_theta=10000)}
    for name, config in configs.items():
        assert float(jnp.abs(logits_of(config) - sound).max()) > 1e-3, name

    def patched(name, replacement):
        kept = getattr(keye, name)
        setattr(keye, name, replacement)
        try:
            return float(jnp.abs(logits_of(tiny) - sound).max())
        finally:
            setattr(keye, name, kept)
    plain_scores, plain_index = keye.index_scores, keye.reference_index

    def latest(scores, seen, topk):
        at = jnp.arange(scores.shape[1])
        return keye_chosen(-jnp.abs(at[None, :] - 1e4) * 0 + at[None, :]
                           * jnp.ones_like(scores), seen, topk)
    keye_chosen = keye.chosen_keys

    def unrotated(ix, n, config):
        return plain_index(ix, n, dict(config, rope_theta=1e30))

    def unnormed(ix, n, config):
        far = dict(ix, k_norm={"scale": jnp.ones_like(ix["k_norm"]["scale"]),
                               "bias": ix["k_norm"]["bias"]})
        kept, keye._layer_norm = keye._layer_norm, lambda x, w, eps: x
        try:
            return plain_index(far, n, config)
        finally:
            keye._layer_norm = kept
    faults = {
        "latest_keys": ("chosen_keys", latest),
        "no_relu": ("index_scores", lambda qi, ki, w: jnp.einsum(
            "qh,qhk->qk", w, jnp.einsum("qhd,kd->qhk", qi, ki))),
        "no_weights": ("index_scores", lambda qi, ki, w: plain_scores(
            qi, ki, jnp.ones_like(w))),
        "unrotated_indexer": ("reference_index", unrotated),
        "no_key_norm": ("reference_index", unnormed),
        "kv_head_h_mod": ("_kv_head_of", lambda h, kv: jnp.arange(h) % kv),
        "no_head_norm": ("_norm", lambda x, scale, eps: (
            x if scale.shape[0] == tiny["head_dim"]
            else x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale)),
    }
    for name, (attribute, replacement) in faults.items():
        assert patched(attribute, replacement) > 1e-3, name


def test_the_tie_rule_is_the_lower_key(jax_cpu):
    """families/keye.py:chosen_keys against a hand-made row: of four equal
    scores two may stay, and they are the two lowest keys."""
    import jax.numpy as jnp
    from benchmark.families import keye
    from ray_tpu.ops.indexer import top_k_mask
    scores = jnp.asarray([[0.5, 2.0, 0.5, 3.0, 0.5, 0.5, -1.0, 9.0]])
    seen = jnp.asarray([[True] * 7 + [False]])
    want = [[True, True, True, True, False, False, False, False]]
    assert np.asarray(keye.chosen_keys(scores, seen, 4)).tolist() == want
    assert np.asarray(top_k_mask(scores, 4, seen)).tolist() == want


def test_bfloat16_step_passes_the_per_token_check(jax_cpu, tiny):
    """reference_loss with a `program_check` answers the loss where the
    program's own forward (bf16, the indexer's walk, the flash_sel kernels,
    the grouped-matmul kernels) agrees with the reference token by token,
    and nan where a bound is broken."""
    jax = jax_cpu
    from benchmark.families import keye
    _cfg, params, tokens = _program(jax, tiny, "flash")
    checked = dict(tiny, program_check={"logprob_median_tol": 0.08,
                                        "logprob_rms_tol": 0.5})
    with jax.default_matmul_precision("highest"):
        plain = float(jax.jit(lambda p, t: keye.reference_loss(
            p, t, tiny))(params, tokens))
        held = float(jax.jit(lambda p, t: keye.reference_loss(
            p, t, checked))(params, tokens))
        checked["program_check"]["logprob_median_tol"] = 1e-6
        broken = float(jax.jit(lambda p, t: keye.reference_loss(
            p, t, checked))(params, tokens))
    assert held == plain and np.isnan(broken)


# ---------------------------------------------------------------------------
# (d) the share: the parts add up to the whole
# ---------------------------------------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(jax_cpu, tiny):
    """model-configs guide, section 4: a whole layer, attention under the
    indexer's selection and the residual included. Every chip computes
    attention, the indexer and the residual alike, so they count once;
    what the four shares' experts add (each the routed part of its own
    four experts) adds up with them to the uncut reference's layer."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import keye
    from ray_tpu.models.gpt import GPTConfig, Setting, gpt_init, layer_fn
    whole = copy.deepcopy(tiny)
    del whole["share"]
    whole["num_experts"] = 16
    full_cfg = GPTConfig(**keye.gpt_config_kwargs(whole), dtype=jnp.float32,
                         attention="reference", remat_policy="none")
    assert full_cfg.experts_held is None
    layer = gpt_init(jax.random.PRNGKey(7), full_cfg)["layers"][1]
    layer["moe"]["router"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(8), (128, 16))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 128), jnp.float32)

    def reference_layer(h):
        mixed, _kl, _pairs = keye.reference_attention(
            layer["attn"], keye._norm(h, layer["ln1"]["scale"], 1e-6), whole)
        h = h + mixed
        m = keye._norm(h, layer["ln2"]["scale"], 1e-6)
        return h, h + keye.reference_experts(layer["moe"], m, whole)[0]

    with jax.default_matmul_precision("highest"):
        alike, want = jax.vmap(reference_layer)(x)
        parts, held_share, kls = [], 0.0, []
        for rank in range(4):
            cut = dict(tiny, share=dict(tiny["share"], rank=rank))
            cfg = GPTConfig(**keye.gpt_config_kwargs(cut),
                            dtype=jnp.float32, attention="reference",
                            remat_policy="none")
            assert cfg.experts_held == (4 * rank, 4)
            mine = dict(layer, moe=dict(layer["moe"], **{
                name: layer["moe"][name][4 * rank:4 * rank + 4]
                for name in ("w_gate", "w_up", "w_down")}))
            out, stats = layer_fn(cfg, 64, Setting())(x, mine)
            parts.append(out - alike)
            held_share += float(stats["expert_slots_held_share"])
            kls.append(float(stats["index_kl"]))
    np.testing.assert_allclose(alike + sum(parts), want, atol=5e-5)
    assert abs(held_share - 1.0) < 1e-6
    assert len(set(kls)) == 1           # the indexer is every chip's alike
    assert float(jnp.abs(alike + parts[0] - want).max()) > 1e-2


# ---------------------------------------------------------------------------
# (e) arithmetic, rules, refusals, names
# ---------------------------------------------------------------------------

def test_param_count_is_the_published_model_and_the_programs_tree(jax_cpu,
                                                                  tiny):
    jax = jax_cpu
    from benchmark.families import keye
    from ray_tpu.models.gpt import GPTConfig, count_params, gpt_init
    cell = _read("benchmark", "configs", "keye-vl-2.0-30b-a3b.json")
    assert keye.param_count(cell) == 562_290_560        # 562.3M held
    published = dict(cell, **cell["published"])
    del published["share"]
    assert 30.5e9 < keye.param_count(published) < 30.7e9
    # (with the embedding's 0.31B, which a token reads one row of, and the
    # indexers' 0.11B: 3.04B without both)
    assert 3.4e9 < keye.active_param_count(published) < 3.5e9
    for config in (tiny, cell):
        cfg = GPTConfig(**keye.gpt_config_kwargs(config))
        tree = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))
        assert count_params(tree) == keye.param_count(config)


def test_the_family_draws_the_embedding_at_the_configurations_spread(jax_cpu,
                                                                    tiny):
    """`assumed.init`: every leaf is gpt_init's but the embedding's rows,
    which families/keye.py:program.init scales to `embedding_init_std`."""
    jax = jax_cpu
    from benchmark.families import keye
    from ray_tpu.models.gpt import gpt_init
    mine = keye.program(tiny).init(jax.random.PRNGKey(2))
    plain = gpt_init(jax.random.PRNGKey(2), keye._train_config(tiny))
    assert tiny["embedding_init_std"] == 1.0
    assert abs(float(np.std(np.asarray(mine["embed"]["table"]))) - 1.0) < 0.02
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(mine)[0],
                            jax.tree_util.tree_leaves(plain)):
        if "embed" not in jax.tree_util.keystr(path):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("seq,topk", [(64, 8), (64, 1), (32, 32), (16, 40)])
def test_selected_pairs_is_a_brute_force_count(jax_cpu, seq, topk):
    """benchmark/kernels/selected_attention.py counts the pairs a selection
    keeps, whichever keys they are: against the selection the indexer's
    search makes from random scores."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import keye
    from benchmark.kernels import selected_attention
    from ray_tpu.ops.indexer import top_k_mask
    scores = jax.random.normal(jax.random.PRNGKey(seq), (seq, seq))
    chosen = top_k_mask(scores, topk, jnp.tril(jnp.ones((seq, seq), bool)))
    assert selected_attention.selected_pairs(seq, topk) == int(chosen.sum()) \
        == keye.selected_pairs(seq, topk) \
        == sum(min(t + 1, topk) for t in range(seq))


def test_flops_and_kernel_arithmetic_count_what_is_computed_here():
    from benchmark.families import keye
    from benchmark.kernels import gqa_attention, selected_attention
    cell = _read("benchmark", "configs", "keye-vl-2.0-30b-a3b.json")
    mix = _read("benchmark", "traffic", "train_b2_s8192_dp.json")
    call = keye.attention_call(cell, mix)
    assert call == {"batch": 2, "heads": 32, "kv_heads": 4, "seq": 8192,
                    "head_dim": 128, "topk": 2048}
    pairs = selected_attention.selected_pairs(8192, 2048)
    assert pairs == 8192 * 2048 - 2048 * 2047 // 2 == 14_681_088
    fwd, fwd_bytes = selected_attention.flash_sel_fwd(cell, mix)
    dq, dq_bytes = selected_attention.flash_sel_bwd_dq(cell, mix)
    dkv, dkv_bytes = selected_attention.flash_sel_bwd_dkv(cell, mix)
    assert fwd == 2 * 2.0 * pairs * 128 * 2 * 32
    assert abs(dq + dkv - 2.5 * fwd) < 1.0 and abs(dq / dkv - 2 / 3) < 1e-12
    wide, narrow = 2 * 8192 * 128 * 32 * 2, 2 * 8192 * 128 * 4 * 2
    selection = 2 * 8192 * 8193 // 2
    assert (fwd_bytes, dq_bytes, dkv_bytes) == (
        2 * wide + 2 * narrow + selection, 3 * wide + 2 * narrow + selection,
        2 * wide + 4 * narrow + selection)
    # a kernel that computes every causal tile reads at most this share of
    # the dense kernels' count
    dense = gqa_attention.flash_fwd(cell, mix)[0]
    assert 0.43 < fwd / dense < 0.44
    # the model's arithmetic: 6 x what a token activates + the selected
    # pairs' products + the indexer's over the causal pairs
    flops = keye.train_flops_per_token(cell, 8192)
    active = 5 * (18_874_368 + 2_260_992 + 262_144 + 1.0 * 4_718_592) \
        + 2048 * 18992
    products = 5 * (3 * 4 * 128 * 32 * pairs / 8192
                    + 3 * 2 * 64 * 16 * 8193 / 2)
    assert flops == 6.0 * active + products
    assert 1.5e9 < flops < 1.7e9


@pytest.mark.parametrize("seq,topk", [(64, 8), (32, 32), (16, 40)])
def test_the_indexer_kernels_arithmetic_is_a_brute_force_count(tiny, seq,
                                                               topk):
    """benchmark/kernels/indexer.py, a function a kernel name, against
    loops over the pairs at a tiny shape: the index heads' scores over
    every causal pair, the target and the gradient over the selected pairs
    alone, every tensor once."""
    from benchmark.kernels import indexer
    config = copy.deepcopy(tiny)
    config["sa_config"]["topk"] = topk
    mix = {"global_batch": 3, "seq": seq, "mesh": {"data": 1}}
    batch, heads, kv_heads, dim = 3, 4, 2, 32
    index_heads, index_dim = 4, 16
    causal = selected = 0
    for t in range(seq):
        causal += batch * (t + 1)
        selected += batch * min(t + 1, topk)
    positions = batch * seq
    operands = (positions * index_heads * index_dim * 2      # qI
                + positions * index_dim * 2                  # kI
                + positions * index_heads * 4)               # w
    index_product = lambda pairs: 2.0 * pairs * index_dim * index_heads
    assert indexer.index_scores(config, mix) == (
        index_product(causal), operands + causal * 4)
    assert indexer.index_search(config, mix) == (0.0, causal * 4 + causal)
    assert indexer.index_kl(config, mix) == (
        2.0 * selected * dim * heads,
        positions * dim * heads * 2 + positions * dim * kv_heads * 2
        + positions * heads * 4 + selected * 4 + selected * 4 + causal)
    assert indexer.index_grad_q(config, mix) == (
        2 * index_product(selected),
        operands + selected * 4 + positions * index_heads * index_dim * 2
        + positions * index_heads * 4)
    assert indexer.index_grad_k(config, mix) == (
        2 * index_product(selected),
        operands + selected * 4 + positions * index_dim * 2)


def test_the_indexer_kernels_least_times_at_the_cell():
    """At keye2_train_1chip: what each yardstick says a call takes at 197
    TFLOP/s and 819 GB/s, against the kernels' first traced times (1.74,
    4.12, 3.61, 3.43, 3.12 ms: PERF.md, PR 41): every share under 100."""
    from benchmark.kernels import indexer
    cell = _read("benchmark", "configs", "keye-vl-2.0-30b-a3b.json")
    mix = _read("benchmark", "traffic", "train_b2_s8192_dp.json")
    least = {}
    for kernel in ("index_scores", "index_search", "index_kl",
                   "index_grad_q", "index_grad_k"):
        flops, hbm_bytes = getattr(indexer, kernel)(cell, mix)
        least[kernel] = 1e3 * max(flops / 197e12, hbm_bytes / 819e9)
    assert 0.69 < least["index_scores"] < 0.71          # the MXU's
    assert 0.40 < least["index_search"] < 0.42          # the memory's
    assert 1.21 < least["index_kl"] < 1.23
    assert 0.60 < least["index_grad_q"] == least["index_grad_k"] < 0.62


@pytest.mark.parametrize("strategy,column", [
    ("tp", (None, "tensor")), ("tp_fsdp", ("fsdp", "tensor"))])
def test_every_new_leaf_gets_its_rule(jax_cpu, tiny, strategy, column):
    jax = jax_cpu
    from jax.sharding import PartitionSpec as P
    from benchmark.families import keye
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    cfg = GPTConfig(**keye.gpt_config_kwargs(tiny))
    params = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                      devices=jax.devices()[:4])
    specs = jax.tree_util.tree_map(
        lambda s: s.spec,
        strategy_from_name(strategy).param_shardings(mesh, params))
    index = specs["layers"][0]["attn"]["index"]
    # whole index heads of wq's columns over `tensor`; the one key head,
    # its norm and the heads' weights are not divided over it
    assert index["wq"] == specs["layers"][0]["attn"]["wq"] == P(*column)
    for leaf in (index["wk"], index["ww"], index["k_norm"]["scale"],
                 index["k_norm"]["bias"]):
        assert "tensor" not in tuple(leaf)


def test_data_parallel_step_equals_one_device(jax_cpu, tiny):
    """One step of the whole tiny model on data=2 (the walk and the
    flash_sel kernels per shard, the KL a mean of the shards') equals the
    one-device step; `tensor` > 1 refuses by name."""
    jax = jax_cpu
    import jax.numpy as jnp
    import optax
    from benchmark.families import keye
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import init_train_state, make_train_step
    cfg = GPTConfig(**keye.gpt_config_kwargs(tiny), dtype=jnp.float32,
                    attention="flash")
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, 512, (4, 129), dtype=np.int32))

    def one_step(name, axes, n):
        mesh = build_mesh(MeshConfig(**axes), devices=jax.devices()[:n])
        strategy = strategy_from_name(name)
        optimizer = optax.sgd(0.1)
        state = init_train_state(
            lambda: gpt_init(jax.random.PRNGKey(3), cfg), optimizer, mesh,
            strategy)
        step = make_train_step(
            lambda p, b: gpt_loss(
                p, b, cfg, mesh=mesh,
                act_sharding=strategy.activation_sharding(mesh)),
            optimizer, mesh, strategy, sample_params=state.params)
        with jax.default_matmul_precision("highest"):
            state, metrics = step(state, {"tokens": tokens})
        return float(metrics["loss"]), jax.device_get(state.params)

    ref_loss, ref_params = one_step("dp", {"data": 1}, 1)
    loss, params = one_step("dp", {"data": 2}, 2)
    # (the balance loss's f and P are the whole batch's on both)
    assert abs(loss - ref_loss) < 1e-5
    for (path, p), r in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_allclose(p, r, rtol=1e-4, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))
    with pytest.raises(ValueError, match="'tensor' > 1"):
        one_step("tp_fsdp", {"data": 1, "fsdp": 2, "tensor": 2}, 4)


@pytest.mark.parametrize("change,says", [
    ({"attention": "ring", "n_kv_heads": 4}, "indexer.*attention='ring'"),
    ({"kv_latent_dim": 32, "qk_nope_dim": 16, "qk_rope_dim": 16,
      "v_head_dim": 32, "n_kv_heads": 4, "qk_head_norm": False},
     "indexer.*a latent block"),
    ({"layer_kinds": ("attention", "window", "attention"),
      "attention_window": 8, "qk_head_norm": False},
     "indexer.*'window' layers"),
    ({"index_heads": 0}, "index_topk=32 needs index_heads"),
], ids=["ring", "latent", "window", "no_heads"])
def test_the_configuration_refuses_by_name(tiny, change, says):
    from benchmark.families import keye
    from ray_tpu.models.gpt import GPTConfig
    with pytest.raises(ValueError, match=says):
        GPTConfig(**dict(keye.gpt_config_kwargs(tiny), **change))


def test_pipeline_refuses_by_name(jax_cpu, tiny):
    jax = jax_cpu
    from benchmark.families import keye
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import make_gpt_pp_loss
    cfg = GPTConfig(**dict(keye.gpt_config_kwargs(tiny), n_experts=0,
                           experts_held=None, n_layers=2))
    mesh = build_mesh(MeshConfig(data=1, pipeline=1),
                      devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="hands back statistics .*index_kl"):
        make_gpt_pp_loss(cfg, mesh, num_microbatches=2)


def test_pipeline_runs_an_indexer_no_layer_carries(jax_cpu, tiny):
    """The pipeline refuses by what the block hands back, not by
    index_topk: a stack of short-convolution layers alone has no attention
    for an indexer to sit in, and runs as that stack does without the
    field."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import keye
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import gpt_params_to_pp, make_gpt_pp_loss
    kwargs = dict(keye.gpt_config_kwargs(tiny), n_experts=0,
                  experts_held=None, dtype=jnp.float32)
    cfg = GPTConfig(**dict(kwargs, layer_kinds=("conv",) * kwargs["n_layers"]))
    assert cfg.index_topk
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.array(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 33)), jnp.int32)}
    mesh = build_mesh(MeshConfig(data=1, pipeline=cfg.n_layers),
                      devices=jax.devices()[:cfg.n_layers])
    loss = make_gpt_pp_loss(cfg, mesh, num_microbatches=2)(
        gpt_params_to_pp(params), batch)
    assert abs(float(loss) - float(gpt_loss(params, batch, cfg))) < 1e-5


def _kernel_calls(jax, jaxpr, rematted=False):
    """(kernel name, whether it runs in a layer's recompute pass: under a
    checkpoint equation of the backward) for every pallas_call of jaxpr
    (tests/test_flash_remat.py's walk)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], rematted
        inner = rematted or eqn.params.get("differentiated", False)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_calls(jax, sub, inner)


def _loops_outside_kernels(jax, jaxpr, path=""):
    """The scope path of every scan and while of jaxpr and of what its
    equations hold, a kernel's body left out."""
    for eqn in jaxpr.eqns:
        here = f"{path}/{eqn.source_info.name_stack}"
        if eqn.primitive.name in ("scan", "while"):
            yield here
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _loops_outside_kernels(jax, sub, here)


def test_the_selection_engages_and_the_walk_runs_once_a_layer(jax_cpu, tiny):
    """The step's kernel calls are the counter: 3 of each flash_sel_* and
    no flash_*, 3 of each of the walk's five kernels (128 positions, 32
    keys a query: the kernels' side of `_selected_attention`), and under
    remat_policy="full" neither the forward kernel nor any of the walk's
    in a recompute pass: FLASH_OUT, FLASH_LSE, INDEX_MASK and INDEX_GRADS
    are kept. The jnp walk (a scan over blocks of queries around a search
    of 32 passes) is not in the step."""
    jax = jax_cpu
    from collections import Counter
    from benchmark.families import keye
    from ray_tpu.models.gpt import gpt_init, gpt_loss
    from ray_tpu.util import profiling
    cfg = keye._train_config(tiny)
    assert cfg.remat_policy == "full" and cfg.index_topk == 32
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    tokens = np.zeros((2, 129), np.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: gpt_loss(p, {"tokens": tokens}, cfg)))(params)
    calls = Counter(_kernel_calls(jax, jaxpr.jaxpr))
    assert calls[("flash_sel_fwd", False)] == 3
    assert calls[("flash_sel_fwd", True)] == 0
    for kernel in ("flash_sel_bwd_dq", "flash_sel_bwd_dkv"):
        assert calls[(kernel, False)] + calls[(kernel, True)] == 3
    assert not any(name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
                   for name, _ in calls)
    for kernel in ("index_scores", "index_search", "index_kl",
                   "index_grad_q", "index_grad_k"):
        assert kernel in profiling.KERNELS
        assert calls[(kernel, False)] == 3 and calls[(kernel, True)] == 0
    # (the search's 32 passes are a loop inside its kernel: the walk's own
    # scans stood in the layer, under `attn_index`)
    loops = list(_loops_outside_kernels(jax, jaxpr.jaxpr))
    assert loops and not any("attn_index" in path for path in loops), loops


def test_the_new_scope_is_a_region_and_reaches_the_compiled_step(jax_cpu,
                                                                 tiny):
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import keye
    from ray_tpu.models.gpt import gpt_init, gpt_loss
    from ray_tpu.util import profiling
    cfg = keye._train_config(tiny)
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    text = jax.jit(jax.grad(lambda p, t: gpt_loss(p, {"tokens": t}, cfg))
                   ).lower(params, jnp.zeros((2, 129), jnp.int32)
                           ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    regions = {profiling._last_of(n, profiling.REGIONS) for n in names}
    assert {"attn_index", "attn_proj", "attn_core", "attn_out", "moe",
            "moe_route"} <= regions
    # the kernels under the selection are attn_core's; the walk's scan, the
    # indexer's projections and its table are attn_index's
    for n in names:
        if "flash_sel_" in n:
            assert profiling._last_of(n, profiling.REGIONS) == "attn_core"
    assert any("attn_index" in n and "while" in n for n in names)
    assert any("attn_index/bsd,dh->bsh" in n for n in names)


def test_configuration_file_keeps_the_catalog_and_states_the_cut():
    cell = _read("benchmark", "configs", "keye-vl-2.0-30b-a3b.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cell["source"])
    changed = {k for k, v in row["config"].items() if cell.get(k, "?") != v}
    assert changed == set(cell["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cell["published"] == {k: row["config"][k] for k in cell["reduced"]}
    assert cell["sa_config"] == row["config"]["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert (cell["hidden_size"], cell["num_attention_heads"],
            cell["num_key_value_heads"], cell["head_dim"],
            cell["moe_intermediate_size"], cell["num_experts_per_tok"],
            cell["rope_theta"], cell["rms_norm_eps"], cell["norm_topk_prob"]
            ) == (2048, 32, 4, 128, 768, 8, 10000000, 1e-06, True)
    assert cell["share"]["chips_per_layer"] * cell["num_experts"] \
        == cell["share"]["num_experts"] == 128
    assert cell["share"]["chips_per_layer"] * cell["vocab_size"] == 151936
    assert {"qk_norm", "router_aux_loss_coef", "indexer_input",
            "indexer_key_norm", "indexer_rotation", "indexer_weights",
            "indexer_tie_rule", "indexer_loss", "chunk_sizes",
            "sequence_length", "init"} <= set(cell["assumed"])
    assert cell["embedding_init_std"] == 1.0
    bench = _read("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["name"])
    assert entry["reduced"] == cell["reduced"]
    assert entry["source"] == cell["source"]
    peak = cell["reduced_why"]["memory_peak_bytes"]
    assert 0.25 * 16.91e9 < peak["chip"] < 16.91e9


# ---------------------------------------------------------------------------
# (f) the benchmark's own checks of the cell that need no chip
# ---------------------------------------------------------------------------

@pytest.mark.timeout(600)
def test_the_cell_rehearses():
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # rehearse.py asks for its own devices
    proc = subprocess.run(
        [sys.executable, "benchmark/rehearse.py", "keye2_train_1chip",
         "--seconds", "2"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "rehearsal passed" in proc.stdout
