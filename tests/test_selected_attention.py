"""Attention under a learned selection: the three `flash_sel_*` kernels
(ops/attention.py) against their reference on the CPU (the kernels in
interpret mode), and the cell's own checks: what keye2_train_1chip hands the
chip's compiler, for a described v5e (the kernels under a selection, the
indexer's walk and the whole step), and the benchmark's rehearsal of the
cell. The indexer alone: tests/test_selected_attention_indexer.py; the
family's program against the reference of benchmark/families/keye.py:
tests/test_selected_attention_model.py."""

import re

import numpy as np
import pytest

from helpers.described_chip import (  # noqa: F401 — fixtures
    cell_step, kernel_ops, v5e, windows)
# (the rehearsal is bound, and so run, first here: see
# tests/test_conv_gqa_model.py)
from helpers.families import family, test_the_cell_rehearses  # noqa: F401
from test_selected_attention_model import FAMILY  # noqa: F401


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
# (b) for a described v5e: the kernels and the walk, and (imported) the
# whole step
# ---------------------------------------------------------------------------


def test_selected_kernels_and_the_walk_compile_at_8192_positions(v5e):
    """keye2_train_1chip's call, [2, 32 on 4, 8192, 128] under a selection
    of one byte a pair ([2, 8192, 8192] int8: a tile of 2048 x 2048 bytes a
    grid step, forward and both backward kernels, dK/dV on the transposed
    selection), and the indexer's walk that makes it (ops/indexer.py), whose
    rows' statistics stay reductions: the chip's compiler fuses a row's
    reduction with its broadcast into a window reduction 16 383 wide (47 ms
    a block where 1.5 do) unless a barrier stands between; and the same walk
    as the flash path runs it, five kernels (PR 41) with no such row left
    to XLA."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops import indexer
    from ray_tpu.ops.attention import flash_attention

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(v5e[0]))
    grads = jax.jit(jax.grad(lambda q, k, v, selected: flash_attention(
        q, k, v, causal=True, selected=selected,
        interpret=False).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    compiled = grads.lower(shape(2, 32, 8192, 128), shape(2, 4, 8192, 128),
                           shape(2, 4, 8192, 128),
                           shape(2, 8192, 8192, dtype=jnp.int8)).compile()
    text = compiled.as_text()
    for kernel in ("flash_sel_fwd", "flash_sel_bwd_dq", "flash_sel_bwd_dkv"):
        assert len(kernel_ops(text, kernel)) == 1, kernel
    dq, dk, dv = compiled.out_info
    assert dq.shape == (2, 32, 8192, 128)
    assert dk.shape == dv.shape == (2, 4, 8192, 128)

    def walk(qi, ki, w, q, k):
        def loss(qi, ki, w):
            selected, kl, _share = indexer.select_and_kl(
                qi, ki, w, q, k, topk=2048, sm_scale=128 ** -0.5)
            return kl, selected
        return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(qi, ki, w)
    compiled = jax.jit(walk).lower(
        shape(2, 16, 8192, 64), shape(2, 8192, 64),
        shape(2, 8192, 16, dtype=jnp.float32), shape(2, 32, 8192, 128),
        shape(2, 4, 8192, 128)).compile()
    wide = windows(compiled.as_text())
    assert all(int(w.split("x")[-1]) <= 128 for w in wide), wide
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9

    # the same walk as kernels (the flash path): each compiles for the chip
    # once, the rows of 8192 keys stay inside them, and what crosses HBM
    # between them (I, g, d w's partial sums) is under the jnp walk's blocks
    def kernels(qi, ki, w, q, k, lse):
        selected, kept, share = indexer.select(qi, ki, w, topk=2048,
                                               interpret=False)

        def loss(qi, ki, w):
            return indexer.kl(qi, ki, w, q, k, lse, selected, kept,
                              sm_scale=128 ** -0.5, interpret=False)
        return selected, share, jax.value_and_grad(loss, (0, 1, 2))(qi, ki, w)
    compiled = jax.jit(kernels).lower(
        shape(2, 16, 8192, 64), shape(2, 8192, 64),
        shape(2, 8192, 16, dtype=jnp.float32), shape(2, 32, 8192, 128),
        shape(2, 4, 8192, 128),
        shape(2, 32, 8192, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    for kernel in ("index_scores", "index_search", "index_kl",
                   "index_grad_q", "index_grad_k"):
        assert len(kernel_ops(text, kernel)) == 1, kernel
    assert all(int(w.split("x")[-1]) <= 128 for w in windows(text))
    assert "while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.4e9

# Imported last: a module's names are collected in the order they are bound,
# so the chip's compiler gets this file's programs after its own tests have
# run, at another minute of a run than the other families' files.
from helpers.described_chip import (  # noqa: E402,F401
    test_the_cells_that_were_there_lower_to_the_same_step,
    test_cell_step_compiles_under_the_chips_memory,
    test_cell_step_makes_a_heads_dw_where_its_logits_are,
    test_the_new_scopes_are_regions_and_reach_the_compiled_step
    as test_the_new_scope_is_a_region_and_reaches_the_compiled_step)
