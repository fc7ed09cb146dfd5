"""Latent attention's kernels: the flash kernels where q.k and v differ in
width (ops/attention.py) and the head's two parts through ops/rope.py's
latent pair, against their references on the CPU (interpret mode), and what
kanana2_train_1chip hands the chip's compiler, for a described v5e: the
latent kernels, the attention block, the sparse block and the whole step.
The family's program against the reference of benchmark/families/kanana.py:
tests/test_latent_moe_model.py; a share's row space: tests/test_share_rows.py."""

import re

import numpy as np
import pytest

from helpers.described_chip import (  # noqa: F401 — fixtures
    cell_configuration, cell_step, kernel_ops, v5e)
from helpers.families import family  # noqa: F401
from test_latent_moe_model import FAMILY  # noqa: F401 — the cell's numbers


# ---------------------------------------------------------------------------
# (a) the flash kernels where q.k and v differ in width
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dqk,dv,seq,blocks", [
    (192, 128, 256, {"block_q": 128, "block_k": 128}),   # the cell's widths
    (192, 128, 256, {}),                                  # one square block
    (96, 64, 128, {"block_q": 64, "block_k": 32}),        # not square
    (48, 32, 64, {}),                                     # below a lane tile
], ids=["192_128_blocks_of_128", "192_128_one_block", "96_64_ragged_blocks",
        "48_32_short"])
def test_flash_forward_and_gradients_where_qk_is_wider_than_v(
        jax_cpu, dqk, dv, seq, blocks):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference
    q, k, v = (jax.random.normal(key, (2, 2, seq, d), jnp.float32)
               for key, d in zip(jax.random.split(jax.random.PRNGKey(0), 3),
                                 (dqk, dqk, dv)))

    def loss(attend):
        return lambda q, k, v: (attend(q, k, v) ** 2).sum()
    got = jax.value_and_grad(
        loss(lambda q, k, v: flash_attention(q, k, v, **blocks)),
        (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loss(mha_reference), (0, 1, 2))(q, k, v)
    assert flash_attention(q, k, v, **blocks).shape == (2, 2, seq, dv)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_v_of_128_under_qk_of_256_leaves_tokens_first(jax_cpu, dtype):
    """The latent cell's call as its kernels see it (q.k 256 wide after the
    fill, v 128): v's width decides where o goes, [B, S, H * 128]; q, k and
    their gradients stay by head."""
    import jax.numpy as jnp
    from helpers.flash_layout import check_tokens_first
    check_tokens_first(jax_cpu, jnp.dtype(dtype).type, dqk=256)


@pytest.mark.parametrize("seq,width,fwd,bwd", [
    (1024, 64, (1024, 1024, 256), (1024, 1024, 128)),     # gpt2s
    (2048, 64, (2048, 2048, 256), (2048, 2048, 128)),     # smollm-1.7b
    (4096, 128, (2048, 2048, 256), (2048, 2048, 128)),    # olmoe-1b-7b
], ids=["gpt2s", "smollm", "olmoe"])
def test_blocks_of_the_cells_with_one_width_are_what_they_were(seq, width,
                                                               fwd, bwd):
    from ray_tpu.ops.attention import _block_sizes
    blocks = _block_sizes(seq, seq, width)
    assert blocks == _block_sizes(seq, seq, width, width)
    assert blocks.fwd == fwd and blocks.dq == blocks.dkv == bwd


def test_blocks_follow_the_wider_of_the_two_widths():
    from ray_tpu.ops.attention import _block_sizes
    # kanana2_train_1chip: q.k 192, v 128, 8192 positions
    # (flash_attention hands the kernels q and k padded to 256)
    blocks = _block_sizes(8192, 8192, 256, 128)
    assert blocks.fwd == (2048, 2048, 256)
    assert blocks.dq == blocks.dkv == (1024, 1024, 128)
    assert _block_sizes(8192, 8192, 128, 192) == blocks
    assert _block_sizes(8192, 8192, 128, 128).dq == (2048, 2048, 128)


@pytest.mark.parametrize("heads,nope,rope,dv,whole_head,latent", [
    (32, 128, 64, 128, False, True),    # kanana2_train_1chip
    (16, 128, 64, 128, False, True),    # its heads over tensor = 2
    (4, 32, 16, 32, False, False),      # tiny-kanana: nope below a lane tile
    (32, 128, 64, 64, False, False),    # v of half a lane tile
    (32, 64, 64, 128, True, False),     # a head of 128, but nope of half a tile
], ids=["kanana", "kanana_tensor_2", "tiny", "v_64", "nope_64"])
def test_a_head_of_192_tiles_as_128_and_64(heads, nope, rope, dv, whole_head,
                                           latent):
    """ops/rope.py's `rope_split` tiles whole heads in whole 128-lane
    tiles: a head of 192 columns is neither a divisor nor a multiple, and
    it is still refused there. The latent block's kernels take the head as
    its two parts, nope of whole lane tiles and the rotated parts of a
    group of heads a tile; where they do not, models/gpt.py keeps the jnp
    assembly (`_rope_tail`, `_latent_heads`)."""
    from ray_tpu.ops.rope import (_lane_tile, _latent_blocks, _rope_blocks,
                                  latent_split)
    assert (_lane_tile(nope + rope) is not None) == whole_head
    assert (_rope_blocks(8192, heads, nope + rope, 2) is not None) == whole_head
    assert (_latent_blocks(8192, heads, nope, rope, dv, 2) is not None) == latent
    assert (latent_split(8192, heads, nope, rope, dv, "bfloat16")
            is not None) == latent


def test_rope_tail_rotates_the_last_columns_only(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import _rope, _rope_tail
    from ray_tpu.ops.rope import rope_table
    t = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 3, 48), jnp.float32)
    got = _rope_tail(t, rope_table(16, 16, 1e6), 16)
    np.testing.assert_array_equal(got[..., :32], t[..., :32])
    positions = jnp.broadcast_to(jnp.arange(16)[None, :], (2, 16))
    want = _rope(t[..., 32:].transpose(0, 2, 1, 3), 1e6, positions)
    np.testing.assert_allclose(got[..., 32:].transpose(0, 2, 1, 3), want,
                               atol=1e-6)


def test_deinterleaved_weights_give_deinterleaved_activations(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import _deinterleaved
    w = jax.random.normal(jax.random.PRNGKey(2), (8, 3 * 12), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 8), jnp.float32)
    y = (x @ w).reshape(5, 3, 12)
    tail = y[..., 4:].reshape(5, 3, 4, 2).swapaxes(-1, -2).reshape(5, 3, 8)
    want = jnp.concatenate([y[..., :4], tail], -1).reshape(5, 36)
    np.testing.assert_array_equal(x @ _deinterleaved(w, 3, 4, 8), want)


# ---------------------------------------------------------------------------
# (b) for a described v5e: the latent kernels, the attention block and
# (imported) the sparse block and the whole step
# ---------------------------------------------------------------------------


# kanana2_train_1chip's latent block: [batch, seq, heads, nope, rope, dv].
LATENT_SHAPE = (2, 8192, 32, 128, 64, 128)


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "forward_backward"])
def test_latent_kernels_compile_for_v5e(v5e, backward):
    """ops/rope.py's latent pair of pairs at the cell's shape: q's heads of
    128 + 64 columns and kv's of 128 + 128 with the shared rotated key part
    into the flash kernels' [B, H, S, 256 | 128], and back. What interpret
    mode cannot see: a pair of heads cut out of three lane tiles at lane
    offset 64, the (1, rows, 64) block of k_rope, the float32 sum over the
    heads carried across the grid's sequential head axis."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.rope import latent_split, rope_table

    batch, seq, heads, nope, rope, dv = LATENT_SHAPE
    q_split, kv_split = latent_split(seq, heads, nope, rope, dv,
                                     jnp.bfloat16, interpret=False)

    def fwd(q, kv, k_rope):
        table = rope_table(seq, rope, 1e6)
        return (q_split(q, *table), *kv_split(kv, k_rope, *table))

    fn = fwd
    if backward:
        fn = jax.grad(lambda *x: sum((t.astype(jnp.float32) ** 2).sum()
                                     for t in fwd(*x)), argnums=(0, 1, 2))
    one_chip = SingleDeviceSharding(v5e[0])
    text = jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct((batch, seq, width), jnp.bfloat16,
                             sharding=one_chip)
        for width in (heads * (nope + rope), heads * (nope + dv), rope))
    ).compile().as_text()
    for kernel, there in (("latent_q_split", True), ("latent_kv_split", True),
                          ("latent_q_merge", backward),
                          ("latent_kv_merge", backward)):
        assert bool(kernel_ops(text, kernel)) == there, kernel
    assert text.count("tpu_custom_call") >= (4 if backward else 2)


# Imported last: a module's names are collected in the order they are bound,
# so the chip's compiler gets this file's programs after its own tests have
# run, at another minute of a run than the other families' files.
from helpers.described_chip import (  # noqa: E402,F401
    test_the_cells_that_were_there_lower_to_the_same_step,
    test_cell_step_compiles_under_the_chips_memory,
    test_cell_step_makes_a_heads_dw_where_its_logits_are,
    test_sparse_layer_compiles_with_both_row_spaces,
    test_the_new_scopes_are_regions_and_reach_the_compiled_step)


def test_latent_block_reaches_the_flash_kernels_without_a_layout_pass(
        cell_step):
    """kanana2_train_1chip's five attention blocks, forward and backward,
    for one described chip, read off the module's one compile of the whole
    step (its entry computation holds the layers): under `attn_proj` /
    `attn_latent` the only tensors by head are the four latent kernels' own
    results. No `copy`, transpose or fusion writes a
    [2, 8192, 32, 256 | 192 | 64]-shaped tensor (the jnp assembly's
    `fusion -> [2, 8192, 32, 256] -> copy -> [2, 32, 8192, 256]` for q and
    again for k), and no activation there is float32."""
    cfg = cell_configuration(FAMILY.cell)
    batch, seq, heads = LATENT_SHAPE[:3]
    assert (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim) == LATENT_SHAPE[2:]
    assert (cell_step.mix["global_batch"], cell_step.mix["seq"]) == (batch,
                                                                    seq)
    text = cell_step.text
    # a layer: each latent kernel and each flash kernel once, and q's and
    # kv's splits again in the recompute
    for kernel, calls in (("latent_q_split", 2), ("latent_kv_split", 2),
                          ("latent_q_merge", 1), ("latent_kv_merge", 1),
                          ("flash_fwd", 1), ("flash_bwd_dq", 1),
                          ("flash_bwd_dkv", 1)):
        assert len(kernel_ops(text, kernel)) == cfg.n_layers * calls, kernel
    # the entry computation's instructions: what is written to memory (an
    # instruction inside a fused computation lives in registers)
    under = [line for line in text[text.index("\nENTRY "):].splitlines()
             if re.search(r'op_name="[^"]*attn_proj', line)]
    assert len(under) > 20 * cfg.n_layers
    by_head = re.compile(rf"\[{batch},(?:{seq},{heads}|{heads},{seq}),\d+\]")
    for line in under:
        made = line.split(" = ", 1)[-1].split("(", 1)[0]
        if by_head.search(line.split(" = ", 1)[-1]):
            # a kernel's call, or an element of its results
            assert re.search(r"/latent_(q|kv)_(split|merge)/pallas_call",
                             line), line
        assert not re.search(rf"f32\[{batch},{seq},\d", made), line
