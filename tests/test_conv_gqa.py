"""The gated short convolution (ops/short_conv.py), grouped-query attention
in the flash kernels (ops/attention.py) and the norm a head (models/gpt.py)
against their plain references on the CPU (the kernels in interpret mode),
and what lfm2_train_1chip hands the chip's compiler, for a described v5e:
its kernels at the cell's shapes, its attention layer, its sparse block and
its whole step. The family's program against the reference of
benchmark/families/lfm2.py: tests/test_conv_gqa_model.py."""

import hashlib

import numpy as np
import pytest

from helpers.described_chip import (  # noqa: F401 — fixtures
    cell_configuration, cell_step, heads_of_64_stay_by_token, v5e)
from helpers.families import family  # noqa: F401
from test_conv_gqa_model import FAMILY  # noqa: F401 — the cell's numbers


# ---------------------------------------------------------------------------
# (a) the operator between a convolution layer's projections
# ---------------------------------------------------------------------------


def _conv_loop(b, c, x, w):
    """out[n, t, ch] = c * sum_j w[ch, j] * (b * x)[t - (L - 1) + j],
    written out position by position in float64."""
    b, c, x, w = (np.asarray(a, np.float64) for a in (b, c, x, w))
    u = b * x
    taps = w.shape[1]
    out = np.zeros_like(u)
    for t in range(u.shape[1]):
        for j in range(taps):
            at = t - (taps - 1) + j
            if at >= 0:
                out[:, t] += w[:, j] * u[:, at]
    return c * out


def _conv_inputs(jax, shape, taps, dtype):
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    b, c, x, g = (jax.random.normal(k, shape, jnp.float32).astype(dtype)
                  for k in keys[:4])
    return b, c, x, jax.random.normal(keys[4], (shape[2], taps)), g


@pytest.mark.parametrize("formulation", ["jnp", "pallas"])
@pytest.mark.parametrize("shape,taps", [
    ((2, 64, 256), 3),        # one block of rows, two of channels
    ((1, 1536, 128), 3),      # three blocks along the sequence: both halos
    ((2, 32, 128), 4),        # another filter length
    ((2, 2, 128), 3),         # a sequence shorter than the filter
    ((1, 24, 96), 3),         # channels that fill no lane tile
], ids=["64x256", "three_seq_blocks", "four_taps", "shorter_than_the_filter",
        "ragged_channels"])
def test_short_conv_values_and_gradients(jax_cpu, formulation, shape, taps):
    """Both formulations against the written-out loop: values, and the
    gradients of all four operands (autodiff of the jnp formulation, the
    backward kernel of the Pallas pair) against finite sums worked out from
    the loop's linearity: d/dc is the filtered u, and the others follow by
    the chain rule through the same loop."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import short_conv as sc
    b, c, x, w, g = _conv_inputs(jax, shape, taps, jnp.float32)
    fn = sc.short_conv_reference if formulation == "jnp" else sc.short_conv
    tiles = sc._conv_blocks(shape[1], shape[2], taps, 4)
    assert (tiles is None) == (shape in ((2, 2, 128), (1, 24, 96)))
    out, vjp = jax.vjp(fn, b, c, x, w)
    np.testing.assert_allclose(out, _conv_loop(b, c, x, w), atol=1e-5)
    db, dc, dx, dw = vjp(g)
    ones = np.ones_like(np.asarray(c))
    filtered = _conv_loop(b, ones, x, w)                  # m = filter(b x)
    np.testing.assert_allclose(dc, np.asarray(g) * filtered, atol=1e-5)
    # du_t = sum_j w_j dm_{t + (L-1) - j}: the loop on the reversed sequence
    dm = np.asarray(g, np.float64) * np.asarray(c, np.float64)
    du = _conv_loop(dm[:, ::-1], ones, ones, w)[:, ::-1]
    np.testing.assert_allclose(db, du * np.asarray(x), atol=1e-5)
    np.testing.assert_allclose(dx, du * np.asarray(b), atol=1e-5)
    u = np.asarray(b, np.float64) * np.asarray(x, np.float64)
    want_dw = np.stack([
        (dm[:, taps - 1 - j:] * u[:, :u.shape[1] - (taps - 1 - j)]).sum((0, 1))
        if taps - 1 - j < u.shape[1] else np.zeros(shape[2])
        for j in range(taps)], axis=1)
    np.testing.assert_allclose(dw, want_dw, atol=2e-4)


def test_the_first_positions_see_zeros_and_no_later_one(jax_cpu):
    """Causal: position t reads u[t-2..t], with zeros before the start; a
    change at position 5 moves positions 5, 6, 7 and nothing else."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.short_conv import short_conv_reference
    b, c, x, w, _ = _conv_inputs(jax, (1, 16, 128), 3, jnp.float32)
    out = np.asarray(short_conv_reference(b, c, x, w))
    u = np.asarray(b * x)[0]
    wn, cn = np.asarray(w), np.asarray(c)[0]
    np.testing.assert_allclose(out[0, 0], cn[0] * wn[:, 2] * u[0], atol=1e-6)
    np.testing.assert_allclose(
        out[0, 1], cn[1] * (wn[:, 1] * u[0] + wn[:, 2] * u[1]), atol=1e-6)
    moved = np.asarray(short_conv_reference(b.at[0, 5].add(1.0), c, x, w))
    changed = np.flatnonzero(np.abs(moved - out)[0].max(axis=1) > 0)
    assert list(changed) == [5, 6, 7]


def test_pallas_pair_equals_the_jnp_formulation_in_bfloat16(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import short_conv as sc
    b, c, x, w, g = _conv_inputs(jax, (2, 64, 256), 3, jnp.bfloat16)
    assert sc._conv_blocks(64, 256, 3, 2) == (64, 256, 16)
    got, got_vjp = jax.vjp(sc.short_conv, b, c, x, w)
    want, want_vjp = jax.vjp(sc.short_conv_reference, b, c, x, w)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    for p, r in zip(got_vjp(g), want_vjp(g)):
        assert p.dtype == r.dtype and p.shape == r.shape
        # the kernel rounds a gradient once, autodiff at every product
        np.testing.assert_allclose(np.asarray(p, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=2e-2, atol=2e-2)


def test_blocks_of_the_cell_and_the_kernels_names(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import short_conv as sc
    from ray_tpu.util.profiling import KERNELS
    # lfm2_train_1chip: [2, 8192, 2048] bf16, 3 taps
    assert sc._conv_blocks(8192, 2048, 3, 2) == (512, 512, 16)
    assert sc._conv_blocks(8192, 2048, 3, 4) == (512, 512, 8)
    assert sc._conv_blocks(8192, 2048, 3, 1) is None
    assert sc._conv_blocks(8200, 2048, 3, 2) is None      # ragged sequence
    z = jnp.zeros((1, 32, 128), jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda b: sc.short_conv(b, z, z, jnp.ones((128, 3))).astype(
            jnp.float32).sum()))(z))
    for kernel in ("short_conv_fwd", "short_conv_bwd"):
        assert kernel in KERNELS and f"name={kernel}" in jaxpr


# ---------------------------------------------------------------------------
# (b) grouped queries in the three flash kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("heads,kv_heads,seq,blocks", [
    (8, 2, 256, {"block_q": 128, "block_k": 128}),   # several blocks a row
    (8, 2, 128, {}),                                  # one square block
    (8, 2, 128, {"block_q": 64, "block_k": 32}),      # not square
    (8, 8, 128, {}),                                  # a head each
    (4, 1, 64, {}),                                   # one key/value head
], ids=["8_on_2_blocks_of_128", "8_on_2_one_block", "8_on_2_ragged_blocks",
        "8_on_8", "4_on_1_short"])
def test_flash_forward_and_gradients_under_grouped_queries(
        jax_cpu, heads, kv_heads, seq, blocks):
    """Against mha_reference with k and v repeated to the query heads'
    count by hand: values, dq, and dk, dv summed over a group's query
    heads, leaving at the key/value heads' count."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (2, heads, seq, 64), jnp.float32)
    k, v = (jax.random.normal(key, (2, kv_heads, seq, 64), jnp.float32)
            for key in keys[1:])
    rep = heads // kv_heads

    def loss(attend):
        return lambda q, k, v: (attend(q, k, v) ** 2).sum()

    def repeated(q, k, v):
        return mha_reference(q, jnp.repeat(k, rep, axis=1),
                             jnp.repeat(v, rep, axis=1))
    got = jax.value_and_grad(
        loss(lambda q, k, v: flash_attention(q, k, v, **blocks)),
        (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loss(repeated), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-4)
    assert got[1][1].shape == (2, kv_heads, seq, 64)
    # mha_reference takes the grouped shapes itself
    np.testing.assert_allclose(mha_reference(q, k, v), repeated(q, k, v),
                               atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_heads_of_128_leave_the_kernels_tokens_first(jax_cpu, dtype):
    """6 query heads on 2 key/value heads, 128 wide, two blocks a row: the
    kernels write o as [B, S, H * 128] and read dO so, a head's block placed
    by the block maps (dK/dV's over a group's three query heads in turn)."""
    import jax.numpy as jnp
    from helpers.flash_layout import check_tokens_first
    check_tokens_first(jax_cpu, jnp.dtype(dtype).type)


# sha256 of the jaxpr of the value and gradients of flash_attention at heads
# of 64 handed BY HEAD, 8 on 2, [1, 8 | 2, 256, 64] float32 in blocks of
# 128: what `flash_attention_native` traces there, so a caller that holds
# [B, H, S, 64] runs the per-head kernels, block maps, reshapes and delta,
# and turns [B, H, S, 64] as it did (heads in pairs are asked for, with
# `in_pairs`, and held by tests/test_paired_heads.py). Recorded anew by PR
# 55, whose kernel bodies are one for a head and for a pair: the scratch
# accumulators gained a leading dimension (one tile here), the block maps
# are composed of a head's part and a block's part (`_Layout`). The same
# arithmetic: values and all three gradients equal the parent's (c7d22f8)
# bit for bit at 11 shapes in float32 and bf16 (64, 128, 192 / 128 and 32
# wide, grouped, a window, a selection, no mask), compared side by side in
# interpret mode when the hashes were taken. Before: 9100c184..09c9,
# 037d008f..1ec1, 51b3e122..9adc (the parent of PR 48's, 8caba70).
NARROW_HEADS_JAXPR_SHA256 = {
    "grouped": (
        {}, "a71e170fd39868cd5dc7ba7ac7774fd1311020670b9e9c3bbcc74f87757dbb20"),
    "window": (
        {"window": 100},
        "963eee5aea45281e3e48f32beb55ca136e11306f2a1723d3a51b398e960d64ac"),
    "selected": (
        {"selected": True},
        "6aa7b0e7bb883ba3f6ff8f4ab7587d852ede61348a0767fb3fe676325090271d"),
}


@pytest.mark.parametrize("kind", list(NARROW_HEADS_JAXPR_SHA256))
def test_heads_of_64_trace_to_the_parents_ops(jax_cpu, kind):
    import jax.numpy as jnp
    from helpers.flash_layout import traced_sha
    from ray_tpu.ops.attention import (flash_attention,
                                       flash_attention_native, tokens_first)
    options, sha = NARROW_HEADS_JAXPR_SHA256[kind]
    if "selected" in options:
        options = {"selected": jnp.tril(jnp.ones((1, 256, 256), jnp.int8))}
    assert not tokens_first(64) and tokens_first(128) and tokens_first(256)
    for attend in (flash_attention_native, flash_attention):
        assert traced_sha(
            jax_cpu, lambda q, k, v: attend(q, k, v, block_q=128,
                                            block_k=128, **options),
            8, 2, 256, 64) == sha


def test_flash_refuses_head_counts_that_do_not_group(jax_cpu):
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 8, 64, 16))
    with pytest.raises(ValueError, match="q 8, k 3, v 3"):
        flash_attention(q, q[:, :3], q[:, :3])
    with pytest.raises(ValueError, match="q 8, k 4, v 2"):
        flash_attention(q, q[:, :4], q[:, :2])


# sha256 of the jaxpr of flash attention's value and gradients at a head
# each ([1, 4, 256, 64] float32, blocks of 128, interpret mode), as the
# parent's ops/attention.py (1eafc89) traces it: a key/value head for every
# query head runs the kernels, grids and index maps it ran before grouped
# queries. (The dense and sparse cells' whole steps: tests/test_latent_moe_model.py,
# OLMOE_STEP_SHA256.) Recorded anew by PR 55 with NARROW_HEADS_JAXPR_SHA256
# above and for its reason (da176eee..f606 before).
FLASH_MHA_JAXPR_SHA256 = (
    "28b1e01e36e68d6418a0c157c96e7424f6263e0ad8fcace2d9c47c9e98bec8c0")


def test_a_head_each_traces_to_the_parents_kernels(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 4, 256, 64), jnp.float32)
    jaxpr = str(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: flash_attention(
            q, k, v, block_q=128, block_k=128).sum(), (0, 1, 2)))(q, q, q))
    assert hashlib.sha256(jaxpr.encode()).hexdigest() \
        == FLASH_MHA_JAXPR_SHA256


@pytest.mark.parametrize("seq,width,wide", [
    (1024, 64, False), (2048, 64, False),       # gpt2s, smollm: as they were
    (4096, 128, True), (8192, 256, True),       # olmoe, kanana: as they were
    (8192, 64, True),                           # lfm2: several blocks of 2048
    (256, 64, False)], ids=["gpt2s", "smollm", "olmoe", "kanana", "lfm2",
                            "short"])
def test_vmem_limit_follows_the_shape(seq, width, wide):
    from ray_tpu.ops import attention
    blocks = attention._block_sizes(seq, seq, width)
    params = attention._compiler_params(width, seq, blocks.fwd[1])
    assert (params is attention._GRID_SEMANTICS_WIDE) == wide
    assert params is attention._compiler_params(width, seq, blocks.dq[1])


# ---------------------------------------------------------------------------
# (c) the norm a head
# ---------------------------------------------------------------------------


def test_head_norm_is_an_rmsnorm_over_each_heads_columns(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import _head_rmsnorm, _rmsnorm
    y = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 6 * 16), jnp.float32)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    want = _rmsnorm(y.reshape(2, 8, 6, 16), scale, 1e-5).reshape(y.shape)
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(
            lambda y, s: (_head_rmsnorm(y, s, 1e-5) ** 3).sum(),
            (0, 1))(y, scale)
        np.testing.assert_allclose(_head_rmsnorm(y, scale, 1e-5), want,
                                   atol=1e-6)
    ref = jax.grad(lambda y, s: (_rmsnorm(
        y.reshape(2, 8, 6, 16), s, 1e-5) ** 3).sum(), (0, 1))(y, scale)
    for g, r in zip(grads, ref):
        np.testing.assert_allclose(g, r, atol=1e-4)
    del got


# ---------------------------------------------------------------------------
# (d) for a described v5e: the cell's kernels, its attention layer, its
# sparse block and (imported) its whole step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("heads,kv_heads", [(32, 8), (32, 32)],
                         ids=["32_on_8", "32_on_32"])
def test_flash_kernels_compile_at_8192_positions_of_64(v5e, heads, kv_heads):
    """lfm2_train_1chip's call, [2, 32 on 8, 8192, 64], forward and both
    backward kernels: several blocks of 2048 a row at a head of 64 need
    more than the default 16 MB of VMEM (`_compiler_params`), grouped or
    not; dK and dV leave at the key/value heads' count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.attention import flash_attention

    def shape(h):
        return jax.ShapeDtypeStruct((2, h, 8192, 64), jnp.bfloat16,
                                    sharding=SingleDeviceSharding(v5e[0]))
    grads = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    compiled = grads.lower(shape(heads), shape(kv_heads),
                           shape(kv_heads)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    dq, dk, dv = compiled.out_info
    assert dq.shape == (2, heads, 8192, 64)
    assert dk.shape == dv.shape == (2, kv_heads, 8192, 64)


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_short_conv_kernels_compile_for_v5e(v5e, backward):
    """ops/short_conv.py's pair at lfm2_train_1chip's [2, 8192, 2048]: the
    sublane rolls, the halo blocks and the single-row loads and stores of
    the taps lay out."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.short_conv import short_conv

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(v5e[0]))

    def fn(b, c, x, w, g):
        out, vjp = jax.vjp(lambda *a: short_conv(*a, interpret=False),
                           b, c, x, w)
        return vjp(g) if backward else out
    x = shape((2, 8192, 2048))
    text = jax.jit(fn).lower(x, x, x, shape((2048, 3), jnp.float32),
                             x).compile().as_text()
    assert ("short_conv_bwd" if backward else "short_conv_fwd") in text


# Imported last: a module's names are collected in the order they are bound,
# so the chip's compiler gets this file's programs after its own tests have
# run, at another minute of a run than the other families' files.
from helpers.described_chip import (  # noqa: E402,F401
    test_the_cells_that_were_there_lower_to_the_same_step,
    test_cell_step_compiles_under_the_chips_memory,
    test_cell_step_makes_a_heads_dw_where_its_logits_are,
    test_sparse_layer_compiles_with_both_row_spaces,
    test_the_new_scopes_are_regions_and_reach_the_compiled_step)


@pytest.mark.parametrize("cell", ["lfm2_train_1chip"])
def test_heads_of_64_reach_wo_without_a_layout_pass(cell_step, cell):
    """The third cell whose heads are 64 wide (32 on 8 at [2, 8192]), held
    to helpers/described_chip.py:heads_of_64_stay_by_token as
    tests/test_chip_compile.py holds gpt2s' and smollm's. The cell has ONE
    attention layer, in the entry computation of its whole step: the text is
    the module's one compile of that step, not a second compile of the
    layer alone."""
    from ray_tpu.ops import attention
    cfg = cell_configuration(FAMILY.cell, attention="flash")
    assert cfg.head_dim == 64 and attention.tokens_first(
        64, cfg.n_heads, cfg.kv_heads)
    assert (cell_step.mix["global_batch"], cell_step.mix["seq"]) == (2, 8192)
    heads_of_64_stay_by_token(cell_step.text, 2, 8192, cfg.n_heads,
                              cfg.kv_heads)
