"""Sliding-window attention in the flash kernels (ops/attention.py) and a
rotation a kind of layer (ops/rope.py) against their references on the CPU
(the kernels in interpret mode), and what laguna_train_1chip hands the
chip's compiler, for a described v5e: the window kernels at the cell's
shape, its two kinds of attention layer and its whole step. The family's
program against the reference of benchmark/families/laguna.py:
tests/test_window_attention_model.py."""

import math
import re

import numpy as np
import pytest

from helpers.described_chip import (  # noqa: F401 — fixtures
    cell_configuration, cell_step, kernel_ops, mixer_layer, v5e,
    written_in_entry)
from helpers.families import family, read  # noqa: F401
from test_window_attention_model import FAMILY  # noqa: F401


# ---------------------------------------------------------------------------
# (a) the three window kernels
# ---------------------------------------------------------------------------


def _qkv(jax, heads, kv_heads, seq, dim, dtype):
    keys = jax.random.split(jax.random.PRNGKey(seq + heads), 4)
    shapes = [(2, heads, seq, dim), (2, kv_heads, seq, dim),
              (2, kv_heads, seq, dim), (2, heads, seq, dim)]
    return [jax.random.normal(k, s, dtype) for k, s in zip(keys, shapes)]


@pytest.mark.parametrize("heads,kv_heads,seq,dim,window,block", [
    (2, 2, 64, 16, 5, 16),        # smaller than a block
    (2, 1, 64, 16, 16, 16),       # a block
    (4, 2, 64, 16, 23, 16),       # larger than one, grouped queries
    (2, 2, 64, 16, 40, 32),       # larger than a block, under two
    (6, 2, 64, 16, 1, 16),        # a query sees itself alone
    (2, 1, 512, 32, 100, None),   # the rule: one block of 512, row groups
    (2, 2, 4096, 32, 300, None),  # the rule: two blocks of 2048 a row
], ids=["under_a_block", "a_block", "over_a_block_grouped", "under_two",
        "itself_alone", "rule_512", "rule_2048"])
def test_window_kernels_match_the_reference(jax_cpu, heads, kv_heads, seq,
                                            dim, window, block):
    """flash_win_fwd, flash_win_bwd_dq and flash_win_bwd_dkv against
    mha_reference(window=): the output and all three gradients, dK and dV
    at the key/value heads' count."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference
    q, k, v, g = _qkv(jax, heads, kv_heads, seq, dim, jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, window=window, block_q=block,
                               block_k=block)

    def oracle(q, k, v):
        return mha_reference(q, k, v, window=window)
    np.testing.assert_allclose(flash(q, k, v), oracle(q, k, v), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(oracle(*a) * g), (0, 1, 2))(q, k, v)
    assert got[1].shape == got[2].shape == (2, kv_heads, seq, dim)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_window_kernels_write_heads_of_128_tokens_first(jax_cpu, dtype):
    """flash_win_* are the same bodies under the same specs: at heads of
    128 (6 on 2, two blocks a row, a window of 100) o leaves and dO arrives
    as [B, S, H * 128]."""
    import jax.numpy as jnp
    from helpers.flash_layout import check_tokens_first
    check_tokens_first(jax_cpu, jnp.dtype(dtype).type, window=100)


def test_the_reference_window_is_itself_and_the_ones_before(jax_cpu):
    """mha_reference(window=3) written out: query i mixes v_{i-2..i}."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import mha_reference
    q = jnp.zeros((1, 1, 8, 4))             # equal scores: a plain mean
    v = jnp.arange(8.0)[None, None, :, None] * jnp.ones((1, 1, 8, 4))
    out = mha_reference(q, q, v, window=3)[0, 0, :, 0]
    np.testing.assert_allclose(out, [0, 0.5, 1, 2, 3, 4, 5, 6], atol=1e-6)
    with pytest.raises(ValueError, match="band under the causal mask"):
        mha_reference(q, q, v, causal=False, window=3)


def _kernel_names(jax, fn, *args):
    return set(re.findall(r"name=(flash_(?:win_)?(?:fwd|bwd_dq|bwd_dkv))\b",
                          str(jax.make_jaxpr(fn)(*args))))


def test_kernel_names_with_a_window_and_without(jax_cpu):
    """A window gives the three kernels names of their own (a trace row, and
    a roofline, is of ONE shape); without one, and with one that reaches
    the sequence's start from its end, they are the parent's."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.util.profiling import KERNELS
    q, k, v, _ = _qkv(jax, 4, 2, 256, 32, jnp.bfloat16)

    def grads(window):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, window=window).astype(jnp.float32).sum(), (0, 1, 2))
    windowed = {"flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv"}
    plain = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert windowed | plain <= set(KERNELS)
    assert _kernel_names(jax, grads(64), q, k, v) == windowed
    assert _kernel_names(jax, grads(None), q, k, v) == plain
    assert _kernel_names(jax, grads(256), q, k, v) == plain
    assert _kernel_names(jax, grads(10_000), q, k, v) == plain


def test_blocks_are_the_shapes_and_the_steps_the_bands():
    """The window does not move the blocks (`_block_sizes`: the sweep of
    PERF.md, PR 37); it sets how many of them a grid row walks."""
    from ray_tpu.ops.attention import _band_steps, _block_sizes
    # laguna_train_1chip: 8192 positions at head 128, window 512: two of
    # the four blocks of 2048 a grid row
    assert _block_sizes(8192, 8192, 128) == (
        (2048, 2048, 256), (2048, 2048, 128), (2048, 2048, 128))
    assert _band_steps(2048, 2048, 512) == 2
    assert _band_steps(2048, 2048, 1) == 1        # the diagonal block alone
    assert _band_steps(2048, 2048, 2049) == 2 == _band_steps(512, 512, 513)
    assert _band_steps(2048, 2048, 2050) == 3 == _band_steps(512, 512, 514)
    assert _band_steps(1024, 512, 512) == 3       # outer's two and one more


@pytest.mark.parametrize("outer,major,group,window", [
    (64, 32, 16, 24), (32, 32, 8, 40), (64, 16, 16, 5), (128, 128, 32, 128)])
@pytest.mark.parametrize("upper", [False, True], ids=["keys", "queries"])
def test_band_tiles_cover_the_band_and_mask_only_on_an_edge(outer, major,
                                                            group, window,
                                                            upper):
    """_for_band's static geometry, replayed with numpy: over the steps of
    one outer block the tiles hold every kept pair once, nothing outside
    them is kept, and a tile with no edge through it carries no mask bit."""
    from ray_tpu.ops import attention as A
    seq = 4 * outer
    kept_want = np.zeros((seq, seq), bool)       # [query, key]
    for i in range(seq):
        kept_want[i, max(0, i - window + 1):i + 1] = True
    seen = np.zeros((seq, seq), int)
    blocks = seq // major

    class When:                                   # pl.when, eagerly
        def __init__(self, cond):
            self.cond = cond

        def __call__(self, fn):
            if self.cond:
                fn()
    real_when, A.pl.when = A.pl.when, When
    try:
        for o in range(seq // outer):
            first = (o * outer // major if upper
                     else (o + 1) * (outer // major) - 1)
            for step in range(A._band_steps(outer, major, window)):
                def tile(rows, cols, band, step=step):
                    block = first + step if upper else first - step
                    r = np.arange(rows.start, rows.stop) + o * outer
                    c = np.arange(cols.start, cols.stop) + block * major
                    q, k = (c, r) if upper else (r, c)
                    diff = q[None, :] - k[:, None] if upper \
                        else q[:, None] - k[None, :]
                    assert diff[0, 0] == band.diff
                    keep = (diff >= 0) & (diff < band.window)
                    assert keep.any()
                    pairs = (np.ix_(c, r) if upper else np.ix_(r, c))
                    seen[pairs] += keep.T if upper else keep
                A._for_band(upper, step, first, blocks, outer, major, group,
                            window, tile)
    finally:
        A.pl.when = real_when
    np.testing.assert_array_equal(seen, kept_want.astype(int))


def test_band_mask_touches_the_chunks_an_edge_crosses(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A
    s = jnp.zeros((128, 384), jnp.float32)
    # query - key = 200 + row - col: causal edge in chunk 1 (cols 128..255)
    # and 2, the window's (64) in chunk 1
    masked = np.asarray(A._band_mask(s, A._Band(200, 64), False))
    diff = 200 + np.arange(128)[:, None] - np.arange(384)[None, :]
    np.testing.assert_array_equal(masked == 0, (diff >= 0) & (diff < 64))
    # a tile wholly inside the band comes back as it is, no select
    jaxpr = str(jax.make_jaxpr(
        lambda s: A._band_mask(s, A._Band(400, 1000), False))(s))
    assert "select_n" not in jaxpr and "iota" not in jaxpr


@pytest.mark.parametrize("kwargs,says", [
    ({"causal": False, "window": 8}, "window=8 needs causal"),
    ({"window": 0}, "window=0 needs causal"),
    ({"window": 8, "block_q": 32, "block_k": 16}, "block_q=32 == block_k"),
], ids=["not_causal", "empty", "blocks"])
def test_flash_refuses_a_window_it_cannot_run(jax_cpu, kwargs, says):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 2, 64, 16))
    with pytest.raises(ValueError, match=says):
        flash_attention(q, q, q, **kwargs)
    with pytest.raises(ValueError, match="seq_q == seq_k"):
        flash_attention(q[:, :, :32], q, q, window=8)


# ---------------------------------------------------------------------------
# (b) a rotation a kind
# ---------------------------------------------------------------------------


def test_yarn_frequencies_are_the_published_blend():
    """Pair i of 32 (64 rotated columns, theta 500 000, factor 64 over 4096
    positions): below pair 5 as they are, from pair 16 divided by 64, a
    linear ramp between; and the attention factor is 0.1 ln 64 + 1."""
    from ray_tpu.ops.rope import yarn_frequencies
    got = yarn_frequencies(500000.0, 64, 64.0, 4096, 64.0, 1.0)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    turns = 4096 * plain / (2 * math.pi)
    assert turns[5] > 64 > turns[6] and turns[15] > 1 > turns[16]
    ramp = np.clip((np.arange(32) - 5) / (16 - 5), 0, 1)
    np.testing.assert_allclose(got, plain * (1 - ramp) + plain / 64 * ramp,
                               rtol=1e-6)
    np.testing.assert_allclose(got[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(got[16:], plain[16:] / 64, rtol=1e-6)
    cell = read("benchmark", "configs", "laguna-xs.2.json")
    assert cell["rope_parameters"]["full_attention"]["attention_factor"] \
        == pytest.approx(0.1 * math.log(64) + 1)


@pytest.mark.parametrize("heads", [6, 8, 2])
def test_partial_table_through_the_kernels_is_the_jnp_rotation(jax_cpu,
                                                               heads):
    """rope_split with a table whose unrotated columns pass (cos 1, sin 0)
    on columns in halves_apart's order = the jnp rotation of the head's
    first half as halves, in that order; at three head counts."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import _rope
    from ray_tpu.ops.rope import (RopeSpec, halves_apart, rope_split,
                                  rope_table)
    spec = RopeSpec(theta=500000.0, rotated=0.5, yarn=(64.0, 4096, 64.0, 1.0),
                    attention_factor=1.4158883)
    dim, seq = 128, 32
    order = np.asarray(halves_apart(dim, spec.columns(dim)))
    assert sorted(order) == list(range(dim))
    assert list(order[:32]) == list(range(32))
    assert list(order[64:96]) == list(range(32, 64))
    x = jax.random.normal(jax.random.PRNGKey(heads), (2, seq, heads * dim))
    want = _rope(x.reshape(2, seq, heads, dim).transpose(0, 2, 1, 3), spec,
                 jnp.broadcast_to(jnp.arange(seq), (2, seq)))
    permuted = x.reshape(2, seq, heads, dim)[..., order].reshape(x.shape)
    got = rope_split(permuted, dim, rope_table(seq, dim, spec))
    np.testing.assert_allclose(got, want[..., order], atol=1e-6)
    # the columns that pass are untouched, to the bit
    np.testing.assert_array_equal(
        got[..., 32:64], permuted.reshape(2, seq, heads, dim).transpose(
            0, 2, 1, 3)[..., 32:64])


# ---------------------------------------------------------------------------
# (c) for a described v5e: the window kernels, the two kinds of attention
# layer and (imported) the whole step
# ---------------------------------------------------------------------------


def test_window_kernels_compile_at_8192_positions_of_128(v5e):
    """laguna_train_1chip's sliding layers' call, [2, 64 on 8, 8192, 128]
    under a window of 512, forward and both backward kernels: two blocks of
    2048 a grid row where the causal kernels walk up to four; dK and dV
    leave at the key/value heads' count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.attention import flash_attention

    def shape(h):
        return jax.ShapeDtypeStruct((2, h, 8192, 128), jnp.bfloat16,
                                    sharding=SingleDeviceSharding(v5e[0]))
    grads = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=512,
        interpret=False).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    compiled = grads.lower(shape(64), shape(8), shape(8)).compile()
    text = compiled.as_text()
    for kernel in ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv"):
        assert len(kernel_ops(text, kernel)) == 1, kernel
    dq, dk, dv = compiled.out_info
    assert dq.shape == (2, 64, 8192, 128)
    assert dk.shape == dv.shape == (2, 8, 8192, 128)


# Imported last: a module's names are collected in the order they are bound,
# so the chip's compiler gets this file's programs after its own tests have
# run, at another minute of a run than the other families' files.
from helpers.described_chip import (  # noqa: E402,F401
    test_the_cells_that_were_there_lower_to_the_same_step,
    test_cell_step_compiles_under_the_chips_memory,
    test_cell_step_makes_a_heads_dw_where_its_logits_are,
    test_the_new_scopes_are_regions_and_reach_the_compiled_step)


@pytest.mark.parametrize("kind,heads", [("attention", 48), ("window", 64)])
def test_heads_of_128_reach_wo_without_a_layout_pass(cell_step, kind, heads):
    """laguna_train_1chip's two kinds of attention layer, [2, 48 | 64 on 8,
    8192, 128], through the flash kernels, the gate a head and `wo`, value
    and gradient under the layer's remat policy, for one described chip,
    read off the module's one compile of the whole step (its entry
    computation holds the layers): a kind's three kernels once a layer of
    that kind (the forward's kept results reach the backward), and in the
    entry computation no `copy`, `transpose` or
    `reshape` writes a tensor of o's element count: the kernels write o and
    read dO as [2, 8192, H * 128], and the gate, its gradient and delta
    reach a head's columns where they lie (`ops/attention.py:head_columns`). A
    [2, 8192, H, 128] view anywhere between the kernels and `wo` brings the
    copies back: the chip tiles that view 8 heads x 128 lanes of one token,
    the columns 8 tokens x 128 lanes."""
    cfg = cell_configuration(FAMILY.cell, attention="flash")
    batch, seq = cell_step.mix["global_batch"], cell_step.mix["seq"]
    assert (batch, seq) == (2, 8192)
    assert (cfg.heads_of(kind), cfg.kv_heads, cfg.head_dim) == (heads, 8, 128)
    layer, group = mixer_layer(cfg, kind, None)
    assert layer[group]["wg"].shape == (cfg.d_model, heads)
    text = cell_step.text
    name = "flash_win_" if kind == "window" else "flash_"
    layers = sum(k == kind for k in cfg.layer_kinds)
    assert layers == (3 if kind == "window" else 2)
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert len(kernel_ops(text, name + kernel)) == layers, kernel
    for dims, line in written_in_entry(text):
        assert math.prod(dims) != batch * seq * heads * 128, line
