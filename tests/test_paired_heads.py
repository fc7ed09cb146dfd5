"""Heads of 64 in pairs (ops/attention.py: `tokens_first`, `_each_head`):
q, k, v, o and their cotangents stay [B, S, heads * 64] from the
projections to `wo`, a grid step of the flash kernels holds whole lane tiles
with two heads in each, and ops/rope.py rotates q and k where they lie. On
the CPU, the kernels in interpret mode."""

import numpy as np
import pytest

# (query heads, key/value heads): gpt2s, lfm2, a shard of smollm on four chips
HEAD_COUNTS = [(12, 12), (32, 8), (16, 16)]
KINDS = ["causal", "window"]


def _columns(x):
    """[B, H, S, D] -> [B, S, H * D]."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _operands(jax, heads, kv_heads, seq, dtype, batch=1):
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(heads + seq), 4)
    shapes = [(batch, heads, seq, 64), (batch, kv_heads, seq, 64),
              (batch, kv_heads, seq, 64)]
    q, k, v = (jax.random.normal(key, shape, jnp.float32).astype(dtype)
               for key, shape in zip(keys, shapes))
    g = jax.random.normal(keys[3], (batch, seq, heads * 64),
                          jnp.float32).astype(dtype)
    return q, k, v, g


def _kind_options(kind):
    return {"window": 100} if kind == "window" else {}


def _selection(jax, batch, seq):
    import jax.numpy as jnp
    chosen = jax.random.uniform(jax.random.PRNGKey(7), (batch, seq, seq)) < 0.4
    chosen |= jnp.eye(seq, dtype=bool)[None]
    return (chosen & jnp.tril(jnp.ones((seq, seq), bool))).astype(jnp.int8)


def _three_ways(jax, heads, kv_heads, seq, dtype, options, blocks=128,
                pairs=True):
    """(value, dq, dk, dv) with every tensor [B, S, heads * 64], from
    mha_reference in float32, from the per-head kernels and (`pairs`) from
    the heads in pairs, under one cotangent that tells heads, rows and
    columns apart."""
    import jax.numpy as jnp
    from ray_tpu.ops import attention
    q, k, v, g = _operands(jax, heads, kv_heads, seq, dtype)
    tiling = {} if blocks is None else {"block_q": blocks, "block_k": blocks}

    def weighed(out):
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))

    def reference(q, k, v):
        return weighed(_columns(attention.mha_reference(q, k, v, **options)))

    def per_head(q, k, v):
        return weighed(_columns(attention.flash_attention_native(
            q, k, v, **tiling, **options)))

    def in_pairs(q, k, v):
        return weighed(attention.flash_attention_native(
            _columns(q), _columns(k), _columns(v),
            in_pairs=(heads, kv_heads), **tiling, **options))
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    results = []
    for fn, args in ((reference, (q32, k32, v32)), (per_head, (q, k, v)),
                     (in_pairs, (q, k, v)))[:2 + pairs]:
        value, grads = jax.jit(jax.value_and_grad(fn, (0, 1, 2)))(*args)
        results.append((value, *(_columns(x).astype(jnp.float32)
                                 for x in grads)))
    return results


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("heads,kv_heads", HEAD_COUNTS,
                         ids=lambda n: str(n))
def test_heads_in_pairs_give_the_references_values_and_gradients(
        jax_cpu, heads, kv_heads, kind):
    """float32, two blocks a row: the output (through the weighed sum) and
    dq, dk, dv of the paired kernels against mha_reference and against the
    per-head kernels; the forward's arithmetic a head is the per-head
    kernel's (the partner's lanes add exact zeros), so the values are
    equal, not close."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import tokens_first
    assert tokens_first(64, heads, kv_heads)
    options = _kind_options(kind)
    reference, per_head, in_pairs = _three_ways(
        jax, heads, kv_heads, 256, jnp.float32, options)
    assert float(in_pairs[0]) == float(per_head[0])
    np.testing.assert_allclose(in_pairs[0], reference[0], rtol=2e-5)
    for got, head, want in zip(in_pairs[1:], per_head[1:], reference[1:]):
        np.testing.assert_allclose(got, want, atol=3e-5)
        np.testing.assert_allclose(got, head, atol=1e-5)


@pytest.mark.parametrize("heads,kv_heads", HEAD_COUNTS,
                         ids=lambda n: str(n))
def test_a_selection_keeps_heads_of_64_by_head(jax_cpu, heads, kv_heads):
    """Under a selection the kernels take q, k and v by head at every width
    (the indexer's own kernels read q and k so, models/gpt.py:
    `_selected_attention`): at the three head counts the per-head kernels
    give the reference's values and gradients, and a selection over heads
    in pairs is refused, not run another way."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import attention
    options = {"selected": _selection(jax, 1, 256)}
    reference, per_head = _three_ways(
        jax, heads, kv_heads, 256, jnp.float32, options, pairs=False)
    np.testing.assert_allclose(per_head[0], reference[0], rtol=2e-5)
    for got, want in zip(per_head[1:], reference[1:]):
        np.testing.assert_allclose(got, want, atol=3e-5)
    q, k, v, _ = _operands(jax, heads, kv_heads, 256, jnp.float32)
    with pytest.raises(ValueError, match="by head"):
        attention.flash_attention_native(
            _columns(q), _columns(k), _columns(v),
            in_pairs=(heads, kv_heads), **options)


@pytest.mark.parametrize("heads,kv_heads", HEAD_COUNTS,
                         ids=lambda n: str(n))
def test_heads_in_pairs_round_once_in_bfloat16(jax_cpu, heads, kv_heads):
    """bf16 in and out, one block a row (the rule's blocks): a gradient is
    rounded once from float32 accumulators, as the per-head kernels round
    it."""
    jax = jax_cpu
    import jax.numpy as jnp
    reference, per_head, in_pairs = _three_ways(
        jax, heads, kv_heads, 128, jnp.bfloat16, {}, blocks=None)
    for got, head, want in zip(in_pairs[1:], per_head[1:], reference[1:]):
        np.testing.assert_allclose(got, want, atol=0.15, rtol=2e-2)
        np.testing.assert_allclose(got, head, atol=0.04, rtol=1e-2)


@pytest.mark.parametrize("heads,kv_heads,width,fills", [
    (12, 12, 64, True), (32, 8, 64, True), (16, 16, 64, True),
    (4, 2, 64, True), (16, 8, 64, True),
    (3, 3, 64, False),      # an odd head count: a tile of k half empty
    (9, 3, 64, False),      # three query heads a key/value head
    (6, 2, 64, False),
    (8, 1, 64, False),      # one key/value head fills half a tile
    (4, 4, 32, False),      # four heads a tile: not built
    (0, 0, 64, False),      # head counts not given
    (6, 2, 128, True), (3, 1, 256, True),
], ids=lambda n: str(n))
def test_the_shape_decides_where_the_heads_lie(heads, kv_heads, width, fills):
    from ray_tpu.ops.attention import tokens_first
    assert tokens_first(width, heads, kv_heads) == fills


@pytest.mark.parametrize("heads,kv_heads", [(3, 3), (9, 3)],
                         ids=lambda n: str(n))
def test_head_counts_that_fill_no_pairs_take_the_per_head_path(
        jax_cpu, heads, kv_heads):
    """The model's path at an odd head count, and at three query heads a
    key/value head: q, k and v split by head, the kernels' [B, H, S, 64]
    turned under `attn_out`, the reference's values and gradients; handing
    such a count in pairs is refused."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    from ray_tpu.ops import attention
    from ray_tpu.ops.rope import rope_table
    cfg = gpt.GPTConfig(vocab_size=64, d_model=heads * 64, n_layers=1,
                        n_heads=heads, n_kv_heads=kv_heads, d_ff=64,
                        max_seq=128, dtype=jnp.float32)
    q, k, v, g = _operands(jax, heads, kv_heads, 128, jnp.float32, batch=2)
    table = rope_table(128, 64, cfg.rope_of("attention"))

    def loss(q, k, v):
        return jnp.sum(gpt._flash_on_mesh(_columns(q), _columns(k),
                                          _columns(v), table, cfg, None) * g)

    def oracle(q, k, v):
        positions = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
        spec = cfg.rope_of("attention")
        return jnp.sum(_columns(attention.mha_reference(
            gpt._rope(q, spec, positions), gpt._rope(k, spec, positions),
            v)) * g)
    # the kernels' blocks are one head's, [B * H, S, 64]
    assert f"f32[{2 * heads},128,64]" in str(jax.make_jaxpr(loss)(q, k, v))
    got = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(oracle, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=3e-5)
    with pytest.raises(ValueError, match="heads in pairs"):
        attention.flash_attention_native(
            _columns(q), _columns(k), _columns(v),
            in_pairs=(heads, kv_heads))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [2, 12])
def test_rotation_in_place_has_the_by_head_kernels_bits(jax_cpu, dtype,
                                                        heads):
    """rope_split(by_head=False): [B, S, H * 64] rotated where it lies has
    the bits of the by-head kernel turned back (the jnp formulation's
    `_split_reference` to a float32 rounding of the sum, as
    tests/test_rope.py holds the by-head kernel), its gradient (rope_merge
    in place) the bits of the by-head kernel's; with no table it is its
    operand and traces no kernel."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import rope
    dtype = jnp.dtype(dtype).type
    keys = jax.random.split(jax.random.PRNGKey(heads), 2)
    x, g = (jax.random.normal(key, (2, 64, heads * 64),
                              jnp.float32).astype(dtype) for key in keys)
    table = rope.rope_table(64, 64, 10000.0)
    assert rope._rope_blocks(64, heads, 64, x.dtype.itemsize) is not None

    def turned_back(y):
        return y.transpose(0, 2, 1, 3).reshape(x.shape)
    got = rope.rope_split(x, 64, table, by_head=False)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_array_equal(
        got, turned_back(rope.rope_split(x, 64, table)))
    want = turned_back(rope._split_reference(x.astype(jnp.float32), table,
                                             64))
    np.testing.assert_array_less(
        jnp.abs(got.astype(jnp.float32) - want),
        jnp.abs(want) * (2.0 ** -8 if x.dtype.itemsize == 2 else 1e-6)
        + 1e-6)

    def weighed(split):
        return lambda x: jnp.sum(split(x).astype(jnp.float32)
                                 * g.astype(jnp.float32))
    np.testing.assert_array_equal(
        jax.grad(weighed(lambda x: rope.rope_split(
            x, 64, table, by_head=False)))(x),
        jax.grad(weighed(lambda x: turned_back(rope.rope_split(
            x, 64, table))))(x))
    assert rope.rope_split(x, 64, by_head=False) is x
    jaxpr = str(jax.make_jaxpr(jax.grad(weighed(
        lambda x: rope.rope_split(x, 64, table, by_head=False))))(x))
    assert jaxpr.count("name=rope_merge") == 1


def test_a_ragged_shape_is_rotated_in_place_by_the_jnp_formulation(jax_cpu):
    """Three heads of 64 fill no whole lane tiles: `_rope_blocks` refuses
    them and the jnp formulation keeps the layout it was asked for."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import rope
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 3 * 64), jnp.float32)
    table = rope.rope_table(64, 64, 10000.0)
    assert rope._rope_blocks(64, 3, 64, 4) is None
    got = rope.rope_split(x, 64, table, by_head=False)
    np.testing.assert_array_equal(
        got, rope.rope_split(x, 64, table).transpose(0, 2, 1, 3).reshape(
            x.shape))


@pytest.mark.parametrize("kind", KINDS)
def test_the_kept_output_of_a_pair_gives_the_gradients(jax_cpu, kind):
    """Under a jax.checkpoint that keeps FLASH_OUT and FLASH_LSE (a
    layer's policy) the kept value is the [B, S, H * 64] the kernels wrote,
    the forward kernel is not traced into the recompute pass, and the three
    gradients are those without the checkpoint, bit for bit."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import attention
    heads, kv_heads, seq = 8, 2, 256
    q, k, v, g = _operands(jax, heads, kv_heads, seq, jnp.float32)
    q, k, v = _columns(q), _columns(k), _columns(v)
    options = _kind_options(kind)
    name = attention._kernel_name("fwd", options.get("window"), None)

    def flash(q, k, v):
        return attention.flash_attention_native(
            q, k, v, in_pairs=(heads, kv_heads), block_q=128, block_k=128,
            **options)
    kept = jax.checkpoint(
        flash, policy=jax.checkpoint_policies.save_only_these_names(
            attention.FLASH_OUT, attention.FLASH_LSE))

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * g)
    got = jax.grad(loss(kept), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    jaxpr = str(jax.make_jaxpr(jax.grad(loss(kept), (0, 1, 2)))(q, k, v))
    assert jaxpr.count(f"name={name}\n") + jaxpr.count(f"name={name} ") == 1


def test_whole_pairs_a_shard_on_a_2x2_mesh(jax_cpu):
    """Eight heads of 64 on four key/value heads over fsdp=2 x tensor=2:
    a shard holds four query heads on two key/value heads, a whole tile of
    k and the two tiles of q that read it, and writes its own columns of
    its own batch row: the one-device values and the three gradients, with
    two rope_split calls (q's and k's) inside the shard_map and none for
    v. (Beside
    test_chip_compile.py::test_tokens_first_heads_are_whole_per_shard_on_a_2x2_mesh,
    which holds heads of 128.)"""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    from ray_tpu.ops.attention import tokens_first
    from ray_tpu.ops.rope import rope_table
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    cfg = gpt.GPTConfig(vocab_size=64, d_model=512, n_layers=1, n_heads=8,
                        n_kv_heads=4, d_ff=64, max_seq=128,
                        dtype=jnp.float32)
    assert cfg.head_dim == 64 and tokens_first(64, 4, 2)
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                      devices=jax.devices()[:4])
    q, k, v, g = _operands(jax, 8, 4, 128, jnp.float32, batch=2)
    q, k, v = _columns(q), _columns(k), _columns(v)
    table = rope_table(128, 64, cfg.rope_of("attention"))

    def attend(mesh):
        def loss(q, k, v):
            out = gpt._flash_on_mesh(q, k, v, table, cfg, mesh)
            assert out.shape == (2, 128, 8 * 64)
            return jnp.sum(out * g), out
        return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)
    (_, out), grads = jax.jit(attend(mesh))(q, k, v)
    (_, want), want_grads = jax.jit(attend(None))(q, k, v)
    np.testing.assert_allclose(out, want, atol=1e-6)
    for a, b in zip(grads, want_grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5)
    jaxpr = str(jax.make_jaxpr(lambda q, k, v: attend(mesh)(q, k, v)[0][0])(
        q, k, v))
    assert jaxpr.count("name=rope_split") == 2
    # nothing by head: a shard holds [1, 128, 4 | 2 heads * 64]
    assert not any(f"f32[1,{h},128,64]" in jaxpr for h in (2, 4))


def test_an_odd_count_a_shard_falls_back_under_the_mesh(jax_cpu):
    """Six heads of 64 over tensor=2 are three a shard: whole heads, no
    whole pairs; the shards split by head and `_tokens_first` turns their
    [B, H, S, 64] outside the shard_map, the one-device values."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    from ray_tpu.ops.rope import rope_table
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    cfg = gpt.GPTConfig(vocab_size=64, d_model=384, n_layers=1, n_heads=6,
                        d_ff=64, max_seq=128, dtype=jnp.float32)
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                      devices=jax.devices()[:4])
    q, k, v, _ = _operands(jax, 6, 6, 128, jnp.float32, batch=2)
    q, k, v = _columns(q), _columns(k), _columns(v)
    table = rope_table(128, 64, cfg.rope_of("attention"))
    sharded = jax.jit(lambda q, k, v: gpt._flash_on_mesh(
        q, k, v, table, cfg, mesh))
    assert "f32[1,3,128,64]" in str(jax.make_jaxpr(sharded)(q, k, v))
    # on one device the six heads fill three pairs
    np.testing.assert_allclose(
        sharded(q, k, v), gpt._flash_on_mesh(q, k, v, table, cfg, None),
        atol=2e-6)
