"""ops/rope.py: the head split and the rotation between the attention
projections and the flash kernels, as one pass (Pallas, interpreted here on
the CPU), against the jnp formulation it replaces on the flash path:
models/gpt.py:_rope after reshape + transpose, and for a latent block's q,
k and v models/gpt.py:_latent_heads (`_rope_tail`, concatenates,
transposes)."""

import numpy as np
import pytest

THETA = 10000.0

# [batch, heads, seq, head_dim] of the benchmark's cells (gpt2s, smollm-1.7b
# on one of four chips, olmoe-1b-7b) and of a 128-token score batch. The
# blocks do not depend on the batch, so it is cut to what a CPU test can
# hold; tests/test_chip_compile.py compiles the whole shapes for the chip.
CELL_SHAPES = [(2, 12, 1024, 64), (1, 16, 2048, 64), (1, 16, 4096, 128),
               (8, 32, 128, 64)]


def _reference(jnp, x, heads, head_dim, rotate):
    """What the flash path did before: reshape, transpose, _rope."""
    from ray_tpu.models.gpt import _rope
    b, s, _ = x.shape
    y = x.reshape(b, s, heads, head_dim).transpose(0, 2, 1, 3)
    if rotate:
        y = _rope(y, THETA, jnp.broadcast_to(jnp.arange(s)[None, :], (b, s)))
    return y


def _inputs(jax, jnp, shape, dtype):
    b, h, s, d = shape
    kx, kw = jax.random.split(jax.random.PRNGKey(s + d))
    x = jax.random.normal(kx, (b, s, h * d), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (b, h, s, d), jnp.float32).astype(dtype)
    return x, w


# ---------------------------------------------------------------- the tiles
@pytest.mark.parametrize("seq,heads,head_dim,itemsize,blocks", [
    (1024, 12, 64, 2, (512, 768)),      # gpt2s_train_1chip
    (2048, 16, 64, 2, (512, 1024)),     # smollm17_train_4chip, tensor = 2
    (4096, 16, 128, 2, (256, 2048)),    # olmoe_train_1chip
    (128, 32, 64, 2, (128, 2048)),      # a 128-token score batch
    (1024, 6, 64, 2, (1024, 384)),      # gpt2s with tensor = 2
    (1024, 12, 64, 4, (512, 768)),      # fp32 activations
    (1024, 4, 256, 2, (512, 1024)),     # a head of two lane tiles
    (256, 16, 16, 2, (256, 256)),       # eight heads a lane tile
    (32, 4, 32, 4, (32, 128)),          # models/gpt.py GPTConfig.tiny's heads
])
def test_rope_blocks_follow_from_the_shape(seq, heads, head_dim, itemsize,
                                           blocks):
    from ray_tpu.ops.rope import _lane_tile, _rope_blocks
    got = _rope_blocks(seq, heads, head_dim, itemsize)
    assert tuple(got) == blocks
    # whole lane tiles of whole heads; blocks that divide the plane
    assert got.cols % _lane_tile(head_dim) == 0
    assert seq % got.rows == 0 and (heads * head_dim) % got.cols == 0


FALLBACK_SHAPES = [
    (128, 3, 64, 2),     # an odd head count at width 64: half a lane tile over
    (128, 4, 48, 2),     # a width that neither divides nor multiplies 128
    (128, 2, 192, 2),    # the same, above 128
    (100, 4, 64, 2),     # a ragged sequence (bf16 packs 16 rows a register)
    (100, 4, 64, 4),     # and for fp32 (8 rows)
    (128, 4, 16, 2),     # four heads of 16 do not fill a lane tile
]


@pytest.mark.parametrize("seq,heads,head_dim,itemsize", FALLBACK_SHAPES)
def test_rope_blocks_refuse_what_does_not_tile(seq, heads, head_dim,
                                               itemsize):
    from ray_tpu.ops.rope import _rope_blocks
    assert _rope_blocks(seq, heads, head_dim, itemsize) is None


def test_rope_blocks_divide_every_plane():
    from ray_tpu.ops.rope import _STEP_ELEMENTS, _rope_blocks
    for seq in (16, 128, 384, 1024, 1536, 4096, 8192):
        for heads, head_dim in ((2, 64), (12, 64), (32, 64), (16, 128),
                                (4, 256), (8, 32)):
            rows, cols = _rope_blocks(seq, heads, head_dim, 2)
            assert seq % rows == 0 and (heads * head_dim) % cols == 0
            assert rows % 16 == 0 and cols % 128 == 0
            assert rows * cols <= max(_STEP_ELEMENTS, 16 * cols)


# ------------------------------------------------- against _rope + transpose
@pytest.mark.parametrize("rotate", [True, False], ids=["rotate", "split"])
@pytest.mark.parametrize("shape", CELL_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_rope_split_forward_has_ropes_bits(jax_cpu, shape, rotate):
    """bf16 in, products and the sum in float32, one rounding: `_rope`
    after the transpose, bit for bit on the chip (PERF.md, PR 28). Here
    XLA's CPU backend contracts multiply-adds into FMAs where it likes,
    in the interpreted kernel and in `_rope` not alike (a TPU's vector
    unit has none to contract into), so an element in ~10^5, whose sum
    lies within a float32 ulp (of its products) of a bf16 tie, rounds to
    the neighbouring bf16 value."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import rope
    b, h, s, d = shape
    x, _ = _inputs(jax, jnp, shape, jnp.bfloat16)
    table = rope.rope_table(s, d, THETA) if rotate else ()
    got = jax.jit(lambda x: rope.rope_split(x, d, table))(x)
    want = jax.jit(lambda x: _reference(jnp, x, h, d, rotate))(x)
    assert got.shape == (b, h, s, d) and got.dtype == jnp.bfloat16
    differs = np.asarray(got != want)
    if not rotate:
        assert not differs.any()
        return
    assert differs.mean() < 1e-4
    got, want = (np.asarray(a.astype(jnp.float32))[differs]
                 for a in (got, want))
    assert (np.abs(got - want)
            <= 2.0 ** -7 * np.maximum(np.abs(got), np.abs(want)) + 1e-6).all()


@pytest.mark.parametrize("rotate", [True, False], ids=["rotate", "split"])
@pytest.mark.parametrize("shape", CELL_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_rope_merge_is_the_gradient_within_bf16_rounding(jax_cpu, shape,
                                                         rotate):
    """`rope_merge` rounds once; the transpose of `_rope` rounds each
    product to bf16 and adds in bf16. Both against the gradient in float32:
    the kernel's error is a rounding, and no larger than the jnp one's."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import rope
    b, h, s, d = shape
    x, w = _inputs(jax, jnp, shape, jnp.bfloat16)
    table = rope.rope_table(s, d, THETA) if rotate else ()

    def pulled_back(f, x):
        return jax.jit(lambda x, w: jax.vjp(f, x)[1](w)[0])(x, w.astype(
            x.dtype))
    got = pulled_back(lambda x: rope.rope_split(x, d, table), x)
    jnp_way = pulled_back(lambda x: _reference(jnp, x, h, d, rotate), x)
    true = pulled_back(lambda x: _reference(jnp, x, h, d, rotate),
                       x.astype(jnp.float32))
    assert got.shape == x.shape and got.dtype == jnp.bfloat16
    if not rotate:
        assert bool(jnp.array_equal(got, jnp_way))
        return
    err = jnp.abs(got.astype(jnp.float32) - true)
    # half a bf16 ulp of the value, and a float32 ulp or two of the sum
    assert bool(jnp.all(err <= jnp.abs(true) * 2.0 ** -8 + 1e-6))
    assert float(err.mean()) <= float(jnp.abs(
        jnp_way.astype(jnp.float32) - true).mean())


def test_rope_split_in_float32(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import rope
    shape = (2, 12, 256, 64)
    _, h, s, d = shape
    x, w = _inputs(jax, jnp, shape, jnp.float32)
    table = rope.rope_table(s, d, THETA)

    def both(f):
        out, pull = jax.vjp(f, x)
        return out, pull(w)[0]
    got = jax.jit(lambda: both(lambda x: rope.rope_split(x, d, table)))()
    want = jax.jit(lambda: both(lambda x: _reference(jnp, x, h, d, True)))()
    for a, r in zip(got, want):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(a, r, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("seq,heads,head_dim,itemsize", FALLBACK_SHAPES)
def test_a_shape_that_does_not_tile_takes_the_jnp_formulation(
        jax_cpu, seq, heads, head_dim, itemsize):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import rope
    dtype = jnp.bfloat16 if itemsize == 2 else jnp.float32
    x, w = _inputs(jax, jnp, (2, heads, seq, head_dim), dtype)
    table = rope.rope_table(seq, head_dim, THETA)

    def loss(f):
        return lambda x: (f(x).astype(jnp.float32) * w).sum()
    new = loss(lambda x: rope.rope_split(x, head_dim, table))
    old = loss(lambda x: _reference(jnp, x, heads, head_dim, True))
    assert "pallas_call" not in str(jax.make_jaxpr(jax.grad(new))(x))
    tol = dict(rtol=1e-6, atol=1e-6) if itemsize == 4 else dict(rtol=0, atol=0)
    np.testing.assert_allclose(
        rope.rope_split(x, head_dim, table).astype(jnp.float32),
        _reference(jnp, x, heads, head_dim, True).astype(jnp.float32), **tol)
    np.testing.assert_allclose(jax.grad(new)(x).astype(jnp.float32),
                               jax.grad(old)(x).astype(jnp.float32), **tol)


def test_rope_split_under_checkpoint_gives_the_same_gradients(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import rope
    shape = (2, 4, 128, 64)
    _, h, s, d = shape
    x, w = _inputs(jax, jnp, shape, jnp.bfloat16)
    table = rope.rope_table(s, d, THETA)      # closed over: outside the remat

    def block(x):
        q = rope.rope_split(x, d, table)
        v = rope.rope_split(x, d)
        return ((q * v).astype(jnp.float32) * w).sum()
    plain = jax.jit(jax.grad(block))(x)
    rematted = jax.jit(jax.grad(jax.checkpoint(block)))(x)
    assert bool(jnp.array_equal(plain, rematted))
    # and the recompute is there to be seen: the forward kernel twice
    jaxpr = str(jax.make_jaxpr(jax.grad(jax.checkpoint(block)))(x))
    assert jaxpr.count("name=rope_split") >= 3
    assert jaxpr.count("name=rope_merge") == 2


@pytest.mark.parametrize("kernel", ["rope_split", "rope_merge"])
def test_rope_kernels_carry_their_names(jax_cpu, kernel):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import rope
    from ray_tpu.util.profiling import KERNELS
    x = jnp.zeros((1, 128, 128), jnp.bfloat16)
    table = rope.rope_table(128, 64, THETA)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda x: rope.rope_split(
        x, 64, table).astype(jnp.float32).sum()))(x))
    assert kernel in KERNELS and f"name={kernel}" in jaxpr


def test_rope_table_is_the_rotation_of_each_head(jax_cpu):
    import jax.numpy as jnp
    from ray_tpu.ops import rope
    cos, sin = rope.rope_table(64, 32, THETA)
    assert cos.shape == sin.shape == (64, 128) and cos.dtype == jnp.float32
    # [cos, cos] and [-sin, sin] a head, the head repeated over the lane tile
    np.testing.assert_array_equal(cos[:, :16], cos[:, 16:32])
    np.testing.assert_array_equal(sin[:, :16], -sin[:, 16:32])
    np.testing.assert_array_equal(cos[:, :32], cos[:, 96:])
    np.testing.assert_array_equal(sin[:, :32], sin[:, 96:])
    # a width that fits no lane tile keeps its own
    assert rope.rope_table(64, 48, THETA)[0].shape == (64, 48)


# ------------------------------------------------- a latent block's q, k, v
# [batch, heads, seq, nope, rope, dv]: kanana2_train_1chip's widths at two
# head blocks a plane (the sum over the heads crosses the grid), a head
# block, four heads of 32 rotated columns a lane tile, a rotated part of
# a whole lane tile (no zero columns).
LATENT_SHAPES = [(1, 16, 32, 128, 64, 128), (2, 4, 64, 128, 64, 128),
                 (1, 4, 32, 128, 32, 256), (1, 2, 32, 256, 128, 128)]
LATENT_IDS = ["x".join(map(str, s)) for s in LATENT_SHAPES]


@pytest.mark.parametrize("seq,heads,nope,rope,dv,itemsize,q,kv", [
    (8192, 32, 128, 64, 128, 2, (256, 1536), (256, 2048)),   # kanana2_train_1chip
    (8192, 16, 128, 64, 128, 2, (256, 1536), (256, 2048)),   # tensor = 2
    (8192, 32, 128, 64, 128, 4, (256, 1536), (256, 2048)),   # fp32 activations
    (32, 16, 128, 64, 128, 2, (32, 1536), (32, 2048)),
    (64, 4, 128, 64, 128, 2, (64, 768), (64, 1024)),
    (32, 4, 128, 32, 256, 2, (32, 640), (32, 1536)),
    (32, 2, 256, 128, 128, 4, (32, 768), (32, 768)),
])
def test_latent_blocks_follow_from_the_shape(seq, heads, nope, rope, dv,
                                             itemsize, q, kv):
    from ray_tpu.ops.rope import _lane_tile, _latent_blocks
    got = _latent_blocks(seq, heads, nope, rope, dv, itemsize)
    assert tuple(got.q) == q and tuple(got.kv) == kv
    # whole groups of heads (their rotated parts fill a lane tile), whole
    # lane tiles of the plane, blocks that divide it
    group = _lane_tile(rope) // rope
    assert got.q.cols % (group * (nope + rope)) == 0 == got.q.cols % 128
    assert got.kv.cols % (nope + dv) == 0
    assert (heads * (nope + rope)) % got.q.cols == 0 == seq % got.q.rows
    assert (heads * (nope + dv)) % got.kv.cols == 0 == seq % got.kv.rows


@pytest.mark.parametrize("seq,heads,nope,rope,dv,itemsize", [
    (128, 4, 32, 16, 32, 2),      # tiny-kanana's heads: nope below a lane tile
    (128, 4, 128, 64, 64, 2),     # v of half a lane tile
    (128, 3, 128, 64, 128, 2),    # three rotated parts: a lane tile and a half
    (128, 4, 128, 48, 128, 2),    # a rotated part that fits no lane tile
    (100, 4, 128, 64, 128, 2),    # a ragged sequence
    (128, 4, 0, 64, 128, 2),      # no unrotated part: ops/rope.py's rope_split
])
def test_latent_blocks_refuse_what_does_not_tile(seq, heads, nope, rope, dv,
                                                 itemsize):
    from ray_tpu.ops import rope as ops
    assert ops._latent_blocks(seq, heads, nope, rope, dv, itemsize) is None
    assert ops.latent_split(seq, heads, nope, rope, dv,
                            "bfloat16" if itemsize == 2 else "float32") is None


def _latent_inputs(jax, jnp, shape, dtype):
    b, h, s, nope, rope, dv = shape
    keys = jax.random.split(jax.random.PRNGKey(h + s + rope), 6)
    widths = (nope + rope, nope + dv)
    padded = nope + rope + (-rope % 128)

    def normal(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)
    operands = (normal(keys[0], b, s, h * widths[0]),
                normal(keys[1], b, s, h * widths[1]),
                normal(keys[2], b, s, rope))
    cotangents = (normal(keys[3], b, h, s, padded),
                  normal(keys[4], b, h, s, padded),
                  normal(keys[5], b, h, s, dv))
    return operands, cotangents


def _latent_forms(jnp, shape, dtype):
    """(the kernels, the jnp assembly of models/gpt.py), each (q, kv,
    k_rope) -> (q, k, v) as the flash kernels read them."""
    from ray_tpu.models.gpt import _latent_heads
    from ray_tpu.ops import rope as ops
    _, h, s, nope, rope, dv = shape
    table = ops.rope_table(s, rope, THETA)
    q_split, kv_split = ops.latent_split(s, h, nope, rope, dv, dtype)

    def kernels(q, kv, k_rope):
        return (q_split(q, *table), *kv_split(kv, k_rope, *table))

    def assembly(q, kv, k_rope):
        return _latent_heads(q, kv, k_rope, table, nope, rope, dv,
                             -rope % 128)
    return kernels, assembly


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shape", LATENT_SHAPES, ids=LATENT_IDS)
def test_latent_split_forward_has_the_assemblys_bits(jax_cpu, shape, dtype):
    """q, k and v out of the kernels against `_rope_tail` + the jnp
    assembly: the columns that are moved bit for bit; the rotated ones too,
    but for the FMAs XLA's CPU backend contracts in one of the two and not
    in the other (test_rope_split_forward_has_ropes_bits)."""
    jax = jax_cpu
    import jax.numpy as jnp
    b, h, s, nope, rope, dv = shape
    (q, kv, k_rope), _ = _latent_inputs(jax, jnp, shape, dtype)
    kernels, assembly = _latent_forms(jnp, shape, dtype)
    got = jax.jit(kernels)(q, kv, k_rope)
    want = jax.jit(assembly)(q, kv, k_rope)
    for a, w, width in zip(got, want, (nope + rope + -rope % 128,) * 2
                           + (dv,)):
        assert a.shape == w.shape == (b, h, s, width)
        assert a.dtype == w.dtype == jnp.dtype(dtype)
    for a, w in zip(got[:2], want[:2]):
        assert bool(jnp.array_equal(a[..., :nope], w[..., :nope]))
        assert not np.asarray(a[..., nope + rope:]).any()
        a, w = (np.asarray(t[..., nope:nope + rope].astype(jnp.float32))
                for t in (a, w))
        differs = a != w
        if dtype == "bfloat16":
            assert differs.mean() < 1e-3
        ulp = 2.0 ** -7 if dtype == "bfloat16" else 2.0 ** -22
        assert (np.abs(a - w)[differs] <= ulp * np.maximum(
            np.abs(a), np.abs(w))[differs] + 1e-6).all()
    assert bool(jnp.array_equal(got[2], want[2]))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shape", LATENT_SHAPES, ids=LATENT_IDS)
def test_latent_merge_is_the_gradient_within_a_rounding(jax_cpu, shape,
                                                        dtype):
    """d q, d kv and d k_rope (the sum over the heads of dk's rotated
    columns, added up in float32 and rounded once) against the assembly's
    gradient taken in float32: the kernels' error is one rounding of the
    value, and no larger than the jnp path's, which rounds every product
    and adds the heads in the activations' dtype."""
    jax = jax_cpu
    import jax.numpy as jnp
    operands, cotangents = _latent_inputs(jax, jnp, shape, dtype)
    kernels, assembly = _latent_forms(jnp, shape, dtype)
    in_float32 = _latent_forms(jnp, shape, "float32")[1]

    def pulled_back(f, cast=lambda t: t):
        return jax.jit(lambda x, g: jax.vjp(f, *x)[1](g))(
            tuple(map(cast, operands)), tuple(map(cast, cotangents)))
    got = pulled_back(kernels)
    jnp_way = pulled_back(assembly)
    true = pulled_back(in_float32, lambda t: t.astype(jnp.float32))
    half_ulp = 2.0 ** -8 if dtype == "bfloat16" else 2.0 ** -22
    for a, j, t, x in zip(got, jnp_way, true, operands):
        assert a.shape == x.shape and a.dtype == x.dtype
        err = jnp.abs(a.astype(jnp.float32) - t)
        # (a float32 ulp or two of the sum over the heads beside it)
        assert bool(jnp.all(err <= jnp.abs(t) * half_ulp + 4e-6))
        if dtype == "bfloat16":
            assert float(err.mean()) <= float(jnp.abs(
                j.astype(jnp.float32) - t).mean())
    # what is moved and not rotated comes back to the bit
    assert bool(jnp.array_equal(got[1], jnp_way[1]))


@pytest.mark.parametrize("kernel", ["latent_q_split", "latent_kv_split",
                                    "latent_q_merge", "latent_kv_merge"])
def test_latent_kernels_carry_their_names(jax_cpu, kernel):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.util.profiling import KERNELS
    shape = LATENT_SHAPES[1]
    operands, _ = _latent_inputs(jax, jnp, shape, "bfloat16")
    kernels, _ = _latent_forms(jnp, shape, "bfloat16")
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda x: sum(
        t.astype(jnp.float32).sum() for t in kernels(*x))))(operands))
    assert kernel in KERNELS and f"name={kernel}" in jaxpr


def test_latent_split_under_checkpoint_gives_the_same_gradients(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    shape = LATENT_SHAPES[1]
    operands, cotangents = _latent_inputs(jax, jnp, shape, "bfloat16")
    kernels, _ = _latent_forms(jnp, shape, "bfloat16")   # table closed over

    def block(x):
        return sum((t.astype(jnp.float32) * g).sum()
                   for t, g in zip(kernels(*x), cotangents))
    plain = jax.jit(jax.grad(block))(operands)
    rematted = jax.jit(jax.grad(jax.checkpoint(block)))(operands)
    for a, b in zip(plain, rematted):
        assert bool(jnp.array_equal(a, b))
    # and the recompute is there to be seen: the forward kernels, which a
    # gradient of this block needs for nothing else
    jaxpr = str(jax.make_jaxpr(jax.grad(jax.checkpoint(block)))(operands))
    for kernel in ("latent_q_split", "latent_kv_split", "latent_q_merge",
                   "latent_kv_merge"):
        assert f"name={kernel}" in jaxpr, kernel


# -------------------------------------------------------- through the model
@pytest.mark.parametrize("qk_norm", [False, True], ids=["plain", "qk_norm"])
def test_gpt_loss_with_flash_equals_reference_attention(jax_cpu, qk_norm):
    """The flash path (rope_split + flash kernels) against
    attention="reference" (reshape, transpose, _rope, mha_reference): loss
    and gradients, in float32 so that only the formulation differs."""
    jax = jax_cpu
    import dataclasses
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    cfg = dataclasses.replace(gpt.GPTConfig.tiny(), dtype=jnp.float32,
                              qk_norm=qk_norm)
    params = gpt.gpt_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0,
                                          cfg.vocab_size)}
    jaxpr = str(jax.make_jaxpr(lambda p: gpt.gpt_loss(p, batch, cfg))(params))
    assert "name=rope_split" in jaxpr      # tiny's heads of 32 do tile

    def loss_and_grads(attention):
        c = dataclasses.replace(cfg, attention=attention)
        return jax.jit(jax.value_and_grad(
            lambda p: gpt.gpt_loss(p, batch, c)))(params)
    (flash, g_flash), (ref, g_ref) = (loss_and_grads("flash"),
                                      loss_and_grads("reference"))
    np.testing.assert_allclose(flash, ref, rtol=1e-5)
    for a, r in zip(jax.tree_util.tree_leaves(g_flash),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, r, rtol=2e-3, atol=2e-5)


def test_attention_block_on_a_mesh_splits_whole_heads_per_shard(jax_cpu):
    """Under fsdp x tensor the pair runs inside the flash call's shard_map:
    the H*D columns over 'tensor', two heads of 32 a shard here, which do
    not fill a lane tile, so this is also the fallback inside a shard."""
    jax = jax_cpu
    import dataclasses
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    cfg = dataclasses.replace(gpt.GPTConfig.tiny(), dtype=jnp.float32)
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                      devices=jax.devices()[:4])
    params = gpt.gpt_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 129), 0,
                                          cfg.vocab_size)}
    with mesh:
        sharded = jax.jit(lambda p: gpt.gpt_loss(p, batch, cfg, mesh))(params)
    single = jax.jit(lambda p: gpt.gpt_loss(p, batch, cfg))(params)
    np.testing.assert_allclose(sharded, single, rtol=1e-5)
