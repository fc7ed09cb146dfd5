"""Compile the kernels that every cell shares for a TPU v5e that is
described, not attached (tests/helpers/described_chip.py): the flash and
rope kernels at the cells' shapes, the attention block and the rematted
layer on the four-chip mesh, the head, the grouped matmuls and the
embedding's lookup. A family's own kernels, its layers and its cell's whole
step compile beside the family's tests (tests/test_conv_gqa.py,
test_window_attention.py, ...), so that no one file grows with every cell:
the driver spreads the run by file.
"""

import pytest

from helpers.described_chip import (  # noqa: F401 — v5e is a fixture
    attention_layer_gradients, heads_of_64_stay_by_token, kernel_ops,
    layer_on_four_chips, v5e)


# GPT-2 small attention shapes: [batch, heads, seq, head_dim].
SHAPE = (8, 12, 1024, 64)


# What the kernels' tiles are derived from, at the lengths the benchmark's
# cells and the serve mixes run: gpt2s, smollm-1.7b, a 128-token score batch,
# olmoe-1b-7b (head width 128, two major blocks a row).
KERNEL_SHAPES = [SHAPE, (4, 16, 2048, 64), (8, 32, 128, 64),
                 (2, 16, 4096, 128)]


@pytest.mark.parametrize("shape", KERNEL_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "forward_backward"])
def test_flash_kernel_compiles_for_v5e(v5e, backward, shape):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    fn = fwd
    if backward:
        fn = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    # forward: one Mosaic call; backward adds the dQ and the dK/dV kernels
    assert text.count("tpu_custom_call") >= (3 if backward else 1)


@pytest.mark.parametrize("shape", KERNEL_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "forward_backward"])
def test_rope_kernels_compile_for_v5e(v5e, backward, shape):
    """ops/rope.py's pair at the same shapes: a projection's [B, S, H*D]
    into the flash kernels' [B, H, S, D] with the rotation, and back. What
    interpret mode cannot see: the roll on a 128-lane tile, the store of a
    64-wide head from a lane offset, the (1, heads, rows, 64) block."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.rope import rope_split, rope_table

    batch, heads, seq, head_dim = shape

    def fwd(x):
        table = rope_table(seq, head_dim, 10000.0)
        return rope_split(x, head_dim, table, interpret=False)

    fn = fwd
    if backward:
        fn = jax.grad(lambda x: (fwd(x).astype(jnp.float32) ** 2).sum())
    x = jax.ShapeDtypeStruct((batch, seq, heads * head_dim), jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))
    text = jax.jit(fn).lower(x).compile().as_text()
    assert "rope_split" in text and ("rope_merge" in text) == backward
    assert text.count("tpu_custom_call") >= (2 if backward else 1)


BLOCK_WIDTHS = [
    (dict(), SHAPE[0], SHAPE[2]),
    (dict(d_model=2048, n_heads=32, d_ff=8192, max_seq=2048), 4, 2048)]


BLOCK_IDS = ["gpt2s_6_heads_a_shard", "smollm_16_heads_a_shard"]


@pytest.mark.parametrize("cell", ["gpt2s_train_1chip",
                                  "smollm17_train_4chip"])
def test_heads_of_64_reach_wo_without_a_layout_pass(v5e, monkeypatch, cell):
    """Two of the three cells whose heads are 64 wide (12 on 12; 32 on 32,
    16 a tensor shard on the fsdp=2 x tensor=2 mesh), their attention
    layer's value and gradient under the layer's remat policy, held to
    helpers/described_chip.py:heads_of_64_stay_by_token; the third, 32 on
    8, is tests/test_conv_gqa.py's, read off its cell's whole step."""
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.models import gpt
    from ray_tpu.ops import attention
    if cell == "smollm17_train_4chip":
        batch, seq = BLOCK_WIDTHS[1][1:]
        cfg, mesh, _, layer, x = layer_on_four_chips(
            v5e, monkeypatch, BLOCK_WIDTHS[1][0], batch, seq)
        layer = {"attn": layer["attn"]}
        shards, one_chip = (2, 2), None     # of the batch, of the heads
    else:
        cfg, batch, seq = gpt.GPTConfig(), SHAPE[0], SHAPE[2]
        mesh = layer = x = None
        shards, one_chip = (1, 1), SingleDeviceSharding(v5e[0])
    heads, kv_heads = cfg.n_heads // shards[1], cfg.kv_heads // shards[1]
    assert cfg.head_dim == 64 and attention.tokens_first(64, heads, kv_heads)
    text = attention_layer_gradients(monkeypatch, cfg, "attention", batch,
                                     seq, one_chip, mesh, layer, x)
    heads_of_64_stay_by_token(text, batch // shards[0], seq, heads, kv_heads)


def test_tokens_first_heads_are_whole_per_shard_on_a_2x2_mesh(jax_cpu):
    """No cell runs heads of 128 under `tensor` > 1, so this holds
    `_per_shard`'s dims of the tokens-first output ("batch", None, "heads")
    on the CPU (interpreted kernels): four heads of 128 on two key/value
    heads over fsdp=2 x tensor=2, each shard writing its own two heads'
    columns of its own batch row, give the one-device values and the three
    gradients."""
    jax = jax_cpu
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import gpt
    from ray_tpu.ops.rope import rope_table
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    cfg = gpt.GPTConfig(vocab_size=64, d_model=512, n_layers=1, n_heads=4,
                        n_kv_heads=2, d_ff=64, max_seq=128,
                        dtype=jnp.float32)
    assert cfg.head_dim == 128
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                      devices=jax.devices()[:4])
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, g = (jax.random.normal(key, (2, 128, 4 * 128)) for key in keys[:2])
    k, v = (jax.random.normal(key, (2, 128, 2 * 128)) for key in keys[2:])
    table = rope_table(128, cfg.head_dim, cfg.rope_of("attention"))

    def attend(mesh):
        def loss(q, k, v):
            out = gpt._flash_on_mesh(q, k, v, table, cfg, mesh)
            assert out.shape == (2, 128, 4 * 128)
            return jnp.sum(out * g), out
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))
    (_, out), grads = attend(mesh)(q, k, v)
    (_, want), want_grads = attend(None)(q, k, v)
    np.testing.assert_allclose(out, want, atol=1e-6)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, atol=1e-5)


@pytest.mark.parametrize("widths,batch,seq", BLOCK_WIDTHS, ids=BLOCK_IDS)
def test_attention_block_compiles_on_four_chip_mesh(v5e, monkeypatch, widths,
                                                    batch, seq):
    """The attention block at GPT-2 small's and at SmolLM-1.7B's widths,
    forward and backward, under tp_fsdp on fsdp=2 x tensor=2. Without the shard_map around the flash
    call the chip's compiler refuses it ("Mosaic kernels cannot be
    automatically partitioned"); the CPU tests cannot see that, because
    the interpreted kernel is plain XLA ops that GSPMD partitions."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    from ray_tpu.ops.rope import rope_table

    cfg, mesh, _, layer, x = layer_on_four_chips(v5e, monkeypatch, widths,
                                                  batch, seq)

    def loss(layer, x):
        table = rope_table(seq, cfg.head_dim, cfg.rope_theta)
        out, _stats = gpt._attention_block(layer, x, cfg, table,
                                           gpt.Setting(mesh))
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        layer, x).compile()
    text = compiled.as_text()
    # three flash kernels, and q and k through rope_split and rope_merge
    # inside the same shard_map, whole heads a shard: 6 and 16 heads of 64,
    # pairs to a lane tile, so v takes no kernel (three each before PR 55)
    assert text.count("tpu_custom_call") >= 7
    assert "rope_split" in text and "rope_merge" in text
    # the tensor-parallel out projection and the fsdp weights need them
    assert "all-reduce" in text or "reduce-scatter" in text
    assert "all-gather" in text


@pytest.mark.parametrize("widths,batch,seq", BLOCK_WIDTHS, ids=BLOCK_IDS)
def test_rematted_layer_runs_the_flash_forward_once_on_four_chips(
        v5e, monkeypatch, capfd, widths, batch, seq):
    """The whole layer under remat_policy="full" (layer_fn's
    jax.checkpoint), value and gradient, on the same mesh: the policy that
    keeps the flash forward's output and lse reaches the names inside
    _per_shard's shard_map, so the compiled program calls flash_fwd once
    and not again under the backward's rematted computation, where
    rope_split still is (what XLA and the cheap kernels run is
    recomputed); and keeping two more sharded tensors a layer brings no
    resharding of the partitioner's own."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import gpt

    cfg, mesh, strategy, layer, x = layer_on_four_chips(
        v5e, monkeypatch, widths, batch, seq)
    assert cfg.remat_policy == "full"

    def loss(layer, x):
        block = gpt.layer_fn(cfg, seq, gpt.Setting(
            mesh, strategy.activation_sharding(mesh)))
        return block(x, layer)[0].astype(jnp.float32).sum()

    capfd.readouterr()
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        layer, x).compile().as_text()
    logged = capfd.readouterr().err
    forward = kernel_ops(text, "flash_fwd")
    assert len(forward) == 1, forward
    assert "rematted_computation" not in forward[0]
    for kernel in ("flash_bwd_dq", "flash_bwd_dkv"):
        assert len(kernel_ops(text, kernel)) == 1, kernel
    # q's and k's rotation, forward and recomputed: the heads of 64 fill a
    # shard's lane tiles in pairs and v takes no kernel (3 + 3 before PR 55)
    rope = kernel_ops(text, "rope_split")
    assert len(rope) == 4
    assert sum("rematted_computation" in line for line in rope) == 2
    assert "involuntary full rematerialization" not in (text + logged).lower()


@pytest.mark.parametrize("widths,batch,seq,four_chips,parent_temp", [
    (dict(), 64, 1024, False, 4_946_158_080),
    (dict(d_model=2048, n_heads=16, max_seq=4096), 2, 4096, False,
     2_472_800_256),
    (dict(d_model=2048, n_heads=32, max_seq=2048, vocab_size=49152,
          tie_embeddings=True), 32, 2048, True, 1_527_926_272)],
    ids=["gpt2s_65536x768x50304", "olmoe_8192x2048x50304",
         "smollm_tied_fsdp2_tensor2"])
def test_head_compiles_with_three_vocabulary_matmuls(v5e, widths, batch, seq,
                                                     four_chips, parent_temp):
    """head_xent's value and gradient at each train cell's shape, the
    four-chip one under tp_fsdp on fsdp=2 x tensor=2 with the tied table:
    three matmuls over the vocabulary where autodiff through the rematted
    body compiled to four, no collective it did not have, and temporaries
    no larger than that body's (parent_temp: the same function compiled
    here before the head's gradient moved into its forward pass; the fp32
    logits of a chunk were most of it)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.models import gpt
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name

    cfg = gpt.GPTConfig(**widths)
    if cfg.tie_embeddings:
        params = {"embed": {"table": jax.ShapeDtypeStruct(
            (cfg.vocab_size, cfg.d_model), jnp.float32)}}
    else:
        params = {"lm_head": jax.ShapeDtypeStruct(
            (cfg.d_model, cfg.vocab_size), jnp.float32)}
    if four_chips:
        mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2), devices=v5e)
        strategy = strategy_from_name("tp_fsdp")
        param_sh = strategy.param_shardings(mesh, params)
        act = strategy.activation_sharding(mesh)
        rows = NamedSharding(mesh, strategy.batch_spec)
        scalar = NamedSharding(mesh, P())
    else:
        act = rows = scalar = SingleDeviceSharding(v5e[0])
        param_sh = jax.tree_util.tree_map(lambda _: act, params)
    params = jax.tree_util.tree_map(
        lambda leaf, sh: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=sh), params, param_sh)
    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype,
                             sharding=act)
    targets = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=rows)

    def loss(params, x, targets):
        total, denom = gpt.head_xent(params, x, targets, cfg)
        return total / jnp.maximum(denom, 1.0)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)),
                       out_shardings=(scalar, (param_sh, act))
                       ).lower(params, x, targets).compile()
    text = compiled.as_text()
    assert text.count(" convolution(") == 3
    assert "rematted_computation" not in text
    assert "involuntary full rematerialization" not in text.lower()
    # x's chunk is gathered once an iteration; the body that recomputed
    # gathered it in the backward's scan again
    assert text.count(" all-gather(") == (3 if four_chips else 0)
    assert compiled.memory_analysis().temp_size_in_bytes <= parent_temp


# (tokens, experts a token, groups, rows expected here, d, an expert's width)
GROUPED_SHAPES = {
    # olmoe_train_1chip: 2 x 4096 tokens x 8 over all 64 experts
    "olmoe": (2 * 4096, 8, 64, 2 * 4096 * 8, 2048, 1024),
    # lfm2_train_1chip: 8 of 64 held, x 4 a token; the widest expert (1536:
    # blocks of 768 and 1024 columns)
    "lfm2": (2 * 8192, 4, 8, 8192, 2048, 1536),
    # laguna_train_1chip: 32 of 256 held, x 8 a token; the narrowest (512)
    "laguna": (2 * 8192, 8, 32, 2 * 8192, 2048, 512),
}


@pytest.mark.parametrize("matrices", ["bfloat16", "float32"])
@pytest.mark.parametrize("cell", list(GROUPED_SHAPES))
def test_grouped_matmul_kernels_compile_for_v5e(v5e, cell, matrices):
    """A cell's two grouped-matmul kernels, forward and both gradients, at
    the cell's shape (olmoe: 2 x 4096 tokens x 8 experts a token in 256-row
    tiles, 64 experts of 2048 x 1024), gate / up and down, a whole expert
    matrix a block. With float32 masters the block lands at 4 bytes an
    element (8 MB at olmoe) beside its rounded copy (4 MB), with the rows
    and the result twice: near the default 16 MB of scoped VMEM and inside
    the kernels' own limit, which is what compiling here shows. The rest of
    the dispatch is sorts and gathers, plain XLA."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops import moe

    one_chip = SingleDeviceSharding(v5e[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    tokens, k, experts, slots, d, width = GROUPED_SHAPES[cell]
    rows = moe.tile_rows(slots, experts, jnp.bfloat16)
    tiles = slots // rows + experts
    plan = moe.Plan(shape((tiles * rows,), jnp.int32),
                    shape((tokens, k), jnp.int32),
                    shape((tiles,), jnp.int32), shape((1,), jnp.int32))

    def grads(x, w_up, w_down, plan):
        # squared, so that the gradients need the forward's result
        def loss(x, w_up, w_down):
            up = moe.grouped_matmul(x, w_up, plan, interpret=False)
            return (moe.grouped_matmul(up, w_down, plan, interpret=False)
                    .astype(jnp.float32) ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(x, w_up, w_down)

    text = jax.jit(grads).lower(
        shape((tiles * rows, d), jnp.bfloat16),
        shape((experts, d, width), matrices),
        shape((experts, width, d), matrices), plan).compile().as_text()
    # forward and the rows' gradient of each, the matrices' gradients
    assert len(kernel_ops(text, "moe_gmm")) == 4
    tgmm = kernel_ops(text, "moe_tgmm")
    assert len(tgmm) == 2
    # the matrices' gradients leave their kernel in the rows' type
    assert any(f" = bf16[{experts},{d},{width}]" in op for op in tgmm)
    assert any(f" = bf16[{experts},{width},{d}]" in op for op in tgmm)


# (table, tokens): smallthinker's, olmoe's and gpt2s' (50 257 rows: no whole
# number of groups)
LOOKUP_SHAPES = {
    "smallthinker": ((37984, 2560), (1, 16384)),
    "olmoe": ((50304, 2048), (2, 4096)),
    "gpt2s": ((50257, 768), (64, 1024)),
}


@pytest.mark.parametrize("cell", list(LOOKUP_SHAPES))
def test_embedding_lookup_compiles_without_a_scatter(v5e, monkeypatch, cell):
    """ops/embedding.py's lookup on one described chip at a cell's table and
    batch: the forward is a gather and no Mosaic call, the gradient adds
    exactly one (`embed_grad`: a group's block of float32 beside its
    rounded copy has to fit the kernel's VMEM at whole rows of d), and no
    scatter is left for the chip to serialise."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops import attention
    from ray_tpu.ops.embedding import embed_lookup
    monkeypatch.setattr(attention, "_default_interpret", lambda: False)
    one_chip = SingleDeviceSharding(v5e[0])
    (vocab, d), (batch, seq) = LOOKUP_SHAPES[cell]
    table = jax.ShapeDtypeStruct((vocab, d), jnp.float32, sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)

    def forward(table, tokens):
        return embed_lookup(table, tokens, jnp.bfloat16)

    def both(table, tokens):
        # squared, so that the gradient needs the forward's rows
        rows, pull = jax.vjp(lambda t: forward(t, tokens), table)
        return pull(rows * rows)[0]
    alone = jax.jit(forward).lower(table, tokens).compile().as_text()
    text = jax.jit(both).lower(table, tokens).compile().as_text()
    assert alone.count("tpu_custom_call") == 0
    assert text.count("tpu_custom_call") == 1
    assert len(kernel_ops(text, "embed_grad")) == 1
    assert " scatter(" not in text and " scatter(" not in alone
    assert f" = f32[{vocab},{d}]" in text
