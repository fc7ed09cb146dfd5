"""Compile the main path's kernels for a TPU v5e that is described, not
attached (the chip's own compiler is installed with libtpu). Nothing runs;
what the compiler would refuse on the chip — a tile it cannot lay out, a
Mosaic call it cannot partition — it refuses here, at no chip time.

All compiles stay in this one process: two processes that ask for the TPU
topology at once collide on /tmp/libtpu_lockfile.
"""

import math
import os
import re

import pytest

# GPT-2 small attention shapes: [batch, heads, seq, head_dim].
SHAPE = (8, 12, 1024, 64)
# What the kernels' tiles are derived from, at the lengths the benchmark's
# cells and the serve mixes run: gpt2s, smollm-1.7b, a 128-token score batch,
# olmoe-1b-7b (head width 128, two major blocks a row).
KERNEL_SHAPES = [SHAPE, (4, 16, 2048, 64), (8, 32, 128, 64),
                 (2, 16, 4096, 128)]


@pytest.fixture(scope="module")
def v5e(jax_cpu):
    """The four devices of a described v5e 2x2 host. The persistent compile
    cache is off around these compiles: a TPU entry written without a chip
    cannot be read back and only warns on the next run."""
    jax = jax_cpu
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this box
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


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
        assert len(_kernel_ops(text, kernel)) == 1, kernel
    dq, dk, dv = compiled.out_info
    assert dq.shape == (2, 64, 8192, 128)
    assert dk.shape == dv.shape == (2, 8, 8192, 128)


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_flash_kernels_compile_at_16384_positions_in_groups_of_7(v5e,
                                                                  window):
    """smallthinker_train_1chip's two calls, [1, 28 on 4, 16384, 128]: the
    causal kernels and the window kernels at a band of 4096 = two major
    blocks of 2048 (three steps a grid row: the block wholly inside the
    band runs unmasked), groups of 7 query heads a key/value head through
    the index maps and `flash_bwd_dkv`'s walk, o written tokens first at 28
    heads of 128; dK and dV leave at the key/value heads' count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops import attention

    def shape(h):
        return jax.ShapeDtypeStruct((1, h, 16384, 128), jnp.bfloat16,
                                    sharding=SingleDeviceSharding(v5e[0]))
    if window:
        outer, major, _ = attention._block_sizes(16384, 16384, 128).fwd
        assert attention._band_steps(outer, major, window) == 3
    grads = jax.jit(jax.grad(lambda q, k, v: attention.flash_attention_native(
        q, k, v, causal=True, window=window,
        interpret=False).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    compiled = grads.lower(shape(28), shape(4), shape(4)).compile()
    text = compiled.as_text()
    name = "flash_win_" if window else "flash_"
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert len(_kernel_ops(text, name + kernel)) == 1, kernel
    dq, dk, dv = compiled.out_info
    assert dq.shape == (1, 28, 16384, 128)
    assert dk.shape == dv.shape == (1, 4, 16384, 128)
    # the forward's output leaves the kernel tokens first: [1, 16384, 28 x 128]
    assert "bf16[1,16384,3584]" in _kernel_ops(text, name + "fwd")[0]


@pytest.mark.parametrize("kind,rotates", [("attention", False),
                                          ("window", True)])
def test_a_kind_that_rotates_nothing_compiles_without_a_rotation(
        v5e, monkeypatch, kind, rotates):
    """smallthinker_train_1chip's two kinds of attention layer, [1, 28 on
    4, 16384, 128], value and gradient for one described chip. The full
    layer rotates nothing: no cosine or sine is computed for it, and its
    head splits (`rope_split`, `rope_merge`) take no table. The window layer
    beside it builds one table and hands it to q's and k's, not to v's."""
    import json

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmark.families import smallthinker
    from ray_tpu.models import gpt
    from ray_tpu.ops import attention
    from ray_tpu.ops.rope import rope_table

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        cfg = gpt.GPTConfig(**smallthinker.gpt_config_kwargs(json.load(f)),
                            attention="flash")
    seq = 16384
    assert (cfg.rope_of(kind) is not None) == rotates
    monkeypatch.setattr(attention, "_default_interpret", lambda: False)
    one_chip = SingleDeviceSharding(v5e[0])
    layers = jax.eval_shape(
        lambda: gpt.gpt_init(jax.random.PRNGKey(0), cfg))["layers"]
    group = gpt._GROUP[kind]
    layer = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        {group: next(layer[group] for layer in layers if group in layer)})
    x = jax.ShapeDtypeStruct((1, seq, cfg.d_model), cfg.dtype,
                             sharding=one_chip)

    def loss(layer, x):
        # layer_fn's own rule: no table for a kind that does not rotate
        table = (rope_table(seq, cfg.head_dim, cfg.rope_of(kind))
                 if rotates else ())
        return gpt._attention_block(layer, x, cfg, table, gpt.Setting(),
                                    kind)[0].astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        layer, x).compile().as_text()
    table = f"f32[{seq},128]"
    splits = _kernel_ops(text, "rope_split")
    merges = _kernel_ops(text, "rope_merge")
    assert len(splits) == len(merges) == 3                 # q, k, v
    with_table = [op for op in splits + merges if table in op]
    assert len(with_table) == (4 if rotates else 0)        # q and k, each way
    trig = re.findall(r" (?:cosine|sine)\(", text)
    assert len(trig) == (2 if rotates else 0), trig


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


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_plain_filter_kernels_compile_for_v5e(v5e, backward):
    """ops/short_conv.py's plain pair (silu of a 4-tap filter) at one
    projection of solar2_train_1chip, [1, 8192, 8 heads x 128]: the halo
    after a block filtered from the block's own last rows lays out too."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.short_conv import silu_conv

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(v5e[0]))

    def fn(x, w, g):
        out, vjp = jax.vjp(lambda *a: silu_conv(*a, interpret=False), x, w)
        return vjp(g) if backward else out
    x = shape((1, 8192, 1024))
    text = jax.jit(fn).lower(x, shape((1024, 4), jnp.float32),
                             x).compile().as_text()
    assert ("conv_silu_bwd" if backward else "conv_silu_fwd") in text


def test_delta_rule_compiles_at_8192_positions_of_128(v5e):
    """ops/linear_attention.py's two kernels at a delta-rule layer of
    solar2_train_1chip, [1, 8, 8192, 128]: `kda_fwd` and `kda_bwd` (the
    chunk function's jax.vjp: the transposed products, the rotations back)
    compile inside their VMEM limit, one Mosaic call each and no XLA loop
    beside them, neither over the 128 chunks nor the 8192 tokens; the
    temporaries are the chunks' kept states (67 MB), far under the gigabyte
    and a half the XLA form was held to."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.linear_attention import kda

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(v5e[0]))
    x = shape((1, 8, 8192, 128))
    args = (x, x, x, shape((1, 8, 8192, 128), jnp.float32),
            shape((1, 8, 8192), jnp.float32))
    compiled = jax.jit(jax.grad(
        lambda *a: jnp.sum(kda(*a, interpret=False).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(\S*kda_(?:fwd|bwd)\S*) = .*custom-call\(", text)
    assert len(calls) == 2 and "fwd" in calls[0] and "bwd" in calls[1], calls
    assert text.count("tpu_custom_call") == 2
    assert " while(" not in text
    # the chunks' states, [8, 128, 128, 128] float32, and little else
    states = 8 * 128 * 128 * 128 * 4
    assert states <= compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * states


def test_delta_rule_layer_compiles_on_four_chip_mesh(v5e, monkeypatch):
    """A delta-rule layer at solar2_train_1chip's widths (8 heads of 128 on
    4096) under tp_fsdp on fsdp=2 x tensor=2, forward and backward: the two
    kernels run per shard (`gpt.py:_per_shard`: a batch row and four whole
    heads a device), as the filters beside them do; GSPMD would refuse the
    Mosaic calls as they stand."""
    import json
    import jax
    import jax.numpy as jnp
    from benchmark.families import solar
    from ray_tpu.models import gpt
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "solar-open2-250b.json")) as f:
        widths = dict(solar.gpt_config_kwargs(json.load(f)), n_layers=1,
                      layer_kinds=("kda",), n_experts=0, experts_held=None,
                      n_shared_experts=0, max_seq=2048)
    cfg, mesh, _, layer, x = _layer_on_four_chips(v5e, monkeypatch, widths,
                                                  2, 2048)

    def loss(layer, x):
        out, _stats = gpt._kda_block(layer["kda"], x, cfg, gpt.Setting(mesh))
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        layer, x).compile().as_text()
    calls = re.findall(r"%(\S*kda_(?:fwd|bwd)\S*) = .*custom-call\(", text)
    assert len(calls) == 2, calls
    # a shard's own slice: a batch row of four heads
    assert re.search(r"kda_fwd\S* = .*bf16\[4,2048,128\]", text)
    assert "conv_silu_fwd" in text and "all-reduce" in text


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
        assert bool(_kernel_ops(text, kernel)) == there, kernel
    assert text.count("tpu_custom_call") >= (4 if backward else 2)


def test_latent_block_reaches_the_flash_kernels_without_a_layout_pass(
        v5e, monkeypatch):
    """kanana2_train_1chip's attention block, forward and backward, for one
    described chip: under `attn_proj` / `attn_latent` the only tensors by
    head are the four latent kernels' own results. No `copy`, transpose or
    fusion writes a [2, 8192, 32, 256 | 192 | 64]-shaped tensor (the jnp
    assembly's `fusion -> [2, 8192, 32, 256] -> copy -> [2, 32, 8192, 256]`
    for q and again for k), and no activation there is float32."""
    import json

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmark.families import kanana
    from ray_tpu.models import gpt
    from ray_tpu.ops import attention
    from ray_tpu.ops.rope import rope_table

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kanana-2-30b-a3b.json")) as f:
        cfg = gpt.GPTConfig(**kanana.gpt_config_kwargs(json.load(f)))
    batch, seq, heads = LATENT_SHAPE[:3]
    assert (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim) == LATENT_SHAPE[2:]
    monkeypatch.setattr(attention, "_default_interpret", lambda: False)
    one_chip = SingleDeviceSharding(v5e[0])
    layer = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(
            lambda: gpt.gpt_init(jax.random.PRNGKey(0), cfg))["layers"][0])
    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype,
                             sharding=one_chip)

    def loss(layer, x):
        table = rope_table(seq, cfg.qk_rope_dim, cfg.rope_theta)
        return gpt._attention_block(layer, x, cfg, table,
                                    gpt.Setting())[0].astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        layer, x).compile().as_text()
    for kernel in ("latent_q_split", "latent_kv_split", "latent_q_merge",
                   "latent_kv_merge", "flash_fwd", "flash_bwd_dq",
                   "flash_bwd_dkv"):
        assert len(_kernel_ops(text, kernel)) == 1, kernel
    # the entry computation's instructions: what is written to memory (an
    # instruction inside a fused computation lives in registers)
    under = [line for line in text[text.index("\nENTRY "):].splitlines()
             if re.search(r'op_name="[^"]*attn_proj', line)]
    assert len(under) > 20
    by_head = re.compile(rf"\[{batch},(?:{seq},{heads}|{heads},{seq}),\d+\]")
    for line in under:
        made = line.split(" = ", 1)[-1].split("(", 1)[0]
        if by_head.search(line.split(" = ", 1)[-1]):
            # a kernel's call, or an element of its results
            assert re.search(r"/latent_(q|kv)_(split|merge)/pallas_call",
                             line), line
        assert not re.search(rf"f32\[{batch},{seq},\d", made), line


@pytest.mark.parametrize("kind,heads", [("attention", 48), ("window", 64)])
def test_heads_of_128_reach_wo_without_a_layout_pass(v5e, monkeypatch, kind,
                                                    heads):
    """laguna_train_1chip's two kinds of attention layer, [2, 48 | 64 on 8,
    8192, 128], through the flash kernels, the gate a head and `wo`, value
    and gradient under the layer's remat policy, for one described chip:
    the three kernels once each (the forward's kept results reach the
    backward), and in the entry computation no `copy`, `transpose` or
    `reshape` writes a tensor of o's element count: the kernels write o and
    read dO as [2, 8192, H * 128], and the gate, its gradient and delta
    reach a head's columns where they lie (`ops/attention.py:head_columns`). A
    [2, 8192, H, 128] view anywhere between the kernels and `wo` brings the
    copies back: the chip tiles that view 8 heads x 128 lanes of one token,
    the columns 8 tokens x 128 lanes."""
    import json

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmark.families import laguna
    from ray_tpu.models import gpt
    from ray_tpu.ops import attention
    from ray_tpu.ops.rope import rope_table

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "laguna-xs.2.json")) as f:
        cfg = gpt.GPTConfig(**laguna.gpt_config_kwargs(json.load(f)),
                            attention="flash")
    batch, seq = 2, 8192
    assert (cfg.heads_of(kind), cfg.kv_heads, cfg.head_dim) == (heads, 8, 128)
    monkeypatch.setattr(attention, "_default_interpret", lambda: False)
    one_chip = SingleDeviceSharding(v5e[0])
    layers = jax.eval_shape(
        lambda: gpt.gpt_init(jax.random.PRNGKey(0), cfg))["layers"]
    group = gpt._GROUP[kind]
    layer = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        {group: next(layer[group] for layer in layers if group in layer)})
    assert layer[group]["wg"].shape == (cfg.d_model, heads)
    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype,
                             sharding=one_chip)

    def loss(layer, x):
        table = rope_table(seq, cfg.head_dim, cfg.rope_of(kind))
        block = jax.checkpoint(
            lambda layer, x: gpt._attention_block(
                layer, x, cfg, table, gpt.Setting(), kind)[0],
            policy=jax.checkpoint_policies.save_only_these_names(
                attention.FLASH_OUT, attention.FLASH_LSE))
        return block(layer, x).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        layer, x).compile().as_text()
    name = "flash_win_" if kind == "window" else "flash_"
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert len(_kernel_ops(text, name + kernel)) == 1, kernel
    written = re.compile(
        r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* "
        r"(copy|transpose|reshape)\(")
    for line in text[text.index("\nENTRY "):].splitlines():
        made = written.match(line)
        if made:
            dims = [int(d) for d in made.group(1).split(",")]
            assert math.prod(dims) != batch * seq * heads * 128, line


PAIRED_CELLS = ["gpt2s_train_1chip", "smollm17_train_4chip",
                "lfm2_train_1chip"]


@pytest.mark.parametrize("cell", PAIRED_CELLS)
def test_heads_of_64_reach_wo_without_a_layout_pass(v5e, monkeypatch, cell):
    """The three cells whose heads are 64 wide (12 on 12; 32 on 32, 16 a
    tensor shard; 32 on 8), their attention layer's value and gradient
    under the layer's remat policy, for the described chip (smollm: the
    fsdp=2 x tensor=2 mesh). The heads fill lane tiles in pairs
    (`ops/attention.py:tokens_first`), so q, k, v, o and their cotangents
    stay [B, S, heads * 64] from the projections' matmuls to `wo` and
    back: the three flash kernels once each, `rope_split` twice forward
    (q's and k's; v takes none) and twice recomputed, `rope_merge` twice,
    every one of them on [B, S, heads * 64] operands, and in the entry
    computation no `copy`, `transpose` or `reshape` under `attn_core` or
    `attn_out` writes a tensor the size of the key/value heads or larger
    (the parent's steps turned [B, H, S, 64] under `attn_out` forward,
    recomputed and backward). The twin of
    test_heads_of_128_reach_wo_without_a_layout_pass."""
    import json

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmark.families import lfm2
    from ray_tpu.models import gpt
    from ray_tpu.ops import attention
    from ray_tpu.ops.rope import rope_table

    monkeypatch.setattr(attention, "_default_interpret", lambda: False)
    if cell == "smollm17_train_4chip":
        batch, seq = BLOCK_WIDTHS[1][1:]
        cfg, mesh, _, layer, x = _layer_on_four_chips(
            v5e, monkeypatch, BLOCK_WIDTHS[1][0], batch, seq)
        layer = {"attn": layer["attn"]}
        shards = (2, 2)                         # of the batch, of the heads
    else:
        if cell == "gpt2s_train_1chip":
            cfg, batch, seq = gpt.GPTConfig(), SHAPE[0], SHAPE[2]
        else:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            with open(os.path.join(root, "benchmark", "configs",
                                   "lfm2-24b-a2b.json")) as f:
                cfg = gpt.GPTConfig(**lfm2.gpt_config_kwargs(json.load(f)),
                                    attention="flash")
            batch, seq = 2, 8192
        mesh, shards = None, (1, 1)
        one_chip = SingleDeviceSharding(v5e[0])
        layers = jax.eval_shape(
            lambda: gpt.gpt_init(jax.random.PRNGKey(0), cfg))["layers"]
        layer = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip),
            {"attn": next(layer["attn"] for layer in layers
                          if "attn" in layer)})
        x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype,
                                 sharding=one_chip)
    heads, kv_heads = cfg.n_heads // shards[1], cfg.kv_heads // shards[1]
    assert cfg.head_dim == 64 and attention.tokens_first(64, heads, kv_heads)

    def loss(layer, x):
        table = rope_table(seq, cfg.head_dim, cfg.rope_of("attention"))
        block = jax.checkpoint(
            lambda layer, x: gpt._attention_block(
                layer, x, cfg, table, gpt.Setting(mesh))[0],
            policy=jax.checkpoint_policies.save_only_these_names(
                attention.FLASH_OUT, attention.FLASH_LSE))
        return block(layer, x).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        layer, x).compile().as_text()
    rows = batch // shards[0]
    by_token = {n: f"bf16[{rows},{seq},{n * 64}]" for n in (heads, kv_heads)}
    for kernel, calls in (("flash_fwd", 1), ("flash_bwd_dq", 1),
                          ("flash_bwd_dkv", 1), ("rope_split", 4),
                          ("rope_merge", 2)):
        ops = _kernel_ops(text, kernel)
        assert len(ops) == calls, (kernel, len(ops))
        for op in ops:
            assert by_token[heads] in op or by_token[kv_heads] in op, op
            assert not re.search(rf"\[{rows},\d+,{seq},64\]", op), op
    written = re.compile(
        r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* "
        r"(copy|transpose|reshape)\(")
    for line in text[text.index("\nENTRY "):].splitlines():
        made = written.match(line)
        if made and re.search(r'op_name="[^"]*attn_(core|out)', line):
            dims = [int(d) for d in made.group(1).split(",")]
            assert math.prod(dims) < rows * seq * kv_heads * 64, line


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


BLOCK_WIDTHS = [
    (dict(), SHAPE[0], SHAPE[2]),
    (dict(d_model=2048, n_heads=32, d_ff=8192, max_seq=2048), 4, 2048)]
BLOCK_IDS = ["gpt2s_6_heads_a_shard", "smollm_16_heads_a_shard"]


def _layer_on_four_chips(v5e, monkeypatch, widths, batch, seq):
    """One layer's parameters and input as shapes under tp_fsdp on the
    described fsdp=2 x tensor=2 mesh -> (cfg, mesh, strategy, layer, x)."""
    import jax
    from ray_tpu.models import gpt
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name

    # jax.default_backend() is the CPU here; take the kernel's TPU branch.
    monkeypatch.setattr(attention, "_default_interpret", lambda: False)
    cfg = gpt.GPTConfig(**widths)
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2), devices=v5e)
    strategy = strategy_from_name("tp_fsdp")

    layer = jax.eval_shape(
        lambda: gpt.gpt_init(jax.random.PRNGKey(0), cfg))["layers"][0]
    layer_sh = strategy.param_shardings(mesh, {"layers": [layer]})["layers"][0]
    layer = jax.tree_util.tree_map(
        lambda leaf, sh: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=sh), layer, layer_sh)
    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype,
                             sharding=strategy.activation_sharding(mesh))
    return cfg, mesh, strategy, layer, x


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

    cfg, mesh, _, layer, x = _layer_on_four_chips(v5e, monkeypatch, widths,
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
        assert len(_kernel_ops(text, kernel)) == 1, kernel
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
    windows = _windows(compiled.as_text())
    assert all(int(w.split("x")[-1]) <= 128 for w in windows), windows
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
        assert len(_kernel_ops(text, kernel)) == 1, kernel
    assert all(int(w.split("x")[-1]) <= 128 for w in _windows(text))
    assert "while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.4e9


def _windows(text):
    """The window sizes ("1x1x255") of the compiled text's reduce-windows."""
    return re.findall(r"reduce-window\([^\n]*window=\{size=([0-9x]+)", text)


def _kernel_ops(text, kernel):
    """The compiled text's lines that define a call of a Mosaic kernel (the
    instruction takes the kernel's name, inside the transforms it was
    traced under: `transpose_jvp_moe_gmm__.24`)."""
    return [line for line in text.splitlines()
            if re.match(rf"\s*%?(?:\w+_)?{kernel}_*[.\d]* = ", line)]


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

    cfg, mesh, strategy, layer, x = _layer_on_four_chips(
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
    forward = _kernel_ops(text, "flash_fwd")
    assert len(forward) == 1, forward
    assert "rematted_computation" not in forward[0]
    for kernel in ("flash_bwd_dq", "flash_bwd_dkv"):
        assert len(_kernel_ops(text, kernel)) == 1, kernel
    # q's and k's rotation, forward and recomputed: the heads of 64 fill a
    # shard's lane tiles in pairs and v takes no kernel (3 + 3 before PR 55)
    rope = _kernel_ops(text, "rope_split")
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
    assert len(_kernel_ops(text, "moe_gmm")) == 4
    tgmm = _kernel_ops(text, "moe_tgmm")
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
    assert len(_kernel_ops(text, "embed_grad")) == 1
    assert " scatter(" not in text and " scatter(" not in alone
    assert f" = f32[{vocab},{d}]" in text


def _configuration_and_traffic(name):
    """benchmark/configs/<name>.json and the traffic of the cell that
    BENCHMARK.json runs it under."""
    import json
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def read(*parts):
        with open(os.path.join(root, *parts)) as f:
            return json.load(f)
    traffic = next(w["traffic"] for w in read("BENCHMARK.json")["workloads"]
                   if w["config"] == name)
    return (read("benchmark", "configs", name + ".json"),
            read("benchmark", "traffic", traffic + ".json"))


# (configuration, rows a tile, tiles of the share's bounded row space, tiles
# for every slot)
SHARE_ROW_SPACES = [
    # 2 x 8192 tokens x 6 a token, 16 of 128 held: 12 288 slots expected in
    # 128-row tiles, 2 x 96 + 16 = 208 tiles (26 624 rows) where every slot
    # needs 784 (100 352)
    ("kanana-2-30b-a3b", 128, 208, 784),
    # x 4 a token, 8 of 64 held: 8192 expected in 256-row tiles, 2 x 32 + 8
    # = 72 tiles (18 432 rows) against 264 (67 584)
    ("lfm2-24b-a2b", 256, 72, 264),
    # 1 x 8192 tokens x 22 a token, 8 of 512 held, in a latent width of
    # 1024: 2816 expected in 128-row tiles, 2 x 22 + 8 = 52 tiles (6656 rows)
    # against 1416 (181 248). k - 1 = 21 rows past a block are two sublane
    # tiles of bfloat16: the run sum's halo follows k (ops/moe.py:_run_halo)
    ("nemotron-3-super-120b-a12b", 128, 52, 1416),
]


@pytest.mark.parametrize("name,tile,bounded,every", SHARE_ROW_SPACES,
                         ids=[c[0] for c in SHARE_ROW_SPACES])
def test_sparse_layer_compiles_with_both_row_spaces(v5e, monkeypatch, name,
                                                    tile, bounded, every):
    """One sparse block of a cell that holds a share of the experts, its
    gradients under the layer's remat, for one described chip: the text
    holds the block over the bounded row space and over every slot's, one
    conditional forward and one backward (the forward one's recomputation
    under the remat is dead code, unless a latent projection reads the
    block's result: then it runs again, a third pass), the kernels once a
    branch; and the token side sized by the slots in every slot's branch
    alone, at any number of experts a token. Tokens a step are the cell's
    own (BENCHMARK.json's traffic), the rows' width the experts' own."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmark import model
    from ray_tpu.models import gpt
    from ray_tpu.ops import attention

    config, mix = _configuration_and_traffic(name)
    batch, seq = mix["global_batch"], mix["seq"]
    monkeypatch.setattr(attention, "_default_interpret", lambda: False)
    cfg = model.family(config)._train_config(config)
    one_chip = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)
    layers = jax.eval_shape(
        lambda: gpt.gpt_init(jax.random.PRNGKey(0), cfg))["layers"]
    sparse = next(layer for layer in layers if "moe" in layer)["moe"]
    # the rows' width: the model's, or the latent one the experts work in
    d = sparse["w_down"].shape[-1]
    matrices = sum(key in sparse for key in ("w_gate", "w_up", "w_down"))
    # forward, backward and, where a latent projection's gradient needs the
    # block's result, the forward again under the remat
    passes = 3 if "w_latent_out" in sparse else 2

    def loss(m, x):
        block = jax.checkpoint(
            lambda x, m: gpt._moe_block({"moe": m}, x, cfg, gpt.Setting())[0],
            policy=jax.checkpoint_policies.save_only_these_names(
                attention.FLASH_OUT, attention.FLASH_LSE))
        return (block(x, m).astype(jnp.float32) ** 2).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        placed(sparse), jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                             cfg.dtype, sharding=one_chip)
    ).compile().as_text()
    assert len(re.findall(r" conditional\(", text)) == passes
    # a branch and matrix: one product a forward pass, two in the backward's
    # branch (the forward again, then the rows' gradient), and one tgmm
    assert len(_kernel_ops(text, "moe_gmm")) == 2 * matrices * (passes + 1)
    assert len(_kernel_ops(text, "moe_tgmm")) == 2 * matrices
    for tiles in (bounded, every):
        # the table of rows by tiles; the dispatched rows and the experts'
        # outputs, forward and backward
        assert f"s32[{tiles},{tile}]" in text, tiles
        assert text.count(f" = bf16[{tiles * tile},{d}]") >= 4, tiles
    # the token side (combine forward, dispatch backward) moves every slot's
    # row, bf16[T, k, d], over every slot's row space only: once a
    # conditional, in the branch the predicate's false picks. The bounded
    # branch gathers its own rows in token order and the tokens' run heads
    # out of moe_run_sum's result, which has a tile of zeros appended.
    per_slot = [line for line in text.splitlines() if re.search(
        rf" = bf16\[{batch * seq},{cfg.expert_top_k},{d}\]\S* gather\(",
        line)]
    assert len(per_slot) == passes
    assert all("/branch_0_fun/" in line for line in per_slot)
    assert len(_kernel_ops(text, "moe_run_sum")) == passes
    runs = f"bf16[{(bounded + 1) * tile},{d}]"
    assert all(runs in line and "/branch_1_fun/" in line
               for line in _kernel_ops(text, "moe_run_sum"))
    # and no element gather or scatter-add of the kept weights
    assert not re.search(r" scatter\(", text)


# (configuration, kernel calls of the compiled step, arguments + temporaries
# as a share of the chip's 16.91 GB)
CELL_STEPS = [
    # olmoe_train_1chip (2 x 4096 tokens): one layer, all 64 experts held,
    # so no conditional and every kernel once: 3 grouped matmuls forward, 3
    # recomputed, 3 for the rows' gradients, 3 tgmm; the float32 masters
    # reach `moe_gmm` as they are kept (PR 42). 11.2 GB when this was
    # written: 7.51 of state, 3.7 of temporaries.
    ("olmoe-1b-7b", {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                     "rope_split": 6, "rope_merge": 3, "moe_gmm": 9,
                     "moe_tgmm": 3, "embed_grad": 1},
     (0.55, 0.75)),
    # kanana2_train_1chip: 5 layers of latent attention at q.k 192 padded
    # to 256 / v 128, one dense and four sparse with 16 of 128 experts held.
    # 5 layers x (forward, kept through the remat, + dQ + dK/dV) flash
    # calls and x (forward + recomputed, backward) of q's and kv's latent
    # kernels, 4 sparse layers x (9 grouped matmuls + 3 recomputed + 3 tgmm),
    # each in the text twice since PR 34: once for the bounded row space and
    # once for every slot's (a step runs one of the two: test_sparse_layer_
    # compiles_with_both_row_spaces). 10.98 GB when this was written: 6.91
    # of state, 4.07 of temporaries.
    ("kanana-2-30b-a3b", {"flash_fwd": 5, "flash_bwd_dq": 5,
                          "flash_bwd_dkv": 5, "moe_gmm": 72, "moe_tgmm": 24,
                          "embed_grad": 1,
                          "latent_q_split": 10, "latent_kv_split": 10,
                          "latent_q_merge": 5, "latent_kv_merge": 5},
     (0.55, 0.92)),
    # lfm2_train_1chip: a convolution layer with the dense MLP, then
    # attention (32 query heads on 8 key/value heads) and three convolution
    # layers with 8 of 64 experts held. One attention layer: one call of
    # each flash kernel; q and k through rope_split forward and recomputed,
    # rope_merge backward (the heads of 64 lie in pairs since PR 55: v takes
    # no kernel; 6 and 3 before); 4 convolution layers x (forward +
    # recomputed) and x backward. 8.90 GB when this was written: 5.63 of
    # state, 3.27 of temporaries.
    ("lfm2-24b-a2b", {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                      "rope_split": 4, "rope_merge": 2, "moe_gmm": 72,
                      "moe_tgmm": 24, "embed_grad": 1, "short_conv_fwd": 8,
                      "short_conv_bwd": 4},
     (0.45, 0.75)),
    # laguna_train_1chip: full attention (48 query heads on 8) with the
    # dense MLP, three sliding-window layers (64 on 8, window 512) and a
    # full one with 32 of 256 experts held. The window layers' kernels
    # carry names of their own and run, like the full layers', once a layer
    # (kept through the remat); q, k, v through rope_split at three head
    # counts. 14.22 GB when this was written: 8.30 of state, 5.92 of
    # temporaries.
    ("laguna-xs.2", {"flash_fwd": 2, "flash_bwd_dq": 2, "flash_bwd_dkv": 2,
                     "flash_win_fwd": 3, "flash_win_bwd_dq": 3,
                     "flash_win_bwd_dkv": 3, "rope_split": 30,
                     "rope_merge": 15, "moe_gmm": 72, "moe_tgmm": 24,
                     "embed_grad": 1},
     (0.78, 0.92)),
    # keye2_train_1chip: five layers alike, 32 query heads on 4 with a norm
    # a head, an indexer a layer (16 heads of 64 on one key head) whose walk
    # is five kernels since PR 41 (scores, search, KL, the gradient by query
    # and by key: one call a layer each, the selection and the gradients
    # kept through the remat, no loop of the jnp walk left), 16 of 128
    # experts held. One call a layer of each kernel under the selection and
    # none of the plain ones; q, k, v and the indexer's q through rope_split
    # forward (its one key head takes the jnp form), q, k, v again in the
    # recompute. 13.13 GB when this was written: 6.75 of state, 6.38 of
    # temporaries (PR 40's walk: the same 6.38).
    ("keye-vl-2.0-30b-a3b", {"flash_sel_fwd": 5, "flash_sel_bwd_dq": 5,
                             "flash_sel_bwd_dkv": 5, "flash_fwd": 0,
                             "index_scores": 5, "index_search": 5,
                             "index_kl": 5, "index_grad_q": 5,
                             "index_grad_k": 5,
                             "rope_split": 35, "rope_merge": 20,
                             "moe_gmm": 90, "moe_tgmm": 30, "embed_grad": 1},
     (0.70, 0.85)),
    # solar2_train_1chip (1 x 8192 tokens): a grouped-query layer that
    # rotates nothing (8 query heads on 1 at head 128: one call of each
    # flash kernel; q, k, v through rope_split without a table, forward and
    # recomputed) and three delta-rule layers, each with the plain filter
    # on q, k and v (forward + recomputed, backward) and the delta rule as
    # XLA; 8 of 320 experts held in all four layers, their rows in tiles
    # of 128. 14.66 GB when this was written: 10.09 of state, 4.57 of
    # temporaries. (The compile takes
    # ~105 s alone here: a time limit of its own.)
    pytest.param("solar-open2-250b",
                 {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                  "rope_split": 6, "rope_merge": 3, "moe_gmm": 72,
                  "moe_tgmm": 24, "embed_grad": 1, "conv_silu_fwd": 18,
                  "conv_silu_bwd": 9},
                 (0.80, 0.93), marks=pytest.mark.timeout(900)),
    # smallthinker_train_1chip (1 x 16 384 tokens): a full layer that
    # rotates nothing and three window layers (4096: a band of two major
    # blocks) at 28 query heads on 4, each layer's routing worked out ahead
    # of its mixer; 16 of 64 ReLU-gated experts held in all four layers
    # (both row spaces in the text, as above). q, k, v through rope_split
    # forward and recomputed in every layer (the full layer's without a
    # table). 11.61 GB when this was written: 7.88 of state, 3.73 of
    # temporaries (benchmark/configs/smallthinker-21b-a3b.json:
    # memory_peak_bytes.described_chip_compile; ~50 s alone here).
    pytest.param("smallthinker-21b-a3b",
                 {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                  "flash_win_fwd": 3, "flash_win_bwd_dq": 3,
                  "flash_win_bwd_dkv": 3, "rope_split": 24,
                  "rope_merge": 12, "moe_gmm": 72, "moe_tgmm": 24,
                  "embed_grad": 1, "moe_run_sum": 8},
                 (0.60, 0.80), marks=pytest.mark.timeout(900)),
]


@pytest.mark.parametrize("name,kernel_calls,share", CELL_STEPS,
                         ids=[getattr(c, "values", c)[0] for c in CELL_STEPS])
def test_cell_step_compiles_under_the_chips_memory(v5e, monkeypatch, name,
                                                   kernel_calls, share):
    """A one-chip cell's whole step (the cell's own traffic: 2 x 8192
    tokens, 2 x 4096 at olmoe; adamw over fp32 masters) for one described
    chip: every Mosaic call lays out, the
    kernels are called as often as the layers say (`embed_grad` once a
    step, the embedding lookup's backward, and never under `moe_tgmm`'s
    name), and arguments + temporaries stay under the chip's 16.91 GB. Under grouped queries k and
    v exist at the key/value heads' count alone: no tensor of the step has
    them at the query heads'."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from benchmark import model
    from ray_tpu.ops import attention
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import TrainState, make_train_step

    config, mix = _configuration_and_traffic(name)
    monkeypatch.setattr(attention, "_default_interpret", lambda: False)
    program = model.family(config).program(config)
    mesh = Mesh(np.array(v5e[:1]), ("data",))
    strategy = strategy_from_name(mix["strategy"])
    optimizer = optax.adamw(config["train"]["learning_rate"])
    whole = NamedSharding(mesh, P())

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=whole),
            tree)
    params = jax.eval_shape(lambda: program.init(jax.random.PRNGKey(0)))
    state = TrainState(placed(params),
                       placed(jax.eval_shape(optimizer.init, params)),
                       jax.ShapeDtypeStruct((), jnp.int32, sharding=whole))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (mix["global_batch"], mix["seq"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, strategy.batch_spec))}
    step = make_train_step(
        lambda p, b: program.loss(p, b, mesh,
                                  strategy.activation_sharding(mesh)),
        optimizer, mesh, strategy, sample_params=params)
    compiled = step.lower(state, batch).compile()
    memory = compiled.memory_analysis()
    peak = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert share[0] * 16.91e9 < peak < share[1] * 16.91e9, peak
    text = compiled.as_text()
    for kernel, calls in kernel_calls.items():
        found = _kernel_ops(text, kernel)
        assert len(found) == calls, (kernel, len(found))
        if kernel.startswith("index_"):
            # once a layer: the walk's results are kept through the remat
            assert not any("rematted_computation" in op for op in found)
    if "index_kl" in kernel_calls:
        # the indexer's walk left no row of 8192 keys to XLA: no window
        # reduction over them (the router's own is 255 wide), and the
        # step's temporaries are at or under those of PR 40's jnp walk
        assert all(int(w.split("x")[-1]) < 512 for w in _windows(text))
        assert memory.temp_size_in_bytes <= 6.39e9
    kv_heads = config.get("num_key_value_heads")
    if kv_heads != config["num_attention_heads"]:
        # dK and dV leave their kernel at the key/value heads' count, and
        # the one tensor at the query heads' that enters it is q (with dO)
        heads = config["num_attention_heads"]
        dim = config.get("head_dim", config["hidden_size"] // heads)
        b, s = mix["global_batch"], mix["seq"]
        from ray_tpu.ops.attention import LANES, tokens_first
        in_pairs = dim < LANES and tokens_first(dim, heads, kv_heads)
        # by head, or (lfm2's heads of 64, in pairs) as the projections
        # wrote them
        at_kv_heads = (f"bf16[{b},{s},{kv_heads * dim}]" if in_pairs
                       else f"bf16[{b * kv_heads},{s},{dim}]")
        for kernel in ("flash_bwd_dkv", "flash_win_bwd_dkv",
                       "flash_sel_bwd_dkv"):
            for dkv in _kernel_ops(text, kernel):
                assert dkv.count(at_kv_heads) >= 4, dkv
        by_head = (f"bf16[{b},{s},{heads * dim}]" if in_pairs
                   else f"bf16[{b},{heads},{s},{dim}]")
        made = [line for line in _kernel_ops(text, "rope_split")
                if f" = {by_head}" in line]
        # q alone is split at the query heads' count: forward, and
        # recomputed, in every full-attention layer (and in every window
        # layer where both kinds have the one head count)
        layers = kernel_calls["flash_fwd"] or kernel_calls["flash_sel_fwd"]
        if "num_attention_heads_per_layer" not in config:
            layers += kernel_calls.get("flash_win_fwd", 0)
        assert len(made) == 2 * layers, made
