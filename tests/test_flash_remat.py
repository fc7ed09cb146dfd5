"""remat_policy="full" keeps the flash forward kernel's output and row
statistics (ops/attention.py: FLASH_OUT, FLASH_LSE) through the layer's
jax.checkpoint, so the kernel runs once a layer and not a second time in
the backward pass; everything else of the layer is recomputed. On the CPU,
the kernels in interpret mode, traced where tracing is enough."""

import dataclasses
import re

import numpy as np
import pytest

from helpers.families import kernel_calls as _kernel_calls

# two layers of multi-head attention (two heads of 64 a tensor shard, the
# least the rope kernel tiles), and of latent attention at the published
# head: q.k 128 + 64 rotated = 192 wide (padded to 256), v 128
BLOCKS = {
    "multi_head": dict(vocab_size=256, d_model=256, n_layers=2, n_heads=4,
                       d_ff=128, max_seq=32),
    "latent_192_128": dict(vocab_size=256, d_model=128, n_layers=2,
                           n_heads=2, d_ff=128, max_seq=32, kv_latent_dim=64,
                           qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128),
}


def _setup(jax, block):
    import jax.numpy as jnp
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    cfg = GPTConfig(**BLOCKS[block])
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (4, 33), dtype=np.int32))
    return cfg, params, {"tokens": tokens}


@pytest.mark.parametrize("block", list(BLOCKS))
def test_full_remat_gives_the_bits_of_none(jax_cpu, block):
    """What the layer keeps ARE the values a second run of the kernel
    would give: the loss and every gradient leaf are equal, not close.
    (XLA's CPU backend carries float32 through a fusion of bfloat16 ops,
    and the two programs fuse differently; with that switched off every
    op rounds to its own type in both.)"""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_loss
    cfg, params, batch = _setup(jax, block)
    assert cfg.remat_policy == "full"

    def value_and_grads(cfg):
        return jax.jit(jax.value_and_grad(
            lambda p: gpt_loss(p, batch, cfg))).lower(params).compile(
                compiler_options={"xla_allow_excess_precision": False})(
                    params)
    loss, grads = value_and_grads(cfg)
    none = dataclasses.replace(cfg, remat_policy="none")
    loss_none, grads_none = value_and_grads(none)
    assert float(loss) == float(loss_none)
    for (path, g), g_none in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(grads_none)):
        assert np.any(np.asarray(g)), jax.tree_util.keystr(path)
        np.testing.assert_array_equal(g, g_none,
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["causal", "window"])
def test_the_kept_tokens_first_output_gives_the_gradients(jax_cpu, kind,
                                                          dtype):
    """Under a jax.checkpoint that keeps FLASH_OUT and FLASH_LSE the kept
    value is the tokens-first one ([B, S, H * 128]: delta is taken from it
    where it lies), and the three gradients are the reference's."""
    import jax.numpy as jnp
    from helpers.flash_layout import check_tokens_first
    check_tokens_first(jax_cpu, jnp.dtype(dtype).type, keep=True,
                       **({"window": 100} if kind == "window" else {}))


def _gradient_jaxpr(jax, entered):
    """The gradient of a 2-layer flash loss, traced: on one device, under
    GSPMD on fsdp=2 x tensor=2 (the kernels inside _per_shard's shard_map),
    and as a stage of parallel/pipeline.py (scanned, inside its own
    shard_map) -> (jaxpr, flash_fwd calls the forward pass writes down)."""
    import jax.numpy as jnp
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import gpt_params_to_pp, make_gpt_pp_loss
    cfg = GPTConfig(**BLOCKS["multi_head"])
    params = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 33), jnp.int32)}
    if entered == "pipeline_stage":
        mesh = build_mesh(MeshConfig(data=2, pipeline=2, tensor=2))
        params = jax.eval_shape(gpt_params_to_pp, params)
        loss = make_gpt_pp_loss(cfg, mesh, 2)
        forward_calls = 1          # one scan body for a stage's one layer
    else:
        mesh = None if entered == "one_device" else build_mesh(
            MeshConfig(data=1, fsdp=2, tensor=2), devices=jax.devices()[:4])

        def loss(p, b):
            return gpt_loss(p, b, cfg, mesh=mesh)
        forward_calls = cfg.n_layers
    return jax.make_jaxpr(jax.grad(loss))(params, batch).jaxpr, forward_calls


@pytest.mark.parametrize("entered", ["one_device", "mesh_2x2",
                                     "pipeline_stage"])
def test_flash_forward_is_traced_once_a_layer(jax_cpu, entered):
    """flash_fwd once a layer and never in the recompute pass, however the
    block is entered; what XLA runs is still recomputed (rope_split stands
    for it: two forward, q's and k's, two again in the backward's
    checkpoint; the heads of 64 fill lane tiles in pairs on every one of
    the three entries, two a tensor shard, so v takes no kernel and two
    rope_merge calls a layer rotate dq and dk back)."""
    jaxpr, forward_calls = _gradient_jaxpr(jax_cpu, entered)
    calls = list(_kernel_calls(jax_cpu, jaxpr))

    def count(kernel, rematted):
        return sum(1 for call in calls if call == (kernel, rematted))
    assert count("flash_fwd", False) == forward_calls
    assert count("flash_fwd", True) == 0
    assert count("rope_split", False) == 2 * forward_calls
    assert count("rope_split", True) == 2 * forward_calls
    for kernel, calls_a_layer in (("flash_bwd_dq", 1), ("flash_bwd_dkv", 1),
                                  ("rope_merge", 2)):
        assert (count(kernel, False) + count(kernel, True)
                == calls_a_layer * forward_calls)


def test_flash_forward_is_recomputed_where_nothing_is_kept(jax_cpu):
    """The control of the walker above: with a bare jax.checkpoint around
    the same block the kernel IS in the recompute pass."""
    jax = jax_cpu
    from ray_tpu.models import gpt
    cfg, params, _ = _setup(jax, "multi_head")
    x = jax.ShapeDtypeStruct((2, 32, cfg.d_model), cfg.dtype)
    block = gpt.layer_fn(dataclasses.replace(cfg, remat_policy="none"), 32,
                         gpt.Setting())
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, layer: jax.checkpoint(block)(x, layer)[0].astype(
            "float32").sum()))(x, params["layers"][0]).jaxpr
    calls = list(_kernel_calls(jax, jaxpr))
    assert calls.count(("flash_fwd", False)) == 1
    assert calls.count(("flash_fwd", True)) == 1


@pytest.mark.parametrize("block", list(BLOCKS))
def test_a_layer_keeps_its_input_the_output_and_lse(jax_cpu, capsys, block):
    """Of a layer's activations the backward pass is handed the layer's
    input, flash_fwd's output as the kernel wrote it ([B, S, H * v width] at
    heads of whole lane tiles and at heads of 64 in pairs, what `attn_out`
    reads) and lse [B*H, 1, S], and nothing else: not q, k, v in the
    kernels' layout, no projection."""
    jax = jax_cpu
    from ray_tpu.models import gpt
    from ray_tpu.ops.attention import tokens_first
    cfg, params, _ = _setup(jax, block)
    batch, seq = 2, 32
    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype)
    layer = gpt.layer_fn(cfg, seq, gpt.Setting())
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(
        lambda x, p: layer(x, p)[0], x, params["layers"][0])
    kept = [line for line in capsys.readouterr().out.splitlines()
            if line and not re.search(r"from the argument p\[|from a constant",
                                      line)]
    v_width = cfg.v_head_dim or cfg.head_dim
    assert tokens_first(v_width, cfg.n_heads, cfg.kv_heads)
    out = (batch, seq, cfg.n_heads * v_width)
    assert sorted(line.split()[0] for line in kept) == sorted([
        f"bf16[{batch},{seq},{cfg.d_model}]",
        f"bf16[{','.join(map(str, out))}]",
        f"f32[{batch * cfg.n_heads},1,{seq}]"]), kept
    assert any("from the argument x" in line for line in kept), kept
    assert any("named 'flash_lse'" in line for line in kept), kept
