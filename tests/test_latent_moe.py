"""Latent attention, the layer pattern, sigmoid routing with a selection
bias, shared experts and one chip's share of the experts (models/gpt.py,
ops/attention.py, ops/moe.py) against the plain float32 reference of
benchmark/families/kanana.py, at a small size on the CPU: seeded random
weights, the kernels in interpret mode."""

import copy
import hashlib
import json
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
    """benchmark/rehearsal/configs/tiny-kanana.json: 1 dense + 2 sparse
    layers, experts 4..7 of 16 held, 3 a token, heads of 32 + 16 / 32."""
    return _read("benchmark", "rehearsal", "configs", "tiny-kanana.json")


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
# (b) the program against the reference: loss and gradients
# ---------------------------------------------------------------------------

def _program(jax, config, attention, dtype=None):
    import jax.numpy as jnp
    from benchmark.families import kanana
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    cfg = GPTConfig(**kanana.gpt_config_kwargs(config), attention=attention,
                    dtype=dtype or jnp.float32, remat_policy="none")
    params = gpt_init(jax.random.PRNGKey(3), cfg)
    # a router with an opinion: at the init's 0.02 every score is 1/2
    for i, layer in enumerate(params["layers"]):
        if "moe" in layer:
            layer["moe"]["router"] = 0.3 * jax.random.normal(
                jax.random.PRNGKey(100 + i), layer["moe"]["router"].shape)
    tokens = np.random.default_rng(5).integers(
        0, config["vocab_size"], (2, 129), dtype=np.int32)
    return cfg, params, jnp.asarray(tokens)


@pytest.fixture(scope="module")
def reference(jax_cpu, tiny):
    jax = jax_cpu
    from benchmark.families import kanana
    _cfg, params, tokens = _program(jax, tiny, "reference")
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: kanana.reference_logits(
            p, t[:, :-1], tiny))(params, tokens)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: kanana.reference_loss(p, t, tiny)))(params, tokens)
    return logits, loss, grads


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_logits_loss_and_gradients_match_the_reference(jax_cpu, tiny,
                                                       reference, attention):
    """The latent block, the dense-then-sparse pattern, the sigmoid rule,
    the shared expert and the held experts, in float32: the whole tree of
    gradients, the selection bias's (exactly zero) included."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_forward, gpt_loss_and_aux
    cfg, params, tokens = _program(jax, tiny, attention)
    assert [sorted(layer) for layer in params["layers"]] == [
        ["attn", "ln1", "ln2", "mlp"]] + [["attn", "ln1", "ln2", "moe"]] * 2
    assert params["layers"][0]["mlp"]["w_up"].shape == (128, 256)
    assert params["layers"][1]["moe"]["w_up"].shape == (4, 128, 64)
    assert params["layers"][1]["moe"]["router"].shape == (128, 16)
    assert params["layers"][1]["moe"]["shared"]["w_up"].shape == (128, 128)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, t: gpt_forward(p, t, cfg))(
            params, tokens[:, :-1])
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p, t: gpt_loss_and_aux(p, {"tokens": t}, cfg),
            has_aux=True))(params, tokens)
    ref_logits, ref_loss, ref_grads = reference
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    assert float(loss) == float(aux["xent"])        # no router loss
    assert "router_balance_loss" not in aux
    assert 0.0 < float(aux["expert_slots_held_share"]) < 1.0
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(
            g, r, atol=1e-5 * max(1.0, float(np.abs(r).max())),
            err_msg=jax.tree_util.keystr(path))
    for layer in grads["layers"][1:]:
        assert not np.any(np.asarray(layer["moe"]["router_bias"]))


def _at_the_cells_head_widths(tiny, interleaved, heads=2):
    """tiny-kanana with a head of 128 + 64 / 128, the cell's: the widths at
    which ops/rope.py's latent kernels engage. One dense layer."""
    return dict(tiny, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128, num_attention_heads=heads,
                num_key_value_heads=heads, num_hidden_layers=1,
                rope_interleave=interleaved)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("interleaved", [True, False],
                         ids=["interleaved", "halves"])
def test_the_latent_kernels_give_the_jnp_paths_loss_and_gradients(
        jax_cpu, tiny, interleaved, dtype):
    """attention="flash" at the cell's head widths (q, k and v through
    latent_q_split / latent_kv_split, the gradients through their merges)
    against attention="reference" (`_rope_tail`, the jnp assembly,
    mha_reference): in float32 only the formulation differs; in bfloat16
    the two round in different places."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import gpt_loss
    config = _at_the_cells_head_widths(tiny, interleaved)

    def loss_and_grads(attention):
        cfg, params, tokens = _program(jax, config, attention,
                                       jnp.dtype(dtype))
        fn = jax.value_and_grad(lambda p, t: gpt_loss(p, {"tokens": t}, cfg))
        return str(jax.make_jaxpr(fn)(params, tokens)), jax.jit(fn)(
            params, tokens)
    jaxpr, (flash, g_flash) = loss_and_grads("flash")
    for kernel in ("latent_q_split", "latent_kv_split", "latent_q_merge",
                   "latent_kv_merge"):
        assert f"name={kernel}" in jaxpr, kernel
    jaxpr, (ref, g_ref) = loss_and_grads("reference")
    # no kernel but the embedding lookup's, which no attention path chooses
    assert jaxpr.count("pallas_call") == jaxpr.count("name=embed_grad") == 1
    exact = dtype == "float32"
    np.testing.assert_allclose(flash, ref, rtol=1e-5 if exact else 2e-3)
    for (path, a), r in zip(jax.tree_util.tree_flatten_with_path(g_flash)[0],
                            jax.tree_util.tree_leaves(g_ref)):
        if exact:
            np.testing.assert_allclose(a, r, rtol=2e-3, atol=2e-5,
                                       err_msg=jax.tree_util.keystr(path))
        else:
            a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
            assert (np.linalg.norm(a - r) <= 0.05 * np.linalg.norm(r) + 1e-6
                    ), jax.tree_util.keystr(path)


def test_a_tiny_latent_block_keeps_the_jnp_assembly(jax_cpu, tiny):
    """Heads of 32 + 16 / 32 fill no lane tiles: no latent kernel, the
    flash kernels alone."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_loss
    cfg, params, tokens = _program(jax, tiny, "flash")
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p, t: gpt_loss(p, {"tokens": t}, cfg)))(params, tokens))
    assert "name=flash_fwd" in jaxpr and "name=latent_" not in jaxpr


def test_the_latent_kernels_run_whole_groups_of_heads_per_shard(jax_cpu,
                                                                tiny):
    """Under fsdp x tensor the kernels run inside the flash call's
    shard_map on their shard's columns: two of four heads a shard, one
    group (two rotated parts of 64 fill a lane tile), and k_rope whole on
    every shard of 'tensor'."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    cfg, params, tokens = _program(
        jax, _at_the_cells_head_widths(tiny, True, heads=4), "flash")
    tokens = np.concatenate([tokens, tokens[::-1]])
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                      devices=jax.devices()[:4])
    fn = jax.value_and_grad(
        lambda p, mesh: gpt_loss(p, {"tokens": tokens}, cfg, mesh))
    with mesh:
        sharded, g_sharded = jax.jit(lambda p: fn(p, mesh))(params)
    single, g_single = jax.jit(lambda p: fn(p, None))(params)
    np.testing.assert_allclose(sharded, single, rtol=1e-5)
    for name in ("wq", "w_kva", "w_kvb"):
        np.testing.assert_allclose(g_sharded["layers"][0]["attn"][name],
                                   g_single["layers"][0]["attn"][name],
                                   rtol=2e-3, atol=2e-5, err_msg=name)


def test_the_bias_changes_the_selection_and_not_the_weights(jax_cpu, tiny):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import _route
    cfg, params, _tokens = _program(jax, tiny, "reference")
    m = dict(params["layers"][1]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, 128), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(jnp.einsum(
        "bsd,de->bse", x, m["router"],
        precision=jax.lax.Precision.HIGHEST)))
    weights, idx, _stats = _route(m, x, cfg)
    unbiased = np.argsort(-scores, axis=-1)[..., :3]
    biased = np.argsort(-(scores + np.asarray(m["router_bias"])),
                        axis=-1)[..., :3]
    assert np.any(np.sort(biased, -1) != np.sort(unbiased, -1))
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(biased, -1))
    kept = np.take_along_axis(scores, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        weights, 2.448 * kept / kept.sum(-1, keepdims=True), rtol=1e-6)
    # a bias that picks the same experts leaves everything as it is
    m["router_bias"] = jnp.zeros_like(m["router_bias"])
    weights0, idx0, _ = _route(m, x, cfg)
    np.testing.assert_array_equal(np.sort(np.asarray(idx0), -1),
                                  np.sort(unbiased, -1))
    np.testing.assert_allclose(weights0.sum(-1), 2.448, rtol=1e-6)


def test_the_kept_weights_are_take_along_axis_to_the_bit(jax_cpu, tiny):
    """scores[idx] comes by a one-hot product (the TPU serialises an
    element gather and its scatter-add): the weights, and the gradients
    that reach the router and x through the scores, are those of the
    gather, bit for bit."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import _route
    cfg, params, _tokens = _program(jax, tiny, "reference")
    m = dict(params["layers"][1]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, 128), jnp.float32)
    cotangent = jax.random.normal(jax.random.PRNGKey(10), (2, 32, 3))

    def by_gather(router, x):
        scores = jax.nn.sigmoid(jnp.einsum(
            "bsd,de->bse", x, router, precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(scores + m["router_bias"], 3)
        kept = jnp.take_along_axis(scores, idx, axis=-1)
        return kept / (jnp.sum(kept, axis=-1, keepdims=True)
                       + cfg.router_renormalise_eps) * cfg.router_scale

    def by_route(router, x):
        return _route({**m, "router": router}, x, cfg)[0]
    want, want_vjp = jax.vjp(by_gather, m["router"], x)
    got, got_vjp = jax.vjp(by_route, m["router"], x)
    np.testing.assert_array_equal(got, want)
    for g, w in zip(got_vjp(cotangent), want_vjp(cotangent)):
        assert np.abs(np.asarray(w)).max() > 0
        np.testing.assert_array_equal(g, w)
    # no element gather of the scores and no scatter in the router
    text = jax.jit(jax.grad(lambda r, x: (by_route(r, x) * cotangent).sum(),
                            argnums=(0, 1))).lower(m["router"], x).as_text()
    assert "scatter" not in text and "stablehlo.gather" not in text
    assert "scatter" in jax.jit(jax.grad(
        lambda r, x: (by_gather(r, x) * cotangent).sum(), argnums=(0, 1))
    ).lower(m["router"], x).as_text()


def test_bfloat16_step_passes_the_per_token_check(jax_cpu, tiny):
    """reference_loss with a `program_check` answers the loss where the
    program's own forward (bf16, flash, the grouped-matmul kernels) agrees
    with the reference token by token, and nan where a bound is broken."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import kanana
    _cfg, params, tokens = _program(jax, tiny, "flash")
    checked = dict(tiny, program_check={"logprob_median_tol": 0.05,
                                        "logprob_rms_tol": 0.2})
    with jax.default_matmul_precision("highest"):
        plain = float(jax.jit(lambda p, t: kanana.reference_loss(
            p, t, tiny))(params, tokens))
        held = float(jax.jit(lambda p, t: kanana.reference_loss(
            p, t, checked))(params, tokens))
        checked["program_check"]["logprob_median_tol"] = 1e-6
        broken = float(jax.jit(lambda p, t: kanana.reference_loss(
            p, t, checked))(params, tokens))
    assert held == plain and np.isnan(broken)
    del jnp


# ---------------------------------------------------------------------------
# (c) the share: the parts add up to the whole
# ---------------------------------------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(jax_cpu, tiny):
    """model-configs guide, section 4: what the four shares of one sparse
    layer give, each the routed part of its own four experts plus the
    shared expert that every chip computes alike, add up, with the shared
    expert counted once, to the uncut reference's layer."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import kanana
    from ray_tpu.models.gpt import (GPTConfig, Setting, _mlp_block,
                                    _moe_block, gpt_init)
    whole = copy.deepcopy(tiny)
    del whole["share"]
    whole["n_routed_experts"] = 16
    full_cfg = GPTConfig(**kanana.gpt_config_kwargs(whole),
                         dtype=jnp.float32, attention="reference")
    assert full_cfg.experts_held is None
    layer = gpt_init(jax.random.PRNGKey(7), full_cfg)["layers"][1]
    layer["moe"]["router"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(8), (128, 16))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 128), jnp.float32)

    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda h: kanana.reference_experts(
            layer["moe"], h, whole))(x)
        shared = _mlp_block(layer["moe"]["shared"], x, full_cfg, Setting())
        parts, held_share = [], 0.0
        for rank in range(4):
            cut = dict(tiny, share=dict(tiny["share"], rank=rank))
            cfg = GPTConfig(**kanana.gpt_config_kwargs(cut),
                            dtype=jnp.float32, attention="reference")
            assert cfg.experts_held == (4 * rank, 4)
            mine = {"moe": dict(layer["moe"], **{
                name: layer["moe"][name][4 * rank:4 * rank + 4]
                for name in ("w_gate", "w_up", "w_down")})}
            part, stats = _moe_block(mine, x, cfg, Setting())
            # the reference, given the same share, gives the same part
            np.testing.assert_allclose(
                part, jax.vmap(lambda h: kanana.reference_experts(
                    mine["moe"], h, cut))(x), atol=2e-5)
            parts.append(part - shared)
            held_share += float(stats["expert_slots_held_share"])
    np.testing.assert_allclose(sum(parts) + shared, want, atol=5e-5)
    assert abs(held_share - 1.0) < 1e-6
    # and a part is not the whole: the absent experts' sum is left out
    assert float(jnp.abs(parts[0] + shared - want).max()) > 1e-2


def test_plan_with_tokens_that_have_no_slot_here(jax_cpu):
    """plan_dispatch(partial=True): a slot whose expert is not among the
    groups gets no row; dispatch, the grouped matmul and combine, forward
    and gradients, equal the masked dense computation over the groups."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    t, k, d, f, groups = 40, 3, 16, 8, 4
    rng = np.random.default_rng(0)
    idx = rng.integers(-4, 12, (t, k)).astype(np.int32)   # 0..3 are here
    idx[:5] = 9                                           # no slot here
    idx[5:8] = [0, 1, 2]                                  # every slot here
    here = (idx >= 0) & (idx < groups)
    assert not here[:5].any() and here[5:8].all()
    plan = moe.plan_dispatch(jnp.asarray(idx), groups, 8, partial=True)
    np.testing.assert_array_equal(plan.token_held, here)
    slots = np.asarray(plan.row_slot)
    real = slots[slots < t * k]
    assert sorted(real) == sorted(np.flatnonzero(here.reshape(-1)))
    rows_of = np.asarray(plan.token_rows)
    np.testing.assert_array_equal(slots[rows_of[here]],
                                  np.flatnonzero(here.reshape(-1)))
    assert (rows_of[~here] == 0).all()
    tile_group = np.asarray(plan.tile_group)
    for row, slot in enumerate(slots):
        if slot < t * k:
            assert idx.reshape(-1)[slot] == tile_group[row // 8]

    x = jax.random.normal(jax.random.PRNGKey(0), (t, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (groups, d, f), jnp.float32)
    weights = jax.random.uniform(jax.random.PRNGKey(2), (t, k), jnp.float32)

    def sparse(x, w, weights):
        out = moe.grouped_matmul(moe.dispatch(x, plan), w, plan)
        return (moe.combine(out, weights, plan) ** 2).sum()

    def dense(x, w, weights):
        every = jnp.einsum("td,gdf->tgf", x, w)
        mask = (jnp.asarray(idx)[..., None] == jnp.arange(groups)) \
            * weights[..., None]                            # [t, k, g]
        return (jnp.einsum("tkg,tgf->tf", mask, every) ** 2).sum()
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(sparse, (0, 1, 2))(x, w, weights)
        want = jax.value_and_grad(dense, (0, 1, 2))(x, w, weights)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=1e-4)
    assert not np.any(np.asarray(got[1][2])[~here])


def _share_plans(jnp, moe, rows=8):
    """A share's plan over a row space smaller than the slots, which
    `rows_to_tokens` reads by the rows, and the same plan without the
    token-ordered view, which it reads by the slots. 64 tokens x 4 choices,
    4 groups of 16 experts held, tiles of 8 rows (16 for bfloat16): tokens
    with none, one, two and all four of their slots here, group 2 chosen by
    nobody, and 160 rows of which the routing fills fewer."""
    t, k, groups, tiles = 64, 4, 4, 160 // rows
    rng = np.random.default_rng(11)
    idx = np.full((t, k), 9, np.int32)                     # not here
    idx[8:24, 0] = rng.choice([0, 1, 3], 16)               # one slot here
    idx[24:40, 1:3] = [[0, 3]] * 8 + [[1, 0]] * 8          # two
    idx[40:44] = [3, 1, 0, 1]                              # all four
    idx[44:, 3] = rng.choice([0, 1, 3, 9, 12], 20)         # one or none
    order = moe.order_slots(jnp.asarray(idx), groups, rows, partial=True)
    plan = moe.lay_out(order, rows, tiles)
    held = np.asarray(plan.token_held).sum(1)
    assert set(held) == {0, 1, 2, 4} and int(order.sizes[2]) == 0
    assert int(plan.tiles_used[0]) < tiles - 1             # padding tiles
    assert plan.by_token is not None
    assert tiles * rows + t < t * k
    return plan, plan._replace(by_token=None), idx


def test_the_token_ordered_view_lists_every_held_row_once(jax_cpu):
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    plan, _by_slots, idx = _share_plans(jnp, moe)
    t, k = idx.shape
    view = plan.by_token
    slots, row_slot = np.asarray(view.slots), np.asarray(plan.row_slot)
    np.testing.assert_array_equal(slots, row_slot[np.asarray(view.rows)])
    assert (np.diff(slots) >= 0).all()
    here = np.asarray(plan.token_held)
    np.testing.assert_array_equal(slots[:here.sum()],
                                  np.flatnonzero(here.reshape(-1)))
    assert (slots[here.sum():] == t * k).all()             # padding, last
    heads = np.asarray(view.heads)
    for token in range(t):
        if here[token].any():
            run = slots[heads[token]:heads[token] + here[token].sum()]
            assert (run // k == token).all()
        else:
            assert heads[token] == len(slots)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("what", ["combine", "dispatch_vjp", "combine_vjp"])
def test_by_the_rows_equals_by_the_slots(jax_cpu, what, dtype):
    """rows_to_tokens over the token-ordered view against the gather of
    every slot: combine's forward, dispatch's backward (the same sum with
    no weights) and, through them, combine's own VJP. The rows past
    tiles_used are never computed on the chip: they hold NaN here and must
    not reach a token."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    d, dt = 256, jnp.dtype(dtype)
    tile = 32 // dt.itemsize
    plan, by_slots, idx = _share_plans(jnp, moe, tile)
    t, k = idx.shape
    r = plan.row_slot.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    z = jax.random.normal(keys[0], (r, d), jnp.float32).astype(dt)
    never = int(plan.tiles_used[0]) * tile
    z = jnp.where(jnp.arange(r)[:, None] < never, z, jnp.nan)
    weights = jax.random.uniform(keys[1], (t, k), jnp.float32)
    g = jax.random.normal(keys[2], (t, d), jnp.float32).astype(dt)
    # a sum of at most four terms in another order: a rounding of the result
    tol = dict(rtol=2e-6, atol=2e-6) if dtype == "float32" \
        else dict(rtol=1e-2, atol=1e-2)

    def both(fn):
        return [np.asarray(x, np.float32) for x in fn(plan)], \
            [np.asarray(x, np.float32) for x in fn(by_slots)]
    if what == "combine":
        got, want = both(lambda p: [moe.rows_to_tokens(z, p, weights),
                                    moe.combine(z, weights, p)])
    elif what == "dispatch_vjp":
        got, want = both(lambda p: jax.vjp(
            lambda x: moe.dispatch(x, p), g)[1](z))
    else:
        z = jnp.nan_to_num(z)        # dz is taken at every row
        got, want = both(lambda p: jax.vjp(
            lambda z, w: moe.combine(z, w, p), z, weights)[1](g))
    for a, b in zip(got, want):
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, **tol)
    if what != "combine_vjp":
        # a token with nothing here gets zeros, one with one row that row
        here = np.asarray(plan.token_held)
        assert not got[0][here.sum(1) == 0].any()


def _long_run_plan(jax, moe, k, tile):
    """A share's plan at k experts a token over tiles of `tile` rows, read
    by the rows wherever `moe._run_halo` divides the tile: 128 tokens, k of
    512 experts held, and `tile` - 1 tokens with one slot here ahead of a
    token with all k, so that its run starts on a block's last row and
    takes k - 1 rows of the next block (the deepest a run can reach past
    one: 16 rows at k = 17, 32 at 33); then tokens with none, with
    several (2 .. k - 1) and with one or none."""
    t, groups = 128, k
    rng = np.random.default_rng(k)
    idx = np.full((t, k), 400, np.int32)                   # not here
    for token in range(tile - 1):                          # one slot here
        idx[token, token % k] = token % groups
    idx[tile - 1] = rng.permutation(groups)                # all k
    for token in range(tile + 8, tile + 24):               # several
        some = 2 + (token - tile - 8) % (k - 2)
        idx[token, rng.permutation(k)[:some]] = rng.permutation(groups)[:some]
    for token in range(tile + 24, t):                      # one or none
        if rng.random() < 0.5:
            idx[token, rng.integers(k)] = rng.integers(groups)
    plan = jax.jit(lambda idx: moe.lay_out(                # one compile
        moe.order_slots(idx, groups, tile, partial=True), tile,
        groups + 8))(idx)
    held = np.asarray(plan.token_held).sum(1)
    assert {0, 1, 2, k} <= set(held) and held[tile - 1] == k
    assert int(plan.tiles_used[0]) < groups + 7            # padding tiles
    assert plan.by_token is not None
    assert int(plan.by_token.heads[tile - 1]) == tile - 1
    return plan, idx


@pytest.mark.parametrize("k,dtype,tile", [
    (17, "bfloat16", 32), (17, "float32", 32),     # halo 16 / 16
    (18, "bfloat16", 64), (18, "float32", 48),     # 32 / 24
    (22, "bfloat16", 64), (22, "float32", 48),     # 32 / 24
    (33, "bfloat16", 64), (33, "float32", 64),     # 32 / 32
    (22, "bfloat16", 16),                          # 32 divides no 16: slots
], ids=str)
def test_runs_past_one_sublane_tile_go_by_the_rows(jax_cpu, k, dtype, tile):
    """`moe_run_sum`'s halo follows k: at 17, 18, 22 and 33 experts a token
    the token side goes by the rows where the halo divides the tile, and is
    the same plan's sum by the slots; `dispatch` and `combine` around a
    grouped matmul give the masked dense computation's values and
    gradients. The rows past tiles_used hold NaN and must reach no token. A
    16-row tile under k = 22 keeps the slots' form."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    d, f, dt = 128, 128, jnp.dtype(dtype)
    plan, idx = _long_run_plan(jax, moe, k, tile)
    by_slots = plan._replace(by_token=None)
    t, groups, r = idx.shape[0], k, plan.row_slot.shape[0]
    by_the_rows = tile % moe._run_halo(k, dt) == 0
    assert by_the_rows == (tile != 16)
    keys = jax.random.split(jax.random.PRNGKey(k), 4)
    z = jax.random.normal(keys[0], (r, d), jnp.float32).astype(dt)
    never = int(plan.tiles_used[0]) * tile
    z = jnp.where(jnp.arange(r)[:, None] < never, z, jnp.nan)
    weights = jax.random.uniform(keys[1], (t, k), jnp.float32)
    assert ("moe_run_sum" in str(jax.make_jaxpr(
        lambda z: moe.rows_to_tokens(z, plan, weights))(z))) == by_the_rows
    # up to 33 terms in another order, rounded once
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" \
        else dict(rtol=1e-2, atol=2e-2)
    here = np.asarray(plan.token_held)
    # combine forward (weighted) and dispatch backward (not), both ways; one
    # jit: eagerly the followers' small ops take longer than the sums
    sums = jax.jit(lambda z: [[moe.rows_to_tokens(z, p, w)
                               for p in (plan, by_slots)]
                              for w in (weights, None)])(z)
    for got, want in sums:
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, **tol)
        assert not got[here.sum(1) == 0].any()
    # unweighted, a token with one row here gets that very row
    one = np.flatnonzero(here.sum(1) == 1)[0]
    np.testing.assert_array_equal(
        got[one], np.asarray(z, np.float32)[
            np.asarray(plan.token_rows)[one][here[one]][0]])

    x = jax.random.normal(keys[2], (t, d), jnp.float32).astype(dt)
    w = (jax.random.normal(keys[3], (groups, d, f), jnp.float32)
         / np.sqrt(d)).astype(dt)

    def sparse(x, w, weights):
        y = moe.combine(moe.grouped_matmul(moe.dispatch(x, plan), w, plan),
                        weights, plan).astype(jnp.float32)
        return (y ** 2).sum(), y

    def dense(x, w, weights):
        every = jnp.einsum("td,gdf->tgf", x, w)
        mask = (jnp.asarray(idx)[..., None] == jnp.arange(groups)) \
            * weights[..., None]                            # [t, k, g]
        y = jnp.einsum("tkg,tgf->tf", mask, every)
        return (y ** 2).sum(), y
    with jax.default_matmul_precision("highest"):
        (_, y), grads = jax.jit(jax.value_and_grad(
            sparse, (0, 1, 2), has_aux=True))(x, w, weights)
        (_, y_want), grads_want = jax.jit(jax.value_and_grad(
            dense, (0, 1, 2), has_aux=True))(
                x.astype(jnp.float32), w.astype(jnp.float32), weights)
    # the rows are rounded to their type after the experts and again as
    # tokens; the gradients carry both roundings
    rel = 1e-5 if dtype == "float32" else 3e-2
    for a, b in zip((y, *grads), (y_want, *grads_want)):
        a, b = np.asarray(a, np.float32), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=rel, atol=rel * np.abs(b).max())
    assert not np.any(np.asarray(grads[2])[~here])


# ---------------------------------------------------------------------------
# (c2) the share's row space: sized for the rows expected, exact past it
# ---------------------------------------------------------------------------

def _experts(x, weights, idx, *matrices, held=None):
    """models/gpt.py's two halves of the sparse block as `_moe_block` joins
    them on one device: the slots' order from the routing decision
    (`_slot_order`: all that needs no row, so that a router ahead of the
    mixer can hand it across), then the experts over it. -> y, or with a
    share (y, [1] whether the bounded row space held the routing)."""
    from ray_tpu.models import gpt
    order = gpt._slot_order(idx, matrices[0].shape[0], held, x.dtype)
    out = gpt._experts(x, weights, order, *matrices, held=held)
    return out[0] if held is None else out


def _masked_dense(x, weights, idx, w_gate, w_up, w_down, first):
    """_experts by the book: every token through every held expert, the
    slots that chose it weighted in, float32."""
    import jax
    import jax.numpy as jnp
    e = w_gate.shape[0]
    mask = jnp.sum((idx[..., None] - first == jnp.arange(e))
                   * weights[..., None], axis=-2)             # [b, s, e]
    act = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, w_gate)) \
        * jnp.einsum("bsd,edf->bsef", x, w_up)
    return jnp.einsum("bsef,efd,bse->bsd", act, w_down, mask)


def _share_operands(jax, held, seed=0, b=2, s=64, k=4, d=16, f=8):
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(keys[0], (b, s, d), jnp.float32),
            jax.random.uniform(keys[1], (b, s, k), jnp.float32),
            *(0.3 * jax.random.normal(key, shape, jnp.float32)
              for key, shape in zip(keys[2:], [(held, d, f), (held, d, f),
                                               (held, f, d)])))


def _distinct_choices(rng, tokens, k, of):
    return np.stack([rng.permutation(of)[:k] for _ in range(tokens)]
                    ).astype(np.int32)


def _conditionals(jaxpr):
    """`cond` equations of a jaxpr at any depth, the kernels' bodies apart
    (a `pl.when` is one too)."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        found += eqn.primitive.name == "cond"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _conditionals(sub)
    return found


def _loss_and_grads(jax, fn, x, weights, idx, *matrices):
    """sum(y^2) and its gradients by x, the weights and the matrices, with
    whatever else fn returns."""
    def loss(x, weights, *matrices):
        y, *rest = fn(x, weights, idx, *matrices)
        return (y ** 2).sum(), (y, rest)
    with jax.default_matmul_precision("highest"):
        (_, (y, rest)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(x, weights, *matrices)
    return y, grads, rest


@pytest.mark.parametrize("held,of", [(4, 32), (8, 32)],
                         ids=["an_eighth", "a_quarter"])
def test_bounded_row_space_equals_the_one_for_every_slot(jax_cpu, monkeypatch,
                                                         held, of):
    """Random routing lands near held / of of the slots here, the bounded
    row space holds them, and _experts gives what it gives over room for
    every slot (the factor out of reach: no check, the parent's code):
    forward and all five gradients to float32 round-off."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    first = 8
    x, weights, *matrices = _share_operands(jax, held)
    idx = jnp.asarray(_distinct_choices(np.random.default_rng(of), 128, 4, of)
                      ).reshape(2, 64, 4)

    def share():       # a new function a call: no trace is found again
        return lambda *operands: _experts(*operands, held=(first, of))
    y, grads, (fitted,) = _loss_and_grads(jax, share(), x, weights, idx,
                                          *matrices)
    assert fitted.shape == (1,) and float(fitted[0]) == 1.0
    assert _conditionals(
        jax.make_jaxpr(share())(x, weights, idx, *matrices).jaxpr) == 1

    monkeypatch.setattr(moe, "_ROW_SPACE_FACTOR", 1 << 20)
    assert _conditionals(
        jax.make_jaxpr(share())(x, weights, idx, *matrices).jaxpr) == 0
    y_every, grads_every, (always,) = _loss_and_grads(
        jax, share(), x, weights, idx, *matrices)
    assert float(always[0]) == 1.0          # nothing to bound: the constant
    # (the bounded row space adds a token's rows in choice order, from its
    # first held one: an ulp or two of float32 from the einsum's order)
    np.testing.assert_allclose(y, y_every, rtol=5e-6, atol=1e-6)
    for g, g_every in zip(grads, grads_every):
        np.testing.assert_allclose(g, g_every, rtol=5e-6, atol=1e-6)
    # and both are the masked dense computation
    np.testing.assert_allclose(
        y, _masked_dense(x, weights, idx, *matrices, first), atol=1e-5)


# held 4 of 32, 512 slots, 8-row tiles: 64 slots expected = 8 tiles, so the
# bounded row space is 2 x 8 + 4 = 20 tiles where every slot needs 68
@pytest.mark.parametrize("here,fits", [
    (512, 0.0),      # every token chose held experts alone
    (17 * 8, 1.0),   # one group of 17 full tiles + 3 empty groups' = 20
    (17 * 8 + 1, 0.0),                       # one row over: 21 tiles
    # the token side goes by the rows where the bounded row space runs and
    # by the slots past it: two and three slots a token here, in runs
    ("two_a_token", 1.0),     # 32 tokens x 2: two groups of 8 tiles + 2
    ("three_a_token", 0.0),   # 64 tokens x 3: three groups of 8 + 1 = 25
], ids=["every_slot_here", "exactly_at_the_bound", "one_row_over",
        "two_slots_a_token_fit", "three_slots_a_token_do_not"])
def test_past_the_bound_the_plan_for_every_slot_runs(jax_cpu, here, fits):
    """No capacity: what does not fit the bounded row space runs over room
    for every slot, and the result and its gradients are the masked dense
    computation's either way; the flag says which ran."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    held, of, first = 4, 32, 8
    assert moe.tile_rows(512 * held // of, held, jnp.float32) == 8
    x, weights, *matrices = _share_operands(jax, held, seed=1)
    flat = np.full(512, first + held + 3, np.int32)           # not here
    if here == 512:
        flat = first + np.random.default_rng(3).integers(0, held, 512)
    elif here == "two_a_token":
        flat.reshape(128, 4)[16:80:2, 1:3] = [first + 2, first]
    elif here == "three_a_token":
        flat.reshape(128, 4)[:64, :3] = [first + 1, first + 3, first]
    else:
        flat[:here] = first                                    # one group
    idx = jnp.asarray(flat.astype(np.int32)).reshape(2, 64, 4)
    y, grads, (fitted,) = _loss_and_grads(
        jax, lambda *a: _experts(*a, held=(first, of)), x, weights, idx,
        *matrices)
    assert float(fitted[0]) == fits

    def dense(x, weights, idx, *matrices):
        return (_masked_dense(x, weights, idx, *matrices, first),)
    want, want_grads, _ = _loss_and_grads(jax, dense, x, weights, idx,
                                          *matrices)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fixed,fitted_want", [
    ((0, 1), [0.0] + [1.0] * 7),
    ((0, 1, 14, 15), [0.0] + [1.0] * 6 + [0.0]),
], ids=["two_on_share_0", "a_collapsed_router"])
def test_eight_shares_one_of_them_past_its_bound_add_up_to_the_whole(
        jax_cpu, fixed, fitted_want):
    """Sixteen experts on eight chips, two each, and a router that sends
    two of every token's four choices to experts 0 and 1: share 0 gets four
    times its expectation and runs the plan for every slot, the other seven
    run bounded (their token side by the rows), and the eight partial sums
    are the uncut layer's. Or all four to experts 0, 1, 14 and 15, a
    collapsed router: shares 0 and 7 are past their bound and no token has
    a row on the other six, whose bounded row spaces are all padding."""
    jax = jax_cpu
    import jax.numpy as jnp
    of, held, k = 16, 2, 4
    x, weights, *matrices = _share_operands(jax, of, seed=2, k=k)
    rng = np.random.default_rng(5)
    free = np.setdiff1d(np.arange(of), fixed)
    idx = np.stack([np.concatenate([fixed,
                                    rng.permutation(free)[:k - len(fixed)]])
                    for _ in range(128)]).astype(np.int32).reshape(2, 64, k)
    idx = jnp.asarray(idx)
    parts, fitted = [], []
    with jax.default_matmul_precision("highest"):
        for rank in range(of // held):
            mine = [m[held * rank:held * (rank + 1)] for m in matrices]
            y, flag = _experts(x, weights, idx, *mine,
                               held=(held * rank, of))
            parts.append(y)
            fitted.append(float(flag[0]))
        whole = _experts(x, weights, idx, *matrices)
        want = _masked_dense(x, weights, idx, *matrices, 0)
    assert fitted == fitted_want
    np.testing.assert_allclose(sum(parts), want, atol=2e-5)
    np.testing.assert_allclose(whole, want, atol=2e-5)


def _plan_by_hand(idx, n_groups, rows):
    """The layout in numpy: slots in expert order (stable), each group
    padded to whole tiles, an empty group one tile, room for every slot."""
    n = idx.size
    flat = idx.reshape(-1)
    tiles = -(-n // rows) + n_groups
    row_slot = np.full(tiles * rows, n, np.int32)
    token_rows = np.zeros(n, np.int32)
    tile_group = np.full(tiles, n_groups - 1, np.int32)
    tile = 0
    for group in range(n_groups):
        members = np.flatnonzero(flat == group)
        row_slot[tile * rows:tile * rows + len(members)] = members
        token_rows[members] = tile * rows + np.arange(len(members))
        took = max(-(-len(members) // rows), 1)
        tile_group[tile:tile + took] = group
        tile += took
    return row_slot, token_rows.reshape(idx.shape), tile_group, tile


@pytest.mark.parametrize("tokens,k,groups,rows", [
    (37, 2, 8, 8), (128, 8, 64, 16), (40, 3, 4, 8)])
def test_the_plan_of_all_the_experts_is_what_it_was(jax_cpu, tokens, k,
                                                    groups, rows):
    """plan_dispatch(partial=False): shapes and values as the layout says,
    room for every slot, no token_held; nothing of the bound reaches it."""
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    idx = np.random.default_rng(tokens).integers(0, groups, (tokens, k)
                                                 ).astype(np.int32)
    idx[: tokens // 4] = 0                                     # skewed
    plan = moe.plan_dispatch(jnp.asarray(idx), groups, rows)
    row_slot, token_rows, tile_group, used = _plan_by_hand(idx, groups, rows)
    assert plan.token_held is None
    # every slot is a row: no token-ordered view, the two sorts of
    # order_slots and no third; nor over a share's room for every slot
    assert plan.by_token is None
    assert str(jax_cpu.make_jaxpr(
        lambda i: moe.plan_dispatch(i, groups, rows))(idx)).count(
            " sort[") == 2
    assert moe.plan_dispatch(jnp.asarray(idx), groups // 2, rows,
                             partial=True).by_token is None
    assert plan.row_slot.shape == row_slot.shape
    np.testing.assert_array_equal(plan.row_slot, row_slot)
    np.testing.assert_array_equal(plan.token_rows, token_rows)
    np.testing.assert_array_equal(plan.tile_group, tile_group)
    np.testing.assert_array_equal(plan.tiles_used, [used])


@pytest.mark.parametrize("whole_layer", [True, False],
                         ids=["all_experts_held", "a_share_held"])
def test_only_a_share_lowers_to_a_conditional(jax_cpu, tiny, monkeypatch,
                                              whole_layer):
    """Lowered for the TPU, where the kernels are Mosaic calls (interpreted,
    every `pl.when` of theirs is a conditional too)."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import kanana
    from ray_tpu.models.gpt import GPTConfig, Setting, _moe_block, gpt_init
    from ray_tpu.ops import attention
    config = copy.deepcopy(tiny)
    if whole_layer:
        del config["share"]
        config["n_routed_experts"] = 16
    cfg = GPTConfig(**kanana.gpt_config_kwargs(config), dtype=jnp.float32,
                    attention="reference")
    layer = gpt_init(jax.random.PRNGKey(0), cfg)["layers"][1]
    x = jnp.zeros((2, 64, 128), jnp.float32)

    # (another batch than the one lowered below: a share's branches are
    # jitted, and a trace of these shapes with the kernels interpreted
    # would be found again)
    bounded = _moe_block(layer, x[:1], cfg, Setting())[1][
        "expert_rows_bounded"]
    if whole_layer:
        assert bounded == 1.0 and isinstance(bounded, float)
    else:
        assert bounded.shape == () and float(bounded) in (0.0, 1.0)

    def loss(layer, x):
        y, stats = _moe_block(layer, x, cfg, Setting())
        return (y ** 2).sum(), stats
    monkeypatch.setattr(attention, "_default_interpret", lambda: False)
    text = jax.jit(jax.value_and_grad(loss, has_aux=True)).trace(
        layer, x).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    # every data movement is a gather: none transposed into a scatter-add,
    # the kept weights' (the layer has a selection bias) included
    assert "scatter" not in text
    conditionals = text.count("stablehlo.case") + text.count("stablehlo.if")
    # a share: one in the forward pass, one in the backward rule
    assert conditionals == (0 if whole_layer else 2)


def test_tile_rows_follow_from_the_held_count():
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    # the cell: 2 x 8192 tokens x 6 a token, 16 of 128 experts held
    slots = 2 * 8192 * 6
    assert moe.tile_rows(slots * 16 // 128, 16, jnp.bfloat16) == 128
    # all of them held: what olmoe's call passes is the slots themselves
    assert slots * 128 // 128 == slots


# ---------------------------------------------------------------------------
# (d) what the other configurations run is what it was
# ---------------------------------------------------------------------------

# sha256 of tiny-olmoe's train step (dp, one CPU device, batch 4 x 129,
# adamw), lowered to StableHLO with locations stripped. A PR that means to
# change OLMoE's program records the new text's hash here: the layer's remat
# keeps the flash forward's output and lse since PR 32 (6f65ebfe..9ff
# before it, the text of every tree from 0d59224 on), and since PR 42 the
# experts' float32 masters reach `moe_gmm` uncast and `combine`'s backward
# holds g until z is there (470f200b..608e before it); since PR 51 the
# embedding's lookup on one device is ops/embedding.py's (a8b7902c..d7c
# before it); since PR 55 the interpreted flash kernels' bodies are the ones
# a head and a pair of heads share (heads of 32 here, a head a grid step:
# the scratch accumulators have a leading dimension of one tile and the
# block maps are composed, tests/test_conv_gqa.py: NARROW_HEADS_JAXPR_SHA256;
# 3e212215..b503 before it). The cells' lowered steps, Mosaic calls and all,
# are tests/test_window_attention.py: LOWERED.
OLMOE_STEP_SHA256 = (
    "27bd3e3034e18f2edcea480e2a18f5bb4a20ebca715246ac2589ec4f25856c85")


def test_tiny_olmoe_step_lowers_to_the_parents_text(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    import optax
    from benchmark import model
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import init_train_state, make_train_step
    config = _read("benchmark", "rehearsal", "configs", "tiny-olmoe.json")
    program = model.family(config).program(config)
    mesh = build_mesh(MeshConfig(data=1), jax.devices()[:1])
    strategy = strategy_from_name("dp")
    act = strategy.activation_sharding(mesh)
    optimizer = optax.adamw(3e-4)
    state = init_train_state(lambda: program.init(jax.random.PRNGKey(0)),
                             optimizer, mesh, strategy)
    step = make_train_step(lambda p, b: program.loss(p, b, mesh, act),
                           optimizer, mesh, strategy,
                           sample_params=state.params)
    text = step.lower(state, {"tokens": jnp.zeros((4, 129), jnp.int32)}
                      ).as_text(debug_info=False)
    text = re.sub(r"loc\([^)]*\)", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == OLMOE_STEP_SHA256


# ---------------------------------------------------------------------------
# (e) arithmetic, rules, names
# ---------------------------------------------------------------------------

def test_param_count_at_the_cell_and_at_the_published_counts(jax_cpu, tiny):
    jax = jax_cpu
    from benchmark.families import kanana
    from ray_tpu.models.gpt import GPTConfig, count_params, gpt_init
    cell = _read("benchmark", "configs", "kanana-2-30b-a3b.json")
    assert kanana.param_count(cell) == 575_955_968            # 575.9M
    assert kanana.share(cell) == (0, 16, 128)
    published = {k: v for k, v in cell.items() if k != "share"}
    published.update(cell["published"])

    def layers(n):
        return kanana.param_count(dict(published, num_hidden_layers=n))
    # the catalog's 36M + 128 x 4.7M a sparse layer, 64.1M the dense one
    assert layers(3) - layers(2) == 36_049_536 + 128 * 4_718_592
    assert layers(1) == (26_345_472 + 512 + 4096 + 3 * 2048 * 6144
                         + 2 * 128256 * 2048 + 2048)
    # and the arithmetic counts the program's own tree
    cfg = GPTConfig(**kanana.gpt_config_kwargs(tiny))
    assert kanana.param_count(tiny) == count_params(
        jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg)))


def test_flops_count_what_is_computed_here():
    from benchmark.families import kanana
    cell = _read("benchmark", "configs", "kanana-2-30b-a3b.json")
    attention = (2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048)
    active = (5 * attention + 3 * 2048 * 6144
              + 4 * (2048 * 128 + 3 * 2048 * 1536
                     + 6 * 16 / 128 * 3 * 2048 * 768) + 2048 * 16032)
    assert kanana.train_flops_per_token(cell, 8192) == pytest.approx(
        6.0 * active + 3.0 * 5 * 32 * (192 + 128) * 8192)
    assert kanana.forward_flops_per_token(cell, 8192) == pytest.approx(
        0.93e9, rel=0.01)                    # ISSUE 31's reckoning


def test_kernel_arithmetic_counts_the_published_widths():
    from benchmark.kernels import mla_attention
    cell = _read("benchmark", "configs", "kanana-2-30b-a3b.json")
    mix = _read("benchmark", "traffic", "train_b2_s8192_dp.json")
    square = 2 * 32 * 8192 * 8192
    fwd, dq, dkv = (f(cell, mix) for f in (
        mla_attention.flash_fwd, mla_attention.flash_bwd_dq,
        mla_attention.flash_bwd_dkv))
    assert fwd[0] == square * (192 + 128)
    # the five products of the backward, each at its own width
    assert dq[0] + dkv[0] == square * (3 * 192 + 2 * 128)
    tensor = 2 * 32 * 8192 * 2
    assert fwd[1] == tensor * (2 * 192 + 2 * 128)
    assert dq[1] == tensor * (3 * 192 + 2 * 128)
    assert dkv[1] == tensor * (3 * 192 + 3 * 128)


@pytest.mark.parametrize("strategy,column,row", [
    ("tp", (None, "tensor"), ("tensor", None)),
    ("tp_fsdp", ("fsdp", "tensor"), ("tensor", "fsdp"))])
def test_every_new_leaf_gets_its_rule(jax_cpu, tiny, strategy, column, row):
    jax = jax_cpu
    from jax.sharding import PartitionSpec as P
    from benchmark.families import kanana
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    cfg = GPTConfig(**kanana.gpt_config_kwargs(tiny))
    params = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                      devices=jax.devices()[:4])
    specs = jax.tree_util.tree_map(
        lambda s: s.spec,
        strategy_from_name(strategy).param_shardings(mesh, params))
    attn, moe = specs["layers"][1]["attn"], specs["layers"][1]["moe"]
    assert attn["wq"] == attn["w_kvb"] == P(*column)
    assert attn["wo"] == P(*row)
    assert attn["w_kva"] == P(None, None) and attn["kv_norm"]["scale"] == P(None)
    assert moe["router_bias"] == P(None)
    # the shared expert is a dense MLP, not a stack of experts
    assert moe["shared"]["w_gate"] == moe["shared"]["w_up"] == P(*column)
    assert moe["shared"]["w_down"] == P(*row)
    assert moe["w_up"] == P("expert", *column)


def test_sharded_step_equals_one_device(jax_cpu, tiny):
    """One step of the whole tiny model on fsdp=2 x tensor=2 (whole heads
    of wq, w_kvb and wo over `tensor`, the kernels per shard) equals the
    one-device step."""
    jax = jax_cpu
    import jax.numpy as jnp
    import optax
    from benchmark.families import kanana
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import init_train_state, make_train_step
    cfg = GPTConfig(**kanana.gpt_config_kwargs(tiny), dtype=jnp.float32,
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
    loss, params = one_step("tp_fsdp", {"data": 1, "fsdp": 2, "tensor": 2}, 4)
    assert abs(loss - ref_loss) < 1e-5
    for (path, p), r in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_allclose(p, r, rtol=1e-4, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_pipeline_refuses_a_layer_pattern_by_name(jax_cpu, tiny):
    jax = jax_cpu
    from benchmark.families import kanana
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import make_gpt_pp_loss
    cfg = GPTConfig(**dict(kanana.gpt_config_kwargs(tiny), n_layers=4))
    mesh = build_mesh(MeshConfig(data=1, pipeline=2),
                      devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="layer 1's parameters are not layer 0's.*moe/router"):
        make_gpt_pp_loss(cfg, mesh, num_microbatches=2)


def test_the_new_scopes_are_regions_and_reach_the_compiled_step(jax_cpu,
                                                                tiny):
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import kanana
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.util import profiling
    assert {"attn_latent", "moe_shared"} <= set(profiling.REGIONS)
    cfg = GPTConfig(**kanana.gpt_config_kwargs(tiny), attention="flash")
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    text = jax.jit(jax.grad(lambda p, t: gpt_loss(p, {"tokens": t}, cfg))
                   ).lower(params, jnp.zeros((2, 129), jnp.int32)
                           ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("attn_proj/attn_latent", "moe/moe_shared", "moe/moe_route",
                  "attn_core", "mlp"):
        assert any(scope in name for name in names), scope


def test_configuration_file_keeps_the_catalog_and_states_the_cut():
    cell = _read("benchmark", "configs", "kanana-2-30b-a3b.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cell["source"])
    changed = {k for k, v in row["config"].items() if cell.get(k, "?") != v}
    assert changed == set(cell["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cell["published"] == {k: row["config"][k] for k in cell["reduced"]}
    assert cell["share"]["chips_per_layer"] * cell["n_routed_experts"] \
        == cell["share"]["n_routed_experts"] == 128
    assert cell["share"]["chips_per_layer"] * cell["vocab_size"] == 128256


# ---------------------------------------------------------------------------
# (f) the benchmark's own checks that need no chip, through their commands
# ---------------------------------------------------------------------------

@pytest.mark.timeout(600)
@pytest.mark.parametrize("command,says", [
    (["benchmark/rehearse.py", "kanana2_train_1chip", "--seconds", "2"],
     "rehearsal passed"),
    (["benchmark/selftest.py"], "selftest passed")],
    ids=["the_cell_rehearsed", "selftest"])
def test_the_benchmarks_cpu_checks_pass(command, says):
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # rehearse.py asks for its own devices
    proc = subprocess.run([sys.executable] + command, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert says in proc.stdout
