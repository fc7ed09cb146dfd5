"""Latent attention, the layer pattern, sigmoid routing with a selection
bias, shared experts and one chip's share of the experts (models/gpt.py,
ops/attention.py, ops/moe.py) against the plain float32 reference of
benchmark/families/kanana.py, at a small size on the CPU: seeded random
weights, the kernels in interpret mode. The checks every family has are
tests/helpers/families.py's, given this file's FAMILY; the kernels alone and
the cell's compile for a described chip: tests/test_latent_moe.py; a share's
row space: tests/test_share_rows.py."""

import copy
import hashlib
import re

import numpy as np
import pytest

from helpers.families import (  # noqa: F401 — fixtures and shared checks
    Family, benchmark_command_says, case, family, programmed, read, reference,
    seeded, test_bfloat16_step_passes_the_per_token_check,
    test_configuration_file_keeps_the_catalog_and_states_the_cut,
    test_every_new_leaf_gets_its_rule,
    test_logits_loss_and_gradients_match_the_reference,
    test_pipeline_refuses_by_name
    as test_pipeline_refuses_a_layer_pattern_by_name,
    test_sharded_step_equals_one_device,
    test_the_programs_gradient_moves_where_the_references_does, tiny)


class Kanana(Family):
    """benchmark/rehearsal/configs/tiny-kanana.json: 1 dense + 2 sparse
    layers, experts 4..7 of 16 held, 3 a token, heads of 32 + 16 / 32."""

    name, tiny, cell = "kanana", "tiny-kanana", "kanana-2-30b-a3b"
    workload = "kanana2_train_1chip"

    # The latent block, the dense-then-sparse pattern, the sigmoid rule,
    # the shared expert and the held experts, in float32: the whole tree of
    # gradients, the selection bias's (exactly zero) included.
    logits_atol, grads_atol = 2e-5, 1e-5

    def built(self, cfg, params):
        assert [sorted(layer) for layer in params["layers"]] == [
            ["attn", "ln1", "ln2", "mlp"]] + [["attn", "ln1", "ln2", "moe"]] * 2
        assert params["layers"][0]["mlp"]["w_up"].shape == (128, 256)
        assert params["layers"][1]["moe"]["w_up"].shape == (4, 128, 64)
        assert params["layers"][1]["moe"]["router"].shape == (128, 16)
        assert params["layers"][1]["moe"]["shared"]["w_up"].shape == (128, 128)

    def statistics(self, aux, loss, reference):
        assert float(loss) == float(aux["xent"])        # no router loss
        assert "router_balance_loss" not in aux
        assert 0.0 < float(aux["expert_slots_held_share"]) < 1.0

    def gradients(self, grads):
        for layer in grads["layers"][1:]:
            assert not np.any(np.asarray(layer["moe"]["router_bias"]))

    # the program's own forward: bf16, flash, the grouped-matmul kernels
    bf16_bounds = {"logprob_median_tol": 0.05, "logprob_rms_tol": 0.2}

    def rules(self, specs, column, row):
        from jax.sharding import PartitionSpec as P
        attn, moe = specs["layers"][1]["attn"], specs["layers"][1]["moe"]
        assert attn["wq"] == attn["w_kvb"] == P(*column)
        assert attn["wo"] == P(*row)
        assert attn["w_kva"] == P(None, None) \
            and attn["kv_norm"]["scale"] == P(None)
        assert moe["router_bias"] == P(None)
        # the shared expert is a dense MLP, not a stack of experts
        assert moe["shared"]["w_gate"] == moe["shared"]["w_up"] == P(*column)
        assert moe["shared"]["w_down"] == P(*row)
        assert moe["w_up"] == P("expert", *column)

    def sharded_step(self, jax, tiny, twin):
        """fsdp=2 x tensor=2: whole heads of wq, w_kvb and wo over
        `tensor`, the kernels per shard."""
        Family.sharded_step(self, jax, tiny, twin)

    pipeline_refusals = [
        case(({"n_layers": 4}, {"pipeline": 2},
              "layer 1's parameters are not layer 0's.*moe/router"),
             "layer_pattern")]


    def scopes(self, names, regions):
        from ray_tpu.util import profiling
        assert {"attn_latent", "moe_shared"} <= set(profiling.REGIONS)
        for scope in ("attn_proj/attn_latent", "moe/moe_shared",
                      "moe/moe_route", "attn_core", "mlp"):
            assert any(scope in name for name in names), scope

    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    in_benchmark_json = states_its_peak = False

    def cut(self, cell, row, bench):
        assert cell["share"]["chips_per_layer"] * cell["n_routed_experts"] \
            == cell["share"]["n_routed_experts"] == 128
        assert cell["share"]["chips_per_layer"] * cell["vocab_size"] == 128256

    # kanana2_train_1chip: 5 layers of latent attention at q.k 192 padded
    # to 256 / v 128, one dense and four sparse with 16 of 128 experts held.
    # 5 layers x (forward, kept through the remat, + dQ + dK/dV) flash
    # calls and x (forward + recomputed, backward) of q's and kv's latent
    # kernels, 4 sparse layers x (9 grouped matmuls + 3 recomputed + 3 tgmm),
    # each in the text twice since PR 34: once for the bounded row space and
    # once for every slot's (a step runs one of the two: test_sparse_layer_
    # compiles_with_both_row_spaces). As the chip runs it every MLP (the
    # dense one and four shared experts) keeps both products through the
    # remat (rung 2, 0.81 GB): 12.03 GB compiled, 6.91 of state and 5.12 of
    # temporaries (11.34 at rung 0, which this file compiled until PR 73,
    # under (0.55, 0.92)); + OVERHEAD 12.45 for the 12.33 the chip read
    # (72.906 %, ledger PR 72).
    cell_kernel_calls = {"flash_fwd": 5, "flash_bwd_dq": 5,
                         "flash_bwd_dkv": 5, "moe_gmm": 72, "moe_tgmm": 24,
                         "embed_grad": 1,
                         "latent_q_split": 10, "latent_kv_split": 10,
                         "latent_q_merge": 5, "latent_kv_merge": 5}
    cell_memory_share = (0.68, 0.74)
    cell_rung = 2
    # 2 x 8192 tokens x 6 a token, 16 of 128 held: 12 288 slots expected in
    # 128-row tiles, 2 x 96 + 16 = 208 tiles (26 624 rows) where every slot
    # needs 784 (100 352)
    row_spaces = (128, 208, 784)


FAMILY = Kanana()


# ---------------------------------------------------------------------------
# (a) the program against the reference: the latent block, the routing
# ---------------------------------------------------------------------------


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
        cfg, params, tokens = FAMILY.program(jax, config, attention,
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


def test_a_tiny_latent_block_keeps_the_jnp_assembly(jax_cpu, seeded):
    """Heads of 32 + 16 / 32 fill no lane tiles: no latent kernel, the
    flash kernels alone."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_loss
    cfg, params, tokens = seeded("flash")
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
    cfg, params, tokens = FAMILY.program(
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


def test_the_bias_changes_the_selection_and_not_the_weights(jax_cpu, seeded):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import _route
    cfg, params, _tokens = seeded("reference")
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


def test_the_kept_weights_are_take_along_axis_to_the_bit(jax_cpu, seeded):
    """scores[idx] comes by a one-hot product (the TPU serialises an
    element gather and its scatter-add): the weights, and the gradients
    that reach the router and x through the scores, are those of the
    gather, bit for bit."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import _route
    cfg, params, _tokens = seeded("reference")
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


# ---------------------------------------------------------------------------
# (b) the share: the parts add up to the whole
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


# ---------------------------------------------------------------------------
# (c) what the other configurations run is what it was
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
# 3e212215..b503 before it); since PR 59 the head's backward rule hands dx
# and dW on through one `optimization_barrier` (models/gpt.py:
# _chunked_xent_bwd: 512 rows of bf16 under a model 128 wide are a lone
# chunk whose logits are the smaller; 27bd3e30..6c85 before it). The cells'
# lowered steps, Mosaic calls and all, are tests/test_lowered_steps.py:
# LOWERED.
OLMOE_STEP_SHA256 = (
    "14606800fe765321d85b8471885aadd593030cf10b9b3a28279793e6be04fdae")


def test_tiny_olmoe_step_lowers_to_the_parents_text(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    import optax
    from benchmark import model
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import init_train_state, make_train_step
    config = read("benchmark", "rehearsal", "configs", "tiny-olmoe.json")
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
# (d) arithmetic
# ---------------------------------------------------------------------------


def test_param_count_at_the_cell_and_at_the_published_counts(jax_cpu, tiny):
    jax = jax_cpu
    from benchmark.families import kanana
    from ray_tpu.models.gpt import GPTConfig, count_params, gpt_init
    cell = read("benchmark", "configs", "kanana-2-30b-a3b.json")
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
    cell = read("benchmark", "configs", "kanana-2-30b-a3b.json")
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
    cell = read("benchmark", "configs", "kanana-2-30b-a3b.json")
    mix = read("benchmark", "traffic", "train_b2_s8192_dp.json")
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


# ---------------------------------------------------------------------------
# (e) the benchmark's own checks that need no chip, through their commands
# ---------------------------------------------------------------------------


@pytest.mark.timeout(600)
@pytest.mark.parametrize("command,says", [
    (["benchmark/rehearse.py", "kanana2_train_1chip", "--seconds", "2"],
     "rehearsal passed"),
    (["benchmark/selftest.py"], "selftest passed")],
    ids=["the_cell_rehearsed", "selftest"])
def test_the_benchmarks_cpu_checks_pass(command, says):
    benchmark_command_says(command, says)
