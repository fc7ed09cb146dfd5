"""A stack of two kinds of layer (gated short convolutions among
grouped-query attention layers with a norm a head, models/gpt.py) against the
plain float32 reference of benchmark/families/lfm2.py, at a small size on
the CPU: seeded random weights, the kernels in interpret mode. The checks
every family has are tests/helpers/families.py's, given this file's FAMILY;
the family's kernels alone and its cell's compile for a described chip:
tests/test_conv_gqa.py."""

import numpy as np
import pytest

# (the rehearsal is bound, and so run, first here and last or midway in the
# other families' files: five subprocesses that each start a cluster do not
# then start in the same minute of a run)
from helpers.families import test_the_cell_rehearses  # noqa: F401
from helpers.families import (  # noqa: F401 — fixtures and shared checks
    Family, case, family, programmed, read, reference, seeded,
    test_bfloat16_step_passes_the_per_token_check,
    test_configuration_file_keeps_the_catalog_and_states_the_cut,
    test_every_new_leaf_gets_its_rule,
    test_logits_loss_and_gradients_match_the_reference,
    test_param_count_is_the_published_model_and_the_programs_tree
    as test_param_count_at_the_cell_is_the_programs_tree,
    test_pipeline_refuses_by_name, test_sharded_step_equals_one_device,
    test_the_configuration_refuses_by_name,
    test_the_programs_gradient_moves_where_the_references_does,
    test_the_reference_tells_each_mechanism_apart,
    test_the_shares_of_a_layer_add_up_to_the_uncut_reference, tiny)


class Lfm2(Family):
    """benchmark/rehearsal/configs/tiny-lfm2.json: conv + dense, then
    attention / conv / conv with experts 4..7 of 16 held, 2 a token; 8
    query heads of 16 on 2 key/value heads."""

    name, tiny, cell = "lfm2", "tiny-lfm2", "lfm2-24b-a2b"
    workload = "lfm2_train_1chip"

    def opinion(self, jax, cfg, params):
        Family.opinion(self, jax, cfg, params)
        for layer in params["layers"]:
            if "attn" in layer:
                # head norms that are not the identity on a unit vector, so
                # that a norm after the rotation would show
                for j, name in enumerate(("q_head_norm", "k_head_norm")):
                    layer["attn"][name]["scale"] = 1.0 + 0.3 * jax.random.normal(
                        jax.random.PRNGKey(200 + j), (cfg.head_dim,))

    # Two kinds of layer in one stack after a leading dense one, grouped
    # queries with the norm a head before the rotation, the sigmoid rule at
    # the published 1e-6 and the held experts, in float32: the whole tree of
    # gradients, the selection bias's (exactly zero) included.
    logits_atol, grads_atol = 2e-5, 1e-5

    def built(self, cfg, params):
        assert [sorted(layer) for layer in params["layers"]] == [
            ["conv", "ln1", "ln2", "mlp"], ["attn", "ln1", "ln2", "moe"],
            ["conv", "ln1", "ln2", "moe"], ["conv", "ln1", "ln2", "moe"]]
        attn, conv = params["layers"][1]["attn"], params["layers"][0]["conv"]
        assert attn["wq"].shape == (128, 128) and attn["wk"].shape == (128, 32)
        assert attn["q_head_norm"]["scale"].shape == (16,)
        assert conv["w_in"].shape == (3, 128, 128)
        assert conv["filter"].shape == (128, 3)
        assert params["layers"][1]["moe"]["w_up"].shape == (4, 128, 64)
        assert "lm_head" not in params                              # tied

    def statistics(self, aux, loss, reference):
        assert float(loss) == float(aux["xent"])        # no router loss
        assert 0.0 < float(aux["expert_slots_held_share"]) < 1.0

    def gradients(self, grads):
        for layer in grads["layers"][1:]:
            assert not np.any(np.asarray(layer["moe"]["router_bias"]))

    def faults(self, jax, tiny, params):
        """One mechanism changed at a time (the norm after the rotation
        among them, because this file's norm scales are not all one)."""
        import jax.numpy as jnp
        lfm2 = self.module
        norm, rotated = lfm2._norm_heads, lfm2._rotated
        scale = params["layers"][1]["attn"]["q_head_norm"]["scale"]
        faults = {
            "_filtered": lambda u, w: u,
            "_gated": lambda b, c, x, w: c * lfm2._filtered(x, w),
            "_norm_heads": lambda t, scale, eps: t,
            "_rotated": lambda t, cos, sin: norm(rotated(
                t / norm(jnp.ones_like(t), scale, 0.0), cos, sin), scale, 0.0),
            "_kv_head_of": lambda h, kv: jnp.arange(h) % kv,
        }
        return [(name, {name: fault}, False) for name, fault in faults.items()]

    # the program's own forward: bf16, flash under grouped queries, the
    # convolution's kernels, the grouped-matmul kernels
    bf16_bounds = {"logprob_median_tol": 0.05, "logprob_rms_tol": 0.2}

    # a whole sparse convolution layer: every chip computes the mixer and
    # the residual alike
    experts_key, shared_layer = "num_experts", 2

    def shared_layer_is(self, layer):
        assert sorted(layer) == ["conv", "ln1", "ln2", "moe"]

    def uncut_layer(self, jax, layer, x, whole):
        lfm2 = self.module

        def reference_layer(h):
            h = h + lfm2.reference_conv(
                layer["conv"], lfm2._norm(h, layer["ln1"]["scale"], 1e-5),
                whole)
            return h, h + lfm2.reference_experts(
                layer["moe"], lfm2._norm(h, layer["ln2"]["scale"], 1e-5),
                whole)
        return jax.vmap(reference_layer)(x)

    cell_params, cell_share = 469_285_248, (0, 8, 64)

    def published(self, cell, tiny_tree):
        # the published model, tied: 23.84B, its name
        published = {k: v for k, v in cell.items() if k != "share"}
        published.update(cell["published"])
        assert round(self.module.param_count(published) / 1e9, 2) == 23.84

    def rules(self, specs, column, row):
        from jax.sharding import PartitionSpec as P
        conv, attn = specs["layers"][0]["conv"], specs["layers"][1]["attn"]
        # the three chunks split by channel, each with its channels' filter
        assert conv["w_in"] == P(None, *column)
        assert conv["filter"] == P("tensor", None)
        assert conv["w_out"] == P(*row)
        assert attn["wq"] == attn["wk"] == attn["wv"] == P(*column)
        assert attn["q_head_norm"]["scale"] == attn["k_head_norm"]["scale"] \
            == P(None)

    def sharded_step(self, jax, tiny, twin):
        """fsdp=2 x tensor=2: a key/value head with its four query heads
        and the channels of B, C, X with their filters on a shard of
        `tensor`, the kernels per shard."""
        Family.sharded_step(self, jax, tiny, twin)

    refusals = [
        case(({"attention": "ring"},
              "n_kv_heads=2 != n_heads=8.*attention='ring'"), "ring"),
        case(({"kv_latent_dim": 64, "qk_nope_dim": 16, "qk_rope_dim": 16,
               "v_head_dim": 16}, "n_kv_heads=2 != n_heads=8.*a latent block"),
             "latent"),
        case(({"n_kv_heads": 3}, "n_kv_heads=3 does not divide n_heads=8"),
             "kv_heads"),
        case(({"layer_kinds": ("conv", "attention")},
              "layer_kinds.*n_layers=4"), "kinds_length"),
        case(({"layer_kinds": ("conv", "mamba", "conv", "conv")},
              "'attention' | 'conv'"), "kinds_names"),
    ]
    pipeline_refusals = [
        case(({"n_experts": 0, "dense_layers": 0, "experts_held": None},
              {"pipeline": 2}, "parameters are not layer 0's.*conv/w_in"),
             "two_kinds"),
        case(({"layer_kinds": ("conv",) * 4, "n_experts": 0,
               "dense_layers": 0, "experts_held": None},
              {"pipeline": 2, "tensor": 2},
              "no rule for conv/filter, conv/w_in, conv/w_out"),
             "conv_under_pp_tp"),
        case(({"layer_kinds": None, "n_experts": 0, "dense_layers": 0,
               "experts_held": None}, {"pipeline": 1, "tensor": 4},
              "n_kv_heads=2 is not whole key/value heads over tp=4"),
             "kv_heads_over_tensor"),
    ]


    def scopes(self, names, regions):
        from ray_tpu.util import profiling
        assert {"conv", "conv_mix"} <= set(profiling.REGIONS)
        assert {"conv", "conv_mix", "attn_proj", "attn_core", "moe",
                "moe_route", "mlp"} <= regions
        # the two projections are `conv`'s, gates and filter `conv_mix`'s
        assert any("conv/bsd,de->bse" in n for n in names)
        assert not any("conv_mix" in n and "dot_general" in n for n in names)

    reduced = {"num_hidden_layers", "num_experts", "vocab_size",
               "layer_types", "num_dense_layers"}
    states_its_peak = False

    def cut(self, cell, row, bench):
        # published layers 1..5: the second leading dense layer, then a
        # period
        assert cell["layer_types"] == row["config"]["layer_types"][1:6] == [
            "conv", "full_attention", "conv", "conv", "conv"]
        assert cell["share"]["chips_per_layer"] * cell["num_experts"] \
            == cell["share"]["num_experts"] == 64
        assert cell["share"]["chips_per_layer"] * cell["vocab_size"] == 65536

    # lfm2_train_1chip: a convolution layer with the dense MLP, then
    # attention (32 query heads on 8 key/value heads) and three convolution
    # layers with 8 of 64 experts held. One attention layer: one call of
    # each flash kernel; q and k through rope_split forward and recomputed,
    # rope_merge backward (the heads of 64 lie in pairs since PR 55: v takes
    # no kernel; 6 and 3 before); 4 convolution layers x (forward +
    # recomputed) and x backward. As the chip runs it the dense MLP keeps
    # both products through the remat (rung 2, 0.77 GB): 9.28 GB compiled,
    # 5.63 of state and 3.65 of temporaries (8.56 at rung 0, which this
    # file compiled until PR 73, under (0.45, 0.75)); + OVERHEAD 9.70 for
    # the 9.53 the chip read (56.346 %, ledger PR 72).
    cell_kernel_calls = {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                         "rope_split": 4, "rope_merge": 2, "moe_gmm": 72,
                         "moe_tgmm": 24, "embed_grad": 1, "short_conv_fwd": 8,
                         "short_conv_bwd": 4}
    cell_memory_share = (0.52, 0.58)
    cell_rung = 2
    # x 4 a token, 8 of 64 held: 8192 expected in 256-row tiles, 2 x 32 + 8
    # = 72 tiles (18 432 rows) against 264 (67 584)
    row_spaces = (256, 72, 264)


FAMILY = Lfm2()


def test_renormalisation_epsilon_is_the_configurations(tiny):
    """1e-6 for this family, 1e-20 (the default) for kanana's."""
    from benchmark.families import kanana, lfm2
    from ray_tpu.models.gpt import GPTConfig
    assert GPTConfig().router_renormalise_eps == 1e-20
    assert GPTConfig(**lfm2.gpt_config_kwargs(
        tiny)).router_renormalise_eps == 1e-6
    other = read("benchmark", "rehearsal", "configs", "tiny-kanana.json")
    assert GPTConfig(**kanana.gpt_config_kwargs(
        other)).router_renormalise_eps == 1e-20


def test_flops_and_kernel_arithmetic_count_what_is_computed_here():
    from benchmark.families import lfm2
    from benchmark.kernels import gqa_attention
    cell = read("benchmark", "configs", "lfm2-24b-a2b.json")
    mix = read("benchmark", "traffic", "train_b2_s8192_dp.json")
    d = 2048
    active = (2 * d * d + 2 * d * 512 + 4 * (4 * d * d + 3 * d)
              + 3 * d * 11776
              + 4 * (d * 64 + 4 * 8 / 64 * 3 * d * 1536) + d * 8192)
    assert lfm2.train_flops_per_token(cell, 8192) == pytest.approx(
        6.0 * active + 3.0 * 32 * 128 * 8192)
    assert lfm2.forward_flops_per_token(cell, 8192) == pytest.approx(
        0.406e9, rel=0.01)        # a third of ISSUE 33's 1.22 GFLOP a token
    assert lfm2.attention_call(cell, mix) == {
        "batch": 2, "heads": 32, "kv_heads": 8, "seq": 8192, "head_dim": 64}
    product = 2 * 32 * 8192 * 8192 * 64
    wide, narrow = 2 * 32 * 8192 * 64 * 2, 2 * 8 * 8192 * 64 * 2
    fwd, dq, dkv = (f(cell, mix) for f in (
        gqa_attention.flash_fwd, gqa_attention.flash_bwd_dq,
        gqa_attention.flash_bwd_dkv))
    assert fwd == (2 * product, 2 * wide + 2 * narrow)
    assert dq[0] + dkv[0] == 5 * product          # the backward's five
    assert dq[1] == 3 * wide + 2 * narrow
    assert dkv[1] == 2 * wide + 4 * narrow        # dK, dV at 8 heads


def test_key_value_heads_stay_whole_over_tensor(jax_cpu, tiny):
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import lfm2
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    cfg = GPTConfig(**lfm2.gpt_config_kwargs(tiny))
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    mesh = build_mesh(MeshConfig(data=1, tensor=4), devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="n_kv_heads=2 is not whole "
                                         "key/value heads over tensor=4"):
        gpt_loss(params, {"tokens": jnp.zeros((2, 129), jnp.int32)}, cfg,
                 mesh=mesh)
