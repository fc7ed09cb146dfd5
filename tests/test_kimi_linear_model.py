"""Delta-rule layers beside a latent-attention layer that rotates nothing,
under a leading dense layer whose mixer is a delta-rule layer (models/gpt.py)
against the plain float32 reference of benchmark/families/kimi_linear.py, at
a small size on the CPU: seeded random weights, the kernels in interpret
mode. The checks every family has are tests/helpers/families.py's, given
this file's FAMILY; the cell's compile for a described chip:
tests/test_kimi_linear.py; the delta rule's and the latent kernels' own
tests: tests/test_linear_attention.py, tests/test_latent_moe_model.py."""

import numpy as np
import pytest

from helpers.families import (  # noqa: F401 — fixtures and shared checks
    Family, case, family, programmed, read, reference, seeded,
    step_kernel_calls, steps_agree,
    test_bfloat16_step_passes_the_per_token_check,
    test_configuration_file_keeps_the_catalog_and_states_the_cut,
    test_every_new_leaf_gets_its_rule,
    test_logits_loss_and_gradients_match_the_reference,
    test_param_count_is_the_published_model_and_the_programs_tree,
    test_sharded_step_equals_one_device, test_the_cell_rehearses,
    test_the_configuration_refuses_by_name,
    test_the_programs_gradient_moves_where_the_references_does,
    test_the_reference_tells_each_mechanism_apart,
    test_the_shares_of_a_layer_add_up_to_the_uncut_reference, tiny)


def _faulty_rule(kimi, fault):
    """benchmark/families/kimi_linear.py:reference_delta_rule handed one
    thing wrong: no decay (alpha = 1), or beta doubled (solar's range, (0,
    2)); None: what it is handed."""
    import jax.numpy as jnp
    sound = kimi.reference_delta_rule

    def rule(q, k, v, log_decay, beta):
        if fault == "no_decay":
            log_decay = jnp.zeros_like(log_decay)
        if fault == "beta_doubled":
            beta = 2.0 * beta
        return sound(q, k, v, log_decay, beta)
    return rule


def _without(kimi, half):
    """reference_feed_forward with one part of it left out: the leading
    layer's dense MLP, or the first of the experts held."""
    import jax.numpy as jnp

    def feed_forward(layer, h, config):
        if "mlp" in layer:
            sound = kimi._swiglu(layer["mlp"], h, jnp.float32)
            return jnp.zeros_like(sound) if half == "dense" else sound
        m = layer["moe"]
        if half == "expert":
            m = dict(m, w_down=m["w_down"].at[0].set(0.0))
        return kimi.reference_experts(m, h, config)
    return feed_forward


def _rotating_part():
    from ray_tpu.ops.rope import RopeSpec
    return {"use_rope": True, "rope": RopeSpec(rotated=0.5)}


class KimiLinear(Family):
    """benchmark/rehearsal/configs/tiny-kimi-linear.json: a delta-rule layer
    (4 heads of 32, 4-tap filters) over a dense MLP of 256, a latent layer
    that rotates nothing (4 heads of 32 + 16 / 32 on a latent of 64) and a
    delta-rule layer, both over experts 4..7 of 16 held, 2 a token, beside a
    shared one."""

    name, tiny, cell = "kimi_linear", "tiny-kimi-linear", "kimi-linear-48b-a3b"
    workload = "kimilinear_train_1chip"

    # Two delta-rule layers (the chunked form against the reference's token a
    # step; the filter kernels), a latent layer without rotation (the flash
    # kernels, or `mha_reference`), a dense MLP and experts beside a shared
    # one, in float32: the whole tree of gradients. The tolerance is solar's,
    # float32's own over 128 tokens of a state that is decayed and
    # overwritten (the two forms sum in another order): 5e-5 in a logit of
    # about 5.
    logits_atol, grads_atol = 5e-5, 2e-5

    def built(self, cfg, params):
        assert [sorted(layer) for layer in params["layers"]] == [
            ["kda", "ln1", "ln2", "mlp"], ["attn", "ln1", "ln2", "moe"],
            ["kda", "ln1", "ln2", "moe"]]
        kda, mla = params["layers"][0]["kda"], params["layers"][1]["attn"]
        assert cfg.head_dim == 32 and cfg.d_model == 128
        assert not cfg.use_rope and not cfg.kda_neg_eigval
        assert cfg.rope_of("attention") is None
        assert kda["wq"].shape == kda["wk"].shape == kda["wv"].shape \
            == (128, 4 * 32)
        assert kda["w_beta"].shape == (128, 4) and kda["a_log"].shape == (4,)
        assert mla["wq"].shape == (128, 4 * 48)
        assert mla["w_kva"].shape == (128, 64 + 16)
        assert mla["w_kvb"].shape == (64, 4 * 64) \
            and mla["wo"].shape == (4 * 32, 128)
        # the leading dense layer lies under a delta-rule mixer
        assert params["layers"][0]["mlp"]["w_up"].shape == (128, 256)
        assert params["layers"][2]["moe"]["w_up"].shape == (4, 128, 64)
        assert params["layers"][2]["moe"]["router"].shape == (128, 16)
        assert params["layers"][1]["moe"]["shared"]["w_up"].shape == (128, 64)

    def statistics(self, aux, loss, reference):
        assert float(loss) == float(aux["xent"])        # no router loss
        assert 0.0 < float(aux["expert_slots_held_share"]) < 1.0
        # beta a head in (0, 1): nothing doubles it
        assert 0.25 < float(aux["kda_beta_mean"]) < 0.75
        assert -1e4 < float(aux["kda_log_decay_min"]) < -87.0

    def moves(self, name):
        # the selection bias enters the choice alone: no gradient
        return "router_bias" not in name

    def other_configurations(self, tiny):
        # the fault this configuration is most likely to hide: q's 16 "rope"
        # columns and the 16 shared key columns ROTATED
        return {"rope_columns_rotated": dict(tiny, mla_use_nope=False)}

    def faults(self, jax, tiny, params):
        """(and the faulty copies with nothing changed give the
        reference's)"""
        kimi = self.module
        return [(fault or "rule_unchanged",
                 {"reference_delta_rule": _faulty_rule(kimi, fault)},
                 fault is None)
                for fault in (None, "no_decay", "beta_doubled")] + [
            (half or "feed_forward_unchanged",
             {"reference_feed_forward": _without(kimi, half)}, half is None)
            for half in (None, "dense", "expert")]

    # the program's own forward: bf16, the flash, filter and delta-rule
    # kernels, the grouped-matmul kernels; nan where any of its three bounds
    # is broken
    bf16_bounds = {"logprob_median_tol": 0.15, "logprob_rms_tol": 0.6,
                   "logprob_p99_tol": 3.0}
    bf16_broken = tuple(bf16_bounds)

    experts_key, shared_layer = "num_experts", 1

    def shared_layer_is(self, layer):
        assert sorted(layer) == ["attn", "ln1", "ln2", "moe"]

    def uncut_layer(self, jax, layer, x, whole):
        import jax.numpy as jnp
        kimi = self.module

        def reference_layer(h):
            h = h + kimi.reference_mixer(
                layer, kimi._norm(h, layer["ln1"]["scale"], 1e-5), whole)
            m = kimi._norm(h, layer["ln2"]["scale"], 1e-5)
            shared = kimi._swiglu(layer["moe"]["shared"], m, jnp.float32)
            return h + shared, h + kimi.reference_experts(layer["moe"], m,
                                                          whole)
        return jax.vmap(reference_layer)(x)

    cell_params, cell_share = 602_434_432, (0, 8, 256)  # ISSUE 65's 602.4M

    def published(self, cell, tiny_tree):
        from ray_tpu.models.gpt import count_params
        kimi = self.module
        # both mixers whole, by ISSUE 65's arithmetic
        m = kimi._matrices(cell)
        assert m["kda"] == 39_460_864 and m["attention"] == 29_114_368
        assert m["dense"] == 63_700_992 and m["router"] == 589_824
        assert m["expert"] == 7_077_888 == m["shared"]
        assert count_params(tiny_tree["layers"][1]["attn"]) == (
            128 * 4 * 48 + 128 * 80 + 64 + 64 * 4 * 64 + 128 * 128)
        # the published model: 49.12B, its name's 48B; 3.5B a token ("A3B")
        published = {k: v for k, v in cell.items() if k != "share"}
        published.update(cell["published"])
        assert round(kimi.param_count(published) / 1e9, 2) == 49.12
        assert round(kimi.active_param_count(published) / 1e9, 1) == 3.5
        assert kimi._kinds(published).count("kda") == 20

    def rules(self, specs, column, row):
        from jax.sharding import PartitionSpec as P
        kda, attn = specs["layers"][0]["kda"], specs["layers"][1]["attn"]
        moe, mlp = specs["layers"][1]["moe"], specs["layers"][0]["mlp"]
        assert kda["wq"] == kda["wk"] == kda["wv"] == kda["w_beta"] \
            == attn["wq"] == attn["w_kvb"] == P(*column)
        assert kda["wo"] == attn["wo"] == P(*row)
        assert kda["wf_up"] == kda["wg_up"] == P(None, "tensor")
        assert kda["wf_down"] == kda["wg_down"] == P(column[0], None)
        assert kda["q_conv"] == kda["k_conv"] == kda["v_conv"] \
            == P("tensor", None)
        assert kda["a_log"] == kda["dt_bias"] == P("tensor")
        assert attn["w_kva"] == P(None, None) \
            and attn["kv_norm"]["scale"] == P(None)
        # the dense layer under the delta-rule mixer, and the shared expert
        assert mlp["w_up"] == moe["shared"]["w_up"] == P(*column)
        assert mlp["w_down"] == moe["shared"]["w_down"] == P(*row)
        assert moe["w_up"] == P("expert", *column)
        assert moe["router_bias"] == P(None)

    def sharded_step(self, jax, tiny, twin):
        """A delta-rule layer over the dense MLP and the latent layer over
        experts on tensor=2 (two delta-rule heads with their filters, decay
        rates and step biases, and two latent heads, on a shard of `tensor`;
        the shared key columns whole on both; the kernels per shard)."""
        two = dict(tiny, num_hidden_layers=2, linear_attn_config=dict(
            tiny["linear_attn_config"], kda_layers=[1], full_attn_layers=[2]))
        steps_agree(jax, self, two, rows=2, strategy="tp",
                    axes={"data": 1, "tensor": 2})

    refusals = [
        case(({"attention": "ring"},
              "'kda' layer's state.*attention='ring'"), "ring"),
        case(({"index_topk": 8, "index_heads": 2, "index_head_dim": 16},
              "rotate nothing.*not for an indexer"), "indexer_unrotated"),
        case((_rotating_part, "latent block rotates.*reads no RopeSpec"),
             "latent_rope_spec"),
        case(({"n_kv_heads": 2}, "n_kv_heads=2 != n_heads=4.*a latent block"),
             "latent_grouped"),
    ]

    def scopes(self, names, regions):
        from ray_tpu.util import profiling
        assert {"kda", "kda_core", "attn_latent", "attn_core", "attn_proj",
                "attn_out", "mlp", "moe", "moe_route", "moe_shared"} <= regions
        assert any("attn_proj/attn_latent" in n for n in names)
        for n in names:
            if "kda_fwd" in n or "kda_bwd" in n:
                assert profiling._last_of(n, profiling.REGIONS) == "kda_core"
            if "conv_silu" in n:
                assert profiling._last_of(n, profiling.REGIONS) == "kda"

    reduced = {"num_hidden_layers", "num_experts", "vocab_size",
               "linear_attn_config"}

    def cut(self, cell, row, bench):
        catalog = row["config"]["linear_attn_config"]
        # of the group, the two layer lists alone, cut to the depth: no
        # width and no head count moves
        assert cell["linear_attn_config"] == dict(
            catalog,
            kda_layers=[i for i in catalog["kda_layers"] if i <= 5],
            full_attn_layers=[i for i in catalog["full_attn_layers"]
                              if i <= 5])
        assert self.module._kinds(cell) == ("kda",) * 3 + ("attention", "kda")
        assert cell["first_k_dense_replace"] == 1 and cell["mla_use_nope"]
        share = cell["share"]
        assert share["expert_parallel"] == share["chips_per_layer"] == 32
        assert share["expert_parallel"] * cell["num_experts"] \
            == share["num_experts"] == 256
        assert share["vocabulary_slices"] * cell["vocab_size"] \
            == share["vocab_size"] == 163840
        assert {"sequence_length", "selection_bias_init_std", "decay_init",
                "kda_form", "mla_rope_columns"} <= set(cell["assumed"])
        assert "9.64 GB" in cell["deployment"]
        assert cell["train"]["learning_rate"] == 1e-7

    # kimilinear_train_1chip (1 x 8192 tokens): four delta-rule layers of 32
    # heads (`kda_fwd` once a layer, kept through the remat, `kda_bwd` once;
    # the plain filter on q, k and v forward, ONCE, and backward: as the chip
    # runs it every layer keeps the top rung of the ladder, so the backward
    # pass neither multiplies nor filters q, k and v again (24 calls of
    # `conv_silu_fwd` at rung 0, which this file compiled until PR 73)), one
    # latent layer that rotates nothing (one call of each flash kernel at
    # q.k 192 padded to 256 / v 128; q's and kv's latent kernels forward +
    # recomputed, their merges backward; no `rope_split` anywhere), 8 of
    # 256 experts held in four layers, their rows in tiles of 128 (each
    # grouped matmul in the text twice: the bounded row space and every
    # slot's). 14.16 GB compiled, 2.05 of it kept (12.56 at rung 0, under
    # (0.57, 0.92)); + OVERHEAD 14.58 for the 14.45 the chip read (85.463 %,
    # ledger PR 72).
    cell_kernel_calls = {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                         "rope_split": 0, "rope_merge": 0,
                         "latent_q_split": 2, "latent_kv_split": 2,
                         "latent_q_merge": 1, "latent_kv_merge": 1,
                         "moe_gmm": 72, "moe_tgmm": 24, "embed_grad": 1,
                         "conv_silu_fwd": 12, "conv_silu_bwd": 12,
                         "kda_fwd": 4, "kda_bwd": 4}
    cell_memory_share = (0.81, 0.87)
    cell_rung = 4


FAMILY = KimiLinear()


# ---------------------------------------------------------------------------
# (a) what only this stack has: a latent block without rotation, a dense
# layer under a delta-rule mixer, beta a head in (0, 1)
# ---------------------------------------------------------------------------


def _one_latent_layer(tiny, wide):
    """tiny-kimi-linear cut to ONE latent layer over the dense MLP; wide:
    heads of 128 + 64 / 128, the cell's, at which ops/rope.py's latent
    kernels engage (two heads: one group)."""
    widths = dict(qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                  num_attention_heads=2, num_key_value_heads=2) if wide else {}
    linear = dict(tiny["linear_attn_config"], kda_layers=[],
                  full_attn_layers=[1], **({"num_heads": 2} if wide else {}))
    return dict(tiny, num_hidden_layers=1, linear_attn_config=linear,
                **widths)


@pytest.mark.parametrize("attention,wide", [
    ("flash", True), ("flash", False), ("reference", False)],
    ids=["latent_kernels", "flash_jnp_assembly", "reference"])
def test_a_latent_layer_that_rotates_nothing_is_the_float32_formula(
        jax_cpu, tiny, attention, wide):
    """q's 192 columns as projected, k = [W_kvb's nope | the shared rope
    columns of c], v: what `_attention_block` adds equals
    kimi_linear.reference_attention, value and gradients, on the flash path
    through ops/rope.py's four latent kernels WITHOUT a table (heads of 128 +
    64 / 128), on the flash path's jnp assembly (heads of 32 + 16 / 32) and
    on the reference path; it DIFFERS from the same weights with the 64
    columns rotated (`mla_use_nope` false), in program and reference alike;
    and no cosine is computed anywhere in it (no table is built). The
    tolerance is float32's over a softmax of 128 keys at full matmul
    precision."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    kimi = FAMILY.module
    config = _one_latent_layer(tiny, wide)
    cfg = FAMILY.config(config, attention=attention, dtype=jnp.float32,
                        remat_policy="none")
    assert cfg.rope_of("attention") is None
    layer = gpt.gpt_init(jax.random.PRNGKey(1), cfg)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 128), jnp.float32)
    weight = jnp.cos(0.37 * jnp.arange(128 * 128).reshape(128, 128))

    def program(layer, x, cfg=cfg, table=()):
        y = gpt._attention_block(layer, x, cfg, table, gpt.Setting())[0]
        return jnp.sum(y * weight), y

    def formula(layer, x, config=config):
        y = jax.vmap(lambda n: kimi.reference_attention(
            layer["attn"], n, config))(x)
        return jnp.sum(y * weight), y
    grad = jax.value_and_grad(program, argnums=(0, 1), has_aux=True)
    jaxpr = str(jax.make_jaxpr(grad)(layer, x))
    assert " cos " not in jaxpr and " sin " not in jaxpr
    kernels = ("latent_q_split", "latent_kv_split", "latent_q_merge",
               "latent_kv_merge")
    assert all((f"name={k}" in jaxpr) == wide for k in kernels)
    assert ("name=flash_fwd" in jaxpr) == (attention == "flash")
    with jax.default_matmul_precision("highest"):
        (_, y), grads = jax.jit(grad)(layer, x)
        (_, want), want_grads = jax.jit(jax.value_and_grad(
            formula, argnums=(0, 1), has_aux=True))(layer, x)
        rotated = jax.jit(lambda l, x: formula(
            l, x, dict(config, mla_use_nope=False))[1])(layer, x)
        turning = FAMILY.config(dict(config, mla_use_nope=False),
                                attention=attention, dtype=jnp.float32,
                                remat_policy="none")
        table = gpt.rope_table(128, turning.qk_rope_dim,
                               turning.rope_of("attention"))
        y_rotated = jax.jit(lambda l, x: program(l, x, turning, table)[1])(
            layer, x)
    np.testing.assert_allclose(y, want, atol=2e-5)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(
            g, r, atol=2e-5 * max(1.0, float(np.abs(r).max())),
            err_msg=jax.tree_util.keystr(path))
    # the rotated block is the same in program and formula, and another
    np.testing.assert_allclose(y_rotated, rotated, atol=2e-5)
    assert float(jnp.abs(rotated - want).max()) > 1e-2


def test_a_dense_layer_lies_under_a_delta_rule_mixer(jax_cpu, seeded):
    """`dense_d_ff` with layer_kinds[0] == "kda": the first layer's block is
    x + KDA(norm1 x), then + SwiGLU(norm2 .) at `intermediate_size`, the
    reference's two halves; it hands back the delta rule's statistics and
    none of a router's."""
    jax = jax_cpu
    from ray_tpu.models.gpt import Setting, layer_fn
    kimi = FAMILY.module
    cfg, params, _tokens = seeded("flash")
    layer = params["layers"][0]
    assert cfg.dense_layers == 1 and cfg.layer_kinds[0] == "kda"
    assert sorted(layer) == ["kda", "ln1", "ln2", "mlp"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 128))
    config = FAMILY.tiny_config()

    def halves(h):
        h = h + kimi.reference_kda(
            layer["kda"], kimi._norm(h, layer["ln1"]["scale"], 1e-5), config)
        return h + kimi._swiglu(
            layer["mlp"], kimi._norm(h, layer["ln2"]["scale"], 1e-5),
            jax.numpy.float32)
    with jax.default_matmul_precision("highest"):
        y, stats = jax.jit(layer_fn(cfg, 128, Setting()))(x, layer)
        want = jax.jit(jax.vmap(halves))(x)
    np.testing.assert_allclose(y, want, atol=5e-5)
    assert sorted(stats) == ["kda_beta_mean", "kda_log_decay_min"]


def test_beta_stays_under_one(jax_cpu, seeded):
    """No key of this configuration doubles beta (solar's
    `kda_allow_neg_eigval`): with a beta projection that saturates the
    sigmoid every head's beta is 1 and no more, where solar's range would
    give 2."""
    jax = jax_cpu
    import dataclasses
    import jax.numpy as jnp
    from ray_tpu.models.gpt import Setting, _kda_block
    cfg, params, _tokens = seeded("flash")
    m = dict(params["layers"][0]["kda"])
    x = jnp.ones((1, 64, 128), jnp.float32)
    m["w_beta"] = jnp.full_like(m["w_beta"], 1.0)          # logits of 128
    _, stats = _kda_block(m, x, cfg, Setting())
    assert 0.999 < float(stats["kda_beta_mean"]) <= 1.0
    _, doubled = _kda_block(
        m, x, dataclasses.replace(cfg, kda_neg_eigval=True), Setting())
    assert float(doubled["kda_beta_mean"]) > 1.99


# ---------------------------------------------------------------------------
# (b) arithmetic, calls
# ---------------------------------------------------------------------------


def test_flops_and_kernel_arithmetic_count_what_is_computed_here():
    """The two arithmetic modules that were there serve this family
    unedited, through `attention_call` and `kda_call`."""
    from benchmark.kernels import delta_rule, kda, mla_attention
    kimi = FAMILY.module
    cell = read("benchmark", "configs", "kimi-linear-48b-a3b.json")
    mix = read("benchmark", "traffic", "train_b1_s8192_dp.json")
    d, s = 2304, 8192
    expert = 3 * d * 1024
    active = (4 * 39_460_864 + 29_114_368 + 3 * d * 9216
              + 4 * (d * 256 + expert + 8 * 8 / 256 * expert) + d * 20480)
    # ISSUE 65's ~333M matmul parameters a token touches here
    assert active == pytest.approx(333e6, rel=0.01)
    rule = 5 * 64 * 128 + 6 * 128 * 128         # a token and head, forward
    assert kimi.train_flops_per_token(cell, s) == pytest.approx(
        6.0 * active + 3.0 * (32 * (192 + 128) * s + 4 * 32 * rule))
    assert kimi.attention_call(cell, mix) == {
        "batch": 1, "heads": 32, "seq": s, "qk_dim": 192, "v_dim": 128}
    assert kimi.kda_call(cell, mix) == {
        "batch": 1, "heads": 32, "seq": s, "head_dim": 128, "taps": 4,
        "chunk": 64}
    square = 32 * s * s
    assert mla_attention.flash_fwd(cell, mix)[0] == square * (192 + 128)
    assert mla_attention.flash_bwd_dq(cell, mix)[0] \
        + mla_attention.flash_bwd_dkv(cell, mix)[0] \
        == square * (3 * 192 + 2 * 128)
    elements = s * 4096
    assert kda.conv_silu_fwd(cell, mix) == (11 * elements, 4 * elements)
    assert kda.conv_silu_bwd(cell, mix) == (32 * elements, 6 * elements)
    # four times solar's call: 32 heads for 8, bound by bytes on the count
    for fn, ms in ((delta_rule.kda_fwd, 4 * 0.205),
                   (delta_rule.kda_bwd, 4 * 0.308)):
        flops, moved = fn(cell, mix)
        assert flops / 197e12 < moved / 819e9
        assert 1e3 * moved / 819e9 == pytest.approx(ms, rel=0.01)
    assert delta_rule.kda_fwd(cell, mix)[0] == 32 * s * rule


def test_the_step_runs_each_kernel_as_often_as_the_layers_say(jax_cpu, tiny):
    """The step's calls are the counter. Under remat_policy="full" a
    delta-rule layer's output and chunk states are kept (KDA_OUT): `kda_fwd`
    once a layer and never in the recompute pass, `kda_bwd` once a layer; the
    filter kernels forward and recomputed; the latent layer's forward flash
    kernel once (FLASH_OUT), and no `rope_split`: nothing is rotated and the
    tiny heads take the jnp assembly."""
    cfg, calls, _jaxpr = step_kernel_calls(jax_cpu, FAMILY, tiny)
    assert cfg.remat_policy == "full"
    assert calls[("flash_fwd", False)] == 1 and calls[("flash_fwd", True)] == 0
    assert calls[("flash_bwd_dq", True)] + calls[("flash_bwd_dq", False)] == 1
    assert calls[("kda_fwd", False)] == 2 and calls[("kda_fwd", True)] == 0
    assert calls[("kda_bwd", False)] + calls[("kda_bwd", True)] == 2
    assert calls[("conv_silu_fwd", False)] == calls[("conv_silu_fwd", True)] \
        == 6
    assert calls[("conv_silu_bwd", False)] + calls[("conv_silu_bwd", True)] \
        == 6
    assert not any(name.startswith(("rope_", "latent_"))
                   for name, _ in calls)
