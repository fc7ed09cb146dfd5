"""Gated delta-rule layers with ONE decay a head at a key and a value width of
their own beside a full-attention layer that rotates nothing under a q/k norm
over the projection, every half under a norm AFTER it (models/gpt.py:
GPTConfig.delta, norm_after) against the plain float32 reference of
benchmark/families/olmo_hybrid.py, at a small size on the CPU: seeded random
weights, the kernels in interpret mode. The checks every family has are
tests/helpers/families.py's, given this file's FAMILY; the cell's compile for
a described chip: tests/test_olmo_hybrid.py; the delta rule's own tests:
tests/test_linear_attention.py."""

import copy

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
    test_the_reference_tells_each_mechanism_apart, tiny)


def _faults(olmo):
    """fault -> {attribute of benchmark/families/olmo_hybrid.py: what stands
    in for it}: the controls of `program_check` that no key of the
    configuration writes."""
    import jax
    import jax.numpy as jnp
    rule, block = olmo.reference_delta_rule, olmo.reference_block

    def no_decay(q, k, v, g, beta):
        return rule(q, k, v, jnp.zeros_like(g), beta)

    def norm_before(layer, x, config):
        eps = float(config["rms_norm_eps"])
        h = x + olmo.reference_mixer(
            layer, olmo._norm(x, layer["ln1"]["scale"], eps), config)
        return h + olmo._swiglu(
            layer["mlp"], olmo._norm(h, layer["ln2"]["scale"], eps),
            jnp.float32)
    return {
        "no_decay": {"reference_delta_rule": no_decay},
        "norm_before_each_half": {"reference_block": norm_before},
        "gate_a_sigmoid": {
            "reference_gate": lambda o, z: o * jax.nn.sigmoid(z)},
        "no_qk_norm": {"reference_qk_norm": lambda y, scale, eps: y},
        "unchanged": {"reference_delta_rule": rule, "reference_block": block},
    }


class OlmoHybrid(Family):
    """benchmark/rehearsal/configs/tiny-olmo-hybrid.json: two gated
    delta-rule layers (heads 2..3 of 4 held, key 32 / value 64, a decay a
    head, ONE 4-tap filter over a head's 128 columns [q | k | v], a SiLU gate
    from a full matrix) around a
    full-attention layer that rotates nothing (2 of 4 heads of 32, the q/k
    norm over the held 64 columns), the norm after each half, a gated MLP of
    256 in every layer."""

    name, tiny, cell = "olmo_hybrid", "tiny-olmo-hybrid", "olmo-hybrid-7b"
    workload = "olmohybrid_train_1chip"

    # solar's and kimi's tolerance: float32's own over 128 tokens of a state
    # that is decayed and overwritten (the two forms sum in another order)
    logits_atol, grads_atol = 5e-5, 2e-5

    def built(self, cfg, params):
        from ray_tpu.models.gpt import DeltaRule
        assert [sorted(layer) for layer in params["layers"]] == [
            ["kda", "ln1", "ln2", "mlp"], ["attn", "ln1", "ln2", "mlp"],
            ["kda", "ln1", "ln2", "mlp"]]
        kda, attn = params["layers"][0]["kda"], params["layers"][1]["attn"]
        assert cfg.delta == DeltaRule(32, 64, "head", "silu")
        assert cfg.norm_after and cfg.qk_norm and cfg.kda_neg_eigval
        assert cfg.head_dim == 32 and cfg.rope_of("attention") is None
        assert kda["w_qkv"].shape == (128, 2 * (32 + 32 + 64))
        assert kda["qkv_conv"].shape == (2 * 128, 4)
        assert kda["wg"].shape == (128, 2 * 64)
        assert kda["w_decay"].shape == kda["w_beta"].shape == (128, 2)
        assert kda["dt_bias"].shape == kda["a_log"].shape == (2,)
        assert kda["o_norm"]["scale"].shape == (64,)
        assert kda["wo"].shape == (128, 128)
        assert not {"wq", "wk", "wv", "q_conv", "wf_down", "wf_up", "wg_down",
                    "wg_up"} & set(kda)
        # the q/k norm's scales over the HELD projection
        assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape \
            == (2 * 32,)

    def statistics(self, aux, loss, reference):
        assert float(loss) == float(aux["xent"])
        # beta a head doubled: (0, 2)
        assert 0.5 < float(aux["kda_beta_mean"]) < 1.5
        assert -1e4 < float(aux["kda_log_decay_min"]) < 0.0

    def other_configurations(self, tiny):
        return {"beta_not_doubled": dict(tiny, linear_allow_neg_eigval=False),
                "full_layer_rotated": dict(
                    tiny, rope_parameters={"rope_theta": 500000.0})}

    def faults(self, jax, tiny, params):
        return [(fault, replaced, fault == "unchanged")
                for fault, replaced in _faults(self.module).items()]

    bf16_bounds = {"logprob_median_tol": 0.15, "logprob_rms_tol": 0.6,
                   "logprob_p99_tol": 3.0}
    bf16_broken = tuple(bf16_bounds)

    cell_params, cell_share = 766_241_946, (0, 15, 30)    # ISSUE 67's 766.2M

    def published(self, cell, tiny_tree):
        olmo = self.module
        m = olmo._matrices(cell)
        # held, by ISSUE 67's arithmetic
        assert m["gdn"] == 33_177_600 + 115_200 + 11_059_200
        assert m["attention"] == 29_491_200 and m["mlp"] == 126_812_160
        published = {k: v for k, v in cell.items() if k != "share"}
        published.update(cell["published"])
        assert round(olmo.param_count(published) / 1e9, 2) == 7.43
        assert olmo._kinds(published).count("kda") == 24

    def rules(self, specs, column, row):
        from jax.sharding import PartitionSpec as P
        kda, attn = specs["layers"][0]["kda"], specs["layers"][1]["attn"]
        assert kda["w_qkv"] == kda["wg"] == kda["w_decay"] == kda["w_beta"] \
            == attn["wq"] == P(*column)
        assert kda["wo"] == attn["wo"] == P(*row)
        assert kda["qkv_conv"] == P("tensor", None)
        assert kda["a_log"] == kda["dt_bias"] == P("tensor")
        assert kda["o_norm"]["scale"] == P(None)

    def sharded_step(self, jax, tiny, twin):
        """A delta-rule layer and the full layer on tensor=2 (a head of each
        with its filters, decay rate and step bias on a shard of `tensor`;
        the q/k norm's mean square and the norm after a mixer over both
        shards' columns and sums; the kernels per shard)."""
        steps_agree(jax, self, dict(tiny, num_hidden_layers=2,
                                    layer_types=tiny["layer_types"][:2]),
                    rows=2, strategy="tp", axes={"data": 1, "tensor": 2})

    refusals = [
        case(({"attention": "ring"},
              "'kda' layer's state.*attention='ring'"), "ring"),
        case((lambda: {"delta": _delta(decay="token")},
              r"delta .*expected decay 'channel' \| 'head'"), "decay_form"),
        case((lambda: {"delta": _delta(gate="tanh")},
              r"delta .*gate 'sigmoid' \| 'silu'"), "gate_form"),
    ]

    def scopes(self, names, regions):
        from ray_tpu.util import profiling
        assert {"kda", "kda_core", "attn_core", "attn_proj", "attn_out",
                "mlp", "norm"} <= regions
        # the norm after a half is counted under `norm`, outside the half
        assert any("/norm/" in n for n in names
                   if "kda" not in n and "attn" not in n and "mlp" not in n)
        for n in names:
            if "kda_fwd" in n or "kda_bwd" in n:
                assert profiling._last_of(n, profiling.REGIONS) == "kda_core"
            if "conv_silu" in n:
                assert profiling._last_of(n, profiling.REGIONS) == "kda"

    reduced = {"num_hidden_layers", "layer_types", "vocab_size",
               "num_attention_heads", "num_key_value_heads",
               "linear_num_key_heads", "linear_num_value_heads"}

    def cut(self, cell, row, bench):
        catalog = row["config"]
        assert cell["layer_types"] == catalog["layer_types"][:4] \
            == ["linear_attention"] * 3 + ["full_attention"]
        # no width moves
        for key in ("hidden_size", "intermediate_size", "linear_key_head_dim",
                    "linear_value_head_dim", "linear_conv_kernel_dim"):
            assert cell[key] == catalog[key]
        assert cell["head_dim"] == 128 == catalog["hidden_size"] \
            // catalog["num_attention_heads"]
        share = cell["share"]
        assert share["tensor_parallel"] == share["chips_per_layer"] == 2
        assert share["tensor_parallel"] * cell["num_attention_heads"] \
            == share["num_attention_heads"] == 30
        assert share["vocabulary_slices"] * cell["vocab_size"] \
            == share["vocab_size"] == 100352
        assert {"sequence_length", "block_form", "no_rotation", "head_dim",
                "delta_rule_form", "decay_init", "embedding_init_std"} \
            <= set(cell["assumed"])
        assert "12.26 GB" in cell["deployment"]
        assert len(cell["departures"]) >= 4

    # olmohybrid_train_1chip (1 x 8192 tokens): three delta-rule layers of
    # 15 heads (`kda_fwd` once a layer, kept through the remat, `kda_bwd`
    # once; ONE plain filter over [q | k | v] forward + recomputed, and
    # backward), one full layer of 15 heads of 128 that rotates nothing (one
    # call of each flash kernel; q, k, v through `rope_split` without a
    # table, forward and recomputed, as solar's grouped-query layer). As
    # the chip runs it all four MLPs keep `up x` through the remat (rung 1,
    # 0.72 GB; `gate x` does not fit): 13.67 GB compiled (13.62 at rung 0,
    # which this file compiled until PR 73, under (0.72, 0.92)); + OVERHEAD
    # 14.09 for the 13.77 the chip read (81.426 %, ledger PR 72).
    cell_kernel_calls = {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                         "rope_split": 6, "rope_merge": 3, "embed_grad": 1,
                         "conv_silu_fwd": 6, "conv_silu_bwd": 3,
                         "kda_fwd": 3, "kda_bwd": 3}
    cell_memory_share = (0.78, 0.84)
    cell_rung = 1


def _delta(**change):
    from ray_tpu.models.gpt import DeltaRule
    return DeltaRule(**dict({"key_dim": 32, "value_dim": 64,
                             "decay": "head", "gate": "silu"}, **change))


FAMILY = OlmoHybrid()

# (this family holds no experts: the shared check of a share's experts is
# not its; its own, below, adds up the heads)


# ---------------------------------------------------------------------------
# The share: heads of both mixers
# ---------------------------------------------------------------------------

def _heads_of(layer, rank, whole_cfg, held):
    """The parameters rank `rank` of the pair holds of one uncut layer: its
    heads' columns of the in-projections, filters, rates, biases and q/k
    norm scales, its rows of wo; what is whole on every chip as it is."""
    size, hd = whole_cfg.delta_rule, whole_cfg.head_dim

    def columns(x, width, axis=-1):
        return x.take(np.arange(rank * held * width,
                                (rank + 1) * held * width), axis=axis)
    out = dict(layer)
    if "kda" in layer:
        m = layer["kda"]
        fused = 2 * size.key_dim + size.value_dim
        out["kda"] = dict(
            m, w_qkv=columns(m["w_qkv"], fused),
            qkv_conv=columns(m["qkv_conv"], fused, 0),
            wg=columns(m["wg"], size.value_dim),
            w_decay=columns(m["w_decay"], 1), w_beta=columns(m["w_beta"], 1),
            a_log=columns(m["a_log"], 1), dt_bias=columns(m["dt_bias"], 1),
            wo=columns(m["wo"], size.value_dim, 0))
    else:
        a = layer["attn"]
        out["attn"] = dict(
            a, **{w: columns(a[w], hd) for w in ("wq", "wk", "wv")},
            wo=columns(a["wo"], hd, 0),
            q_norm={"scale": columns(a["q_norm"]["scale"], hd)},
            k_norm={"scale": columns(a["k_norm"]["scale"], hd)})
    return out


@pytest.mark.parametrize("kind", ["kda", "attention"])
def test_a_pair_of_shares_is_the_uncut_reference_layer(jax_cpu, kind):
    """model-configs guide, section 4: a whole layer, mixer, MLP and both
    norms included. On a mesh of tensor = 2 each shard holds one share's
    heads and the two sums a deployment all-reduces are real (the q/k norm's
    mean square over the projection, the mixer's output before its norm);
    the MLP is counted once. That equals the uncut reference's layer; a
    delta-rule mixer's two shares, each run as the one-chip cell runs it,
    add up to the uncut mixer (no statistic crosses its heads before wo);
    and one share alone is not the whole."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import Setting, _kda_block, gpt_init, layer_fn
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    olmo = FAMILY.module
    tiny = FAMILY.tiny_config()
    whole = copy.deepcopy(tiny)
    del whole["share"]
    for key in olmo._HEAD_KEYS:
        whole[key] = 4

    def config(c, **fields):
        return FAMILY.config(c, dtype=jnp.float32, remat_policy="none",
                             **fields)
    cfg = config(whole)
    index = list(cfg.layer_kinds).index(kind)
    layer = gpt_init(jax.random.PRNGKey(7), cfg)["layers"][index]
    # norms with scales that differ from one, so that they are seen
    for name in ("ln1", "ln2"):
        layer[name]["scale"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(11), (128,))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 128), jnp.float32)

    mesh = build_mesh(MeshConfig(data=1, tensor=2), devices=jax.devices()[:2])
    strategy = strategy_from_name("tp")
    placed = jax.device_put(layer, strategy.param_shardings(mesh, layer))
    where = Setting(mesh, strategy.activation_sharding(mesh))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(
            lambda h: olmo.reference_block(layer, h, whole)))(x)
        got, _ = jax.jit(layer_fn(cfg, 64, where))(x, placed)
        np.testing.assert_allclose(got, want, atol=5e-5)
        # a share as the cell runs it: the program's layer is the
        # reference's on the same held parameters, and not the whole
        parts = []
        for rank in range(2):
            mine_config = dict(tiny, share=dict(tiny["share"], rank=rank))
            assert olmo.share(mine_config) == (2 * rank, 2, 4)
            mine_cfg = config(mine_config)
            mine = _heads_of(layer, rank, cfg, 2)
            out, _ = jax.jit(layer_fn(mine_cfg, 64, Setting()))(x, mine)
            np.testing.assert_allclose(out, jax.vmap(
                lambda h: olmo.reference_block(mine, h, mine_config))(x),
                atol=5e-5)
            assert float(jnp.abs(out - want).max()) > 1e-2
            if kind == "kda":
                parts.append(_kda_block(mine["kda"], x, mine_cfg,
                                        Setting())[0])
        if kind == "kda":
            np.testing.assert_allclose(sum(parts), jax.vmap(
                lambda h: olmo.reference_gdn(layer["kda"], h, whole))(x),
                atol=5e-5)


# ---------------------------------------------------------------------------
# Arithmetic, calls
# ---------------------------------------------------------------------------

def test_flops_and_kernel_arithmetic_count_what_is_computed_here():
    """benchmark/kernels/gated_delta.py at dk != dv and a decay a head, and
    flash_attention.py through `attention_call`, unedited."""
    from benchmark.kernels import (delta_rule, flash_attention, gated_delta,
                                   kda)
    olmo = FAMILY.module
    cell = read("benchmark", "configs", "olmo-hybrid-7b.json")
    mix = read("benchmark", "traffic", "train_b1_s8192_dp.json")
    d, s = 3840, 8192
    active = 3 * 44_352_000 + 29_491_200 + 4 * 126_812_160 + d * 12544
    # ISSUE 67's ~718M matmul parameters a token goes through here
    assert active == pytest.approx(718e6, rel=0.01)
    rule = 64 * (3 * 96 + 2 * 192) + 6 * 96 * 192    # a token and head
    assert gated_delta.flops_per_token(64, 96, 192) == rule
    # at dk = dv the count is kda.py's, which stated it first
    assert gated_delta.flops_per_token(64, 128, 128) \
        == delta_rule.delta_rule_flops_per_token(64, 128, 128)
    assert olmo.train_flops_per_token(cell, s) == pytest.approx(
        6.0 * active + 3.0 * (15 * 2 * 128 * s + 3 * 15 * rule))
    assert olmo.attention_call(cell, mix) == {
        "batch": 1, "heads": 15, "seq": s, "head_dim": 128}
    assert olmo.kda_call(cell, mix) == {
        "batch": 1, "heads": 15, "seq": s, "key_dim": 96, "value_dim": 192,
        "head_dim": 384, "taps": 4, "chunk": 64}
    # ONE filtered tensor of 15 x 384 = 45 lane tiles of columns
    elements = s * 15 * 384
    assert elements % 128 == 0
    assert kda.conv_silu_fwd(cell, mix) == (11 * elements, 4 * elements)
    assert kda.conv_silu_bwd(cell, mix) == (32 * elements, 6 * elements)
    assert flash_attention.flash_fwd(cell, mix)[0] == 2.0 * 15 * s * s * 128
    tokens = 15 * s
    flops, moved = gated_delta.kda_fwd(cell, mix)
    assert flops == tokens * rule
    assert moved == tokens * (2 * (2 * 96 + 2 * 192) + 8 + 4 * 96 * 192 / 64)
    flops, moved = gated_delta.kda_bwd(cell, mix)
    assert flops == 2 * tokens * rule
    assert moved == tokens * (2 * (4 * 96 + 3 * 192) + 16 + 4 * 96 * 192 / 64)
    # both bound by bytes on the count, as solar's and kimi's
    for fn in (gated_delta.kda_fwd, gated_delta.kda_bwd):
        flops, moved = fn(cell, mix)
        assert flops / 197e12 < moved / 819e9


def test_the_step_runs_each_kernel_as_often_as_the_layers_say(jax_cpu, tiny):
    """The step's calls are the counter. Under remat_policy="full" a
    delta-rule layer's output, chunk states and kept matrices are kept
    (KDA_OUT): `kda_fwd` once a layer and never in the recompute pass,
    `kda_bwd` once a layer; the filter kernels forward and recomputed; the
    full layer's forward flash kernel once (FLASH_OUT), and no `rope_split`:
    nothing is rotated."""
    cfg, calls, _jaxpr = step_kernel_calls(jax_cpu, FAMILY, tiny)
    assert cfg.remat_policy == "full"
    assert calls[("flash_fwd", False)] == 1 and calls[("flash_fwd", True)] == 0
    assert calls[("flash_bwd_dq", True)] + calls[("flash_bwd_dq", False)] == 1
    assert calls[("kda_fwd", False)] == 2 and calls[("kda_fwd", True)] == 0
    assert calls[("kda_bwd", False)] + calls[("kda_bwd", True)] == 2
    # ONE filter call a layer, over [q | k | v]
    assert calls[("conv_silu_fwd", False)] == calls[("conv_silu_fwd", True)] \
        == 2
    assert calls[("conv_silu_bwd", False)] + calls[("conv_silu_bwd", True)] \
        == 2
    assert not any(name.startswith(("rope_", "latent_"))
                   for name, _ in calls)
