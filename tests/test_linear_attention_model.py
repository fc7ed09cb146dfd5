"""A grouped-query layer that rotates nothing under a gate an element and
delta-rule layers (models/gpt.py) against the plain float32 reference of
benchmark/families/solar.py, at a small size on the CPU: seeded random
weights, the kernels in interpret mode. The checks every family has are
tests/helpers/families.py's, given this file's FAMILY; the kernels alone and
the cell's compile for a described chip: tests/test_linear_attention.py."""

import copy
import math

import numpy as np
import pytest

from helpers.families import (  # noqa: F401 — fixtures and shared checks
    Family, case, family, programmed, read, reference, seeded,
    step_kernel_calls, steps_agree,
    test_bfloat16_step_passes_the_per_token_check,
    test_configuration_file_keeps_the_catalog_and_states_the_cut,
    test_every_new_leaf_gets_its_rule,
    test_logits_loss_and_gradients_match_the_reference,
    test_param_count_is_the_published_model_and_the_programs_tree
    as test_param_count_is_the_cut_and_the_programs_tree,
    test_sharded_step_equals_one_device,
    test_the_configuration_refuses_by_name,
    test_the_reference_tells_each_mechanism_apart, tiny)


def _faulty_kda(solar, fault):
    """benchmark/families/solar.py:reference_kda with one thing wrong."""
    import jax
    import jax.numpy as jnp

    def kda(m, n, config):
        f32 = jnp.float32
        dim = config["linear_attn_config"]["head_dim"]
        s = n.shape[0]

        def filtered(x, taps):
            if fault == "filter_turned_round":      # reaches forward in time
                return solar._filtered(x[::-1], taps)[::-1]
            return solar._filtered(x, taps)
        q, k, v = (filtered(n @ m[w].astype(f32), m[taps]).reshape(s, -1, dim)
                   for w, taps in (("wq", "q_conv"), ("wk", "k_conv"),
                                   ("wv", "v_conv")))
        q = solar._unit(q) / math.sqrt(dim)
        k = k if fault == "keys_not_normalised" else solar._unit(k)
        step = jax.nn.softplus(n @ m["wf_down"].astype(f32)
                               @ m["wf_up"].astype(f32) + m["dt_bias"])
        log_decay = (-jnp.exp(m["a_log"].astype(f32))[None, :, None]
                     * step.reshape(s, -1, dim))
        if fault == "no_decay":
            log_decay = jnp.zeros_like(log_decay)
        beta = 2.0 * jax.nn.sigmoid(n @ m["w_beta"].astype(f32))
        o = solar.reference_delta_rule(q, k, v, log_decay, beta)
        o = solar._norm(o, m["o_norm"]["scale"], float(config["rms_norm_eps"]))
        gate = jax.nn.sigmoid(n @ m["wg_down"].astype(f32)
                              @ m["wg_up"].astype(f32))
        if fault == "no_norm_gate":
            gate = jnp.ones_like(gate)
        return (o.reshape(s, -1) * gate) @ m["wo"].astype(f32)
    return kda


class Solar(Family):
    """benchmark/rehearsal/configs/tiny-solar.json: a grouped-query layer (4
    query heads of 32 on 2, no rotation, a gate an element) then three
    delta-rule layers of 4 heads of 32 with 4-tap filters, every layer with
    experts 4..7 of 16 held, 2 a token, beside a shared one."""

    name, tiny, cell = "solar", "tiny-solar", "solar-open2-250b"
    workload = "solar2_train_1chip"

    # A grouped-query layer that rotates nothing under a gate an element,
    # three delta-rule layers (the chunked form against the reference's token
    # a step; the filter and flash kernels), experts beside a shared
    # one in every layer, in float32: the whole tree of gradients. The
    # tolerance is float32's own over 128 tokens of a state that is decayed
    # and overwritten (the two forms sum in another order), five layers deep:
    # 5e-5 in a logit of about 5.
    attentions = ("flash",)
    logits_atol, grads_atol = 5e-5, 2e-5

    def built(self, cfg, params):
        assert [sorted(layer) for layer in params["layers"]] == [
            ["attn", "ln1", "ln2", "moe"]] + [["kda", "ln1", "ln2", "moe"]] * 3
        gqa, kda = params["layers"][0]["attn"], params["layers"][1]["kda"]
        assert cfg.head_dim == 32 and cfg.d_model == 128 and not cfg.use_rope
        assert gqa["wq"].shape == gqa["wg"].shape == (128, 4 * 32)  # an element
        assert gqa["wk"].shape == gqa["wv"].shape == (128, 2 * 32)
        assert kda["wq"].shape == kda["wk"].shape == kda["wv"].shape \
            == (128, 128)
        assert kda["q_conv"].shape == (128, 4) and kda["a_log"].shape == (4,)
        assert kda["wf_down"].shape == (128, 32) == kda["wg_up"].shape[::-1]
        assert kda["w_beta"].shape == (128, 4) \
            and kda["dt_bias"].shape == (128,)
        assert params["layers"][1]["moe"]["w_up"].shape == (4, 128, 64)
        assert params["layers"][1]["moe"]["router"].shape == (128, 16)

    def statistics(self, aux, loss, reference):
        assert float(loss) == float(aux["xent"])        # no router loss
        assert 0.0 < float(aux["expert_slots_held_share"]) < 1.0
        # beta up to 2; some channel's decay over a chunk passes float32's
        # range at the seeded rates (the chunked form divides by none)
        assert 0.5 < float(aux["kda_beta_mean"]) < 1.5
        assert -1e4 < float(aux["kda_log_decay_min"]) < -87.0

    def moves(self, name):
        # the selection bias enters the choice alone: no gradient
        return "router_bias" not in name

    def other_configurations(self, tiny):
        return {"beta_not_doubled": dict(tiny, kda_allow_neg_eigval=False),
                "no_gqa_gate": dict(tiny, use_gqa_gate=False),
                "a_rotation": dict(tiny, use_rope=True)}

    def faults(self, jax, tiny, params):
        """(and the faulty copy of the delta-rule layer with nothing changed
        gives the reference's)"""
        return [(fault or "nothing_changed",
                 {"reference_kda": _faulty_kda(self.module, fault)},
                 fault is None)
                for fault in (None, "no_decay", "keys_not_normalised",
                              "filter_turned_round", "no_norm_gate")]

    def told_apart(self, gap):
        # (keys that are not unit vectors make the transition expand under
        # beta > 1: the state overflows, and nan is told apart too)
        return not gap < 1e-3

    # the program's own forward: bf16, the flash and filter kernels, the
    # chunked delta rule, the grouped-matmul kernels; nan where any of its
    # three bounds is broken
    bf16_bounds = {"logprob_median_tol": 0.15, "logprob_rms_tol": 0.6,
                   "logprob_p99_tol": 3.0}
    bf16_broken = tuple(bf16_bounds)

    cell_params, cell_share = 840_872_600, (0, 8, 320)  # ISSUE 46's 840.8M

    def published(self, cell, tiny_tree):
        from ray_tpu.models.gpt import count_params
        solar = self.module
        # the mixers at the heads held, by ISSUE 46's arithmetic
        gqa = tiny_tree["layers"][0]["attn"]
        assert count_params(gqa) == 128 * 32 * (3 * 4 + 2 * 2)
        m = solar._matrices(cell)
        assert m["attention"] == 13_631_488 and m["kda"] == 18_120_704
        assert m["expert"] == 15_728_640 == m["shared"]
        # the published model: 250B, 15B a token, its name (250B-A15B); a
        # gate a head for the element gate (assumed) would be 0.40B fewer
        # over 12 layers
        published = {k: v for k, v in cell.items() if k != "share"}
        published.update(cell["published"])
        assert round(solar.param_count(published) / 1e9) == 250
        assert round(solar.active_param_count(published) / 1e9) == 15
        assert round(12 * 4096 * (64 * 128 - 64) / 1e9, 2) == 0.40

    def rules(self, specs, column, row):
        from jax.sharding import PartitionSpec as P
        attn, kda = specs["layers"][0]["attn"], specs["layers"][1]["kda"]
        # the gate an element: its columns are whole heads too
        assert attn["wq"] == attn["wk"] == attn["wg"] == P(*column)
        assert kda["wq"] == kda["wk"] == kda["wv"] == kda["w_beta"] \
            == P(*column)
        assert kda["wo"] == attn["wo"] == P(*row)
        assert kda["wf_up"] == kda["wg_up"] == P(None, "tensor")
        assert kda["wf_down"] == kda["wg_down"] == P(column[0], None)
        assert kda["q_conv"] == kda["k_conv"] == kda["v_conv"] \
            == P("tensor", None)
        assert kda["a_log"] == kda["dt_bias"] == P("tensor")
        assert kda["o_norm"]["scale"] == P(None)

    def sharded_step(self, jax, tiny, twin):
        """A grouped-query and a delta-rule layer on tensor=2 (two
        delta-rule heads with their filters, decay rates and step biases,
        and a key/value head with its two query heads and their gates, on a
        shard of `tensor`; the filter and flash kernels per shard): the
        `kda/*` rows of parallel/sharding.py's table."""
        steps_agree(jax, self, dict(tiny, num_hidden_layers=2), rows=2,
                    strategy="tp", axes={"data": 1, "tensor": 2})

    refusals = [
        case(({"attention": "ring"},
              "'kda' layer's state.*attention='ring'"), "ring"),
        case(({"attention_gate": "head"}, "attention_gate='head'"),
             "gate_name"),
        case(({"use_rope": False, "index_topk": 4, "index_heads": 2,
               "index_head_dim": 16, "layer_kinds": None},
              "use_rope=False.*indexer"), "indexer_unrotated"),
        case(({"layer_kinds": ("attention", "gdn", "gdn", "gdn")},
              "'conv' | 'window' | 'kda'"), "kinds_names"),
    ]


    def scopes(self, names, regions):
        from ray_tpu.util import profiling
        assert {"kda", "kda_core"} <= set(profiling.REGIONS)
        assert {"conv_silu_fwd", "conv_silu_bwd", "kda_fwd",
                "kda_bwd"} <= set(profiling.KERNELS)
        assert {"kda", "kda_core", "attn_gate", "attn_proj", "attn_core",
                "attn_out", "moe", "moe_route", "moe_shared"} <= regions
        # the delta rule's two kernels are kda_core's, the forward's in phase
        # forward and the backward's in the transpose; the filters' are kda's
        core = [n for n in names
                if profiling._last_of(n, profiling.REGIONS) == "kda_core"]
        assert any("/kda_fwd/" in n and "transpose(" not in n for n in core)
        assert any("/kda_bwd/" in n and "transpose(" in n for n in core)
        for n in names:
            if "kda_fwd" in n or "kda_bwd" in n:
                assert profiling._last_of(n, profiling.REGIONS) == "kda_core"
        for n in names:
            if "conv_silu" in n:
                assert profiling._last_of(n, profiling.REGIONS) == "kda"

    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size",
               "num_attention_heads", "num_key_value_heads",
               "linear_attn_config", "gqa_layers"}

    def cut(self, cell, row, bench):
        # of the group, the head count alone: no width moves
        assert dict(cell["linear_attn_config"], num_heads=64) \
            == row["config"]["linear_attn_config"]
        # published layers 0..3: one whole period
        assert cell["gqa_layers"] == [0] == [
            i for i in row["config"]["gqa_layers"] if i < 4]
        share = cell["share"]
        assert share["expert_parallel"] == share["chips_per_layer"] == 40
        assert share["expert_parallel"] * cell["n_routed_experts"] \
            == share["n_routed_experts"] == 320
        assert share["tensor_parallel"] * cell["vocab_size"] == 196608
        assert share["tensor_parallel"] * cell["num_attention_heads"] \
            == share["num_attention_heads"] == 64
        assert share["tensor_parallel"] * cell["num_key_value_heads"] == 8
        assert share["tensor_parallel"] * cell["linear_attn_config"][
            "num_heads"] == share["linear_attn_heads"] == 64
        assert {"kda_form", "gqa_gate", "router_score", "decay_init",
                "sequence_length"} <= set(cell["assumed"])

    # solar2_train_1chip (1 x 8192 tokens): a grouped-query layer that
    # rotates nothing (8 query heads on 1 at head 128: one call of each
    # flash kernel; q, k, v through rope_split without a table, forward and
    # recomputed) and three delta-rule layers, each with the plain filter
    # on q, k and v (forward + recomputed, backward) and the delta rule as
    # XLA; 8 of 320 experts held in all four layers, their rows in tiles
    # of 128. 14.66 GB when this was written: 10.09 of state, 4.57 of
    # temporaries. (The compile takes
    # ~105 s alone here: a time limit of its own.)
    cell_kernel_calls = {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                         "rope_split": 6, "rope_merge": 3, "moe_gmm": 72,
                         "moe_tgmm": 24, "embed_grad": 1,
                         "conv_silu_fwd": 18, "conv_silu_bwd": 9}
    cell_memory_share = (0.80, 0.93)
    # rung 0 is the floor: the reckoning reads 15.24 GB where its ceiling is
    # 14.88 and has nothing left to drop; the step compiles to 13.83 (+
    # OVERHEAD 14.25) and the chip reads 14.08 (83.261 %, ledger PR 72), so
    # `_working_set` reads this cell ~1 GB high (ROADMAP D29)
    cell_reckoned = (0.0, 0.36)


FAMILY = Solar()


# ---------------------------------------------------------------------------
# (a) the share: the parts add up to the whole
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["kda", "attention"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(jax_cpu, tiny,
                                                             kind):
    """model-configs guide, section 4, with the heads shared too: a whole
    layer of 16 experts and 8 mixer heads (4 key/value heads in the
    grouped-query layer) over 4 chips that hold 4 experts each and, in pairs
    (tensor parallel 2 inside each of two groups), 4 heads each. A head
    share's mixer output is its heads' rows of the output projection's sum,
    so the two head shares add up to the uncut mixer; the residual and the
    shared expert are every chip's alike and count once; what the four
    expert shares add (each from the SAME input, the full mixer's result,
    as it has it after the all-reduce) adds up with them to the uncut
    reference's layer."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import solar
    from ray_tpu.models import gpt
    from ray_tpu.models.gpt import GPTConfig, Setting, gpt_init
    whole = copy.deepcopy(tiny)
    del whole["share"]
    whole.update(n_routed_experts=16, num_attention_heads=8,
                 num_key_value_heads=4,
                 gqa_layers=[0] if kind == "attention" else [])
    whole["linear_attn_config"]["num_heads"] = 8
    full_cfg = GPTConfig(**solar.gpt_config_kwargs(whole), dtype=jnp.float32,
                         attention="reference", remat_policy="none")
    assert full_cfg.experts_held is None
    layer = gpt_init(jax.random.PRNGKey(7), full_cfg)["layers"][0]
    group = "attn" if kind == "attention" else "kda"
    assert sorted(layer) == sorted([group, "ln1", "ln2", "moe"])
    layer["moe"]["router"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(8), (128, 16))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 128), jnp.float32)
    eps = float(tiny["rms_norm_eps"])

    def reference_layer(h):
        h = h + solar.reference_mixer(
            layer, solar._norm(h, layer["ln1"]["scale"], eps), whole)
        m = solar._norm(h, layer["ln2"]["scale"], eps)
        shared = solar._swiglu(layer["moe"]["shared"], m, jnp.float32)
        return h, h + shared, h + solar.reference_experts(layer["moe"], m,
                                                          whole)

    def head_share(rank):
        """The mixer's parameters a chip of tensor rank `rank` holds: heads
        4 rank .. 4 rank + 3 (key/value heads 2 rank, 2 rank + 1)."""
        def cut(name, leaf):
            heads = {"wk": 4, "wv": 4}.get(name, 8) if kind == "attention" \
                else 8
            if name in ("wo",):
                return leaf.reshape(heads, -1, 128)[
                    heads // 2 * rank:heads // 2 * (rank + 1)].reshape(-1, 128)
            if name in ("wf_down", "wg_down", "o_norm"):
                return leaf                     # every head's
            if name in ("q_conv", "k_conv", "v_conv", "dt_bias", "a_log"):
                parts = leaf.reshape((heads, -1) + leaf.shape[1:])
                return parts[heads // 2 * rank:heads // 2 * (rank + 1)
                             ].reshape((-1,) + leaf.shape[1:])
            parts = leaf.reshape(leaf.shape[0], heads, -1)
            return parts[:, heads // 2 * rank:heads // 2 * (rank + 1)
                         ].reshape(leaf.shape[0], -1)
        return {name: cut(name, leaf) for name, leaf in layer[group].items()}

    held_heads = dict(tiny, num_attention_heads=4, num_key_value_heads=2,
                      gqa_layers=whole["gqa_layers"])
    with jax.default_matmul_precision("highest"):
        mixed, alike, want = jax.vmap(reference_layer)(x)
        # the eight heads' mixer, from its two shares of four
        cfg = GPTConfig(**solar.gpt_config_kwargs(held_heads),
                        dtype=jnp.float32, attention="reference",
                        remat_policy="none")
        assert cfg.n_heads == 4
        normed = gpt._rmsnorm(x, layer["ln1"]["scale"], eps)
        shares = []
        for rank in range(2):
            if kind == "kda":
                part, _ = gpt._kda_block(head_share(rank), normed, cfg,
                                         Setting())
            else:
                part, _ = gpt._attention_block({"attn": head_share(rank)},
                                               normed, cfg, (), Setting())
            shares.append(part)
        np.testing.assert_allclose(x + sum(shares), mixed, atol=2e-5)
        assert float(jnp.abs(x + shares[0] - mixed).max()) > 1e-2
        # the sixteen experts, from the four shares of four, each on the
        # all-reduced mixer output
        parts, held_share = [], 0.0
        normed = gpt._rmsnorm(mixed, layer["ln2"]["scale"], eps)
        for rank in range(4):
            cut = dict(held_heads, share=dict(tiny["share"], rank=rank))
            cfg = GPTConfig(**solar.gpt_config_kwargs(cut),
                            dtype=jnp.float32, attention="reference",
                            remat_policy="none")
            assert cfg.experts_held == (4 * rank, 4)
            mine = {"moe": dict(layer["moe"], **{
                name: layer["moe"][name][4 * rank:4 * rank + 4]
                for name in ("w_gate", "w_up", "w_down")})}
            out, stats = gpt._moe_block(mine, normed, cfg, Setting())
            # the shared expert, the same on every chip, taken off
            parts.append(mixed + out - alike)
            held_share += float(stats["expert_slots_held_share"])
    np.testing.assert_allclose(alike + sum(parts), want, atol=5e-5)
    assert abs(held_share - 1.0) < 1e-6
    assert float(jnp.abs(alike + parts[0] - want).max()) > 1e-2


# ---------------------------------------------------------------------------
# (b) arithmetic, refusals, names
# ---------------------------------------------------------------------------


def test_flops_and_kernel_arithmetic_count_what_is_computed_here():
    from benchmark.families import solar
    from benchmark.kernels import gqa_attention, kda
    cell = read("benchmark", "configs", "solar-open2-250b.json")
    mix = read("benchmark", "traffic", "train_b1_s8192_dp.json")
    d, s = 4096, 8192
    expert = 3 * d * 1280
    active = (13_631_488 + 3 * 18_120_704
              + 4 * (d * 320 + expert + 8 * 8 / 320 * expert) + d * 24576)
    rule = 5 * 64 * 128 + 6 * 128 * 128         # a token and head, forward
    assert kda.delta_rule_flops_per_token(64, 128, 128) == rule == 139_264
    assert solar.train_flops_per_token(cell, s) == pytest.approx(
        6.0 * active + 3.0 * (8 * 4 * 128 * s / 2 + 3 * 8 * rule))
    assert solar.forward_flops_per_token(cell, s) == pytest.approx(
        0.52e9, rel=0.02)                  # ISSUE 46's ~0.52 GFLOP a token
    assert solar.attention_call(cell, mix) == {
        "batch": 1, "heads": 8, "kv_heads": 1, "seq": s, "head_dim": 128}
    assert solar.kda_call(cell, mix) == {
        "batch": 1, "heads": 8, "seq": s, "head_dim": 128, "taps": 4,
        "chunk": 64}
    assert gqa_attention.flash_fwd(cell, mix)[0] == 2 * 8 * s * s * 128
    elements = s * 1024
    assert kda.conv_silu_fwd(cell, mix) == (11 * elements, 4 * elements)
    assert kda.conv_silu_bwd(cell, mix) == (32 * elements, 6 * elements)
    flops, moved = kda.delta_rule(cell, mix)
    assert flops == 8 * s * rule
    assert moved == 8 * s * (8 * 128 + 4 * 128 + 4 + 2 * 4 * 128 * 128 / 64)


@pytest.mark.parametrize("batch,seq", [(3, 128), (2, 64)])
def test_the_delta_rule_kernels_arithmetic_is_a_brute_force_count(tiny, batch,
                                                                  seq):
    """benchmark/kernels/delta_rule.py, a function a kernel name, against
    loops over the chunks at a tiny shape (4 heads of 32, chunks of 64):
    the forward is kda.py:delta_rule's products and no more than its bytes
    (the chunks' states cross once a kernel), the backward twice the
    products, every tensor once."""
    from benchmark.kernels import delta_rule, kda
    mix = {"global_batch": batch, "seq": seq, "mesh": {"data": 1}}
    heads, dim, chunk = 4, 32, 64
    flops = fwd_bytes = bwd_bytes = 0
    for _ in range(batch * heads):
        for _ in range(seq // chunk):
            flops += (2 * chunk * chunk * dim // 2) * 2       # A, Aqk: halves
            flops += 2 * chunk * chunk * (dim + dim) // 2     # the solve's
            flops += 3 * 2 * chunk * dim * dim + 2 * chunk * chunk * dim // 2
            moved = chunk * dim * 2                  # a two-byte tensor's rows
            fwd_bytes += 4 * moved + chunk * dim * 4 + chunk * 4 \
                + dim * dim * 4                      # q k v o; a; beta; state
            bwd_bytes += 7 * moved + 2 * (chunk * dim * 4 + chunk * 4) \
                + dim * dim * 4
    assert delta_rule.kda_fwd(tiny, mix) == (flops, fwd_bytes)
    assert delta_rule.kda_bwd(tiny, mix) == (2 * flops, bwd_bytes)
    whole, whole_bytes = kda.delta_rule(tiny, mix)
    assert flops == whole and fwd_bytes < whole_bytes
    assert whole_bytes - fwd_bytes == batch * heads * (seq // chunk) \
        * dim * dim * 4


def test_the_delta_rule_kernels_least_times_at_the_cell():
    """Both are bound by bytes on the mathematics' count, a fifth and a
    third of a millisecond a call at [1, 8, 8192, 128]."""
    from benchmark.kernels import delta_rule, kda
    cell = read("benchmark", "configs", "solar-open2-250b.json")
    mix = read("benchmark", "traffic", "train_b1_s8192_dp.json")
    for fn, ms in ((delta_rule.kda_fwd, 0.205), (delta_rule.kda_bwd, 0.308)):
        flops, moved = fn(cell, mix)
        assert flops / 197e12 < moved / 819e9
        assert 1e3 * moved / 819e9 == pytest.approx(ms, rel=0.01)
    assert delta_rule.kda_fwd(cell, mix)[0] == kda.delta_rule(cell, mix)[0]
    assert delta_rule.kda_fwd(cell, mix)[1] < kda.delta_rule(cell, mix)[1]


@pytest.mark.parametrize("delta", [
    None, ("head", "silu")], ids=["three_projections_a_decay_a_channel",
                                  "one_qkv_filter_a_decay_a_head"])
def test_a_layer_by_token_is_the_layer_by_head(jax_cpu, tiny, monkeypatch,
                                               delta):
    """At heads of whole lane tiles (128 / 128) a delta-rule layer keeps q,
    k, v, the log-decay and o [B, S, H w] from the filter to `wo` and its
    norms sum a head's squares through `head_columns` at full precision
    (`_kda_block`; the widths decide, ops/linear_attention.py:by_token): it
    is the layer that turns them by head, as every width did before and 96 /
    192 still do, to float32 rounding (the sums' order), value, statistics
    and the gradients of every parameter and of x. Both forms of layer: the
    three projections with a decay a channel and the sigmoid gate pair
    (solar, kimi), and ONE [q | k | v] projection and filter, whose parts
    are cut out as lane tiles, with a decay a head and the SiLU gate."""
    jax = jax_cpu
    import dataclasses
    import jax.numpy as jnp
    from benchmark.families import solar
    from ray_tpu.models import gpt
    cfg = dataclasses.replace(
        gpt.GPTConfig(**solar.gpt_config_kwargs(tiny), dtype=jnp.float32),
        d_model=64, n_heads=2, head_dim=128,
        delta=delta and gpt.DeltaRule(128, 128, *delta))
    kinds = list(cfg.layer_kinds)
    layer = gpt.gpt_init(jax.random.PRNGKey(3), cfg)["layers"][
        kinds.index("kda")]["kda"]
    assert ("w_qkv" in layer) == (delta is not None)
    layer["o_norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(5), (128,))
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 80, 64), jnp.float32)
    weight = jnp.cos(0.37 * jnp.arange(80 * 64).reshape(80, 64))

    def run():
        def scalar(layer, x):
            out, stats = gpt._kda_block(layer, x, cfg, gpt.Setting())
            return jnp.sum(out * weight), (out, stats)
        text = str(jax.make_jaxpr(scalar)(layer, x))
        (_, (out, stats)), grads = jax.jit(jax.value_and_grad(
            scalar, argnums=(0, 1), has_aux=True))(layer, x)
        return out, stats, grads, text
    with jax.default_matmul_precision("highest"):
        out, stats, grads, text = run()
        monkeypatch.setattr(gpt, "by_token", lambda dk, dv: False)
        want, want_stats, want_grads, by_head = run()
    # the kernels' operands: a token's columns, or a head's rows
    assert "bf16" not in text and "f32[1,128,256]" in text
    assert "f32[2,128,128]" in by_head and "f32[1,128,256]" not in by_head
    np.testing.assert_allclose(out, want, atol=2e-5 * float(
        jnp.abs(want).max()))
    for name in want_stats:
        np.testing.assert_allclose(stats[name], want_stats[name], rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert np.any(np.asarray(w)), path
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.abs(w).max()), err_msg=str(path))


def test_the_kind_is_read_off_the_parameters_and_no_table_is_built(jax_cpu,
                                                                   tiny):
    """A layer's mixer is what its parameters hold (`kda` | `attn`), and a
    stack that rotates nothing builds no rope table: no cosine in the
    forward's jaxpr, where the same stack with use_rope has them."""
    jax = jax_cpu
    import dataclasses
    import jax.numpy as jnp
    from benchmark.families import solar
    from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init
    cfg = GPTConfig(**solar.gpt_config_kwargs(tiny), dtype=jnp.float32)
    assert cfg.rope_of("attention") is None
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 128), jnp.int32)

    def text(c, p):
        return str(jax.make_jaxpr(lambda p: gpt_forward(p, tokens, c)[0])(p))
    assert " cos " not in text(cfg, params)
    rotating = dataclasses.replace(cfg, use_rope=True)
    assert rotating.rope_of("attention").plain
    assert " cos " in text(rotating, params)
    # the same configuration walks the layers in the other order when their
    # parameters are: nothing reads layer_kinds after gpt_init
    swapped = dict(params, layers=params["layers"][::-1])
    out, stats = jax.jit(lambda p: gpt_forward(p, tokens, cfg))(swapped)
    assert np.isfinite(out).all() and "kda_beta_mean" in stats
    # and a stack without delta-rule layers hands back none of their
    # statistics
    plain = dataclasses.replace(cfg, layer_kinds=("attention",) * 4)
    _, stats = jax.jit(lambda p: gpt_forward(p, tokens, plain))(
        gpt_init(jax.random.PRNGKey(0), plain))
    assert "kda_beta_mean" not in stats


def test_pipeline_refuses_by_what_it_observes(jax_cpu, tiny):
    """parallel/pipeline.py has never heard of a delta-rule layer: it
    refuses the cell's stack because its layers are not alike, and a stack
    of delta-rule layers alone because the block hands back statistics."""
    jax = jax_cpu
    from benchmark.families import solar
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import make_gpt_pp_loss
    mesh = build_mesh(MeshConfig(pipeline=1), devices=jax.devices()[:1])
    kwargs = dict(solar.gpt_config_kwargs(tiny), max_seq=64)
    with pytest.raises(ValueError, match="are not layer 0's"):
        make_gpt_pp_loss(GPTConfig(**kwargs), mesh, 1)
    alike = dict(kwargs, layer_kinds=("kda",) * 4, n_experts=0,
                 experts_held=None, n_shared_experts=0)
    with pytest.raises(ValueError, match="statistics.*kda_beta_mean"):
        make_gpt_pp_loss(GPTConfig(**alike), mesh, 1)


def test_the_delta_rule_runs_its_forward_once_a_layer(jax_cpu, tiny):
    """The step's calls are the counter. Under remat_policy="full" a
    delta-rule layer's output and its chunks' states are kept (KDA_OUT), so
    `kda_fwd` runs once a layer and never in the recompute pass, and
    `kda_bwd` once a layer; the filter kernels, which XLA's recompute pass
    holds, run forward and recomputed; the grouped-query layer's forward
    kernel once."""
    cfg, calls, _jaxpr = step_kernel_calls(jax_cpu, FAMILY, tiny)
    assert cfg.remat_policy == "full"
    assert calls[("flash_fwd", False)] == 1 and calls[("flash_fwd", True)] == 0
    assert calls[("conv_silu_fwd", False)] == calls[("conv_silu_fwd", True)] \
        == 9
    assert calls[("conv_silu_bwd", False)] + calls[("conv_silu_bwd", True)] \
        == 9
    assert calls[("kda_fwd", False)] == 3 and calls[("kda_fwd", True)] == 0
    assert calls[("kda_bwd", False)] + calls[("kda_bwd", True)] == 3
