"""A stack whose every layer is a mixer AND a gated MLP, the mixer a Mamba-2
scan of several blocks of heads on one B/C group or grouped-query attention
without rotation at a softmax scale of the configuration's, under four scalar
multipliers and a tied head (models/gpt.py: the `ssm_ff` kind, `Multipliers`)
against the plain float32 reference of benchmark/families/granite_hybrid.py,
at a small size on the CPU: seeded random weights, the kernels in interpret
mode. The checks every family has are tests/helpers/families.py's, given this
file's FAMILY; the scan's kernels alone are tests/test_state_space.py's, the
cell's compiles for a described chip tests/test_hybrid_mixer.py's."""

import dataclasses
import re

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
    test_pipeline_refuses_by_name,
    test_sharded_step_equals_one_device, test_the_cell_rehearses,
    test_the_configuration_refuses_by_name,
    test_the_programs_gradient_moves_where_the_references_does,
    test_the_reference_tells_each_mechanism_apart, tiny)


def _scale_on(**fields):
    """A softmax scale of the configuration's on a block it is not threaded
    to."""
    def change():
        from ray_tpu.models.gpt import Multipliers
        return dict(fields, layer_kinds=None, ssm=None, use_rope=True,
                    multipliers=Multipliers(attention=0.03125))
    return change


class GraniteHybrid(Family):
    """benchmark/rehearsal/configs/tiny-granite-hybrid.json: the pattern MMAM,
    every layer a mixer and a gated MLP of 256; Mamba-2 of 32 heads of 8 on
    ONE group (two blocks of 16 heads a grid step), state 32, chunks of 32;
    4 query heads on 2 at 32 without rotation, softmax scale 1/32 (not
    32^-1/2); multipliers 3 (embedding), 0.4 (residual), 2 (logits), none of
    them 1; a tied head."""

    name, tiny, cell = ("granite_hybrid", "tiny-granite-hybrid",
                        "granite-4.0-h-micro")
    workload = "granite4hm_train_1chip"

    # Three scans of two blocks of heads (the chunked form against the
    # reference's token a step), one grouped-query layer, four gated MLPs,
    # the multipliers, in float32: every logit (at most 0.5 here) and the
    # whole tree of gradients. The two forms sum a state in another order;
    # this sandbox read 4.3e-7 and 5.4e-8.
    logits_atol, grads_atol = 1e-5, 5e-6

    def built(self, cfg, params):
        assert [sorted(layer) for layer in params["layers"]] == [
            ["ln1", "ln2", "mlp", "ssm"]] * 2 + [
            ["attn", "ln1", "ln2", "mlp"]] + [["ln1", "ln2", "mlp", "ssm"]]
        assert cfg.layer_kinds == ("ssm_ff", "ssm_ff", "attention", "ssm_ff")
        assert "lm_head" not in params and cfg.tie_embeddings
        ssm, attn = params["layers"][0]["ssm"], params["layers"][2]["attn"]
        assert ssm["w_z"].shape == (128, 256) and ssm["w_dt"].shape == (128, 32)
        assert ssm["w_xbc"].shape == (128, 256 + 2 * 32)      # ONE group
        assert ssm["norm"]["scale"].shape == (256,)
        assert attn["wq"].shape == (128, 128) and attn["wk"].shape == (128, 64)
        assert params["layers"][0]["mlp"]["w_gate"].shape == (128, 256)
        m = cfg.multipliers
        assert (m.embedding, m.residual, m.attention, m.logits) \
            == (3.0, 0.4, 0.03125, 2.0)
        assert cfg.sm_scale == 0.03125 != cfg.head_dim ** -0.5
        from ray_tpu.ops.state_space import _heads_a_step
        assert _heads_a_step(cfg.ssm.chunk, cfg.ssm.heads) == 16  # two blocks

    def statistics(self, aux, loss, reference):
        assert float(loss) == float(aux["xent"])
        assert 0.0 < float(aux["ssm_dt_mean"]) < 0.2
        assert float(aux["ssm_log_decay_min"]) < -1.0

    def moves(self, name):
        return True                 # every leaf, the filter's bias included

    def other_configurations(self, tiny):
        """The four multipliers and the layer's second half, the gate's place
        and the state's precision: each a key of the configuration or a
        fault of the reference (granite_hybrid.FAULTS)."""
        return {"softmax_scale_of_the_head": dict(
                    tiny, attention_multiplier=32 ** -0.5),
                "embedding_multiplier_1": dict(tiny, embedding_multiplier=1),
                "residual_multiplier_1": dict(tiny, residual_multiplier=1),
                "logits_scaling_1": dict(tiny, logits_scaling=1),
                "no_second_half": dict(tiny, fault="no_mlp"),
                "gate_after_norm": dict(tiny, fault="gate_after_norm"),
                "bfloat16_state": dict(tiny, fault="bf16_state")}

    def told_apart(self, gap):
        # (the rounded state is the weakest here: 1.4e-3 of logits of 0.5)
        return gap > 5e-4

    # the program's own forward: bf16, the flash, filter and scan kernels;
    # nan where any of its three bounds is broken (this sandbox read 0.0011 /
    # 0.0018 / 0.0053 of logits of at most 0.5)
    bf16_bounds = {"logprob_median_tol": 0.006, "logprob_rms_tol": 0.01,
                   "logprob_p99_tol": 0.03}
    bf16_broken = tuple(bf16_bounds)

    cell_params = 772_160_448

    def published(self, cell, tiny_tree):
        from ray_tpu.models.gpt import count_params
        module = self.module
        m = module._matrices(cell)
        # ISSUE 62's arithmetic, a layer at a time
        assert m["ssm"] + module._ssm_small(cell) == 25_847_232
        assert m["attention"] == 10_485_760 and m["mlp"] == 50_331_648
        whole = {**cell, **cell["published"]}
        assert module.param_count(whole) == 3_191_396_096       # "3B"
        assert count_params(tiny_tree["layers"][2]["attn"]) \
            == 2 * 128 * 128 + 2 * 128 * 64
        # the head's share of a token's FLOPs: about half the whole model's
        assert module.head_flops_share(cell, 8192) == pytest.approx(
            0.032, abs=5e-4)
        assert module.head_flops_share(whole, 8192) == pytest.approx(
            0.062, abs=5e-4)

    def rules(self, specs, column, row):
        from jax.sharding import PartitionSpec as P
        ssm, attn = specs["layers"][0]["ssm"], specs["layers"][2]["attn"]
        assert ssm["w_z"] == ssm["w_dt"] == attn["wq"] == P(*column)
        assert ssm["w_out"] == attn["wo"] == P(*row)
        assert ssm["w_xbc"] == P(column[0], None)
        assert ssm["conv"] == P(None, None) and ssm["conv_bias"] == P(None)
        assert ssm["a_log"] == ssm["dt_bias"] == ssm["d"] == P("tensor")
        assert ssm["norm"]["scale"] == P("tensor")
        # the second half of a state-space layer is the MLP every dense
        # stack has: no new matrix, no new row of the table
        for layer in specs["layers"]:
            assert layer["mlp"]["w_gate"] == layer["mlp"]["w_up"] \
                == P(*column)
            assert layer["mlp"]["w_down"] == P(*row)
        assert "lm_head" not in specs

    def sharded_step(self, jax, tiny, twin):
        """A state-space layer with its MLP and an attention layer on
        tensor=2 (16 heads of the one group a shard, a key/value head with
        its two query heads; the tied matrix's two gradients on one leaf)."""
        steps_agree(jax, self, dict(
            tiny, num_hidden_layers=2, layer_types=["mamba", "attention"]),
            rows=2, strategy="tp", axes={"data": 1, "tensor": 2}, atol=2e-6)

    refusals = [
        case(({"ssm": None}, "'ssm_ff' layers need their sizes"), "no_sizes"),
        case(({"attention": "ring"},
              "'ssm_ff' layer's state.*attention='ring'"), "ring"),
        case((_scale_on(kv_latent_dim=32, qk_nope_dim=16, qk_rope_dim=16,
                        v_head_dim=32, n_kv_heads=0),
              "multipliers.attention.*not threaded to a latent block"),
             "scale_on_latent"),
        case((_scale_on(index_topk=4, index_heads=2, index_head_dim=16),
              "multipliers.attention.*not threaded to an indexer"),
             "scale_on_indexer"),
        case(({"layer_kinds": ("ssm_ff", "mamba", "attention", "ssm_ff")},
              "'ssm_ff'"), "kinds_names"),
    ]

    # (an embedding multiplier is the one of the four a pipeline stage does
    # not carry: rank 0's lookup is written out there)
    pipeline_refusals = [
        case(({"layer_kinds": ("attention",) * 4, "ssm": None},
              {"pipeline": 2}, "multipliers.embedding=3.0.*written out"),
             "embedding_multiplier"),
        case(({}, {"pipeline": 2}, "no state-space layer's state"), "state"),
    ]


    def scopes(self, names, regions):
        from ray_tpu.util import profiling
        assert {"ssm", "ssm_core", "mlp", "attn_proj", "attn_core",
                "attn_out", "head", "embed"} <= regions
        assert {"ssd_fwd", "ssd_bwd", "conv_silu_fwd", "conv_silu_bwd",
                "flash_fwd"} <= set(profiling.KERNELS)
        core = [n for n in names
                if profiling._last_of(n, profiling.REGIONS) == "ssm_core"]
        assert any("/ssd_fwd/" in n and "transpose(" not in n for n in core)
        assert any("/ssd_bwd/" in n and "transpose(" in n for n in core)
        for n in names:
            if "conv_silu" in n:
                assert profiling._last_of(n, profiling.REGIONS) == "ssm"
            assert not ("ssd_fwd" in n and "rematted_computation" in n), n
        # the multipliers' products stand under the scopes that hold them
        for scope in ("embed", "head"):
            assert any(re.search(rf"[(/]{scope}\)?/mul$", n) for n in names)

    reduced = {"num_hidden_layers", "layer_types", "vocab_size"}

    def cut(self, cell, row, bench):
        # published layers 0..9: one whole period, 9 Mamba-2 to 1 attention
        types = row["config"]["layer_types"]
        assert cell["layer_types"] == types[:10] \
            and types[:10] == types[10:20] == types[20:30] == types[30:]
        assert [cell["layer_types"].count(t) for t in ("mamba", "attention")] \
            == [9, 1]
        share = cell["share"]
        assert share["chips_per_layer"] == 1          # the layers are whole
        assert share["pipeline_stages"] * cell["num_hidden_layers"] \
            == share["num_hidden_layers"] == 40
        assert share["vocab_parallel"] * cell["vocab_size"] \
            == share["vocab_size"] == 100352
        assert cell["vocab_size"] % 128 == 0 and cell["vocab_size"] * 8 \
            >= row["config"]["vocab_size"]            # the floor: an eighth
        assert {"head_dim", "init", "ssm_init", "sequence_length",
                "tied_head_on_the_first_stage"} <= set(cell["assumed"])
        for key in ("source", "share", "reduced", "published", "reduced_why",
                    "distorts", "assumed", "departures", "deployment",
                    "train", "program_check"):
            assert key in cell or key in cell["reduced_why"], key
        entry = next(w for w in bench["workloads"]
                     if w["name"] == self.workload)
        assert (entry["chips"], entry["config"]) == (1, cell["name"])
        for metric in ("ssd_fwd_roofline", "ssd_bwd_roofline"):
            assert next(m for m in bench["per_layer"] if m["name"] == metric)[
                "workloads"] == [self.workload]

    # granite4hm_train_1chip (1 x 8192 tokens): nine scans of four blocks of
    # 16 heads (forward once: SSD_OUT is kept; backward once), each with ONE
    # filter call over 4352 channels (forward + recomputed, backward); one
    # grouped-query layer of 32 on 8 heads of 64 IN PAIRS without a
    # rotation: q, k and v reach the kernels as projected, no `rope_split`
    # at all; one lookup. Compiled ONCE, as the chip runs it (the builder
    # reads a v5e's limit and keeps `up x` in all ten MLPs, which
    # tests/test_hybrid_mixer.py reads off the same step): 13.63 GB, 9.27 of
    # state (12 B a parameter; the gradient is a temporary) and 4.36 of
    # temporaries, 81 % of the chip; the step that keeps nothing more, which
    # this file compiled until PR 70, was 12.42 GB under (0.68, 0.80), and
    # the bounds moved by the kept product's 0.07 (~50 s alone here).
    cell_kernel_calls = {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                         "rope_split": 0, "rope_merge": 0, "ssd_fwd": 9,
                         "ssd_bwd": 9, "conv_silu_fwd": 18, "conv_silu_bwd": 9,
                         "embed_grad": 1}
    cell_memory_share = (0.75, 0.87)
    cell_rung = 1


FAMILY = GraniteHybrid()


def test_flops_and_kernel_arithmetic_count_what_is_computed_here():
    from benchmark.families import granite_hybrid as g
    from benchmark.kernels import gqa_attention, kda, ssd, ssd_bwd
    cell = FAMILY.cell_config()
    mix = read("benchmark", "traffic", "train_b1_s8192_dp.json")
    s = 8192
    scan = 64 * 256 + 4 * 64 * 128 + 128 * 256 / 64       # a token and head
    assert ssd.ssd_flops_per_token(256, 64, 128, 64) == scan == 49_664
    active = 9 * 25_821_184 + 10_485_760 + 10 * 50_331_648 + 2048 * 12_544
    assert g.train_flops_per_token(cell, s) == pytest.approx(
        6.0 * active + 3.0 * (32 * 4 * 64 * s / 2 + 9 * 64 * scan))
    assert g.train_flops_per_token(cell, s) == pytest.approx(4.82e9, rel=2e-3)
    assert g.forward_flops_per_token(cell, s) * 3 \
        == g.train_flops_per_token(cell, s)
    assert g.attention_call(cell, mix) == {
        "batch": 1, "heads": 32, "kv_heads": 8, "seq": s, "head_dim": 64}
    assert g.ssd_call(cell, mix) == {
        "batch": 1, "seq": s, "heads": 64, "head_dim": 64, "groups": 1,
        "state": 128, "chunk": 256}
    # the ONE filter call of a Mamba layer: [1, 8192, 4352], 4 taps
    assert g.kda_call(cell, mix)["heads"] * 64 == 4352
    flops, moved = kda.conv_silu_fwd(cell, mix)
    assert moved == 2 * s * 4352 * 2 and flops == 11 * s * 4352
    assert kda.conv_silu_bwd(cell, mix)[1] == 3 * s * 4352 * 2
    assert gqa_attention.flash_fwd(cell, mix)[0] == 2 * 32 * s * s * 64
    # the scan's two kernels under their names: the forward is ssd.py's,
    # bound by bytes; the backward three times its products, bound by FLOPs
    assert ssd_bwd.ssd_fwd(cell, mix) == ssd.ssd(cell, mix)
    flops, moved = ssd_bwd.ssd_fwd(cell, mix)
    assert flops == s * 64 * scan and moved == s * 64 * (256 + 8 + 4 + 256)
    assert 1e3 * moved / 819e9 == pytest.approx(0.335, rel=0.01) \
        and flops / 197e12 < moved / 819e9
    back, back_moved = ssd_bwd.ssd_bwd(cell, mix)
    assert back == 3 * flops \
        and back_moved == s * 64 * (3 * 128 + 16 + 8 + 128)
    assert 1e3 * back / 197e12 == pytest.approx(0.396, rel=0.01) \
        and back / 197e12 > back_moved / 819e9


def test_the_scan_runs_its_forward_once_a_layer(jax_cpu, tiny):
    """The step's calls are the counter. Under remat_policy="full" a
    state-space layer's output and its chunks' states are kept (SSD_OUT), so
    `ssd_fwd` runs once a layer and never in the recompute pass, though the
    layer now holds an MLP behind it; the filter, which XLA's recompute pass
    holds, runs forward and recomputed."""
    cfg, calls, _jaxpr = step_kernel_calls(jax_cpu, FAMILY, tiny)
    assert cfg.remat_policy == "full"
    assert calls[("ssd_fwd", False)] == 3 and calls[("ssd_fwd", True)] == 0
    assert calls[("ssd_bwd", False)] + calls[("ssd_bwd", True)] == 3
    # (at 320 channels the filter's blocks engage or not by the shape:
    # either way forward and recompute run the same calls)
    assert calls[("conv_silu_fwd", False)] == calls[("conv_silu_fwd", True)]
    assert calls[("flash_fwd", False)] == 1 and calls[("flash_fwd", True)] == 0


def test_a_multiplier_of_one_emits_no_op_and_none_is_the_parents_step(jax_cpu):
    """`Multipliers()` (every one 1, no scale) traces to the jaxpr of a
    configuration without the record, and each multiplier that is not 1
    adds its products and nothing else."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import (GPTConfig, Multipliers, gpt_init,
                                    gpt_loss)
    plain = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32,
                                tie_embeddings=True, attention="reference",
                                remat_policy="none")
    params = gpt_init(jax.random.PRNGKey(0), plain)
    tokens = jnp.zeros((1, 33), jnp.int32)

    def text(cfg):
        # (a custom_vjp's functions print with their addresses)
        return re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(
            lambda p: gpt_loss(p, {"tokens": tokens}, cfg))(params)))
    base = text(plain)
    ones = dataclasses.replace(plain, multipliers=Multipliers())
    assert ones.sm_scale is None and text(ones) == base
    muls = base.count(" mul ")
    for change, more in (({"embedding": 12.0}, 1), ({"logits": 8.0}, 1),
                         ({"residual": 0.22}, 2 * plain.n_layers),
                         ({"attention": 0.25}, 0)):
        cfg = dataclasses.replace(plain, multipliers=Multipliers(**change))
        assert text(cfg).count(" mul ") == muls + more, change
    # the softmax scale is a constant of the scores' product
    scaled = dataclasses.replace(plain, multipliers=Multipliers(
        attention=plain.head_dim ** -0.5))
    assert text(scaled) == base


def test_the_tied_matrix_takes_both_gradients_on_one_leaf(jax_cpu, tiny):
    """Embedding x 3 and logits / 2 through ONE matrix: its gradient is the
    sum of the lookup's (rows of the tokens seen, scaled by the embedding's
    multiplier) and the head's (every row, of the scaled logits)."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import gpt_init, gpt_loss
    cfg = FAMILY.config(dict(tiny, num_hidden_layers=1,
                             layer_types=["attention"]),
                        dtype=jnp.float32, attention="reference",
                        remat_policy="none")
    params = gpt_init(jax.random.PRNGKey(1), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 64, (1, 33), dtype=np.int32))            # rows 64.. never looked up
    with jax.default_matmul_precision("highest"):
        grads = jax.jit(jax.grad(
            lambda p: gpt_loss(p, {"tokens": tokens}, cfg)))(params)
        untied = dataclasses.replace(cfg, tie_embeddings=False)
        split = dict(params, lm_head=params["embed"]["table"].T)
        apart = jax.jit(jax.grad(
            lambda p: gpt_loss(p, {"tokens": tokens}, untied)))(split)
    table = grads["embed"]["table"]
    np.testing.assert_allclose(
        table, apart["embed"]["table"] + apart["lm_head"].T, atol=1e-7)
    assert float(jnp.abs(apart["embed"]["table"][64:]).max()) == 0.0
    assert float(jnp.abs(table[64:]).max()) > 0.0
