"""A router that reads the layer's normed input ahead of the mixer, ReLU-gated
experts and a kind of attention layer that rotates nothing beside one that
does (models/gpt.py) against the plain float32 reference of
benchmark/families/smallthinker.py, at a small size on the CPU: seeded random
weights, the kernels in interpret mode. The checks every family has are
tests/helpers/families.py's, given this file's FAMILY; each mechanism alone
and the cell's compile for a described chip: tests/test_smallthinker_routing.py."""

import contextlib

import numpy as np
import pytest

# (the rehearsal is bound, and so run, first here and last or midway in the
# other families' files: five subprocesses that each start a cluster do not
# then start in the same minute of a run)
from helpers.families import test_the_cell_rehearses  # noqa: F401
from helpers.families import (  # noqa: F401 — fixtures and shared checks
    Family, case, family, patched as _patched, programmed, read, reference,
    seeded, steps_agree, test_bfloat16_step_passes_the_per_token_check,
    test_configuration_file_keeps_the_catalog_and_states_the_cut,
    test_logits_loss_and_gradients_match_the_reference,
    test_param_count_is_the_published_model_and_the_programs_tree,
    test_pipeline_refuses_by_name, test_sharded_step_equals_one_device,
    test_the_configuration_refuses_by_name,
    test_the_programs_gradient_moves_where_the_references_does,
    test_the_shares_of_a_layer_add_up_to_the_uncut_reference, tiny)


class SmallThinker(Family):
    """benchmark/rehearsal/configs/tiny-smallthinker.json, two periods
    deep: a full layer that rotates nothing, then three sliding layers of
    window 24 that rotate, twice; 14 query heads of 32 on 2 key/value heads
    (groups of 7); experts 4..7 of 16 held, 4 a token, ReLU-gated, routed
    from the layer's normed input."""

    name, tiny, cell = ("smallthinker", "tiny-smallthinker",
                        "smallthinker-21b-a3b")
    workload = "smallthinker_train_1chip"

    def shaped(self, config, periods=2):
        """`periods` of the published pattern: both kinds of layer and both
        rotations occur that often."""
        return dict(config, num_hidden_layers=4 * periods,
                    sliding_window_layout=[0, 1, 1, 1] * periods,
                    rope_layout=[0, 1, 1, 1] * periods)

    # Two periods of (full without rotation, window x 3 rotated), groups
    # of 7 query heads a key/value head, the routing carried across the
    # mixer, ReLU-gated experts of which a quarter is held, in float32: every
    # logit to 5e-5 (tests/test_linear_attention_model.py's tolerance and its
    # reason: float32 sums in another order, here the flash kernels' blocks
    # and the grouped matmuls' tiles against whole rows) and the whole tree
    # of gradients.
    logits_atol, grads_atol = 5e-5, 2e-5

    def built(self, cfg, params):
        assert [sorted(layer) for layer in params["layers"]] == (
            [["attn", "ln1", "ln2", "moe"]]
            + [["ln1", "ln2", "moe", "window_attn"]] * 3) * 2
        assert cfg.rope_of("attention") is None
        assert cfg.rope_of("window").plain
        assert cfg.rope_of("window").theta == 1.5e6
        full, sliding = (params["layers"][4]["attn"],
                         params["layers"][5]["window_attn"])
        assert full["wq"].shape == sliding["wq"].shape == (128, 14 * 32)
        assert full["wk"].shape == sliding["wv"].shape == (128, 2 * 32)
        assert params["layers"][0]["moe"]["w_up"].shape == (4, 128, 64)
        assert params["layers"][0]["moe"]["router"].shape == (128, 16)
        assert params["lm_head"].shape == (128, 512)                # untied

    def statistics(self, aux, loss, reference):
        assert float(loss) == float(aux["xent"])        # no router loss
        assert 0.0 < float(aux["expert_slots_held_share"]) < 1.0
        assert 0.0 < float(aux["expert_hidden_zero_share"]) < 1.0

    def moves(self, name):
        return True

    # the program's own forward: bf16, both kinds' flash kernels, the
    # grouped-matmul kernels; nan where one of the three bounds is broken
    bf16_bounds = {"logprob_median_tol": 0.08, "logprob_rms_tol": 0.5,
                   "logprob_p99_tol": 1.0}
    bf16_broken = tuple(bf16_bounds)

    # a whole sliding-window layer, routing ahead of attention, attention
    # and residual included: every chip computes the router, attention and
    # the residual alike; the four ranks' experts are 0..3, 4..7, 8..11,
    # 12..15
    experts_key, shared_layer = "moe_num_primary_experts", 2

    def shared_layer_is(self, layer):
        assert sorted(layer) == ["ln1", "ln2", "moe", "window_attn"]

    def uncut_layer(self, jax, layer, x, whole):
        st = self.module

        def before_the_experts(h):
            n1 = st._norm(h, layer["ln1"]["scale"], 1e-6)
            return h + st.reference_attention(layer["window_attn"], n1, whole,
                                              1, 1)
        return (jax.vmap(before_the_experts)(x), jax.vmap(
            lambda h: st.reference_layer(layer, h, whole, 1, 1))(x))

    cell_params, cell_share = 656_529_920, (0, 16, 64)

    def published(self, cell, tiny_tree):
        # the published model: 21.5B, of which 3.7B a token with the
        # embedding and the head (0.78B) and 2.9B without: its name (21B-A3B)
        st = self.module
        published = {k: v for k, v in cell.items() if k != "share"}
        published.update(cell["published"])
        assert round(st.param_count(published) / 1e9, 1) == 21.5
        active = st.active_param_count(published)
        assert round(active / 1e9, 1) == 3.7
        assert round((active - 2 * 151936 * 2560) / 1e9, 1) == 2.9

    def sharded_step(self, jax, tiny, twin):
        """The two-period model on fsdp=2 x tensor=2: a key/value head with
        its seven query heads on a shard of `tensor`, the slots' order
        worked out per shard ahead of the mixer and handed to the experts'
        shard_map after it."""
        cfg, _ = steps_agree(jax, self, tiny, twin())
        assert cfg.remat_policy == "full"

    # The refusals parallel/pipeline.py gives today, kept: a period of
    # kinds is layers that are not alike; window layers it refuses by name;
    # a sparse stack hands back the router's statistics, wherever it routes
    # from.
    pipeline_refusals = [
        case(({}, {"pipeline": 1}, "layer 1's parameters are not layer 0's"),
             "the_period"),
        case(({"layer_kinds": ("window",) * 8}, {"pipeline": 1},
              "hands back statistics"), "window_experts"),
        case(({"layer_kinds": ("window",) * 8, "n_experts": 0,
               "experts_held": None}, {"pipeline": 1},
              "no sliding-window layers"), "window_layers"),
        case(({"layer_kinds": None}, {"pipeline": 1},
              "hands back statistics .*expert_rows_bounded"), "experts"),
    ]
    refusals = [
        case(({"route_from": "attention"}, "route_from='attention'"),
             "route_from"),
        case(({"gate_activation": "gelu"}, "gate_activation='gelu'"),
             "activation"),
        case(({"index_topk": 8, "index_heads": 2, "index_head_dim": 16,
               "layer_kinds": None}, "rotate nothing.*not for an indexer"),
             "indexer"),
    ]

    reduced = {"num_hidden_layers", "moe_num_primary_experts", "vocab_size",
               "sliding_window_layout", "rope_layout"}

    def cut(self, cell, row, bench):
        # published layers 0..3: one whole period
        for key in ("sliding_window_layout", "rope_layout"):
            assert cell[key] == row["config"][key][:4] == [0, 1, 1, 1]
        share = cell["share"]
        assert share["chips_per_layer"] * cell["moe_num_primary_experts"] \
            == share["moe_num_primary_experts"] == 64
        assert share["chips_per_layer"] * cell["vocab_size"] \
            == share["vocab_size"] == 151936
        assert {"router_input", "router_score", "expert_activation", "rope",
                "sequence_length", "embedding_init_std"} <= set(
                    cell["assumed"])
        for key in ("reduced_why", "departures", "deployment", "train",
                    "program_check"):
            assert key in cell, key
        workload = next(w for w in bench["workloads"]
                        if w["name"] == "smallthinker_train_1chip")
        assert workload["traffic"] == "train_b1_s16384_dp"
        assert workload["chips"] == 1
        listed = {m["name"] for m in bench["per_layer"]
                  if "smallthinker_train_1chip" in m.get("workloads", ())}
        assert {"train_route_ahead_pct", "train_attn_window_pct",
                "train_moe_route_pct", "swa_fwd_roofline",
                "swa_bwd_dq_roofline", "swa_bwd_dkv_roofline",
                "gqa_fwd_roofline", "gqa_bwd_dq_roofline",
                "gqa_bwd_dkv_roofline"} <= listed

    # smallthinker_train_1chip (1 x 16 384 tokens): a full layer that
    # rotates nothing and three window layers (4096: a band of two major
    # blocks) at 28 query heads on 4, each layer's routing worked out ahead
    # of its mixer; 16 of 64 ReLU-gated experts held in all four layers
    # (both row spaces in the text, as above). q, k, v through rope_split
    # forward and recomputed in every layer (the full layer's without a
    # table). 11.61 GB when this was written: 7.88 of state, 3.73 of
    # temporaries (benchmark/configs/smallthinker-21b-a3b.json:
    # memory_peak_bytes.described_chip_compile; ~50 s alone here).
    cell_kernel_calls = {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                         "flash_win_fwd": 3, "flash_win_bwd_dq": 3,
                         "flash_win_bwd_dkv": 3, "rope_split": 24,
                         "rope_merge": 12, "moe_gmm": 72, "moe_tgmm": 24,
                         "embed_grad": 1, "moe_run_sum": 8}
    cell_memory_share = (0.60, 0.80)


FAMILY = SmallThinker()


# ---------------------------------------------------------------------------
# (a) the reference's faults: what `program_check` rests on
# ---------------------------------------------------------------------------


def _reference_logits(jax, config, params, tokens):
    from benchmark.families import smallthinker
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: smallthinker.reference_logits(
            p, t[:, :-1], config))(params, tokens)


def faulty_reference(fault: str, config):
    """(a context in which benchmark/families/smallthinker.py's reference
    has `fault`, the configuration to hand it): each fault of what this
    configuration brings, in the reference alone. The chip's controls
    (`program_check` in the configuration file) are made with these."""
    import jax
    import jax.numpy as jnp
    from benchmark.families import smallthinker as st
    n = config["num_hidden_layers"]
    window = config["sliding_window_size"]

    def routed_from_the_mixed_stream(layer, x, config, window, rotates):
        eps = float(config["rms_norm_eps"])
        a = layer["window_attn" if window else "attn"]
        h = x + st.reference_attention(
            a, st._norm(x, layer["ln1"]["scale"], eps), config, window,
            rotates)
        n2 = st._norm(h, layer["ln2"]["scale"], eps)
        return h + st.reference_experts(
            layer["moe"], n2, st.reference_routing(layer["moe"], n2, config),
            config)

    def unnormalised(m, x, config):
        scores = jax.nn.softmax(x @ m["router"].astype(jnp.float32), -1)
        top, chosen = jax.lax.top_k(
            scores, config["moe_num_active_primary_experts"])
        return jnp.einsum("sk,ske->se", top, jax.nn.one_hot(
            chosen, st.share(config)[2], dtype=jnp.float32))

    same = contextlib.nullcontext()
    return {
        "router_fed_the_mixed_stream": lambda: (
            _patched(st, reference_layer=routed_from_the_mixed_stream),
            config),
        "silu_for_relu": lambda: (
            _patched(st, _gate_activation=jax.nn.silu), config),
        "full_layer_rotated": lambda: (
            same, dict(config, rope_layout=[1] * n)),
        "window_layer_unrotated": lambda: (
            same, dict(config, rope_layout=[0] * n)),
        "window_halved": lambda: (
            same, dict(config, sliding_window_size=window // 2)),
        "window_one_less": lambda: (
            same, dict(config, sliding_window_size=window - 1)),
        "window_one_more": lambda: (
            same, dict(config, sliding_window_size=window + 1)),
        "top_weights_unnormalised": lambda: (
            _patched(st, reference_routing=unnormalised), config),
    }[fault]()


FAULTS = ("router_fed_the_mixed_stream", "silu_for_relu",
          "full_layer_rotated", "window_layer_unrotated", "window_halved",
          "window_one_less", "window_one_more", "top_weights_unnormalised")


@pytest.mark.parametrize("fault", FAULTS)
def test_the_reference_tells_each_fault_apart(jax_cpu, tiny, seeded,
                                              reference, fault):
    """What `program_check` rests on: the reference with one mechanism
    changed gives other logits, and the program (held to the sound
    reference to 5e-5 above) is as far from it. A window of 23 and of 25
    for 24 stand in for the cell's 4095 and 4097, which the chip's bounds
    cannot tell."""
    jax = jax_cpu
    import jax.numpy as jnp
    _cfg, params, tokens = seeded("reference")
    patch, config = faulty_reference(fault, tiny)
    with patch:
        faulty = _reference_logits(jax, config, params, tokens)
    assert float(jnp.abs(faulty - reference[0]).max()) > 1e-3


def test_routing_from_the_input_is_not_routing_from_the_mixed_stream(
        jax_cpu, tiny, reference):
    """The switch changes the result: the same weights routed from the
    stream after the mixer (today's default) give other logits, those of
    the reference's matching fault."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import gpt_forward
    cfg, params, tokens = FAMILY.program(jax, tiny, "reference",
                                   route_from="mixed")
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, t: gpt_forward(p, t, cfg))(
            params, tokens[:, :-1])
    assert float(jnp.abs(logits - reference[0]).max()) > 1e-3
    patch, config = faulty_reference("router_fed_the_mixed_stream", tiny)
    with patch:
        faulty = _reference_logits(jax, config, params, tokens)
    np.testing.assert_allclose(logits, faulty, atol=5e-5)


# ---------------------------------------------------------------------------
# (b) arithmetic
# ---------------------------------------------------------------------------


def test_flops_and_kernel_arithmetic_count_what_is_computed_here():
    from benchmark.families import smallthinker as st
    from benchmark.kernels import gqa_attention, window_attention
    cell = read("benchmark", "configs", "smallthinker-21b-a3b.json")
    mix = read("benchmark", "traffic", "train_b1_s16384_dp.json")
    d, s, w = 2560, 16384, 4096
    attention = 2 * d * 28 * 128 + 2 * d * 4 * 128
    active = (4 * (attention + d * 64 + 6 * 16 / 64 * 3 * d * 768)
              + d * 37984)
    pairs = s * w - w * (w - 1) // 2
    assert window_attention.band_pairs(s, w) == pairs == 58_722_304
    # a window layer keeps 43.75 % of a full layer's pairs at 16 384
    assert pairs / (s * (s + 1) // 2) == pytest.approx(0.4375, abs=2e-4)
    assert st.train_flops_per_token(cell, s) == pytest.approx(
        6.0 * active + 12.0 * 128 * 28 * (s / 2 + 3 * pairs / s))
    assert st.forward_flops_per_token(cell, s) == pytest.approx(
        0.706e9, rel=0.01)                 # ISSUE 49's ~703M a token
    call = {"batch": 1, "heads": 28, "kv_heads": 4, "seq": s,
            "head_dim": 128}
    assert st.attention_call(cell, mix) == call
    assert st.window_call(cell, mix) == dict(call, window=w)
    assert gqa_attention.flash_fwd(cell, mix)[0] == 2 * 28 * s * s * 128
    product = 2.0 * pairs * 128 * 28
    wide, narrow = 28 * s * 128 * 2, 4 * s * 128 * 2
    fwd, dq, dkv = (f(cell, mix) for f in (
        window_attention.flash_win_fwd, window_attention.flash_win_bwd_dq,
        window_attention.flash_win_bwd_dkv))
    assert fwd == (2 * product, 2 * wide + 2 * narrow)
    assert dq[0] + dkv[0] == 5 * product          # the backward's five
    assert dkv[1] == 2 * wide + 4 * narrow        # dK, dV at 4 heads
