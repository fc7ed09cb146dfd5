"""A stack whose attention layers carry an indexer (models/gpt.py) against
the plain float32 reference of benchmark/families/keye.py, at a small size
on the CPU: seeded random weights, the kernels in interpret mode. The checks
every family has are tests/helpers/families.py's, given this file's FAMILY;
the kernels alone and the cell's compile for a described chip:
tests/test_selected_attention.py."""

import copy

import numpy as np
import pytest

from helpers.families import (  # noqa: F401 — fixtures and shared checks
    Family, case, family, flash_twin, programmed, read, reference, seeded,
    step_kernel_calls, steps_agree,
    test_bfloat16_step_passes_the_per_token_check,
    test_configuration_file_keeps_the_catalog_and_states_the_cut,
    test_every_new_leaf_gets_its_rule,
    test_logits_loss_and_gradients_match_the_reference,
    test_param_count_is_the_published_model_and_the_programs_tree,
    test_pipeline_refuses_by_name,
    test_the_configuration_refuses_by_name,
    test_the_programs_gradient_moves_where_the_references_does,
    test_the_reference_tells_each_mechanism_apart,
    test_the_shares_of_a_layer_add_up_to_the_uncut_reference, tiny)


class Keye(Family):
    """benchmark/rehearsal/configs/tiny-keye.json: three layers, 4 query
    heads of 32 on 2 key/value heads with a norm a head, an indexer of 4
    heads of 16 on one key head that keeps 32 keys a query, experts 4..7 of
    16 held, 2 a token, renormalised."""

    name, tiny, cell = "keye", "tiny-keye", "keye-vl-2.0-30b-a3b"
    workload = "keye2_train_1chip"
    remat, tokens_seed = None, 4

    def opinion(self, jax, cfg, params):
        """The routers as gpt_init draws them."""

    def reference_more(self, jax, params, tokens, config):
        """(xent, balance, kl, share): the loss's parts."""
        keye = self.module
        return jax.jit(lambda p, t: keye.reference_losses(p, t, config)[1:])(
            params, tokens)

    # Grouped queries with a norm a head, the indexer (its LayerNorm, its
    # rotation at its own width, its weights), the selection of 32 of up to
    # 128 keys, its KL in the loss beside the balance loss, experts on a
    # share with a renormalised top-2, in float32: logits, the loss and its
    # parts, and the whole tree of gradients, the indexer's among them.
    logits_atol, grads_atol = 5e-5, 2e-5

    def built(self, cfg, params):
        assert [sorted(layer) for layer in params["layers"]] == [
            ["attn", "ln1", "ln2", "moe"]] * 3
        attn = params["layers"][0]["attn"]
        assert sorted(attn) == ["index", "k_head_norm", "q_head_norm", "wk",
                                "wo", "wq", "wv"]
        assert attn["wq"].shape == (128, 4 * 32)
        assert attn["wk"].shape == (128, 2 * 32)
        assert {n: x.shape for n, x in attn["index"].items()
                if n != "k_norm"} \
            == {"wq": (128, 4 * 16), "wk": (128, 16), "ww": (128, 4)}
        assert sorted(attn["index"]["k_norm"]) == ["bias", "scale"]
        assert params["layers"][1]["moe"]["w_up"].shape == (4, 128, 64)
        assert params["layers"][1]["moe"]["router"].shape == (128, 16)

    def statistics(self, aux, loss, reference):
        xent, balance, kl, share = reference[3]
        np.testing.assert_allclose(aux["xent"], xent, rtol=1e-6)
        np.testing.assert_allclose(aux["router_balance_loss"], balance,
                                   rtol=1e-5)
        # the statistic is the layers' mean, the loss takes their sum
        np.testing.assert_allclose(3 * aux["index_kl"], kl, rtol=1e-5)
        np.testing.assert_allclose(aux["index_selected_share"], share,
                                   rtol=1e-6)
        np.testing.assert_allclose(
            loss, aux["xent"] + 0.001 * aux["router_balance_loss"]
            + 3 * aux["index_kl"], rtol=1e-6)
        assert 0.05 < float(aux["index_kl"]) < 2.0
        assert 0.0 < float(aux["expert_slots_held_share"]) < 1.0

    def moves(self, name):
        return True

    def other_configurations(self, tiny):
        """A topk of 31 and of 33 for 32 stand in for the cell's 2047 and
        2049, which the chip's check cannot tell apart at seeded weights;
        the tie rule is held by test_top_k_mask_is_lax_top_k_ties_included
        (tests/test_selected_attention.py) and, in `faults`, by a reference
        that keeps the HIGHER key at a tie giving another selection on
        quantised scores."""
        def sa(**change):
            return dict(tiny, sa_config=dict(tiny["sa_config"], **change))
        return {"no_selection": sa(topk=10 ** 6), "topk_16": sa(topk=16),
                "topk_64": sa(topk=64), "topk_31": sa(topk=31),
                "topk_33": sa(topk=33),
                "not_renormalised": dict(tiny, norm_topk_prob=False),
                "other_theta": dict(tiny, rope_theta=10000)}

    def faults(self, jax, tiny, params):
        import jax.numpy as jnp
        keye = self.module
        plain_scores, plain_index = keye.index_scores, keye.reference_index
        keye_chosen = keye.chosen_keys

        def latest(scores, seen, topk):
            at = jnp.arange(scores.shape[1])
            return keye_chosen(-jnp.abs(at[None, :] - 1e4) * 0 + at[None, :]
                               * jnp.ones_like(scores), seen, topk)

        def unrotated(ix, n, config):
            return plain_index(ix, n, dict(config, rope_theta=1e30))

        def unnormed(ix, n, config):
            far = dict(ix, k_norm={
                "scale": jnp.ones_like(ix["k_norm"]["scale"]),
                "bias": ix["k_norm"]["bias"]})
            kept, keye._layer_norm = keye._layer_norm, lambda x, w, eps: x
            try:
                return plain_index(far, n, config)
            finally:
                keye._layer_norm = kept
        faults = {
            "latest_keys": ("chosen_keys", latest),
            "no_relu": ("index_scores", lambda qi, ki, w: jnp.einsum(
                "qh,qhk->qk", w, jnp.einsum("qhd,kd->qhk", qi, ki))),
            "no_weights": ("index_scores", lambda qi, ki, w: plain_scores(
                qi, ki, jnp.ones_like(w))),
            "unrotated_indexer": ("reference_index", unrotated),
            "no_key_norm": ("reference_index", unnormed),
            "kv_head_h_mod": ("_kv_head_of",
                              lambda h, kv: jnp.arange(h) % kv),
            "no_head_norm": ("_norm", lambda x, scale, eps: (
                x if scale.shape[0] == tiny["head_dim"]
                else x * jax.lax.rsqrt(
                    jnp.mean(x * x, -1, keepdims=True) + eps) * scale)),
        }
        return [(name, {attribute: replacement}, False)
                for name, (attribute, replacement) in faults.items()]

    # the program's own forward: bf16, the indexer's walk, the flash_sel
    # kernels, the grouped-matmul kernels
    bf16_bounds = {"logprob_median_tol": 0.08, "logprob_rms_tol": 0.5}

    # a whole layer, attention under the indexer's selection and the
    # residual included: every chip computes attention, the indexer and the
    # residual alike
    experts_key, shared_layer = "num_experts", 1

    def uncut_layer(self, jax, layer, x, whole):
        keye = self.module

        def reference_layer(h):
            mixed, _kl, _pairs = keye.reference_attention(
                layer["attn"], keye._norm(h, layer["ln1"]["scale"], 1e-6),
                whole)
            h = h + mixed
            m = keye._norm(h, layer["ln2"]["scale"], 1e-6)
            return h, h + keye.reference_experts(layer["moe"], m, whole)[0]
        return jax.vmap(reference_layer)(x)

    def shares_statistics(self, stats):
        # the indexer is every chip's alike
        assert len({float(s["index_kl"]) for s in stats}) == 1

    cell_params = 562_290_560       # 562.3M held

    def published(self, cell, tiny_tree):
        keye = self.module
        published = dict(cell, **cell["published"])
        del published["share"]
        assert 30.5e9 < keye.param_count(published) < 30.7e9
        # (with the embedding's 0.31B, which a token reads one row of, and
        # the indexers' 0.11B: 3.04B without both)
        assert 3.4e9 < keye.active_param_count(published) < 3.5e9

    def rules(self, specs, column, row):
        from jax.sharding import PartitionSpec as P
        index = specs["layers"][0]["attn"]["index"]
        # whole index heads of wq's columns over `tensor`; the one key head,
        # its norm and the heads' weights are not divided over it
        assert index["wq"] == specs["layers"][0]["attn"]["wq"] == P(*column)
        for leaf in (index["wk"], index["ww"], index["k_norm"]["scale"],
                     index["k_norm"]["bias"]):
            assert "tensor" not in tuple(leaf)

    refusals = [
        case(({"attention": "ring", "n_kv_heads": 4},
              "indexer.*attention='ring'"), "ring"),
        case(({"kv_latent_dim": 32, "qk_nope_dim": 16, "qk_rope_dim": 16,
               "v_head_dim": 32, "n_kv_heads": 4, "qk_head_norm": False},
              "indexer.*a latent block"), "latent"),
        case(({"layer_kinds": ("attention", "window", "attention"),
               "attention_window": 8, "qk_head_norm": False},
              "indexer.*'window' layers"), "window"),
        case(({"index_heads": 0}, "index_topk=32 needs index_heads"),
             "no_heads"),
    ]
    pipeline_refusals = [
        case(({"n_experts": 0, "experts_held": None, "n_layers": 2},
              {"pipeline": 1}, "hands back statistics .*index_kl"),
             "statistics")]

    def scopes(self, names, regions):
        import re
        from ray_tpu.util import profiling
        assert {"attn_index", "attn_proj", "attn_core", "attn_out", "moe",
                "moe_route"} <= regions
        # the kernels under the selection are attn_core's; the walk's
        # kernels (the CPU's compile of the tiny step, which this read until
        # PR 73, had the interpreter's `while` in their place), the
        # indexer's projections and its table are attn_index's
        for n in names:
            if "flash_sel_" in n:
                assert profiling._last_of(n, profiling.REGIONS) == "attn_core"
            if re.search(r"/index_\w+/pallas_call", n):
                assert profiling._last_of(n, profiling.REGIONS) == "attn_index"
        assert any("attn_index" in n and "index_search" in n for n in names)
        assert any("attn_index/bsd,dh->bsh" in n for n in names)

    reduced = {"num_hidden_layers", "num_experts", "vocab_size"}

    def cut(self, cell, row, bench):
        assert cell["sa_config"] == row["config"]["sa_config"] == {
            "indexer_head_dim": 64, "indexer_num_heads": 16,
            "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
            "q_chunk_size": 512, "topk": 2048}
        assert (cell["hidden_size"], cell["num_attention_heads"],
                cell["num_key_value_heads"], cell["head_dim"],
                cell["moe_intermediate_size"], cell["num_experts_per_tok"],
                cell["rope_theta"], cell["rms_norm_eps"],
                cell["norm_topk_prob"]
                ) == (2048, 32, 4, 128, 768, 8, 10000000, 1e-06, True)
        assert cell["share"]["chips_per_layer"] * cell["num_experts"] \
            == cell["share"]["num_experts"] == 128
        assert cell["share"]["chips_per_layer"] * cell["vocab_size"] == 151936
        assert {"qk_norm", "router_aux_loss_coef", "indexer_input",
                "indexer_key_norm", "indexer_rotation", "indexer_weights",
                "indexer_tie_rule", "indexer_loss", "chunk_sizes",
                "sequence_length", "init"} <= set(cell["assumed"])
        assert cell["embedding_init_std"] == 1.0

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
    cell_kernel_calls = {"flash_sel_fwd": 5, "flash_sel_bwd_dq": 5,
                         "flash_sel_bwd_dkv": 5, "flash_fwd": 0,
                         "index_scores": 5, "index_search": 5,
                         "index_kl": 5, "index_grad_q": 5,
                         "index_grad_k": 5,
                         "rope_split": 35, "rope_merge": 20,
                         "moe_gmm": 90, "moe_tgmm": 30, "embed_grad": 1}
    cell_memory_share = (0.70, 0.85)


FAMILY = Keye()


# ---------------------------------------------------------------------------
# (a) the whole model against the family's reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_the_two_losses_reach_disjoint_parameters(jax_cpu, tiny, attention):
    """The cross-entropy's (and the balance loss's) gradient of every
    parameter of the indexer and the KL's gradient of every other
    parameter are exactly zero: one step on the sum is the two separate
    optimisations."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_loss_and_aux
    cfg, params, tokens = FAMILY.program(jax, tiny, attention)

    def part(which):
        def loss(p):
            total, aux = gpt_loss_and_aux(p, {"tokens": tokens}, cfg)
            return aux["index_kl"] if which == "kl" \
                else total - 3 * aux["index_kl"]
        return jax.jit(jax.grad(loss))(params)
    for which, in_indexer in (("kl", True), ("rest", False)):
        grads = part(which)
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
            name = jax.tree_util.keystr(path)
            if ("index" in name) == in_indexer:
                if "bias" not in name or which == "kl":
                    assert np.any(np.asarray(g)), (which, name)
            else:
                assert not np.any(np.asarray(g)), (which, name)


def test_a_sequence_of_at_most_topk_is_plain_causal_attention(jax_cpu, tiny):
    """Every causal key is selected: the logits are those of the same
    weights without an indexer, the plain kernels run (no flash_sel_*),
    and the KL is still taken."""
    jax = jax_cpu
    import dataclasses
    from ray_tpu.models.gpt import gpt_forward, gpt_loss_and_aux
    cfg, params, tokens = FAMILY.program(jax, tiny, "flash", index_topk=128)
    plain = dataclasses.replace(cfg, index_topk=0, index_heads=0,
                                index_head_dim=0)
    bare = copy.deepcopy(params)
    for layer in bare["layers"]:
        del layer["attn"]["index"]
    logits, stats = gpt_forward(params, tokens[:, :-1], cfg)
    want, _ = gpt_forward(bare, tokens[:, :-1], plain)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    assert float(stats["index_selected_share"]) == 1.0
    assert float(stats["index_kl"]) > 0.01
    text = str(jax.make_jaxpr(jax.grad(lambda p: gpt_loss_and_aux(
        p, {"tokens": tokens}, cfg)[0]))(params))
    assert "name=flash_fwd" in text and "flash_sel" not in text


def test_the_tie_rule_is_the_lower_key(jax_cpu):
    """families/keye.py:chosen_keys against a hand-made row: of four equal
    scores two may stay, and they are the two lowest keys."""
    import jax.numpy as jnp
    from benchmark.families import keye
    from ray_tpu.ops.indexer import top_k_mask
    scores = jnp.asarray([[0.5, 2.0, 0.5, 3.0, 0.5, 0.5, -1.0, 9.0]])
    seen = jnp.asarray([[True] * 7 + [False]])
    want = [[True, True, True, True, False, False, False, False]]
    assert np.asarray(keye.chosen_keys(scores, seen, 4)).tolist() == want
    assert np.asarray(top_k_mask(scores, 4, seen)).tolist() == want


# ---------------------------------------------------------------------------
# (b) arithmetic, rules, refusals, names
# ---------------------------------------------------------------------------


def test_the_family_draws_the_embedding_at_the_configurations_spread(jax_cpu,
                                                                    tiny):
    """`assumed.init`: every leaf is gpt_init's but the embedding's rows,
    which families/keye.py:program.init scales to `embedding_init_std`."""
    jax = jax_cpu
    from benchmark.families import keye
    from ray_tpu.models.gpt import gpt_init
    mine = keye.program(tiny).init(jax.random.PRNGKey(2))
    plain = gpt_init(jax.random.PRNGKey(2), keye._train_config(tiny))
    assert tiny["embedding_init_std"] == 1.0
    assert abs(float(np.std(np.asarray(mine["embed"]["table"]))) - 1.0) < 0.02
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(mine)[0],
                            jax.tree_util.tree_leaves(plain)):
        if "embed" not in jax.tree_util.keystr(path):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("seq,topk", [(64, 8), (64, 1), (32, 32), (16, 40)])
def test_selected_pairs_is_a_brute_force_count(jax_cpu, seq, topk):
    """benchmark/kernels/selected_attention.py counts the pairs a selection
    keeps, whichever keys they are: against the selection the indexer's
    search makes from random scores."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import keye
    from benchmark.kernels import selected_attention
    from ray_tpu.ops.indexer import top_k_mask
    scores = jax.random.normal(jax.random.PRNGKey(seq), (seq, seq))
    chosen = top_k_mask(scores, topk, jnp.tril(jnp.ones((seq, seq), bool)))
    assert selected_attention.selected_pairs(seq, topk) == int(chosen.sum()) \
        == keye.selected_pairs(seq, topk) \
        == sum(min(t + 1, topk) for t in range(seq))


def test_flops_and_kernel_arithmetic_count_what_is_computed_here():
    from benchmark.families import keye
    from benchmark.kernels import gqa_attention, selected_attention
    cell = read("benchmark", "configs", "keye-vl-2.0-30b-a3b.json")
    mix = read("benchmark", "traffic", "train_b2_s8192_dp.json")
    call = keye.attention_call(cell, mix)
    assert call == {"batch": 2, "heads": 32, "kv_heads": 4, "seq": 8192,
                    "head_dim": 128, "topk": 2048}
    pairs = selected_attention.selected_pairs(8192, 2048)
    assert pairs == 8192 * 2048 - 2048 * 2047 // 2 == 14_681_088
    fwd, fwd_bytes = selected_attention.flash_sel_fwd(cell, mix)
    dq, dq_bytes = selected_attention.flash_sel_bwd_dq(cell, mix)
    dkv, dkv_bytes = selected_attention.flash_sel_bwd_dkv(cell, mix)
    assert fwd == 2 * 2.0 * pairs * 128 * 2 * 32
    assert abs(dq + dkv - 2.5 * fwd) < 1.0 and abs(dq / dkv - 2 / 3) < 1e-12
    wide, narrow = 2 * 8192 * 128 * 32 * 2, 2 * 8192 * 128 * 4 * 2
    selection = 2 * 8192 * 8193 // 2
    assert (fwd_bytes, dq_bytes, dkv_bytes) == (
        2 * wide + 2 * narrow + selection, 3 * wide + 2 * narrow + selection,
        2 * wide + 4 * narrow + selection)
    # a kernel that computes every causal tile reads at most this share of
    # the dense kernels' count
    dense = gqa_attention.flash_fwd(cell, mix)[0]
    assert 0.43 < fwd / dense < 0.44
    # the model's arithmetic: 6 x what a token activates + the selected
    # pairs' products + the indexer's over the causal pairs
    flops = keye.train_flops_per_token(cell, 8192)
    active = 5 * (18_874_368 + 2_260_992 + 262_144 + 1.0 * 4_718_592) \
        + 2048 * 18992
    products = 5 * (3 * 4 * 128 * 32 * pairs / 8192
                    + 3 * 2 * 64 * 16 * 8193 / 2)
    assert flops == 6.0 * active + products
    assert 1.5e9 < flops < 1.7e9


@pytest.mark.parametrize("seq,topk", [(64, 8), (32, 32), (16, 40)])
def test_the_indexer_kernels_arithmetic_is_a_brute_force_count(tiny, seq,
                                                               topk):
    """benchmark/kernels/indexer.py, a function a kernel name, against
    loops over the pairs at a tiny shape: the index heads' scores over
    every causal pair, the target and the gradient over the selected pairs
    alone, every tensor once."""
    from benchmark.kernels import indexer
    config = copy.deepcopy(tiny)
    config["sa_config"]["topk"] = topk
    mix = {"global_batch": 3, "seq": seq, "mesh": {"data": 1}}
    batch, heads, kv_heads, dim = 3, 4, 2, 32
    index_heads, index_dim = 4, 16
    causal = selected = 0
    for t in range(seq):
        causal += batch * (t + 1)
        selected += batch * min(t + 1, topk)
    positions = batch * seq
    operands = (positions * index_heads * index_dim * 2      # qI
                + positions * index_dim * 2                  # kI
                + positions * index_heads * 4)               # w
    index_product = lambda pairs: 2.0 * pairs * index_dim * index_heads
    assert indexer.index_scores(config, mix) == (
        index_product(causal), operands + causal * 4)
    assert indexer.index_search(config, mix) == (0.0, causal * 4 + causal)
    assert indexer.index_kl(config, mix) == (
        2.0 * selected * dim * heads,
        positions * dim * heads * 2 + positions * dim * kv_heads * 2
        + positions * heads * 4 + selected * 4 + selected * 4 + causal)
    assert indexer.index_grad_q(config, mix) == (
        2 * index_product(selected),
        operands + selected * 4 + positions * index_heads * index_dim * 2
        + positions * index_heads * 4)
    assert indexer.index_grad_k(config, mix) == (
        2 * index_product(selected),
        operands + selected * 4 + positions * index_dim * 2)


def test_the_indexer_kernels_least_times_at_the_cell():
    """At keye2_train_1chip: what each yardstick says a call takes at 197
    TFLOP/s and 819 GB/s, against the kernels' first traced times (1.74,
    4.12, 3.61, 3.43, 3.12 ms: PERF.md, PR 41): every share under 100."""
    from benchmark.kernels import indexer
    cell = read("benchmark", "configs", "keye-vl-2.0-30b-a3b.json")
    mix = read("benchmark", "traffic", "train_b2_s8192_dp.json")
    least = {}
    for kernel in ("index_scores", "index_search", "index_kl",
                   "index_grad_q", "index_grad_k"):
        flops, hbm_bytes = getattr(indexer, kernel)(cell, mix)
        least[kernel] = 1e3 * max(flops / 197e12, hbm_bytes / 819e9)
    assert 0.69 < least["index_scores"] < 0.71          # the MXU's
    assert 0.40 < least["index_search"] < 0.42          # the memory's
    assert 1.21 < least["index_kl"] < 1.23
    assert 0.60 < least["index_grad_q"] == least["index_grad_k"] < 0.62


def test_data_parallel_step_equals_one_device(jax_cpu, tiny, seeded,
                                              programmed):
    """One step of the whole tiny model on data=2 (the walk and the
    flash_sel kernels per shard, the KL a mean of the shards') equals the
    one-device step; `tensor` > 1 refuses by name."""
    # (the balance loss's f and P are the whole batch's on both)
    _cfg, one_step = steps_agree(
        jax_cpu, FAMILY, tiny, flash_twin(seeded, programmed), strategy="dp",
        axes={"data": 2})
    with pytest.raises(ValueError, match="'tensor' > 1"):
        one_step("tp_fsdp", {"data": 1, "fsdp": 2, "tensor": 2}, 4)


def test_pipeline_runs_an_indexer_no_layer_carries(jax_cpu, tiny):
    """The pipeline refuses by what the block hands back, not by
    index_topk: a stack of short-convolution layers alone has no attention
    for an indexer to sit in, and runs as that stack does without the
    field."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import keye
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import gpt_params_to_pp, make_gpt_pp_loss
    kwargs = dict(keye.gpt_config_kwargs(tiny), n_experts=0,
                  experts_held=None, dtype=jnp.float32)
    cfg = GPTConfig(**dict(kwargs, layer_kinds=("conv",) * kwargs["n_layers"]))
    assert cfg.index_topk
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.array(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 33)), jnp.int32)}
    mesh = build_mesh(MeshConfig(data=1, pipeline=cfg.n_layers),
                      devices=jax.devices()[:cfg.n_layers])
    loss = make_gpt_pp_loss(cfg, mesh, num_microbatches=2)(
        gpt_params_to_pp(params), batch)
    assert abs(float(loss) - float(gpt_loss(params, batch, cfg))) < 1e-5


def _loops_outside_kernels(jax, jaxpr, path=""):
    """The scope path of every scan and while of jaxpr and of what its
    equations hold, a kernel's body left out."""
    for eqn in jaxpr.eqns:
        here = f"{path}/{eqn.source_info.name_stack}"
        if eqn.primitive.name in ("scan", "while"):
            yield here
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _loops_outside_kernels(jax, sub, here)


def test_the_selection_engages_and_the_walk_runs_once_a_layer(jax_cpu, tiny):
    """The step's kernel calls are the counter: 3 of each flash_sel_* and
    no flash_*, 3 of each of the walk's five kernels (128 positions, 32
    keys a query: the kernels' side of `_selected_attention`), and under
    remat_policy="full" neither the forward kernel nor any of the walk's
    in a recompute pass: FLASH_OUT, FLASH_LSE, INDEX_MASK and INDEX_GRADS
    are kept. The jnp walk (a scan over blocks of queries around a search
    of 32 passes) is not in the step."""
    jax = jax_cpu
    from ray_tpu.util import profiling
    cfg, calls, jaxpr = step_kernel_calls(jax, FAMILY, tiny)
    assert cfg.remat_policy == "full" and cfg.index_topk == 32
    assert calls[("flash_sel_fwd", False)] == 3
    assert calls[("flash_sel_fwd", True)] == 0
    for kernel in ("flash_sel_bwd_dq", "flash_sel_bwd_dkv"):
        assert calls[(kernel, False)] + calls[(kernel, True)] == 3
    assert not any(name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
                   for name, _ in calls)
    for kernel in ("index_scores", "index_search", "index_kl",
                   "index_grad_q", "index_grad_k"):
        assert kernel in profiling.KERNELS
        assert calls[(kernel, False)] == 3 and calls[(kernel, True)] == 0
    # (the search's 32 passes are a loop inside its kernel: the walk's own
    # scans stood in the layer, under `attn_index`)
    loops = list(_loops_outside_kernels(jax, jaxpr.jaxpr))
    assert loops and not any("attn_index" in path for path in loops), loops
