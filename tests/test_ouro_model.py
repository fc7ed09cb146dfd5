"""A looped, weight-shared stack: dense layers under a norm either side of each
half run `total_ut_steps` times a step over the SAME weights with the final
norm inside the loop, every pass read by the head, and an exit gate that
weights the passes' cross-entropies a token (models/gpt.py: GPTConfig.loop,
norm_after "both") against the plain float32 reference of
benchmark/families/ouro.py, at a small size on the CPU: seeded random
weights, the kernels in interpret mode. The checks every family has are
tests/helpers/families.py's, given this file's FAMILY; the cell's compile for
a described chip: tests/test_ouro.py."""

import re

import numpy as np
import pytest

from helpers.families import (  # noqa: F401 — fixtures and shared checks
    Family, case, family, patched, programmed, read, reference, seeded,
    step_kernel_calls, steps_agree,
    test_bfloat16_step_passes_the_per_token_check,
    test_configuration_file_keeps_the_catalog_and_states_the_cut,
    test_every_new_leaf_gets_its_rule,
    test_logits_loss_and_gradients_match_the_reference,
    test_param_count_is_the_published_model_and_the_programs_tree,
    test_pipeline_refuses_by_name, test_sharded_step_equals_one_device,
    test_the_cell_rehearses, test_the_configuration_refuses_by_name,
    test_the_programs_gradient_moves_where_the_references_does,
    test_the_reference_tells_each_mechanism_apart, tiny)


def _faults(ouro):
    """fault -> {attribute of benchmark/families/ouro.py: what stands in for
    it}: the controls of `program_check` that no key of the configuration
    writes (three passes in place of four is a key: `total_ut_steps`).
    Called before any of them is in place: what a fault wraps is the sound
    function."""
    import jax
    import jax.numpy as jnp
    block, norm, entropy = (ouro.reference_block, ouro._norm,
                            ouro.reference_entropy)

    def no_norm_after_the_mixer(layer, x, config):
        return block(dict(layer, ln1_after={"scale": None}), x, config)

    def norm_or_none(x, scale, eps):
        return x if scale is None else norm(x, scale, eps)

    def other_weights_a_pass(params, t, config):
        layers = params["layers"]
        return layers if t % 2 == 0 else layers[::-1]
    return {
        "final_norm_left_out_between_passes": {
            "reference_next_input": lambda normed, stream: stream},
        "no_norm_after_the_mixer": {
            "reference_block": no_norm_after_the_mixer,
            "_norm": norm_or_none},
        "weights_not_shared": {"reference_layers": other_weights_a_pass},
        "gate_without_its_running_product": {
            "reference_exit": lambda lam: lam / jnp.sum(lam, axis=0,
                                                        keepdims=True)},
        "entropys_gradient_left_out": {
            "reference_entropy": lambda p: jax.lax.stop_gradient(entropy(p))},
        "unchanged": {"reference_block": block},
    }


# what each fault moves: the last pass's logits (what the shared check
# compares), the exit distribution, or the gradient alone
_MOVES = {"final_norm_left_out_between_passes": "logits",
          "no_norm_after_the_mixer": "logits",
          "weights_not_shared": "logits",
          "gate_without_its_running_product": "exit",
          "entropys_gradient_left_out": "gradient", "unchanged": None}


class Ouro(Family):
    """benchmark/rehearsal/configs/tiny-ouro.json: two dense layers (4 heads
    of 32, every column rotated as halves at theta 1e6, a gated MLP of 256)
    under four norm scales a layer, run 4 times a step over the same weights
    with the final norm inside the loop, an untied head over 512 ids that
    reads every pass, an exit gate [128, 1] with a bias, beta 0.1."""

    name, tiny, cell = "ouro", "tiny-ouro", "ouro-2.6b"
    workload = "ouro26_train_1chip"

    logits_atol, grads_atol = 5e-5, 2e-5

    def opinion(self, jax, cfg, params):
        """Norm scales and a gate's bias that differ from their seeds, so
        that each of the five norms and the bias is seen."""
        scales = [params["final_norm"]] + [
            layer[name] for layer in params["layers"]
            for name in ("ln1", "ln1_after", "ln2", "ln2_after")]
        for i, norm in enumerate(scales):
            norm["scale"] = 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(200 + i), norm["scale"].shape)
        params["exit_gate"]["b"] = params["exit_gate"]["b"] - 0.5

    def built(self, cfg, params):
        from ray_tpu.models.gpt import Loop
        assert cfg.loop == Loop(4, 0.1) and cfg.norm_after == "both"
        assert [sorted(layer) for layer in params["layers"]] == [
            ["attn", "ln1", "ln1_after", "ln2", "ln2_after", "mlp"]] * 2
        assert params["exit_gate"]["w"].shape == (128, 1)
        assert params["exit_gate"]["b"].shape == (1,)
        assert sorted(params) == ["embed", "exit_gate", "final_norm",
                                  "layers", "lm_head"]

    def reference_more(self, jax, params, tokens, config):
        """Every pass's logits, the exit distribution, each pass's plain
        mean cross-entropy."""
        import jax.numpy as jnp
        ouro = self.module
        logits, p = jax.jit(lambda pr, t: ouro.reference_logits_passes(
            pr, t[:, :-1], config))(params, tokens)
        logp = jax.nn.log_softmax(logits, -1)
        xent = -jnp.mean(jnp.take_along_axis(
            logp, tokens[None, :, 1:, None], -1), (1, 2, 3))
        return logits, p, xent

    def statistics(self, aux, loss, reference):
        _logits, p, xent = reference[3]
        passes = p.shape[0]
        np.testing.assert_allclose(
            [aux[f"xent_pass_{t + 1}"] for t in range(passes)], xent,
            rtol=1e-5)
        np.testing.assert_allclose(
            [aux[f"exit_p_{t + 1}"] for t in range(passes)],
            np.mean(p, (1, 2)), atol=1e-6)
        assert float(aux["xent"]) == float(aux[f"xent_pass_{passes}"])
        assert float(loss) != float(aux["xent"])
        h = -np.sum(p * np.log(p), 0).mean()
        np.testing.assert_allclose(aux["exit_entropy"], h, rtol=1e-5)
        np.testing.assert_allclose(
            aux["exit_expected_passes"],
            sum((t + 1) * np.mean(p[t]) for t in range(passes)), rtol=1e-5)
        # the loss is the objective: sum_t p_t l_t - beta H, a mean
        assert 0.0 < float(aux["exit_entropy"]) < np.log(passes)

    def moves(self, name):
        # every leaf learns: each norm's scale, the gate's column and bias
        return True

    def other_configurations(self, tiny):
        return {"three_passes": dict(tiny, total_ut_steps=3)}

    def faults(self, jax, tiny, params):
        return [(fault, replaced, _MOVES[fault] != "logits")
                for fault, replaced in _faults(self.module).items()]

    bf16_bounds = {"logprob_median_tol": 0.15, "logprob_rms_tol": 0.6,
                   "logprob_p99_tol": 3.0, "exit_p_tol": 0.2}
    bf16_broken = tuple(bf16_bounds)

    cell_params = 444_665_857           # ISSUE 71's 444.7M, a sixth

    def published(self, cell, tiny_tree):
        ouro = self.module
        assert ouro._layer_matrices(cell) + 4 * 2048 == 51_388_416
        published = dict(cell, **cell["published"])
        # ISSUE 71's 2668M: 48 x 51.39M + 2 x 49 152 x 2048 (+ the final
        # norm and the gate)
        assert round(ouro.param_count(published) / 1e6) == 2668
        assert ouro.passes(cell) == ouro.passes(published) == 4

    def rules(self, specs, column, row):
        from jax.sharding import PartitionSpec as P
        layer = specs["layers"][0]
        assert layer["attn"]["wq"] == layer["mlp"]["w_up"] == P(*column)
        assert layer["attn"]["wo"] == layer["mlp"]["w_down"] == P(*row)
        for name in ("ln1_after", "ln2_after"):
            assert layer[name]["scale"] == P(None)
        assert specs["exit_gate"]["w"] == P(None, None)
        assert specs["exit_gate"]["b"] == P(None)

    def sharded_step(self, jax, tiny, twin):
        """The whole looped step on fsdp=2 x tensor=2: the scan over the
        passes under GSPMD, the stream pinned at each layer's and each
        pass's end, the kernels per shard inside the loop's body."""
        steps_agree(jax, self, tiny, twin())

    refusals = [
        case(({"attention": "ring"},
              "looped stack .loop. .*not for attention='ring'"), "ring"),
        case((lambda: {"loop": _loop(passes=0)},
              r"loop .*expected passes >= 1"), "no_pass"),
        case((lambda: {"mtp": _module()},
              "loop runs the final norm inside every pass.*mtp"),
             "prediction_module"),
        case(({"norm_after": "neither"},
              r"norm_after='neither': expected False \| True \| 'both'"),
             "norm_form"),
    ]
    pipeline_refusals = [
        case(({}, {"pipeline": 2},
              r"exit gate \(GPTConfig.loop=Loop\(passes=4.*circular schedule"),
             "looped")]

    def scopes(self, names, regions):
        from ray_tpu.util import profiling
        assert {"exit_gate", "head", "norm", "attn_core", "attn_proj",
                "attn_out", "mlp", "embed"} <= regions
        # the loop is one body: the layers' ops lie inside a while
        assert any("/while/body/" in n and "mlp" in n for n in names)
        gate = [n for n in names if re.search("[(/]exit_gate[)/]", n)]
        assert len(gate) > 4
        for n in gate:
            assert profiling._last_of(n, profiling.REGIONS) == "exit_gate", n

    reduced = {"num_hidden_layers", "layer_types", "vocab_size"}

    def cut(self, cell, row, bench):
        catalog = row["config"]
        assert cell["layer_types"] == catalog["layer_types"][:8] \
            == ["full_attention"] * 8
        # no width, no head count and not the number of passes moves
        for key in ("hidden_size", "intermediate_size", "head_dim",
                    "num_attention_heads", "num_key_value_heads",
                    "total_ut_steps", "rope_theta"):
            assert cell[key] == catalog[key]
        share = cell["share"]
        assert share["pipeline_stages"] * cell["num_hidden_layers"] \
            == share["num_hidden_layers"] == 48
        assert share["vocabulary_slices"] * cell["vocab_size"] \
            == share["vocab_size"] == 49152
        assert {"sequence_length", "exit_entropy_coef", "exit_gate",
                "final_norm_inside_the_loop", "four_norms_a_layer",
                "embedding_init_std", "learning_rate"} <= set(cell["assumed"])
        assert "7.11 GB" in cell["deployment"]
        assert len(cell["departures"]) >= 3

    # ouro26_train_1chip (2 x 4096 tokens): 8 layers of 16 heads of 128 in
    # ONE loop body, so each flash kernel stands once a layer in the text
    # though it runs four times a step (32 would be the passes unrolled); q,
    # k, v through `rope_split` forward and recomputed, `rope_merge` thrice
    # a layer backward, as olmoe's layer
    cell_kernel_calls = {"flash_fwd": 8, "flash_bwd_dq": 8,
                         "flash_bwd_dkv": 8, "rope_split": 48,
                         "rope_merge": 24, "embed_grad": 1}
    cell_memory_share = (0.6, 0.95)
    # the loop's row of `_working_set` reads 11.52 GB where the step compiles
    # to 12.29 (+ OVERHEAD 12.71) and the chip reads 12.55 (74.239 %, ledger
    # PR 72): the chip's compiler hoists the bf16 casts of every layer's
    # matrices out of both loops, which the row does not count. It decides
    # nothing (a looped stack's rung is 0 by rule): ROADMAP D29
    cell_reckoned = (1.19, 0.0)


def _loop(**change):
    from ray_tpu.models.gpt import Loop
    return Loop(**dict({"passes": 4, "entropy_coef": 0.1}, **change))


def _module():
    from ray_tpu.models.gpt import PredictionModule
    return PredictionModule(("attention",))


FAMILY = Ouro()


# ---------------------------------------------------------------------------
# Every pass, the exit distribution, and the faults that move no last logit
# ---------------------------------------------------------------------------

def test_every_passes_logits_and_the_exit_distribution_match(jax_cpu, seeded,
                                                             reference):
    """gpt_forward_passes in float32 against the reference: EVERY pass's
    logits and p_t to 5e-5; p sums to one over the passes; the forward
    serving reads (gpt_forward) is the last pass's."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_forward, gpt_forward_passes
    cfg, params, tokens = seeded("flash")
    want, want_p, _ = reference[3]
    with jax.default_matmul_precision("highest"):
        (logits, p), last = jax.jit(lambda pr, t: (
            gpt_forward_passes(pr, t, cfg), gpt_forward(pr, t, cfg)[0]))(
                params, tokens[:, :-1])
    np.testing.assert_allclose(logits, want, atol=5e-5)
    np.testing.assert_allclose(p, want_p, atol=5e-5)
    np.testing.assert_allclose(np.sum(p, 0), 1.0, atol=1e-6)
    np.testing.assert_array_equal(last, logits[-1])
    # the passes differ: a loop that ran the stack once would not
    assert float(np.abs(logits[0] - logits[-1]).max()) > 1e-2


def test_the_reference_tells_a_gate_and_an_entropy_fault_apart(jax_cpu, tiny,
                                                               seeded,
                                                               reference):
    """The two faults that leave every logit alone: the gate's products
    without the 1 - lam factors move p (and the loss); the entropy's
    gradient left out moves nothing of the forward and the gate's gradient
    by beta dH / dw."""
    jax = jax_cpu
    ouro = FAMILY.module
    _cfg, params, tokens = seeded("reference")
    _logits, loss, grads, (_, p, _) = reference

    def loss_and_grads():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda pr: ouro.reference_loss(pr, tokens, tiny)))(params)
    faults = _faults(ouro)
    with patched(ouro, **faults["gate_without_its_running_product"]):
        with jax.default_matmul_precision("highest"):
            _, other_p = jax.jit(lambda pr, t: ouro.reference_logits_passes(
                pr, t[:, :-1], tiny))(params, tokens)
        assert float(np.abs(other_p - p).max()) > 0.05
    with patched(ouro, **faults["entropys_gradient_left_out"]):
        same_loss, other = loss_and_grads()
    assert abs(float(same_loss) - float(loss)) < 1e-6
    moved = float(np.abs(other["exit_gate"]["w"]
                         - grads["exit_gate"]["w"]).max())
    assert moved > 10 * FAMILY.grads_atol * max(
        1.0, float(np.abs(grads["exit_gate"]["w"]).max()))


# ---------------------------------------------------------------------------
# Three tests only this family has
# ---------------------------------------------------------------------------

def test_a_shared_weights_gradient_is_the_sum_over_the_passes(jax_cpu, tiny,
                                                              seeded,
                                                              programmed):
    """(a) The program's gradient of every layer's every weight equals the
    SUM of the four gradients an untied 4 x N-layer reference gives its
    copies of it (the reference run on a tree that holds the N layers four
    times over: pass t differentiates its own copy)."""
    jax = jax_cpu
    ouro = FAMILY.module
    _cfg, params, tokens = seeded("flash")
    _logits, (_loss, grads) = programmed("flash")
    passes, n = ouro.passes(tiny), tiny["num_hidden_layers"]
    untied = dict(params, layers=params["layers"] * passes)
    with jax.default_matmul_precision("highest"):
        copies = jax.jit(jax.grad(
            lambda pr: ouro.reference_loss(pr, tokens, tiny)))(untied)
    for i in range(n):
        summed = jax.tree_util.tree_map(
            lambda *g: sum(g), *(copies["layers"][t * n + i]
                                 for t in range(passes)))
        one = copies["layers"][i]
        for (path, g), s, first in zip(
                jax.tree_util.tree_flatten_with_path(grads["layers"][i])[0],
                jax.tree_util.tree_leaves(summed),
                jax.tree_util.tree_leaves(one)):
            bound = FAMILY.grads_atol * max(1.0, float(np.abs(s).max()))
            np.testing.assert_allclose(g, s, atol=bound,
                                       err_msg=jax.tree_util.keystr(path))
            # and a copy's own gradient is not the sum
            if np.asarray(s).ndim == 2:
                assert float(np.abs(np.asarray(first) - s).max()) > bound


def test_the_step_holds_each_layers_kernels_once(jax_cpu, tiny):
    """(b) The passes are ONE traced body: the gradient of the loss holds
    each flash kernel once a LAYER (2 here), inside the scan over the
    passes, not once a layer and pass (8); forward kept through the remat
    (never in the recompute pass), `rope_split` forward and recomputed."""
    cfg, calls, jaxpr = step_kernel_calls(jax_cpu, FAMILY, tiny)
    layers = tiny["num_hidden_layers"]
    assert cfg.loop.passes == 4 and cfg.remat_policy == "full"
    assert calls[("flash_fwd", False)] == layers
    assert calls[("flash_fwd", True)] == 0
    for kernel in ("flash_bwd_dq", "flash_bwd_dkv"):
        assert calls[(kernel, True)] + calls[(kernel, False)] == layers
    # and the loop is a scan of 4 trips, forward and backward
    scans = [eqn.params["length"] for eqn in jaxpr.jaxpr.eqns
             if eqn.primitive.name == "scan"]
    assert scans.count(4) >= 2, scans


@pytest.mark.parametrize("rows", [256, 200])
def test_the_loss_takes_a_weight_a_token(jax_cpu, rows):
    """(c) chunked_xent and chunked_xent_recompute under a 0/1 weight give
    what they give without the third result, bit for bit (values and
    gradients); under a float weight the two agree with each other, the
    weighted sum is the plain log_softmax's, the third result is the rows'
    own loss, and d / d weight is the token's loss in both. 200 rows: two
    chunks of 128, the second padded."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import chunked_xent, chunked_xent_recompute
    k = jax.random.split(jax.random.PRNGKey(rows), 4)
    x = jax.random.normal(k[0], (rows, 64), jnp.float32)
    w = jax.random.normal(k[1], (64, 384), jnp.float32) * 0.2
    targets = jax.random.randint(k[2], (rows,), 0, 384)
    kept = jnp.ones((rows,), jnp.float32).at[3].set(0.0)
    weight = jax.random.uniform(k[3], (rows,)) * kept
    nll = -jnp.take_along_axis(jax.nn.log_softmax(x @ w, -1),
                               targets[:, None], -1)[:, 0]

    def sums(fn, mask, **more):
        def total(x, w, mask):
            out = fn(x, w, targets, mask, 128, **more)
            return out[0] + 0.5 * out[1], out
        (_, out), grads = jax.jit(jax.value_and_grad(
            total, argnums=(0, 1, 2), has_aux=True))(x, w, mask)
        return out, grads
    for fn in (chunked_xent, chunked_xent_recompute):
        today, today_grads = sums(fn, kept)
        with_plain, grads = sums(fn, kept, plain=True)
        assert len(today) == 2 and len(with_plain) == 3
        for a, b in zip(today + today_grads, with_plain[:2] + grads):
            np.testing.assert_array_equal(a, b)
    (total, denom, plain), (gx, gw, gm) = sums(chunked_xent, weight,
                                               plain=True)
    again, (rx, rw, rm) = sums(chunked_xent_recompute, weight, plain=True)
    for a, b in zip((total, denom, plain), again):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(total, jnp.sum(weight * nll), rtol=1e-5)
    np.testing.assert_allclose(denom, jnp.sum(weight), rtol=1e-6)
    np.testing.assert_allclose(plain, nll, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gx, rx, atol=1e-5)
    np.testing.assert_allclose(gw, rw, atol=1e-4)
    for g in (gm, rm):
        # d (total + denom / 2) / d weight: the token's loss, and a half
        np.testing.assert_allclose(g, nll + 0.5, rtol=1e-5, atol=1e-5)
    # the rows' plain loss carries no gradient
    gp = jax.grad(lambda x: jnp.sum(chunked_xent(
        x, w, targets, weight, 128, True)[2]))(x)
    assert not np.any(np.asarray(gp))


def test_flops_count_every_pass_and_the_reckoning_every_kept_copy():
    """train_flops_per_token counts the layers, attention and the head T
    times (once would read a quarter of the truth, the parameters T times
    over would read past it), the flash kernels' shape is olmoe's, and
    memory.reckoned_peak holds every gradient across a loop under all that
    the passes keep."""
    from benchmark.kernels import flash_attention
    from ray_tpu.parallel import memory
    ouro = FAMILY.module
    cell = read("benchmark", "configs", "ouro-2.6b.json")
    mix = read("benchmark", "traffic", "train_b2_s4096_dp.json")
    d, s = 2048, 4096
    active = 8 * (4 * d * d + 3 * d * 5632) + d * 8192
    assert active == pytest.approx(427.8e6, rel=1e-3)
    once = 6.0 * active + 3.0 * 8 * 16 * 2 * 128 * s
    assert ouro.train_flops_per_token(cell, s) == 4 * once
    assert ouro.train_flops_per_token(dict(cell, total_ut_steps=1), s) == once
    assert ouro.forward_flops_per_token(cell, s) * 3 == 4 * once
    assert ouro.attention_call(cell, mix) == {
        "batch": 2, "heads": 16, "seq": s, "head_dim": 128}
    olmoe = read("benchmark", "configs", "olmoe-1b-7b.json")
    assert flash_attention.flash_fwd(cell, mix) \
        == flash_attention.flash_fwd(olmoe, mix)
    peak = memory.reckoned_peak
    # one pass: the walk; four: every gradient under four times the kept
    assert peak(100, 5, [10, 10], 7, [6, 6], 3, 2) \
        == 100 + memory.OVERHEAD + max(32, 14, 25 + 6 + 3, 15 + 12 + 3)
    assert peak(100, 5, [10, 10], 7, [6, 6], 3, 2, passes=4) \
        == 100 + memory.OVERHEAD + 25 + 4 * 12 + 3
