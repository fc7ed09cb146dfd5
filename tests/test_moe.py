"""Sparse experts (models/gpt.py's _moe_block over ops/moe.py) against the
plain float32 reference of benchmark/families/olmoe.py, at a small OLMoE on
the CPU: seeded random weights, the kernels in interpret mode; and
olmoe_train_1chip's whole step compiled for a described chip (imported)."""

import os

import numpy as np
import pytest

from helpers.described_chip import cell_step, v5e  # noqa: F401 — fixtures
from helpers.families import ROOT, Family, family  # noqa: F401
from helpers.sparse_block import experts as _experts

class Olmoe(Family):
    """The first sparse family: its checks below predate the shared ones
    (tests/helpers/families.py) and keep their own small configuration; the
    class holds what the cell's compile for a described chip is held to."""

    name, tiny, cell = "olmoe", "tiny-olmoe", "olmoe-1b-7b"
    workload = "olmoe_train_1chip"

    # olmoe_train_1chip (2 x 4096 tokens): one layer, all 64 experts held,
    # so no conditional and every kernel once: 3 grouped matmuls forward, 3
    # recomputed, 3 for the rows' gradients, 3 tgmm; the float32 masters
    # reach `moe_gmm` as they are kept (PR 42). 11.2 GB when this was
    # written: 7.51 of state, 3.7 of temporaries.
    cell_kernel_calls = {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                         "rope_split": 6, "rope_merge": 3, "moe_gmm": 9,
                         "moe_tgmm": 3, "embed_grad": 1}
    cell_memory_share = (0.55, 0.75)


FAMILY = Olmoe()



# a small OLMoE under the keys of benchmark/configs/olmoe-1b-7b.json
SMALL = {
    "family": "olmoe", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": False, "qk_norm": True, "vocab_size": 256,
    "max_position_embeddings": 64, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "router_aux_loss_coef": 0.01,
    "router_z_loss_coef": 0.001,
}
BATCH, SEQ = 4, 24          # 96 tokens x 2: no multiple of a 16-row tile


def _small(jax, dtype):
    from benchmark.families import olmoe
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    cfg = GPTConfig(**olmoe.gpt_config_kwargs(SMALL), dtype=dtype,
                    attention="reference", remat_policy="none")
    params = gpt_init(jax.random.PRNGKey(3), cfg)
    # a router with an opinion: at the init's 0.02 every probability is 1/8
    for i, layer in enumerate(params["layers"]):
        layer["moe"]["router"] = jax.random.normal(
            jax.random.PRNGKey(100 + i), layer["moe"]["router"].shape)
    tokens = np.random.default_rng(5).integers(
        0, SMALL["vocab_size"], (BATCH, SEQ + 1), dtype=np.int32)
    return cfg, params, jax.numpy.asarray(tokens)


@pytest.fixture(scope="module")
def reference(jax_cpu):
    """The reference's logits, loss and gradients, float32 at full matmul
    precision."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import olmoe
    _cfg, params, tokens = _small(jax, jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: olmoe.reference_logits(
            p, t[:, :-1], SMALL))(params, tokens)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: olmoe.reference_loss(p, t, SMALL)))(params, tokens)
    return logits, loss, grads


# (a) logits and loss. bfloat16 (activations of 8 bits of mantissa through
# two layers into logits of size ~1): a token whose second and third expert
# lie within a rounding of each other goes to another expert than in the
# reference and its logits then differ by up to ~2, so the bound is on the
# typical token (the 90th percentile of a token's largest logit error: 0.09
# measured, 0.2 allowed), on the mean error (0.023 measured, 0.05 allowed)
# and on the loss (2.6e-3 measured, 1e-2 allowed). A float32 step misses
# its own bound by three orders of magnitude when computed in bfloat16.
@pytest.mark.parametrize("dtype,typical_tol,mean_tol,loss_tol", [
    ("float32", 2e-5, 2e-5, 1e-5), ("bfloat16", 0.2, 0.05, 1e-2)])
def test_logits_and_loss_match_the_reference(jax_cpu, reference, dtype,
                                             typical_tol, mean_tol, loss_tol):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import gpt_forward, gpt_loss
    cfg, params, tokens = _small(jax, jnp.dtype(dtype))
    ref_logits, ref_loss, _ = reference
    with jax.default_matmul_precision("highest"):
        logits, loss = jax.jit(lambda p, t: (
            gpt_forward(p, t[:, :-1], cfg)[0],
            gpt_loss(p, {"tokens": t}, cfg)))(params, tokens)
    error = np.abs(np.asarray(logits.astype(jnp.float32) - ref_logits))
    assert np.quantile(error.max(-1), 0.9) < typical_tol
    assert error.mean() < mean_tol
    if dtype == "float32":
        assert error.max() < typical_tol
    assert abs(float(loss) - float(ref_loss)) < loss_tol


# (b) gradients of the whole loss, router losses included
@pytest.fixture(scope="module")
def loss_aux_grads(jax_cpu):
    """((loss, aux), grads) of the program in float32, as a user's own step
    would take them: jax.value_and_grad(..., has_aux=True)."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import gpt_loss_and_aux
    cfg, params, tokens = _small(jax, jnp.float32)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, t: gpt_loss_and_aux(p, {"tokens": t}, cfg),
            has_aux=True))(params, tokens)


def test_gradients_match_the_reference(jax_cpu, reference, loss_aux_grads):
    jax = jax_cpu
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(loss_aux_grads[1])
    ref = jax.tree_util.tree_leaves(reference[2])
    assert len(flat) == len(ref)
    for (path, g), r in zip(flat, ref):
        scale = float(jnp.max(jnp.abs(r))) + 1e-8
        assert float(jnp.max(jnp.abs(g - r))) < 1e-4 * scale + 1e-7, \
            jax.tree_util.keystr(path)


def test_loss_and_aux_returns_the_routing_statistics(loss_aux_grads):
    (loss, aux), _grads = loss_aux_grads
    assert set(aux) == {"xent", "router_balance_loss", "router_z_loss",
                        "expert_load_max_over_mean",
                        "expert_slots_held_share", "expert_rows_bounded"}
    assert aux["expert_slots_held_share"] == 1.0    # all the experts held
    assert aux["expert_rows_bounded"] == 1.0        # so nothing to bound
    np.testing.assert_allclose(
        loss, aux["xent"] + 0.01 * aux["router_balance_loss"]
        + 0.001 * aux["router_z_loss"], rtol=1e-6)
    assert float(aux["expert_load_max_over_mean"]) >= 1.0


# (c) dispatch alone against the masked dense form: no token dropped
def _dense_experts(x, weights, idx, w_gate, w_up, w_down):
    import jax
    import jax.numpy as jnp
    e = w_gate.shape[0]
    mask = jnp.sum(jax.nn.one_hot(idx, e) * weights[..., None], axis=-2)
    act = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, w_gate)) \
        * jnp.einsum("bsd,edf->bsef", x, w_up)
    return jnp.einsum("bsef,efd,bse->bsd", act, w_down, mask)


def _routing(case, tokens, e, k, rng):
    if case == "all_to_one":          # every token's k slots to expert 5
        return np.full((tokens, k), 5, np.int32)
    if case == "experts_without_a_token":   # only experts 1 and 6 are used
        return np.tile(np.array([[1, 6]], np.int32), (tokens, 1))
    return np.argsort(rng.random((tokens, e)), axis=1)[:, :k].astype(np.int32)


@pytest.mark.parametrize("case,batch,seq", [
    ("all_to_one", 2, 24), ("experts_without_a_token", 2, 24),
    ("ragged", 3, 7),                 # 42 slots: no multiple of any tile
    ("ragged", 1, 1)])
def test_dispatch_equals_masked_dense(jax_cpu, case, batch, seq):
    jax = jax_cpu
    import jax.numpy as jnp
    e, k, d, f = 8, 2, 32, 16
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((batch, seq, d)), jnp.float32)
    idx = jnp.asarray(_routing(case, batch * seq, e, k, rng)).reshape(
        batch, seq, k)
    weights = jnp.asarray(rng.random((batch, seq, k)), jnp.float32)
    mats = [jnp.asarray(rng.standard_normal(s) / 4, jnp.float32)
            for s in ((e, d, f), (e, d, f), (e, f, d))]

    def both(fn):
        loss = lambda x, w, *m: jnp.sum(jnp.sin(fn(x, w, idx, *m)))  # noqa: E731
        return jax.jit(lambda *a: (
            fn(a[0], a[1], idx, *a[2:]),
            jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*a)))(x, weights, *mats)
    with jax.default_matmul_precision("highest"):
        y, grads = both(_experts)
        y_dense, grads_dense = both(_dense_experts)
    # equal up to the order of float32 sums (a matrix's gradient adds up to
    # 48 rows here): a dropped or doubled slot would be a term of size ~1
    np.testing.assert_allclose(y, y_dense, rtol=1e-5, atol=1e-5)
    for g, g_dense in zip(grads, grads_dense):
        np.testing.assert_allclose(g, g_dense, rtol=1e-4, atol=1e-4)


def test_plan_holds_every_slot_once(jax_cpu):
    """Under any routing each of the T x k token-slots owns exactly one row,
    in a tile of its own expert, and the rest of the rows are padding."""
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    rng = np.random.default_rng(2)
    for case in ("all_to_one", "experts_without_a_token", "ragged"):
        idx = _routing(case, 37, 8, 2, rng)
        plan = moe.plan_dispatch(jnp.asarray(idx), 8, 8)
        row_slot = np.asarray(plan.row_slot)
        held = row_slot[row_slot < idx.size]
        assert sorted(held) == list(range(idx.size))
        rows = np.asarray(plan.token_rows).reshape(-1)
        assert (row_slot[rows] == np.arange(idx.size)).all()
        assert (np.asarray(plan.tile_group)[rows // 8]
                == idx.reshape(-1)).all()
        assert int(plan.tiles_used[0]) * 8 >= rows.max() + 1


@pytest.mark.parametrize("slots,experts,dtype,rows", [
    (65536, 64, "bfloat16", 256),     # olmoe_train_1chip
    # solar2_train_1chip's share (8192 x 8 slots, 8 of 320 experts held): a
    # mean group of 204 rows fills the MXU's 128, so no tile is under them
    (1638, 8, "bfloat16", 128),
    # a mean group of at most 128 rows keeps the quarter
    (800, 8, "bfloat16", 16), (1024, 8, "bfloat16", 32),
    (192, 8, "float32", 8), (192, 8, "bfloat16", 16), (8, 4, "float32", 8)])
def test_tile_rows_follow_from_the_shape(slots, experts, dtype, rows):
    from ray_tpu.ops import moe
    assert moe.tile_rows(slots, experts, dtype) == rows


# (d) the k probabilities are used as they come; the balance loss counts
# all k choices (the block before PR 27 renormalised, and counted the first)
def test_router_keeps_probabilities_and_counts_every_choice(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import _route
    cfg, params, _ = _small(jax, jnp.float32)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (2, 16, cfg.d_model))
    m = params["layers"][0]["moe"]
    weights, idx, stats = _route(m, x, cfg)
    logits = np.asarray(x.reshape(32, -1) @ m["router"], np.float64)
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=1)[:, :2]
    np.testing.assert_array_equal(idx.reshape(32, 2), order)
    np.testing.assert_allclose(weights.reshape(32, 2),
                               np.take_along_axis(probs, order, 1), rtol=1e-5)
    assert float(jnp.max(jnp.sum(weights, -1))) < 0.999   # not renormalised
    load = np.bincount(order.reshape(-1), minlength=8) / 32.0
    first = np.bincount(order[:, 0], minlength=8) / 32.0
    balance = 8 * np.sum(load * probs.mean(0))
    assert abs(balance - 8 * np.sum(first * probs.mean(0))) > 0.1
    np.testing.assert_allclose(stats["router_balance_loss"], balance,
                               rtol=1e-5)
    np.testing.assert_allclose(
        stats["router_z_loss"],
        np.mean(np.log(np.exp(logits).sum(-1)) ** 2), rtol=1e-5)
    np.testing.assert_allclose(stats["expert_load_max_over_mean"],
                               load.max() * 8 / 2, rtol=1e-6)


# (e) one step under a mesh equals the one-device step
@pytest.fixture(scope="module")
def one_device_step(jax_cpu):
    return _one_step(jax_cpu, "dp", {"data": 1})


def _one_step(jax, strategy, axes):
    import jax.numpy as jnp
    import optax
    from ray_tpu.models.gpt import gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import init_train_state, make_train_step
    cfg, _params, tokens = _small(jax, jnp.float32)
    mesh = build_mesh(MeshConfig(**axes),
                      devices=jax.devices()[:int(np.prod(list(axes.values())))])
    strategy = strategy_from_name(strategy)
    optimizer = optax.sgd(0.1)
    state = init_train_state(lambda: gpt_init(jax.random.PRNGKey(3), cfg),
                             optimizer, mesh, strategy)
    step = make_train_step(
        lambda p, b: gpt_loss(p, b, cfg, mesh=mesh,
                              act_sharding=strategy.activation_sharding(mesh)),
        optimizer, mesh, strategy, sample_params=state.params)
    with jax.default_matmul_precision("highest"):
        state, metrics = step(state, {"tokens": tokens})
    return float(metrics["loss"]), jax.device_get(state.params)


@pytest.mark.parametrize("strategy,axes", [
    ("tp", {"data": 2, "expert": 4}), ("fsdp", {"data": 2, "fsdp": 2})])
def test_sharded_step_equals_one_device(jax_cpu, one_device_step, strategy,
                                        axes):
    jax = jax_cpu
    loss, params = _one_step(jax, strategy, axes)
    ref_loss, ref_params = one_device_step
    assert abs(loss - ref_loss) < 1e-5
    for (path, p), r in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_allclose(p, r, rtol=1e-4, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


# (f) flash attention at OLMoE's head width and length: two major blocks a
# row under _block_sizes' 2048 cap
def test_block_sizes_at_head_width_128_and_4096_positions():
    from ray_tpu.ops.attention import _block_sizes
    blocks = _block_sizes(4096, 4096, 128)
    assert blocks.fwd == (2048, 2048, 256)
    assert blocks.dq == blocks.dkv == (2048, 2048, 128)


def test_flash_with_two_major_blocks_a_row_at_head_width_128(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 1, 4096, 128),
                                 jnp.float32) for i in range(3))
    out = flash_attention(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-4


# the satellites: sharding rules, initialisation, kernel names
@pytest.mark.parametrize("strategy,gate_up,down", [
    ("tp", ("expert", None, "tensor"), ("expert", "tensor", None)),
    ("tp_fsdp", ("expert", "fsdp", "tensor"), ("expert", "tensor", "fsdp"))])
def test_every_moe_leaf_gets_its_rule(jax_cpu, strategy, gate_up, down):
    jax = jax_cpu
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ray_tpu.parallel.sharding import _path_str, strategy_from_name
    _cfg, params, _ = _small(jax, jnp.float32)
    rules = strategy_from_name(strategy).param_rules
    want = {"w_gate": P(*gate_up), "w_up": P(*gate_up), "w_down": P(*down),
            "router": P(None, None)}
    seen = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = _path_str(path)
        if "/moe/" in name:
            seen.add(name.rsplit("/", 1)[1])
            assert rules.spec_for(name, leaf.shape) == want[
                name.rsplit("/", 1)[1]], name
    assert seen == set(want)


def test_experts_are_initialised_at_their_fan_in(jax_cpu):
    jax = jax_cpu
    from benchmark.families import olmoe
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    wide = dict(SMALL, hidden_size=256, intermediate_size=128,
                num_hidden_layers=1, num_attention_heads=4)
    cfg = GPTConfig(**olmoe.gpt_config_kwargs(wide))
    layer = gpt_init(jax.random.PRNGKey(0), cfg)["layers"][0]
    for name, fan_in in (("w_gate", 256), ("w_up", 256)):
        std = float(np.std(np.asarray(layer["moe"][name])))
        assert abs(std * np.sqrt(fan_in) - 1.0) < 0.02, name
    assert float(np.std(np.asarray(layer["moe"]["w_down"]))) \
        == pytest.approx(1.0 / np.sqrt(2 * 128), rel=0.02)
    for name in ("q_norm", "k_norm"):
        assert (np.asarray(layer["attn"][name]["scale"]) == 1.0).all()


@pytest.mark.parametrize("kernel", ["moe_gmm", "moe_tgmm"])
def test_grouped_matmul_kernels_carry_their_names(jax_cpu, kernel):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    from ray_tpu.util.profiling import KERNELS
    plan = moe.plan_dispatch(jnp.zeros((8, 1), jnp.int32), 2, 8)
    x = jnp.zeros((plan.row_slot.shape[0], 16))
    w = jnp.zeros((2, 16, 16))
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda x, w: moe.grouped_matmul(x, w, plan).sum(), argnums=(0, 1)))(
            x, w))
    assert kernel in KERNELS and f"name={kernel}" in jaxpr


# The matrices in the type they are kept in: a float32 master under
# bfloat16 rows is rounded a block at a time inside `moe_gmm`, and its
# gradient comes back as float32, widened from `moe_tgmm`'s rounding of its
# accumulator. Every case is the bits of `w.astype(bfloat16)` ahead of the
# call with the gradient widened after it.
# (routing of 48 tokens x 2 over 4 groups in 16-row tiles, held groups or
# None for all of them, tiles of the row space or None for every slot's)
def _masters_case(case):
    rng = np.random.default_rng(17)
    if case == "a_group_of_several_tiles":    # 80 slots of expert 2: 5 tiles
        idx = np.where(rng.random((48, 2)) < 0.8, 2,
                       rng.integers(0, 4, (48, 2)))
        return idx.astype(np.int32), False, None
    if case == "groups_of_one_tile":          # 3 slots an expert
        return (np.arange(12, dtype=np.int32).reshape(6, 2) % 4), False, None
    if case == "unused_tiles_at_the_end":     # 10 tiles laid out, 4 used
        return np.tile(np.array([[0, 3]], np.int32), (4, 1)), False, 10
    # a share: experts 0..3 of 16 held, the bounded row space
    return rng.integers(0, 16, (48, 2)).astype(np.int32), True, 8


@pytest.mark.parametrize("case", [
    "a_group_of_several_tiles", "groups_of_one_tile",
    "unused_tiles_at_the_end", "a_shares_partial_order"])
@pytest.mark.parametrize("kdim,n", [(64, 256), (256, 64)],
                         ids=["gate_up", "down"])
def test_float32_masters_give_the_bits_of_their_bfloat16_copies(
        jax_cpu, monkeypatch, kdim, n, case):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    # blocks of 128 columns: two column blocks of the wide side, forward
    # (gate_up) and in the rows' gradient (down)
    monkeypatch.setattr(moe, "_WEIGHT_BLOCK_BYTES", 0)
    idx, partial, tiles = _masters_case(case)
    order = moe.order_slots(jnp.asarray(idx), 4, 16, partial=partial)
    plan = moe.lay_out(order, 16, tiles or moe._every_slot(idx.size, 4, 16))
    used = int(plan.tiles_used[0])
    assert used <= plan.tile_group.shape[0]
    if case == "a_group_of_several_tiles":
        assert int(np.sum(np.asarray(plan.tile_group)[:used] == 2)) >= 4
    if case == "unused_tiles_at_the_end":
        assert used < plan.tile_group.shape[0] - 2
    rng = np.random.default_rng(23)
    x = moe.dispatch(jnp.asarray(rng.standard_normal((idx.shape[0], kdim)),
                                 jnp.bfloat16), plan)
    w = jnp.asarray(rng.standard_normal((4, kdim, n)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((x.shape[0], n)), jnp.bfloat16)

    def run(x, w):
        y, pull = jax.vjp(
            lambda x, w: moe.grouped_matmul(x, w, plan, interpret=True), x, w)
        return (y,) + pull(g)
    y, dx, dw = jax.jit(run)(x, w)
    y_copy, dx_copy, dw_copy = jax.jit(run)(x, w.astype(jnp.bfloat16))
    assert dw.dtype == jnp.float32 and dw_copy.dtype == jnp.bfloat16
    rows = used * 16        # the tiles past the last used one are not written
    for ours, copys in ((y[:rows], y_copy[:rows]), (dx[:rows], dx_copy[:rows]),
                        (dw, dw_copy.astype(jnp.float32))):
        assert ours.dtype == copys.dtype
        np.testing.assert_array_equal(
            np.asarray(ours.astype(jnp.float32)),
            np.asarray(copys.astype(jnp.float32)))
    assert float(jnp.abs(dw).sum()) > 0 and float(jnp.abs(y[:rows]).sum()) > 0


@pytest.mark.parametrize("held", [None, (4, 16)],
                         ids=["all_the_experts", "a_share"])
def test_the_sparse_block_on_masters_is_the_block_on_their_copies(jax_cpu,
                                                                  held):
    """`_experts` whole (dispatch, the three grouped matmuls with SwiGLU
    between, the weighted return; for a share through `in_row_space`'s
    conditional and its recomputing backward): float32 masters in, the
    value, the rows' and the weights' gradients and the three matrices'
    gradients are those of bfloat16 copies made ahead of it, bit for bit."""
    jax = jax_cpu
    import jax.numpy as jnp
    e, of, k, d, f = 4, 4 if held is None else held[1], 2, 128, 256
    rng = np.random.default_rng(29)
    x = jnp.asarray(rng.standard_normal((2, 48, d)), jnp.bfloat16)
    idx = jnp.asarray(np.argsort(rng.random((2, 48, of)), axis=2)[..., :k]
                      .astype(np.int32))
    weights = jnp.asarray(rng.random((2, 48, k)), jnp.float32)
    mats = [jnp.asarray(rng.standard_normal(s) / 8, jnp.float32)
            for s in ((e, d, f), (e, d, f), (e, f, d))]

    def run(x, weights, *mats):
        def block(x, weights, *mats):
            y = _experts(x, weights, idx, *mats, held=held)
            return y if held is None else y[0]
        y, pull = jax.vjp(block, x, weights, *mats)
        return (y,) + pull(jnp.ones_like(y))
    ours = jax.jit(run)(x, weights, *mats)
    copies = jax.jit(run)(x, weights, *[w.astype(jnp.bfloat16) for w in mats])
    assert all(g.dtype == jnp.float32 for g in ours[3:])
    assert all(g.dtype == jnp.bfloat16 for g in copies[3:])
    for a, b in zip(ours, copies):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))
        assert float(jnp.abs(a.astype(jnp.float32)).sum()) > 0


def _kernel_calls(jax, jaxpr, found=None):
    """{kernel name: [scratch operands of each call]} of a jaxpr, through
    its sub-jaxprs but not into the kernels' own."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.setdefault(eqn.params["name"], []).append(
                eqn.params["grid_mapping"].num_scratch_operands)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(jax, sub, found)
    return found


@pytest.mark.parametrize("matrices,scratch", [("bfloat16", 0),
                                              ("float32", 3)])
def test_matrices_of_the_rows_type_run_the_kernel_as_it_was(jax_cpu, matrices,
                                                            scratch):
    """No rounded block where there is nothing to round: bfloat16 matrices
    under bfloat16 rows (a model kept in bfloat16) get `moe_gmm` without a
    scratch, forward and in the rows' gradient; float32 masters get the
    block as it is kept, its rounded copy and the fetch's semaphore."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    plan = moe.plan_dispatch(jnp.zeros((8, 1), jnp.int32), 2, 16)
    x = jnp.zeros((plan.row_slot.shape[0], 16), jnp.bfloat16)
    w = jnp.zeros((2, 16, 16), matrices)
    calls = _kernel_calls(jax, jax.make_jaxpr(jax.grad(
        lambda x, w: moe.grouped_matmul(x, w, plan).astype(
            jnp.float32).sum(), argnums=(0, 1)))(x, w).jaxpr)
    assert calls == {"moe_gmm": [scratch, scratch], "moe_tgmm": [1]}


def _master_casts(jax, jaxpr, shapes, inside=(), found=None):
    """The primitives around every cast of a float32 operand of one of
    `shapes` to bfloat16 in a jaxpr, the kernels' own bodies left out."""
    import jax.numpy as jnp
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        aval = eqn.invars[0].aval if eqn.invars else None
        if (eqn.primitive.name == "convert_element_type"
                and eqn.params["new_dtype"] == jnp.bfloat16
                and aval.dtype == jnp.float32 and aval.shape in shapes):
            found.append(inside)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _master_casts(jax, sub, shapes, inside + (eqn.primitive.name,),
                          found)
    return found


@pytest.mark.parametrize("devices", [1, 2])
@pytest.mark.parametrize("config", ["tiny-olmoe", "tiny-lfm2"])
def test_the_masters_are_cast_only_where_they_are_gathered(jax_cpu, config,
                                                           devices):
    """On one device the step holds no cast of the experts' float32 masters
    to bfloat16 outside the kernels (all the experts held, and a share):
    `moe_gmm` reads them as they are kept. Under a mesh of two the matrices
    are handed whole to every device, and the cast stays ahead of the
    `shard_map`: three a sparse layer, none inside it."""
    import json
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark import model
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs",
                           config + ".json")) as f:
        config = json.load(f)
    program = model.family(config).program(config)
    mesh = build_mesh(MeshConfig(data=devices),
                      devices=jax.devices()[:devices])
    strategy = strategy_from_name("dp")
    params = jax.eval_shape(lambda: program.init(jax.random.PRNGKey(0)))
    sparse = [layer["moe"] for layer in params["layers"] if "moe" in layer]
    masters = [m[name] for m in sparse
               for name in ("w_gate", "w_up", "w_down")]
    assert masters and all(w.dtype == jnp.float32 for w in masters)
    # six rows: no activation has the shape of a stack of matrices
    batch = {"tokens": jax.ShapeDtypeStruct((6, 129), jnp.int32)}
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, b: program.loss(
        p, b, mesh, strategy.activation_sharding(mesh))))(params, batch)
    casts = _master_casts(jax, jaxpr.jaxpr, {w.shape for w in masters})
    if devices == 1:
        assert casts == []
    else:
        # the forward's and, under the layer's remat, the recomputation's
        assert len(casts) >= 3 * len(sparse)
        assert not any("shard_map" in inside for inside in casts)


def test_run_sum_kernel_carries_its_name(jax_cpu):
    """A share's bounded row space sums a token's rows in `moe_run_sum`,
    in combine's forward and in dispatch's backward."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    from ray_tpu.util.profiling import KERNELS
    order = moe.order_slots(jnp.full((32, 4), 7, jnp.int32).at[:, 0].set(1),
                            2, 8, partial=True)
    plan = moe.lay_out(order, 8, 8)
    assert plan.by_token is not None
    z = jnp.zeros((64, 16))
    forward = str(jax.make_jaxpr(
        lambda z, w: moe.combine(z, w, plan))(z, jnp.ones((32, 4))))
    backward = str(jax.make_jaxpr(jax.grad(
        lambda x: moe.dispatch(x, plan).sum()))(jnp.zeros((32, 16))))
    assert "moe_run_sum" in KERNELS
    assert "name=moe_run_sum" in forward and "name=moe_run_sum" in backward


# (g) the family's own half of `correct` (reference_loss's program_check:
# per-token log-probabilities of the step's forward against the reference),
# at the rehearsal size with its bound: the sound program gets the
# reference's loss, and what a first loss at random weights cannot see (a
# renormalised top-k, a dropped token-slot, fp8 weights) gets no number.
# (Only the experts' stacked matrices have more than two dimensions.)
@pytest.mark.parametrize("fault", [
    None, "renormalised_top_k", "dropped_slot", "fp8_weights",
    "fp8_expert_matrices"])
def test_reference_loss_holds_the_program_to_its_logprobs(
        jax_cpu, monkeypatch, fault):
    jax = jax_cpu
    import json
    import jax.numpy as jnp
    from benchmark.families import olmoe
    from ray_tpu.models import gpt
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs",
                           "tiny-olmoe.json")) as f:
        tiny = json.load(f)
    params = jax.jit(olmoe.program(tiny).init)(jax.random.PRNGKey(7))
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        0, tiny["vocab_size"], (4, 129), dtype=np.int32))
    route = gpt._route

    def faulty(m, x, cfg):
        weights, idx, stats = route(m, x, cfg)
        if fault == "renormalised_top_k":
            weights = weights / jnp.sum(weights, -1, keepdims=True)
        else:
            weights = weights.at[..., -1].set(0.0)
        return weights, idx, stats
    if fault in ("renormalised_top_k", "dropped_slot"):
        monkeypatch.setattr(gpt, "_route", faulty)
    # fp8 (e4m3) matrices: everywhere, or the experts' three alone
    low = jax.tree_util.tree_map_with_path(
        lambda path, x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        if x.ndim > (2 if fault == "fp8_expert_matrices" else 1) else x,
        params)

    def check(params, low, tokens):
        logp, _ = olmoe._logprobs_and_router(params, tokens, tiny)
        return (olmoe.reference_loss(params, tokens, tiny), -jnp.mean(logp),
                *olmoe.program_logprob_gap(low, tokens, tiny, logp))
    with jax.default_matmul_precision("highest"):   # as train_cell.py calls it
        loss, xent, median_low, rms_low = map(
            float, jax.jit(check)(params, low, tokens))
    if fault is None:       # the reference's loss: cross-entropy + router's
        assert xent < loss < xent + 0.2
    elif fault == "fp8_weights":
        assert median_low > tiny["program_check"]["logprob_median_tol"]
        assert rms_low > tiny["program_check"]["logprob_rms_tol"]
    elif fault == "fp8_expert_matrices":    # the median's to catch
        assert median_low > tiny["program_check"]["logprob_median_tol"]
    else:
        assert np.isnan(loss)

# Imported last: a module's names are collected in the order they are bound,
# so the chip's compiler gets this file's programs after its own tests have
# run, at another minute of a run than the other families' files.
from helpers.described_chip import (  # noqa: E402,F401
    test_the_cells_that_were_there_lower_to_the_same_step,
    test_cell_step_compiles_under_the_chips_memory,
    test_cell_step_makes_a_heads_dw_where_its_logits_are)
