"""The gated short convolution (ops/short_conv.py), grouped-query attention
in the flash kernels (ops/attention.py), the norm a head, and a stack of two
kinds of layer (models/gpt.py) against the plain float32 reference of
benchmark/families/lfm2.py, at a small size on the CPU: seeded random
weights, the kernels in interpret mode."""

import copy
import hashlib
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    """benchmark/rehearsal/configs/tiny-lfm2.json: conv + dense, then
    attention / conv / conv with experts 4..7 of 16 held, 2 a token; 8
    query heads of 16 on 2 key/value heads."""
    return _read("benchmark", "rehearsal", "configs", "tiny-lfm2.json")


# ---------------------------------------------------------------------------
# (a) the operator between a convolution layer's projections
# ---------------------------------------------------------------------------

def _conv_loop(b, c, x, w):
    """out[n, t, ch] = c * sum_j w[ch, j] * (b * x)[t - (L - 1) + j],
    written out position by position in float64."""
    b, c, x, w = (np.asarray(a, np.float64) for a in (b, c, x, w))
    u = b * x
    taps = w.shape[1]
    out = np.zeros_like(u)
    for t in range(u.shape[1]):
        for j in range(taps):
            at = t - (taps - 1) + j
            if at >= 0:
                out[:, t] += w[:, j] * u[:, at]
    return c * out


def _conv_inputs(jax, shape, taps, dtype):
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    b, c, x, g = (jax.random.normal(k, shape, jnp.float32).astype(dtype)
                  for k in keys[:4])
    return b, c, x, jax.random.normal(keys[4], (shape[2], taps)), g


@pytest.mark.parametrize("formulation", ["jnp", "pallas"])
@pytest.mark.parametrize("shape,taps", [
    ((2, 64, 256), 3),        # one block of rows, two of channels
    ((1, 1536, 128), 3),      # three blocks along the sequence: both halos
    ((2, 32, 128), 4),        # another filter length
    ((2, 2, 128), 3),         # a sequence shorter than the filter
    ((1, 24, 96), 3),         # channels that fill no lane tile
], ids=["64x256", "three_seq_blocks", "four_taps", "shorter_than_the_filter",
        "ragged_channels"])
def test_short_conv_values_and_gradients(jax_cpu, formulation, shape, taps):
    """Both formulations against the written-out loop: values, and the
    gradients of all four operands (autodiff of the jnp formulation, the
    backward kernel of the Pallas pair) against finite sums worked out from
    the loop's linearity: d/dc is the filtered u, and the others follow by
    the chain rule through the same loop."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import short_conv as sc
    b, c, x, w, g = _conv_inputs(jax, shape, taps, jnp.float32)
    fn = sc.short_conv_reference if formulation == "jnp" else sc.short_conv
    tiles = sc._conv_blocks(shape[1], shape[2], taps, 4)
    assert (tiles is None) == (shape in ((2, 2, 128), (1, 24, 96)))
    out, vjp = jax.vjp(fn, b, c, x, w)
    np.testing.assert_allclose(out, _conv_loop(b, c, x, w), atol=1e-5)
    db, dc, dx, dw = vjp(g)
    ones = np.ones_like(np.asarray(c))
    filtered = _conv_loop(b, ones, x, w)                  # m = filter(b x)
    np.testing.assert_allclose(dc, np.asarray(g) * filtered, atol=1e-5)
    # du_t = sum_j w_j dm_{t + (L-1) - j}: the loop on the reversed sequence
    dm = np.asarray(g, np.float64) * np.asarray(c, np.float64)
    du = _conv_loop(dm[:, ::-1], ones, ones, w)[:, ::-1]
    np.testing.assert_allclose(db, du * np.asarray(x), atol=1e-5)
    np.testing.assert_allclose(dx, du * np.asarray(b), atol=1e-5)
    u = np.asarray(b, np.float64) * np.asarray(x, np.float64)
    want_dw = np.stack([
        (dm[:, taps - 1 - j:] * u[:, :u.shape[1] - (taps - 1 - j)]).sum((0, 1))
        if taps - 1 - j < u.shape[1] else np.zeros(shape[2])
        for j in range(taps)], axis=1)
    np.testing.assert_allclose(dw, want_dw, atol=2e-4)


def test_the_first_positions_see_zeros_and_no_later_one(jax_cpu):
    """Causal: position t reads u[t-2..t], with zeros before the start; a
    change at position 5 moves positions 5, 6, 7 and nothing else."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.short_conv import short_conv_reference
    b, c, x, w, _ = _conv_inputs(jax, (1, 16, 128), 3, jnp.float32)
    out = np.asarray(short_conv_reference(b, c, x, w))
    u = np.asarray(b * x)[0]
    wn, cn = np.asarray(w), np.asarray(c)[0]
    np.testing.assert_allclose(out[0, 0], cn[0] * wn[:, 2] * u[0], atol=1e-6)
    np.testing.assert_allclose(
        out[0, 1], cn[1] * (wn[:, 1] * u[0] + wn[:, 2] * u[1]), atol=1e-6)
    moved = np.asarray(short_conv_reference(b.at[0, 5].add(1.0), c, x, w))
    changed = np.flatnonzero(np.abs(moved - out)[0].max(axis=1) > 0)
    assert list(changed) == [5, 6, 7]


def test_pallas_pair_equals_the_jnp_formulation_in_bfloat16(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import short_conv as sc
    b, c, x, w, g = _conv_inputs(jax, (2, 64, 256), 3, jnp.bfloat16)
    assert sc._conv_blocks(64, 256, 3, 2) == (64, 256, 16)
    got, got_vjp = jax.vjp(sc.short_conv, b, c, x, w)
    want, want_vjp = jax.vjp(sc.short_conv_reference, b, c, x, w)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    for p, r in zip(got_vjp(g), want_vjp(g)):
        assert p.dtype == r.dtype and p.shape == r.shape
        # the kernel rounds a gradient once, autodiff at every product
        np.testing.assert_allclose(np.asarray(p, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=2e-2, atol=2e-2)


def test_blocks_of_the_cell_and_the_kernels_names(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import short_conv as sc
    from ray_tpu.util.profiling import KERNELS
    # lfm2_train_1chip: [2, 8192, 2048] bf16, 3 taps
    assert sc._conv_blocks(8192, 2048, 3, 2) == (512, 512, 16)
    assert sc._conv_blocks(8192, 2048, 3, 4) == (512, 512, 8)
    assert sc._conv_blocks(8192, 2048, 3, 1) is None
    assert sc._conv_blocks(8200, 2048, 3, 2) is None      # ragged sequence
    z = jnp.zeros((1, 32, 128), jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda b: sc.short_conv(b, z, z, jnp.ones((128, 3))).astype(
            jnp.float32).sum()))(z))
    for kernel in ("short_conv_fwd", "short_conv_bwd"):
        assert kernel in KERNELS and f"name={kernel}" in jaxpr


# ---------------------------------------------------------------------------
# (b) grouped queries in the three flash kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heads,kv_heads,seq,blocks", [
    (8, 2, 256, {"block_q": 128, "block_k": 128}),   # several blocks a row
    (8, 2, 128, {}),                                  # one square block
    (8, 2, 128, {"block_q": 64, "block_k": 32}),      # not square
    (8, 8, 128, {}),                                  # a head each
    (4, 1, 64, {}),                                   # one key/value head
], ids=["8_on_2_blocks_of_128", "8_on_2_one_block", "8_on_2_ragged_blocks",
        "8_on_8", "4_on_1_short"])
def test_flash_forward_and_gradients_under_grouped_queries(
        jax_cpu, heads, kv_heads, seq, blocks):
    """Against mha_reference with k and v repeated to the query heads'
    count by hand: values, dq, and dk, dv summed over a group's query
    heads, leaving at the key/value heads' count."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (2, heads, seq, 64), jnp.float32)
    k, v = (jax.random.normal(key, (2, kv_heads, seq, 64), jnp.float32)
            for key in keys[1:])
    rep = heads // kv_heads

    def loss(attend):
        return lambda q, k, v: (attend(q, k, v) ** 2).sum()

    def repeated(q, k, v):
        return mha_reference(q, jnp.repeat(k, rep, axis=1),
                             jnp.repeat(v, rep, axis=1))
    got = jax.value_and_grad(
        loss(lambda q, k, v: flash_attention(q, k, v, **blocks)),
        (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loss(repeated), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-4)
    assert got[1][1].shape == (2, kv_heads, seq, 64)
    # mha_reference takes the grouped shapes itself
    np.testing.assert_allclose(mha_reference(q, k, v), repeated(q, k, v),
                               atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_heads_of_128_leave_the_kernels_tokens_first(jax_cpu, dtype):
    """6 query heads on 2 key/value heads, 128 wide, two blocks a row: the
    kernels write o as [B, S, H * 128] and read dO so, a head's block placed
    by the block maps (dK/dV's over a group's three query heads in turn)."""
    import jax.numpy as jnp
    from helpers.flash_layout import check_tokens_first
    check_tokens_first(jax_cpu, jnp.dtype(dtype).type)


# sha256 of the jaxpr of the value and gradients of the parent's
# (8caba70) flash_attention at heads of 64, 8 on 2, [1, 8 | 2, 256, 64]
# float32 in blocks of 128: what `flash_attention_native` traces there, so a
# head narrower than a lane tile runs the parent's kernels, block maps,
# reshapes and delta op for op, and the caller turns [B, H, S, 64] as it did.
NARROW_HEADS_JAXPR_SHA256 = {
    "grouped": (
        {}, "9100c184863fbb861ccecb2874ff34cb147dfa93efae6957b231cbb36e4a09c9"),
    "window": (
        {"window": 100},
        "037d008fc5dc2bc2cdd7c9d3b5264ed76a51371b53fa8ba1b26a3da3c3051ec1"),
    "selected": (
        {"selected": True},
        "51b3e122e67c55fb344b7cdb29812a45a22a33465ca8f1181ca0540d96109adc"),
}


@pytest.mark.parametrize("kind", list(NARROW_HEADS_JAXPR_SHA256))
def test_heads_of_64_trace_to_the_parents_ops(jax_cpu, kind):
    import jax.numpy as jnp
    from helpers.flash_layout import traced_sha
    from ray_tpu.ops.attention import (flash_attention,
                                       flash_attention_native, tokens_first)
    options, sha = NARROW_HEADS_JAXPR_SHA256[kind]
    if "selected" in options:
        options = {"selected": jnp.tril(jnp.ones((1, 256, 256), jnp.int8))}
    assert not tokens_first(64) and tokens_first(128) and tokens_first(256)
    for attend in (flash_attention_native, flash_attention):
        assert traced_sha(
            jax_cpu, lambda q, k, v: attend(q, k, v, block_q=128,
                                            block_k=128, **options),
            8, 2, 256, 64) == sha


def test_flash_refuses_head_counts_that_do_not_group(jax_cpu):
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 8, 64, 16))
    with pytest.raises(ValueError, match="q 8, k 3, v 3"):
        flash_attention(q, q[:, :3], q[:, :3])
    with pytest.raises(ValueError, match="q 8, k 4, v 2"):
        flash_attention(q, q[:, :4], q[:, :2])


# sha256 of the jaxpr of flash attention's value and gradients at a head
# each ([1, 4, 256, 64] float32, blocks of 128, interpret mode), as the
# parent's ops/attention.py (1eafc89) traces it: a key/value head for every
# query head runs the kernels, grids and index maps it ran before grouped
# queries. (The dense and sparse cells' whole steps: tests/test_latent_moe.py,
# OLMOE_STEP_SHA256.)
FLASH_MHA_JAXPR_SHA256 = (
    "da176eee3b10ec97395f7f3e95788ed5d28f185ef37813e4b6b5c61196fdf606")


def test_a_head_each_traces_to_the_parents_kernels(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 4, 256, 64), jnp.float32)
    jaxpr = str(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: flash_attention(
            q, k, v, block_q=128, block_k=128).sum(), (0, 1, 2)))(q, q, q))
    assert hashlib.sha256(jaxpr.encode()).hexdigest() \
        == FLASH_MHA_JAXPR_SHA256


@pytest.mark.parametrize("seq,width,wide", [
    (1024, 64, False), (2048, 64, False),       # gpt2s, smollm: as they were
    (4096, 128, True), (8192, 256, True),       # olmoe, kanana: as they were
    (8192, 64, True),                           # lfm2: several blocks of 2048
    (256, 64, False)], ids=["gpt2s", "smollm", "olmoe", "kanana", "lfm2",
                            "short"])
def test_vmem_limit_follows_the_shape(seq, width, wide):
    from ray_tpu.ops import attention
    blocks = attention._block_sizes(seq, seq, width)
    params = attention._compiler_params(width, seq, blocks.fwd[1])
    assert (params is attention._GRID_SEMANTICS_WIDE) == wide
    assert params is attention._compiler_params(width, seq, blocks.dq[1])


# ---------------------------------------------------------------------------
# (c) the norm a head
# ---------------------------------------------------------------------------

def test_head_norm_is_an_rmsnorm_over_each_heads_columns(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import _head_rmsnorm, _rmsnorm
    y = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 6 * 16), jnp.float32)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    want = _rmsnorm(y.reshape(2, 8, 6, 16), scale, 1e-5).reshape(y.shape)
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(
            lambda y, s: (_head_rmsnorm(y, s, 1e-5) ** 3).sum(),
            (0, 1))(y, scale)
        np.testing.assert_allclose(_head_rmsnorm(y, scale, 1e-5), want,
                                   atol=1e-6)
    ref = jax.grad(lambda y, s: (_rmsnorm(
        y.reshape(2, 8, 6, 16), s, 1e-5) ** 3).sum(), (0, 1))(y, scale)
    for g, r in zip(grads, ref):
        np.testing.assert_allclose(g, r, atol=1e-4)
    del got


# ---------------------------------------------------------------------------
# (d) the program against the reference: logits, loss and gradients
# ---------------------------------------------------------------------------

def _program(jax, config, attention, dtype=None):
    import jax.numpy as jnp
    from benchmark.families import lfm2
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    cfg = GPTConfig(**lfm2.gpt_config_kwargs(config), attention=attention,
                    dtype=dtype or jnp.float32, remat_policy="none")
    params = gpt_init(jax.random.PRNGKey(3), cfg)
    for i, layer in enumerate(params["layers"]):
        if "moe" in layer:
            # a router with an opinion: at the init's 0.02 every score is 1/2
            layer["moe"]["router"] = 0.3 * jax.random.normal(
                jax.random.PRNGKey(100 + i), layer["moe"]["router"].shape)
        if "attn" in layer:
            # head norms that are not the identity on a unit vector, so that
            # a norm after the rotation would show
            for j, name in enumerate(("q_head_norm", "k_head_norm")):
                layer["attn"][name]["scale"] = 1.0 + 0.3 * jax.random.normal(
                    jax.random.PRNGKey(200 + j), (cfg.head_dim,))
    tokens = np.random.default_rng(5).integers(
        0, config["vocab_size"], (2, 129), dtype=np.int32)
    return cfg, params, jnp.asarray(tokens)


@pytest.fixture(scope="module")
def reference(jax_cpu, tiny):
    jax = jax_cpu
    from benchmark.families import lfm2
    _cfg, params, tokens = _program(jax, tiny, "reference")
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: lfm2.reference_logits(
            p, t[:, :-1], tiny))(params, tokens)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: lfm2.reference_loss(p, t, tiny)))(params, tokens)
    return logits, loss, grads


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_logits_loss_and_gradients_match_the_reference(jax_cpu, tiny,
                                                       reference, attention):
    """Two kinds of layer in one stack after a leading dense one, grouped
    queries with the norm a head before the rotation, the sigmoid rule at
    the published 1e-6 and the held experts, in float32: the whole tree of
    gradients, the selection bias's (exactly zero) included."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_forward, gpt_loss_and_aux
    cfg, params, tokens = _program(jax, tiny, attention)
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
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, t: gpt_forward(p, t, cfg))(
            params, tokens[:, :-1])
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p, t: gpt_loss_and_aux(p, {"tokens": t}, cfg),
            has_aux=True))(params, tokens)
    ref_logits, ref_loss, ref_grads = reference
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    assert float(loss) == float(aux["xent"])        # no router loss
    assert 0.0 < float(aux["expert_slots_held_share"]) < 1.0
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(
            g, r, atol=1e-5 * max(1.0, float(np.abs(r).max())),
            err_msg=jax.tree_util.keystr(path))
    for layer in grads["layers"][1:]:
        assert not np.any(np.asarray(layer["moe"]["router_bias"]))


def test_the_reference_tells_each_mechanism_apart(jax_cpu, tiny, reference):
    """What `program_check` rests on: the reference with one mechanism
    changed gives other logits (the norm after the rotation among them,
    because this test's norm scales are not all one)."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import lfm2
    _cfg, params, tokens = _program(jax, tiny, "reference")
    sound = reference[0]
    norm, rotated = lfm2._norm_heads, lfm2._rotated
    faults = {
        "_filtered": lambda u, w: u,
        "_gated": lambda b, c, x, w: c * lfm2._filtered(x, w),
        "_norm_heads": lambda t, scale, eps: t,
        "_rotated": lambda t, cos, sin: norm(rotated(
            t / norm(jnp.ones_like(t), params["layers"][1]["attn"][
                "q_head_norm"]["scale"], 0.0), cos, sin),
            params["layers"][1]["attn"]["q_head_norm"]["scale"], 0.0),
        "_kv_head_of": lambda h, kv: jnp.arange(h) % kv,
    }
    for name, fault in faults.items():
        kept = getattr(lfm2, name)
        setattr(lfm2, name, fault)
        try:
            with jax.default_matmul_precision("highest"):
                logits = jax.jit(lambda p, t: lfm2.reference_logits(
                    p, t[:, :-1], tiny))(params, tokens)
        finally:
            setattr(lfm2, name, kept)
        assert float(jnp.abs(logits - sound).max()) > 1e-3, name


def test_bfloat16_step_passes_the_per_token_check(jax_cpu, tiny):
    """reference_loss with a `program_check` answers the loss where the
    program's own forward (bf16, flash under grouped queries, the
    convolution's kernels, the grouped-matmul kernels) agrees with the
    reference token by token, and nan where a bound is broken."""
    jax = jax_cpu
    from benchmark.families import lfm2
    _cfg, params, tokens = _program(jax, tiny, "flash")
    checked = dict(tiny, program_check={"logprob_median_tol": 0.05,
                                        "logprob_rms_tol": 0.2})
    with jax.default_matmul_precision("highest"):
        plain = float(jax.jit(lambda p, t: lfm2.reference_loss(
            p, t, tiny))(params, tokens))
        held = float(jax.jit(lambda p, t: lfm2.reference_loss(
            p, t, checked))(params, tokens))
        checked["program_check"]["logprob_median_tol"] = 1e-6
        broken = float(jax.jit(lambda p, t: lfm2.reference_loss(
            p, t, checked))(params, tokens))
    assert held == plain and np.isnan(broken)


def test_renormalisation_epsilon_is_the_configurations(jax_cpu, tiny):
    """1e-6 for this family, 1e-20 (the default) for kanana's."""
    from benchmark.families import kanana, lfm2
    from ray_tpu.models.gpt import GPTConfig
    assert GPTConfig().router_renormalise_eps == 1e-20
    assert GPTConfig(**lfm2.gpt_config_kwargs(
        tiny)).router_renormalise_eps == 1e-6
    other = _read("benchmark", "rehearsal", "configs", "tiny-kanana.json")
    assert GPTConfig(**kanana.gpt_config_kwargs(
        other)).router_renormalise_eps == 1e-20


# ---------------------------------------------------------------------------
# (e) the share: the parts add up to the whole
# ---------------------------------------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(jax_cpu, tiny):
    """model-configs guide, section 4: a whole sparse convolution layer,
    mixer and residual included. Every chip computes the mixer and the
    residual alike, so they count once; what the four shares' experts add
    (each the routed part of its own four experts) adds up with them to the
    uncut reference's layer."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import lfm2
    from ray_tpu.models.gpt import GPTConfig, Setting, gpt_init, layer_fn
    whole = copy.deepcopy(tiny)
    del whole["share"]
    whole["num_experts"] = 16
    full_cfg = GPTConfig(**lfm2.gpt_config_kwargs(whole), dtype=jnp.float32,
                         attention="reference", remat_policy="none")
    assert full_cfg.experts_held is None
    layer = gpt_init(jax.random.PRNGKey(7), full_cfg)["layers"][2]
    assert sorted(layer) == ["conv", "ln1", "ln2", "moe"]
    layer["moe"]["router"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(8), (128, 16))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 128), jnp.float32)

    def reference_layer(h):
        h = h + lfm2.reference_conv(
            layer["conv"], lfm2._norm(h, layer["ln1"]["scale"], 1e-5), whole)
        return h, h + lfm2.reference_experts(
            layer["moe"], lfm2._norm(h, layer["ln2"]["scale"], 1e-5), whole)

    with jax.default_matmul_precision("highest"):
        mixed, want = jax.vmap(reference_layer)(x)
        parts, held_share = [], 0.0
        for rank in range(4):
            cut = dict(tiny, share=dict(tiny["share"], rank=rank))
            cfg = GPTConfig(**lfm2.gpt_config_kwargs(cut), dtype=jnp.float32,
                            attention="reference", remat_policy="none")
            assert cfg.experts_held == (4 * rank, 4)
            mine = dict(layer, moe=dict(layer["moe"], **{
                name: layer["moe"][name][4 * rank:4 * rank + 4]
                for name in ("w_gate", "w_up", "w_down")}))
            out, stats = layer_fn(cfg, 64, Setting())(x, mine)
            # mixer and residual, the same on every chip, taken off
            parts.append(out - mixed)
            held_share += float(stats["expert_slots_held_share"])
    np.testing.assert_allclose(mixed + sum(parts), want, atol=5e-5)
    assert abs(held_share - 1.0) < 1e-6
    # and a part is not the whole: the absent experts' sum is left out
    assert float(jnp.abs(mixed + parts[0] - want).max()) > 1e-2


# ---------------------------------------------------------------------------
# (f) arithmetic, rules, refusals, names
# ---------------------------------------------------------------------------

def test_param_count_at_the_cell_is_the_programs_tree(jax_cpu, tiny):
    jax = jax_cpu
    from benchmark.families import lfm2
    from ray_tpu.models.gpt import GPTConfig, count_params, gpt_init
    cell = _read("benchmark", "configs", "lfm2-24b-a2b.json")
    assert lfm2.param_count(cell) == 469_285_248
    assert lfm2.share(cell) == (0, 8, 64)
    for config in (cell, tiny):
        cfg = GPTConfig(**lfm2.gpt_config_kwargs(config))
        assert lfm2.param_count(config) == count_params(
            jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg)))
    # the published model, tied: 23.84B, its name
    published = {k: v for k, v in cell.items() if k != "share"}
    published.update(cell["published"])
    assert round(lfm2.param_count(published) / 1e9, 2) == 23.84


def test_flops_and_kernel_arithmetic_count_what_is_computed_here():
    from benchmark.families import lfm2
    from benchmark.kernels import gqa_attention
    cell = _read("benchmark", "configs", "lfm2-24b-a2b.json")
    mix = _read("benchmark", "traffic", "train_b2_s8192_dp.json")
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


@pytest.mark.parametrize("strategy,column,row", [
    ("tp", (None, "tensor"), ("tensor", None)),
    ("tp_fsdp", ("fsdp", "tensor"), ("tensor", "fsdp"))])
def test_every_new_leaf_gets_its_rule(jax_cpu, tiny, strategy, column, row):
    jax = jax_cpu
    from jax.sharding import PartitionSpec as P
    from benchmark.families import lfm2
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    cfg = GPTConfig(**lfm2.gpt_config_kwargs(tiny))
    params = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                      devices=jax.devices()[:4])
    specs = jax.tree_util.tree_map(
        lambda s: s.spec,
        strategy_from_name(strategy).param_shardings(mesh, params))
    conv, attn = specs["layers"][0]["conv"], specs["layers"][1]["attn"]
    # the three chunks split by channel, each with its channels' filter
    assert conv["w_in"] == P(None, *column)
    assert conv["filter"] == P("tensor", None)
    assert conv["w_out"] == P(*row)
    assert attn["wq"] == attn["wk"] == attn["wv"] == P(*column)
    assert attn["q_head_norm"]["scale"] == attn["k_head_norm"]["scale"] \
        == P(None)


def test_sharded_step_equals_one_device(jax_cpu, tiny):
    """One step of the whole tiny model on fsdp=2 x tensor=2 (a key/value
    head with its four query heads and the channels of B, C, X with their
    filters on a shard of `tensor`, the kernels per shard) equals the
    one-device step."""
    jax = jax_cpu
    import jax.numpy as jnp
    import optax
    from benchmark.families import lfm2
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import init_train_state, make_train_step
    cfg = GPTConfig(**lfm2.gpt_config_kwargs(tiny), dtype=jnp.float32,
                    attention="flash")
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, 512, (4, 129), dtype=np.int32))

    def one_step(name, axes, n):
        mesh = build_mesh(MeshConfig(**axes), devices=jax.devices()[:n])
        strategy = strategy_from_name(name)
        optimizer = optax.sgd(0.1)
        state = init_train_state(
            lambda: gpt_init(jax.random.PRNGKey(3), cfg), optimizer, mesh,
            strategy)
        step = make_train_step(
            lambda p, b: gpt_loss(
                p, b, cfg, mesh=mesh,
                act_sharding=strategy.activation_sharding(mesh)),
            optimizer, mesh, strategy, sample_params=state.params)
        with jax.default_matmul_precision("highest"):
            state, metrics = step(state, {"tokens": tokens})
        return float(metrics["loss"]), jax.device_get(state.params)

    ref_loss, ref_params = one_step("dp", {"data": 1}, 1)
    loss, params = one_step("tp_fsdp", {"data": 1, "fsdp": 2, "tensor": 2}, 4)
    assert abs(loss - ref_loss) < 1e-5
    for (path, p), r in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_allclose(p, r, rtol=1e-4, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("change,says", [
    ({"attention": "ring"}, "n_kv_heads=2 != n_heads=8.*attention='ring'"),
    ({"kv_latent_dim": 64, "qk_nope_dim": 16, "qk_rope_dim": 16,
      "v_head_dim": 16}, "n_kv_heads=2 != n_heads=8.*a latent block"),
    ({"n_kv_heads": 3}, "n_kv_heads=3 does not divide n_heads=8"),
    ({"layer_kinds": ("conv", "attention")}, "layer_kinds.*n_layers=4"),
    ({"layer_kinds": ("conv", "mamba", "conv", "conv")},
     "'attention' | 'conv'"),
], ids=["ring", "latent", "kv_heads", "kinds_length", "kinds_names"])
def test_the_configuration_refuses_by_name(tiny, change, says):
    from benchmark.families import lfm2
    from ray_tpu.models.gpt import GPTConfig
    with pytest.raises(ValueError, match=says):
        GPTConfig(**dict(lfm2.gpt_config_kwargs(tiny), **change))


@pytest.mark.parametrize("change,mesh_axes,says", [
    ({"n_experts": 0, "dense_layers": 0, "experts_held": None},
     {"pipeline": 2}, "parameters are not layer 0's.*conv/w_in"),
    ({"layer_kinds": ("conv",) * 4, "n_experts": 0, "dense_layers": 0,
      "experts_held": None}, {"pipeline": 2, "tensor": 2},
     "no rule for conv/filter, conv/w_in, conv/w_out"),
    ({"layer_kinds": None, "n_experts": 0, "dense_layers": 0,
      "experts_held": None}, {"pipeline": 1, "tensor": 4},
     "n_kv_heads=2 is not whole key/value heads over tp=4"),
], ids=["two_kinds", "conv_under_pp_tp", "kv_heads_over_tensor"])
def test_pipeline_refuses_by_name(jax_cpu, tiny, change, mesh_axes, says):
    jax = jax_cpu
    from benchmark.families import lfm2
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import make_gpt_pp_loss
    cfg = GPTConfig(**dict(lfm2.gpt_config_kwargs(tiny), **change))
    n = int(np.prod(list(mesh_axes.values())))
    mesh = build_mesh(MeshConfig(data=1, **mesh_axes),
                      devices=jax.devices()[:n])
    with pytest.raises(ValueError, match=says):
        make_gpt_pp_loss(cfg, mesh, num_microbatches=2)


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


def test_the_new_scopes_are_regions_and_reach_the_compiled_step(jax_cpu,
                                                                tiny):
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import lfm2
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.util import profiling
    assert {"conv", "conv_mix"} <= set(profiling.REGIONS)
    cfg = GPTConfig(**lfm2.gpt_config_kwargs(tiny), attention="flash")
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    text = jax.jit(jax.grad(lambda p, t: gpt_loss(p, {"tokens": t}, cfg))
                   ).lower(params, jnp.zeros((2, 129), jnp.int32)
                           ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    regions = {profiling._last_of(n, profiling.REGIONS) for n in names}
    assert {"conv", "conv_mix", "attn_proj", "attn_core", "moe",
            "moe_route", "mlp"} <= regions
    # the two projections are `conv`'s, gates and filter `conv_mix`'s
    assert any("conv/bsd,de->bse" in n for n in names)
    assert not any("conv_mix" in n and "dot_general" in n for n in names)


def test_configuration_file_keeps_the_catalog_and_states_the_cut():
    cell = _read("benchmark", "configs", "lfm2-24b-a2b.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cell["source"])
    changed = {k for k, v in row["config"].items() if cell.get(k, "?") != v}
    assert changed == set(cell["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "num_dense_layers"}
    assert cell["published"] == {k: row["config"][k] for k in cell["reduced"]}
    # published layers 1..5: the second leading dense layer, then a period
    assert cell["layer_types"] == row["config"]["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert cell["share"]["chips_per_layer"] * cell["num_experts"] \
        == cell["share"]["num_experts"] == 64
    assert cell["share"]["chips_per_layer"] * cell["vocab_size"] == 65536
    bench = _read("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["name"])
    assert entry["reduced"] == cell["reduced"]
    assert entry["source"] == cell["source"]


# ---------------------------------------------------------------------------
# (g) the benchmark's own check of the cell that needs no chip
# ---------------------------------------------------------------------------

@pytest.mark.timeout(600)
def test_the_cell_rehearses():
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # rehearse.py asks for its own devices
    proc = subprocess.run(
        [sys.executable, "benchmark/rehearse.py", "lfm2_train_1chip",
         "--seconds", "2"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "rehearsal passed" in proc.stdout
