"""Sliding-window attention in the flash kernels (ops/attention.py), a
rotation a kind of layer (ops/rope.py), and a stack of window and full
attention layers with a head count of their own a kind and a gate a head
(models/gpt.py) against the plain float32 reference of
benchmark/families/laguna.py, at a small size on the CPU: seeded random
weights, the kernels in interpret mode."""

import copy
import hashlib
import json
import math
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
    """benchmark/rehearsal/configs/tiny-laguna.json: full attention + dense,
    then sliding x 3 and full with experts 4..7 of 16 held, 2 a token; 6
    (full) and 8 (sliding) query heads of 32 on 2 key/value heads, window
    24, half a head rotated with YaRN frequencies on the full layers."""
    return _read("benchmark", "rehearsal", "configs", "tiny-laguna.json")


# ---------------------------------------------------------------------------
# (a) the three window kernels
# ---------------------------------------------------------------------------

def _qkv(jax, heads, kv_heads, seq, dim, dtype):
    keys = jax.random.split(jax.random.PRNGKey(seq + heads), 4)
    shapes = [(2, heads, seq, dim), (2, kv_heads, seq, dim),
              (2, kv_heads, seq, dim), (2, heads, seq, dim)]
    return [jax.random.normal(k, s, dtype) for k, s in zip(keys, shapes)]


@pytest.mark.parametrize("heads,kv_heads,seq,dim,window,block", [
    (2, 2, 64, 16, 5, 16),        # smaller than a block
    (2, 1, 64, 16, 16, 16),       # a block
    (4, 2, 64, 16, 23, 16),       # larger than one, grouped queries
    (2, 2, 64, 16, 40, 32),       # larger than a block, under two
    (6, 2, 64, 16, 1, 16),        # a query sees itself alone
    (2, 1, 512, 32, 100, None),   # the rule: one block of 512, row groups
    (2, 2, 4096, 32, 300, None),  # the rule: two blocks of 2048 a row
], ids=["under_a_block", "a_block", "over_a_block_grouped", "under_two",
        "itself_alone", "rule_512", "rule_2048"])
def test_window_kernels_match_the_reference(jax_cpu, heads, kv_heads, seq,
                                            dim, window, block):
    """flash_win_fwd, flash_win_bwd_dq and flash_win_bwd_dkv against
    mha_reference(window=): the output and all three gradients, dK and dV
    at the key/value heads' count."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention, mha_reference
    q, k, v, g = _qkv(jax, heads, kv_heads, seq, dim, jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, window=window, block_q=block,
                               block_k=block)

    def oracle(q, k, v):
        return mha_reference(q, k, v, window=window)
    np.testing.assert_allclose(flash(q, k, v), oracle(q, k, v), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(oracle(*a) * g), (0, 1, 2))(q, k, v)
    assert got[1].shape == got[2].shape == (2, kv_heads, seq, dim)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_window_kernels_write_heads_of_128_tokens_first(jax_cpu, dtype):
    """flash_win_* are the same bodies under the same specs: at heads of
    128 (6 on 2, two blocks a row, a window of 100) o leaves and dO arrives
    as [B, S, H * 128]."""
    import jax.numpy as jnp
    from helpers.flash_layout import check_tokens_first
    check_tokens_first(jax_cpu, jnp.dtype(dtype).type, window=100)


def test_the_reference_window_is_itself_and_the_ones_before(jax_cpu):
    """mha_reference(window=3) written out: query i mixes v_{i-2..i}."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import mha_reference
    q = jnp.zeros((1, 1, 8, 4))             # equal scores: a plain mean
    v = jnp.arange(8.0)[None, None, :, None] * jnp.ones((1, 1, 8, 4))
    out = mha_reference(q, q, v, window=3)[0, 0, :, 0]
    np.testing.assert_allclose(out, [0, 0.5, 1, 2, 3, 4, 5, 6], atol=1e-6)
    with pytest.raises(ValueError, match="band under the causal mask"):
        mha_reference(q, q, v, causal=False, window=3)


def _kernel_names(jax, fn, *args):
    return set(re.findall(r"name=(flash_(?:win_)?(?:fwd|bwd_dq|bwd_dkv))\b",
                          str(jax.make_jaxpr(fn)(*args))))


def test_kernel_names_with_a_window_and_without(jax_cpu):
    """A window gives the three kernels names of their own (a trace row, and
    a roofline, is of ONE shape); without one, and with one that reaches
    the sequence's start from its end, they are the parent's."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.util.profiling import KERNELS
    q, k, v, _ = _qkv(jax, 4, 2, 256, 32, jnp.bfloat16)

    def grads(window):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, window=window).astype(jnp.float32).sum(), (0, 1, 2))
    windowed = {"flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv"}
    plain = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert windowed | plain <= set(KERNELS)
    assert _kernel_names(jax, grads(64), q, k, v) == windowed
    assert _kernel_names(jax, grads(None), q, k, v) == plain
    assert _kernel_names(jax, grads(256), q, k, v) == plain
    assert _kernel_names(jax, grads(10_000), q, k, v) == plain


def test_blocks_are_the_shapes_and_the_steps_the_bands():
    """The window does not move the blocks (`_block_sizes`: the sweep of
    PERF.md, PR 37); it sets how many of them a grid row walks."""
    from ray_tpu.ops.attention import _band_steps, _block_sizes
    # laguna_train_1chip: 8192 positions at head 128, window 512: two of
    # the four blocks of 2048 a grid row
    assert _block_sizes(8192, 8192, 128) == (
        (2048, 2048, 256), (2048, 2048, 128), (2048, 2048, 128))
    assert _band_steps(2048, 2048, 512) == 2
    assert _band_steps(2048, 2048, 1) == 1        # the diagonal block alone
    assert _band_steps(2048, 2048, 2049) == 2 == _band_steps(512, 512, 513)
    assert _band_steps(2048, 2048, 2050) == 3 == _band_steps(512, 512, 514)
    assert _band_steps(1024, 512, 512) == 3       # outer's two and one more


@pytest.mark.parametrize("outer,major,group,window", [
    (64, 32, 16, 24), (32, 32, 8, 40), (64, 16, 16, 5), (128, 128, 32, 128)])
@pytest.mark.parametrize("upper", [False, True], ids=["keys", "queries"])
def test_band_tiles_cover_the_band_and_mask_only_on_an_edge(outer, major,
                                                            group, window,
                                                            upper):
    """_for_band's static geometry, replayed with numpy: over the steps of
    one outer block the tiles hold every kept pair once, nothing outside
    them is kept, and a tile with no edge through it carries no mask bit."""
    from ray_tpu.ops import attention as A
    seq = 4 * outer
    kept_want = np.zeros((seq, seq), bool)       # [query, key]
    for i in range(seq):
        kept_want[i, max(0, i - window + 1):i + 1] = True
    seen = np.zeros((seq, seq), int)
    blocks = seq // major

    class When:                                   # pl.when, eagerly
        def __init__(self, cond):
            self.cond = cond

        def __call__(self, fn):
            if self.cond:
                fn()
    real_when, A.pl.when = A.pl.when, When
    try:
        for o in range(seq // outer):
            first = (o * outer // major if upper
                     else (o + 1) * (outer // major) - 1)
            for step in range(A._band_steps(outer, major, window)):
                def tile(rows, cols, band, step=step):
                    block = first + step if upper else first - step
                    r = np.arange(rows.start, rows.stop) + o * outer
                    c = np.arange(cols.start, cols.stop) + block * major
                    q, k = (c, r) if upper else (r, c)
                    diff = q[None, :] - k[:, None] if upper \
                        else q[:, None] - k[None, :]
                    assert diff[0, 0] == band.diff
                    keep = (diff >= 0) & (diff < band.window)
                    assert keep.any()
                    pairs = (np.ix_(c, r) if upper else np.ix_(r, c))
                    seen[pairs] += keep.T if upper else keep
                A._for_band(upper, step, first, blocks, outer, major, group,
                            window, tile)
    finally:
        A.pl.when = real_when
    np.testing.assert_array_equal(seen, kept_want.astype(int))


def test_band_mask_touches_the_chunks_an_edge_crosses(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import attention as A
    s = jnp.zeros((128, 384), jnp.float32)
    # query - key = 200 + row - col: causal edge in chunk 1 (cols 128..255)
    # and 2, the window's (64) in chunk 1
    masked = np.asarray(A._band_mask(s, A._Band(200, 64), False))
    diff = 200 + np.arange(128)[:, None] - np.arange(384)[None, :]
    np.testing.assert_array_equal(masked == 0, (diff >= 0) & (diff < 64))
    # a tile wholly inside the band comes back as it is, no select
    jaxpr = str(jax.make_jaxpr(
        lambda s: A._band_mask(s, A._Band(400, 1000), False))(s))
    assert "select_n" not in jaxpr and "iota" not in jaxpr


@pytest.mark.parametrize("kwargs,says", [
    ({"causal": False, "window": 8}, "window=8 needs causal"),
    ({"window": 0}, "window=0 needs causal"),
    ({"window": 8, "block_q": 32, "block_k": 16}, "block_q=32 == block_k"),
], ids=["not_causal", "empty", "blocks"])
def test_flash_refuses_a_window_it_cannot_run(jax_cpu, kwargs, says):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    q = jnp.zeros((1, 2, 64, 16))
    with pytest.raises(ValueError, match=says):
        flash_attention(q, q, q, **kwargs)
    with pytest.raises(ValueError, match="seq_q == seq_k"):
        flash_attention(q[:, :, :32], q, q, window=8)


# ---------------------------------------------------------------------------
# (b) a rotation a kind
# ---------------------------------------------------------------------------

def test_yarn_frequencies_are_the_published_blend():
    """Pair i of 32 (64 rotated columns, theta 500 000, factor 64 over 4096
    positions): below pair 5 as they are, from pair 16 divided by 64, a
    linear ramp between; and the attention factor is 0.1 ln 64 + 1."""
    from ray_tpu.ops.rope import yarn_frequencies
    got = yarn_frequencies(500000.0, 64, 64.0, 4096, 64.0, 1.0)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    turns = 4096 * plain / (2 * math.pi)
    assert turns[5] > 64 > turns[6] and turns[15] > 1 > turns[16]
    ramp = np.clip((np.arange(32) - 5) / (16 - 5), 0, 1)
    np.testing.assert_allclose(got, plain * (1 - ramp) + plain / 64 * ramp,
                               rtol=1e-6)
    np.testing.assert_allclose(got[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(got[16:], plain[16:] / 64, rtol=1e-6)
    cell = _read("benchmark", "configs", "laguna-xs.2.json")
    assert cell["rope_parameters"]["full_attention"]["attention_factor"] \
        == pytest.approx(0.1 * math.log(64) + 1)


@pytest.mark.parametrize("heads", [6, 8, 2])
def test_partial_table_through_the_kernels_is_the_jnp_rotation(jax_cpu,
                                                               heads):
    """rope_split with a table whose unrotated columns pass (cos 1, sin 0)
    on columns in halves_apart's order = the jnp rotation of the head's
    first half as halves, in that order; at three head counts."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import _rope
    from ray_tpu.ops.rope import (RopeSpec, halves_apart, rope_split,
                                  rope_table)
    spec = RopeSpec(theta=500000.0, rotated=0.5, yarn=(64.0, 4096, 64.0, 1.0),
                    attention_factor=1.4158883)
    dim, seq = 128, 32
    order = np.asarray(halves_apart(dim, spec.columns(dim)))
    assert sorted(order) == list(range(dim))
    assert list(order[:32]) == list(range(32))
    assert list(order[64:96]) == list(range(32, 64))
    x = jax.random.normal(jax.random.PRNGKey(heads), (2, seq, heads * dim))
    want = _rope(x.reshape(2, seq, heads, dim).transpose(0, 2, 1, 3), spec,
                 jnp.broadcast_to(jnp.arange(seq), (2, seq)))
    permuted = x.reshape(2, seq, heads, dim)[..., order].reshape(x.shape)
    got = rope_split(permuted, dim, rope_table(seq, dim, spec))
    np.testing.assert_allclose(got, want[..., order], atol=1e-6)
    # the columns that pass are untouched, to the bit
    np.testing.assert_array_equal(
        got[..., 32:64], permuted.reshape(2, seq, heads, dim).transpose(
            0, 2, 1, 3)[..., 32:64])


# ---------------------------------------------------------------------------
# (c) the whole stack against the family's reference
# ---------------------------------------------------------------------------

def _program(jax, config, attention, dtype=None):
    import jax.numpy as jnp
    from benchmark.families import laguna
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    cfg = GPTConfig(**laguna.gpt_config_kwargs(config), attention=attention,
                    dtype=dtype or jnp.float32, remat_policy="none")
    params = gpt_init(jax.random.PRNGKey(3), cfg)
    for i, layer in enumerate(params["layers"]):
        if "moe" in layer:
            # a router with an opinion: at the init's 0.02 every score is 1/2
            layer["moe"]["router"] = 0.3 * jax.random.normal(
                jax.random.PRNGKey(100 + i), layer["moe"]["router"].shape)
    tokens = np.random.default_rng(5).integers(
        0, config["vocab_size"], (2, 129), dtype=np.int32)
    return cfg, params, jnp.asarray(tokens)


@pytest.fixture(scope="module")
def reference(jax_cpu, tiny):
    jax = jax_cpu
    from benchmark.families import laguna
    _cfg, params, tokens = _program(jax, tiny, "reference")
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: laguna.reference_logits(
            p, t[:, :-1], tiny))(params, tokens)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: laguna.reference_loss(p, t, tiny)))(params, tokens)
    return logits, loss, grads


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_logits_loss_and_gradients_match_the_reference(jax_cpu, tiny,
                                                       reference, attention):
    """Both kinds of attention layer in one stack, two head counts on one
    key/value head count, the partial YaRN rotation with its attention
    factor beside the plain one, the window, the gate a head, a dense layer
    and then experts beside a shared one, in float32: the whole tree of
    gradients. On the flash path the full layers' q and k columns are
    permuted in the weights and three head counts go through rope_split."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_forward, gpt_loss_and_aux
    cfg, params, tokens = _program(jax, tiny, attention)
    assert [sorted(layer) for layer in params["layers"]] == [
        ["attn", "ln1", "ln2", "mlp"]] + [
        ["ln1", "ln2", "moe", "window_attn"]] * 3 + [
        ["attn", "ln1", "ln2", "moe"]]
    full, sliding = (params["layers"][0]["attn"],
                     params["layers"][1]["window_attn"])
    assert cfg.head_dim == 32 and cfg.d_model == 128
    assert full["wq"].shape == (128, 6 * 32) and full["wg"].shape == (128, 6)
    assert sliding["wq"].shape == (128, 8 * 32)
    assert sliding["wo"].shape == (8 * 32, 128)
    assert sliding["wg"].shape == (128, 8)
    assert full["wk"].shape == sliding["wv"].shape == (128, 2 * 32)
    assert params["layers"][1]["moe"]["w_up"].shape == (4, 128, 64)
    assert params["layers"][1]["moe"]["router"].shape == (128, 16)
    assert params["lm_head"].shape == (128, 512)                # untied
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, t: gpt_forward(p, t, cfg))(
            params, tokens[:, :-1])
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p, t: gpt_loss_and_aux(p, {"tokens": t}, cfg),
            has_aux=True))(params, tokens)
    ref_logits, ref_loss, ref_grads = reference
    np.testing.assert_allclose(logits, ref_logits, atol=5e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    assert float(loss) == float(aux["xent"])        # no router loss
    assert 0.0 < float(aux["expert_slots_held_share"]) < 1.0
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(ref_grads)):
        assert np.any(np.asarray(r)), jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            g, r, atol=2e-5 * max(1.0, float(np.abs(r).max())),
            err_msg=jax.tree_util.keystr(path))


def test_the_reference_tells_each_mechanism_apart(jax_cpu, tiny, reference):
    """What `program_check` rests on: the reference with one mechanism
    changed gives other logits. (A window of 23 and of 25 for 24 stand in
    for the cell's 511 and 513.)"""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import laguna
    _cfg, params, tokens = _program(jax, tiny, "reference")
    sound = reference[0]
    rope = tiny["rope_parameters"]

    def with_rope(kind, **change):
        changed = copy.deepcopy(tiny)
        changed["rope_parameters"][kind] = dict(rope[kind], **change)
        return changed
    configs = {
        "causal_whole": dict(tiny, sliding_window=10 ** 9),
        "window_23": dict(tiny, sliding_window=23),
        "window_25": dict(tiny, sliding_window=25),
        "sliding_table_on_full": with_rope(
            "full_attention", **rope["sliding_attention"]),
        "full_table_on_sliding": with_rope(
            "sliding_attention", **rope["full_attention"]),
        "whole_head_rotated": with_rope("full_attention",
                                        partial_rotary_factor=1),
        "no_attention_factor": with_rope("full_attention",
                                         attention_factor=1.0),
        "yarn_not_blended": with_rope("full_attention", rope_type="default"),
        "no_gate": dict(tiny, gating=False),
        "unscaled": dict(tiny, moe_routed_scaling_factor=1.0),
    }

    def logits_of(config):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, t: laguna.reference_logits(
                p, t[:, :-1], config))(params, tokens)
    for name, config in configs.items():
        assert float(jnp.abs(logits_of(config) - sound).max()) > 1e-3, name
    kept = laguna._kv_head_of
    laguna._kv_head_of = lambda h, kv: jnp.arange(h) % kv
    try:
        assert float(jnp.abs(logits_of(tiny) - sound).max()) > 1e-3
    finally:
        laguna._kv_head_of = kept


def test_bfloat16_step_passes_the_per_token_check(jax_cpu, tiny):
    """reference_loss with a `program_check` answers the loss where the
    program's own forward (bf16, the window and the full flash kernels, the
    grouped-matmul kernels) agrees with the reference token by token, and
    nan where a bound is broken."""
    jax = jax_cpu
    from benchmark.families import laguna
    _cfg, params, tokens = _program(jax, tiny, "flash")
    checked = dict(tiny, program_check={"logprob_median_tol": 0.08,
                                        "logprob_rms_tol": 0.5})
    with jax.default_matmul_precision("highest"):
        plain = float(jax.jit(lambda p, t: laguna.reference_loss(
            p, t, tiny))(params, tokens))
        held = float(jax.jit(lambda p, t: laguna.reference_loss(
            p, t, checked))(params, tokens))
        checked["program_check"]["logprob_median_tol"] = 1e-6
        broken = float(jax.jit(lambda p, t: laguna.reference_loss(
            p, t, checked))(params, tokens))
    assert held == plain and np.isnan(broken)


# ---------------------------------------------------------------------------
# (d) the share: the parts add up to the whole
# ---------------------------------------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(jax_cpu, tiny):
    """model-configs guide, section 4: a whole sparse sliding-window layer,
    attention, gate and residual included. Every chip computes attention,
    the residual and the shared expert alike, so they count once; what the
    four shares' experts add (each the routed part of its own four experts)
    adds up with them to the uncut reference's layer."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import laguna
    from ray_tpu.models.gpt import GPTConfig, Setting, gpt_init, layer_fn
    whole = copy.deepcopy(tiny)
    del whole["share"]
    whole["num_experts"] = 16
    full_cfg = GPTConfig(**laguna.gpt_config_kwargs(whole), dtype=jnp.float32,
                         attention="reference", remat_policy="none")
    assert full_cfg.experts_held is None
    layer = gpt_init(jax.random.PRNGKey(7), full_cfg)["layers"][2]
    assert sorted(layer) == ["ln1", "ln2", "moe", "window_attn"]
    layer["moe"]["router"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(8), (128, 16))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 128), jnp.float32)

    def reference_layer(h):
        h = h + laguna.reference_attention(
            layer["window_attn"],
            laguna._norm(h, layer["ln1"]["scale"], 1e-6), whole,
            "sliding_attention", 8)
        m = laguna._norm(h, layer["ln2"]["scale"], 1e-6)
        shared = laguna._swiglu(layer["moe"]["shared"], m, jnp.float32)
        return h + shared, h + laguna.reference_experts(layer["moe"], m,
                                                        whole)

    with jax.default_matmul_precision("highest"):
        alike, want = jax.vmap(reference_layer)(x)
        parts, held_share = [], 0.0
        for rank in range(4):
            cut = dict(tiny, share=dict(tiny["share"], rank=rank))
            cfg = GPTConfig(**laguna.gpt_config_kwargs(cut),
                            dtype=jnp.float32, attention="reference",
                            remat_policy="none")
            assert cfg.experts_held == (4 * rank, 4)
            mine = dict(layer, moe=dict(layer["moe"], **{
                name: layer["moe"][name][4 * rank:4 * rank + 4]
                for name in ("w_gate", "w_up", "w_down")}))
            out, stats = layer_fn(cfg, 64, Setting())(x, mine)
            # attention, residual and the shared expert, the same on every
            # chip, taken off
            parts.append(out - alike)
            held_share += float(stats["expert_slots_held_share"])
    np.testing.assert_allclose(alike + sum(parts), want, atol=5e-5)
    assert abs(held_share - 1.0) < 1e-6
    # and a part is not the whole: the absent experts' sum is left out
    assert float(jnp.abs(alike + parts[0] - want).max()) > 1e-2


# ---------------------------------------------------------------------------
# (e) arithmetic, rules, refusals, names
# ---------------------------------------------------------------------------

def test_param_count_is_the_published_model_and_the_programs_tree(jax_cpu,
                                                                  tiny):
    jax = jax_cpu
    from benchmark.families import laguna
    from ray_tpu.models.gpt import GPTConfig, count_params, gpt_init
    cell = _read("benchmark", "configs", "laguna-xs.2.json")
    assert laguna.param_count(cell) == 691_623_936
    assert laguna.share(cell) == (0, 32, 256)
    for config in (cell, tiny):
        cfg = GPTConfig(**laguna.gpt_config_kwargs(config))
        assert laguna.param_count(config) == count_params(
            jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg)))
    # the published model: 33.4B, 3.0B a token, its name (33.4B-A3B). The
    # gate a head (assumed) is 4.7M of it; an element-wise gate [d, H x D]
    # would add 0.63B and make it 34.1B
    published = {k: v for k, v in cell.items() if k != "share"}
    published.update(cell["published"])
    assert round(laguna.param_count(published) / 1e9, 1) == 33.4
    assert round(laguna.active_param_count(published) / 1e9, 1) == 3.0
    gates = 2048 * (10 * 48 + 30 * 64)
    assert gates == 4_915_200
    assert round((laguna.param_count(published) - gates
                  + 2048 * 128 * (10 * 48 + 30 * 64)) / 1e9, 1) == 34.1


def test_flops_and_kernel_arithmetic_count_what_is_computed_here():
    from benchmark.families import laguna
    from benchmark.kernels import gqa_attention, window_attention
    cell = _read("benchmark", "configs", "laguna-xs.2.json")
    mix = _read("benchmark", "traffic", "train_b2_s8192_dp.json")
    d, s, w = 2048, 8192, 512
    full = 2 * d * 48 * 128 + 2 * d * 8 * 128 + d * 48
    sliding = 2 * d * 64 * 128 + 2 * d * 8 * 128 + d * 64
    active = (2 * full + 3 * sliding + 3 * d * 8192
              + 4 * (d * 256 + 3 * d * 512 + 8 * 32 / 256 * 3 * d * 512)
              + d * 12544)
    pairs = s * w - w * (w - 1) // 2
    assert window_attention.band_pairs(s, w) == pairs == 4_063_488
    assert laguna.train_flops_per_token(cell, s) == pytest.approx(
        6.0 * active + 12.0 * 128 * (2 * 48 * s / 2 + 3 * 64 * pairs / s))
    assert laguna.forward_flops_per_token(cell, s) == pytest.approx(
        0.80e9, rel=0.01)                  # ISSUE 37's ~0.80 GFLOP a token
    assert laguna.attention_call(cell, mix) == {
        "batch": 2, "heads": 48, "kv_heads": 8, "seq": s, "head_dim": 128}
    assert laguna.window_call(cell, mix) == {
        "batch": 2, "heads": 64, "kv_heads": 8, "seq": s, "head_dim": 128,
        "window": w}
    # the full layers' calls are counted at 48 on 8
    assert gqa_attention.flash_fwd(cell, mix)[0] == 2 * 2 * 48 * s * s * 128
    product = 2.0 * pairs * 128 * 2 * 64
    wide, narrow = 2 * 64 * s * 128 * 2, 2 * 8 * s * 128 * 2
    fwd, dq, dkv = (f(cell, mix) for f in (
        window_attention.flash_win_fwd, window_attention.flash_win_bwd_dq,
        window_attention.flash_win_bwd_dkv))
    assert fwd == (2 * product, 2 * wide + 2 * narrow)
    assert dq[0] + dkv[0] == 5 * product          # the backward's five
    assert dq[1] == 3 * wide + 2 * narrow
    assert dkv[1] == 2 * wide + 4 * narrow        # dK, dV at 8 heads


@pytest.mark.parametrize("seq,window", [(64, 8), (64, 1), (32, 32), (16, 40)])
def test_band_pairs_is_a_brute_force_count(seq, window):
    """The rooflines' S W - W (W - 1) / 2 against a count of the pairs
    mha_reference's mask keeps, and the window kernels' FLOPs with it."""
    from benchmark.kernels import window_attention
    kept = sum(1 for i in range(seq) for j in range(seq)
               if 0 <= i - j < window)
    assert window_attention.band_pairs(seq, window) == kept
    config = {"family": "laguna", "num_hidden_layers": 1,
              "layer_types": ["sliding_attention"],
              "mlp_layer_types": ["sparse"],
              "num_attention_heads_per_layer": [4], "num_attention_heads": 2,
              "num_key_value_heads": 2, "head_dim": 16,
              "sliding_window": window}
    mix = {"global_batch": 3, "seq": seq, "mesh": {"data": 1}}
    flops, moved = window_attention.flash_win_fwd(config, mix)
    assert flops == 2 * (2 * kept * 16) * 3 * 4       # S and PV, every head
    assert moved == 2 * (3 * seq * 16 * 2) * (4 + 2)  # Q, O at 4; K, V at 2


@pytest.mark.parametrize("strategy,column,row", [
    ("tp", (None, "tensor"), ("tensor", None)),
    ("tp_fsdp", ("fsdp", "tensor"), ("tensor", "fsdp"))])
def test_every_new_leaf_gets_its_rule(jax_cpu, tiny, strategy, column, row):
    jax = jax_cpu
    from jax.sharding import PartitionSpec as P
    from benchmark.families import laguna
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    cfg = GPTConfig(**laguna.gpt_config_kwargs(tiny))
    params = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                      devices=jax.devices()[:4])
    specs = jax.tree_util.tree_map(
        lambda s: s.spec,
        strategy_from_name(strategy).param_shardings(mesh, params))
    # a window layer's matrices find attention's rules; the gate's columns
    # are heads
    for attn in (specs["layers"][0]["attn"],
                 specs["layers"][1]["window_attn"]):
        assert attn["wq"] == attn["wk"] == attn["wv"] == attn["wg"] \
            == P(*column)
        assert attn["wo"] == P(*row)


def test_sharded_step_equals_one_device(jax_cpu, tiny):
    """One step of the whole tiny model on fsdp=2 x tensor=2 (a key/value
    head with its three or four query heads and their gates on a shard of
    `tensor`, the kernels per shard) equals the one-device step."""
    jax = jax_cpu
    import jax.numpy as jnp
    import optax
    from benchmark.families import laguna
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import init_train_state, make_train_step
    cfg = GPTConfig(**laguna.gpt_config_kwargs(tiny), dtype=jnp.float32,
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
    ({"attention": "ring"}, "'window' layer.*attention='ring'"),
    ({"attention_window": 0}, "attention_window=0"),
    ({"window_heads": 7}, "n_kv_heads=2 does not divide n_heads=7"),
    ({"qk_head_norm": True}, "'window' layer.*qk_norm or qk_head_norm"),
    ({"layer_kinds": ("attention", "swa", "swa", "swa", "attention")},
     "'attention' | 'conv' | 'window'"),
], ids=["ring", "no_window", "window_heads", "head_norm", "kinds_names"])
def test_the_configuration_refuses_by_name(tiny, change, says):
    from benchmark.families import laguna
    from ray_tpu.models.gpt import GPTConfig
    with pytest.raises(ValueError, match=says):
        GPTConfig(**dict(laguna.gpt_config_kwargs(tiny), **change))


def test_head_dim_is_a_field_and_defaults_to_the_hidden_size_over_heads():
    import dataclasses
    from ray_tpu.models.gpt import GPTConfig
    assert GPTConfig().head_dim == 64 and GPTConfig.tiny().head_dim == 32
    wide = GPTConfig(d_model=128, n_heads=6, n_kv_heads=2, head_dim=32)
    assert wide.head_dim == 32 and wide.qk_head_dim == 32
    assert dataclasses.replace(wide, n_layers=3).head_dim == 32
    assert wide.heads_of("window") == wide.heads_of("attention") == 6
    assert wide.rope_of("window") == wide.rope_of("attention")
    assert wide.rope_of("attention").plain


@pytest.mark.parametrize("change,mesh_axes,says", [
    ({"layer_kinds": ("window",) * 5, "n_experts": 0, "dense_layers": 0,
      "experts_held": None, "attention_gate": False}, {"pipeline": 1},
     "no sliding-window layers"),
    ({"layer_kinds": None, "n_experts": 0, "dense_layers": 0,
      "experts_held": None}, {"pipeline": 1},
     "no rule for a gate a head"),
], ids=["window_layers", "gate"])
def test_pipeline_refuses_by_name(jax_cpu, tiny, change, mesh_axes, says):
    jax = jax_cpu
    from benchmark.families import laguna
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import make_gpt_pp_loss
    cfg = GPTConfig(**dict(laguna.gpt_config_kwargs(tiny), **change))
    n = int(np.prod(list(mesh_axes.values())))
    mesh = build_mesh(MeshConfig(data=1, **mesh_axes),
                      devices=jax.devices()[:n])
    with pytest.raises(ValueError, match=says):
        make_gpt_pp_loss(cfg, mesh, num_microbatches=2)


def _kernel_calls(jax, jaxpr, rematted=False):
    """(kernel name, whether it runs in a layer's recompute pass: under a
    checkpoint equation of the backward) for every pallas_call of jaxpr
    (tests/test_flash_remat.py's walk)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], rematted
        inner = rematted or eqn.params.get("differentiated", False)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_calls(jax, sub, inner)


def test_the_window_engages_and_its_forward_runs_once_a_layer(jax_cpu, tiny):
    """The step's kernel calls are the counter: 3 of each flash_win_* and 2
    of each flash_*, and under remat_policy="full" neither forward kernel
    in a recompute pass (FLASH_OUT and FLASH_LSE are named in the window
    kernel's forward rule too)."""
    jax = jax_cpu
    from collections import Counter
    from benchmark.families import laguna
    from ray_tpu.models.gpt import gpt_init, gpt_loss
    cfg = laguna._train_config(tiny)
    assert cfg.remat_policy == "full"
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    tokens = np.zeros((2, 129), np.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: gpt_loss(p, {"tokens": tokens}, cfg)))(params)
    calls = Counter(_kernel_calls(jax, jaxpr.jaxpr))
    for kind, layers in (("flash_win", 3), ("flash", 2)):
        assert calls[(kind + "_fwd", False)] == layers
        assert calls[(kind + "_fwd", True)] == 0
        for kernel in (kind + "_bwd_dq", kind + "_bwd_dkv"):
            # (the backward pass holds them inside the checkpoint equation)
            assert calls[(kernel, False)] + calls[(kernel, True)] == layers
    # what XLA runs is still recomputed: the window layers' q (8 heads of 32
    # fill lane tiles; 6 and 2 take rope_split's jnp form), split forward
    # and again in the backward's checkpoint
    assert calls[("rope_split", False)] == calls[("rope_split", True)] == 3


def test_the_new_scopes_are_regions_and_reach_the_compiled_step(jax_cpu,
                                                                tiny):
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import laguna
    from ray_tpu.models.gpt import gpt_init, gpt_loss
    from ray_tpu.util import profiling
    assert {"attn_window", "attn_gate"} <= set(profiling.REGIONS)
    cfg = laguna._train_config(tiny)
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    text = jax.jit(jax.grad(lambda p, t: gpt_loss(p, {"tokens": t}, cfg))
                   ).lower(params, jnp.zeros((2, 129), jnp.int32)
                           ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    regions = {profiling._last_of(n, profiling.REGIONS) for n in names}
    assert {"attn_window", "attn_gate", "attn_proj", "attn_core", "attn_out",
            "moe", "moe_route", "moe_shared", "mlp"} <= regions
    # the window layers' kernels are attn_window's, the full layers'
    # attn_core's; the gate's matmul is attn_gate's, not attn_out's
    for n in names:
        if "flash_win_" in n:
            assert profiling._last_of(n, profiling.REGIONS) == "attn_window"
        elif "flash_" in n:
            assert profiling._last_of(n, profiling.REGIONS) == "attn_core"
    assert any("attn_gate/bsd,dh->bsh" in n for n in names)


def test_configuration_file_keeps_the_catalog_and_states_the_cut():
    cell = _read("benchmark", "configs", "laguna-xs.2.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cell["source"])
    changed = {k for k, v in row["config"].items() if cell.get(k, "?") != v}
    assert changed == set(cell["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types", "num_attention_heads_per_layer"}
    assert cell["published"] == {k: row["config"][k] for k in cell["reduced"]}
    # published layers 0..4: the leading dense layer, then a whole period
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert cell[key] == row["config"][key][:5]
    assert cell["layer_types"] == ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert cell["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert cell["share"]["chips_per_layer"] * cell["num_experts"] \
        == cell["share"]["num_experts"] == 256
    assert cell["share"]["chips_per_layer"] * cell["vocab_size"] == 100352
    assert {"gating", "router_score", "sequence_length"} <= set(
        cell["assumed"])
    bench = _read("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["name"])
    assert entry["reduced"] == cell["reduced"]
    assert entry["source"] == cell["source"]
    peak = cell["reduced_why"]["memory_peak_bytes"]
    assert 0.25 * 16.91e9 < peak["chip"] < 16.91e9


# ---------------------------------------------------------------------------
# (f) what was there is what it was
# ---------------------------------------------------------------------------

# sha256 (16 digits) of each cell's train step lowered for the TPU at the
# real sizes, Mosaic calls in it (their backend_config masked, locations
# stripped), nothing compiled: the parent's (708aa58), read before this
# PR's first edit. A PR that means to change a cell's step replaces its
# line; one that does not finds out here. PR 39 (parent 8427b12) meant to
# change kanana's (f5d072a05f3156d1 there: its latent block reaches the
# flash kernels through ops/rope.py's latent kernels) and no other; laguna's
# is its parent's.
LOWERED = {
    # PR 51 recorded every one-chip cell anew and the four-chip cell not:
    # on one device the embedding's lookup is ops/embedding.py's (a gather
    # of the master rows, `embed_grad` backward), under the four-chip mesh
    # it is the parent's expression and the step the parent's text. Before
    # it: gpt2s 0a5e354a41f2d309, lfm2 6d8075c1983c7f5a, olmoe
    # de1ac5dddca614d0, kanana ccd30b735ee79374, laguna 2c9cca064dffa677,
    # keye c7b4fffd346aa0f2, solar 830c2fd63a15f131.
    # heads of 64: untouched by PR 48 (which is the proof
    # that the three cells bypass it: the kernels write [B * H, S, 64], XLA
    # turns it under `attn_out`, outside the shard_map; the first two also
    # untouched by PR 42, having no sparse layer)
    "gpt2s_train_1chip": "e83fa754d169a879",
    "smollm17_train_4chip": "3d347ff7870a2d4a",
    "lfm2_train_1chip": "ee4dcfeb681f3aa3",
    # heads (v's) of 128, recorded anew by PR 48: the flash kernels write o
    # and read dO as [B, S, H * 128], `wo` reads that as it is, delta and a
    # gate a head go through `head_columns` (a875c8421b01b065,
    # 64dedc5a37df64cf, a13b1de35328fc71, 2420d0b4f00749c5 and
    # aa90d217a4905e58 before it: PR 42's masters in `moe_gmm`, and at
    # solar PR 47's `kda_fwd` / `kda_bwd`)
    "olmoe_train_1chip": "37f86a82ad7f1fa7",
    "kanana2_train_1chip": "8e6cd298cd0a8c16",
    "laguna_train_1chip": "e425c199e1b22258",
    "keye2_train_1chip": "b478ecfb41cd7a16",
    "solar2_train_1chip": "42d57e72e853172f",
}


@pytest.mark.timeout(600)
@pytest.mark.parametrize("cell", list(LOWERED))
def test_the_cells_that_were_there_lower_to_the_same_step(jax_cpu,
                                                          monkeypatch, cell):
    """The cells' programs are the text that was recorded: a PR that means
    to change a cell's program records its new hash above and says so."""
    jax = jax_cpu
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from benchmark import model
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train import train_step as ts
    monkeypatch.setattr(attention, "_default_interpret", lambda: False)
    bench = _read("BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    config = _read(next(c for c in bench["configs"]
                        if c["name"] == entry["config"])["file"])
    mix = _read("benchmark", "traffic", entry["traffic"] + ".json")
    program = model.family(config).program(config)
    mesh = build_mesh(MeshConfig(**mix["mesh"]),
                      devices=jax.devices()[:entry["chips"]])
    strategy = strategy_from_name(mix["strategy"])
    optimizer = optax.adamw(config["train"]["learning_rate"])
    params = jax.eval_shape(lambda: program.init(jax.random.PRNGKey(0)))
    shardings = strategy.param_shardings(mesh, params)

    def place(tree, sh):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sh)
    state = ts.TrainState(
        place(params, shardings),
        place(jax.eval_shape(optimizer.init, params),
              ts._opt_state_shardings(optimizer, params, shardings, mesh)),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (mix["global_batch"], mix["seq"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, strategy.batch_spec))}
    step = ts.make_train_step(
        lambda p, b: program.loss(p, b, mesh,
                                  strategy.activation_sharding(mesh)),
        optimizer, mesh, strategy, sample_params=params)
    text = step.trace(state, batch).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=False)
    text = re.sub(r"loc\([^)]*\)", "", text)
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = "..."', text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == LOWERED[cell]


# ---------------------------------------------------------------------------
# (g) the benchmark's own check of the cell that needs no chip
# ---------------------------------------------------------------------------

@pytest.mark.timeout(600)
def test_the_cell_rehearses():
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # rehearse.py asks for its own devices
    proc = subprocess.run(
        [sys.executable, "benchmark/rehearse.py", "laguna_train_1chip",
         "--seconds", "2"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "rehearsal passed" in proc.stdout
