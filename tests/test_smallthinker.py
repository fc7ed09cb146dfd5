"""A router that reads the layer's normed input ahead of the mixer
(`route_from="input"`, scope `route_ahead`), ReLU-gated experts
(`gate_activation="relu"`) and a kind of attention layer that rotates nothing
beside one that does (models/gpt.py) against the plain float32 reference of
benchmark/families/smallthinker.py, at a small size on the CPU: seeded random
weights, the kernels in interpret mode."""

import contextlib
import copy
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


def _deep(config, periods=2):
    """`periods` of the published pattern: both kinds of layer and both
    rotations occur that often."""
    return dict(config, num_hidden_layers=4 * periods,
                sliding_window_layout=[0, 1, 1, 1] * periods,
                rope_layout=[0, 1, 1, 1] * periods)


@pytest.fixture(scope="module")
def tiny():
    """benchmark/rehearsal/configs/tiny-smallthinker.json, two periods
    deep: a full layer that rotates nothing, then three sliding layers of
    window 24 that rotate, twice; 14 query heads of 32 on 2 key/value heads
    (groups of 7); experts 4..7 of 16 held, 4 a token, ReLU-gated, routed
    from the layer's normed input."""
    return _deep(_read("benchmark", "rehearsal", "configs",
                       "tiny-smallthinker.json"))


# ---------------------------------------------------------------------------
# (a) the whole stack against the family's reference
# ---------------------------------------------------------------------------

def _program(jax, config, attention, dtype=None, **change):
    import jax.numpy as jnp
    from benchmark.families import smallthinker
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    cfg = GPTConfig(**dict(smallthinker.gpt_config_kwargs(config), **change),
                    attention=attention, dtype=dtype or jnp.float32,
                    remat_policy="none")
    params = gpt_init(jax.random.PRNGKey(3), cfg)
    for i, layer in enumerate(params["layers"]):
        # a router with an opinion: at the init's 0.02 every logit is 0
        layer["moe"]["router"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(100 + i), layer["moe"]["router"].shape)
    tokens = np.random.default_rng(5).integers(
        0, config["vocab_size"], (2, 129), dtype=np.int32)
    return cfg, params, jnp.asarray(tokens)


def _reference_logits(jax, config, params, tokens):
    from benchmark.families import smallthinker
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: smallthinker.reference_logits(
            p, t[:, :-1], config))(params, tokens)


@pytest.fixture(scope="module")
def reference(jax_cpu, tiny):
    jax = jax_cpu
    from benchmark.families import smallthinker
    _cfg, params, tokens = _program(jax, tiny, "reference")
    logits = _reference_logits(jax, tiny, params, tokens)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: smallthinker.reference_loss(p, t, tiny)))(
            params, tokens)
    return logits, loss, grads


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_logits_loss_and_gradients_match_the_reference(jax_cpu, tiny,
                                                       reference, attention):
    """Two periods of (full without rotation, window x 3 rotated), groups
    of 7 query heads a key/value head, the routing carried across the
    mixer, ReLU-gated experts of which a quarter is held, in float32: every
    logit to 5e-5 (tests/test_linear_attention.py's tolerance and its
    reason: float32 sums in another order, here the flash kernels' blocks
    and the grouped matmuls' tiles against whole rows) and the whole tree
    of gradients."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_forward, gpt_loss_and_aux
    cfg, params, tokens = _program(jax, tiny, attention)
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
    assert 0.0 < float(aux["expert_hidden_zero_share"]) < 1.0
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(ref_grads)):
        assert np.any(np.asarray(r)), jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            g, r, atol=2e-5 * max(1.0, float(np.abs(r).max())),
            err_msg=jax.tree_util.keystr(path))


@contextlib.contextmanager
def _patched(module, **names):
    kept = {name: getattr(module, name) for name in names}
    for name, value in names.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in kept.items():
            setattr(module, name, value)


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
def test_the_reference_tells_each_fault_apart(jax_cpu, tiny, reference,
                                              fault):
    """What `program_check` rests on: the reference with one mechanism
    changed gives other logits, and the program (held to the sound
    reference to 5e-5 above) is as far from it. A window of 23 and of 25
    for 24 stand in for the cell's 4095 and 4097, which the chip's bounds
    cannot tell."""
    jax = jax_cpu
    import jax.numpy as jnp
    _cfg, params, tokens = _program(jax, tiny, "reference")
    patch, config = faulty_reference(fault, tiny)
    with patch:
        faulty = _reference_logits(jax, config, params, tokens)
    assert float(jnp.abs(faulty - reference[0]).max()) > 1e-3


def test_bfloat16_step_passes_the_per_token_check(jax_cpu, tiny):
    """reference_loss with a `program_check` answers the loss where the
    program's own forward (bf16, both kinds' flash kernels, the
    grouped-matmul kernels) agrees with the reference token by token, and
    nan where one of the three bounds is broken."""
    jax = jax_cpu
    from benchmark.families import smallthinker
    _cfg, params, tokens = _program(jax, tiny, "flash")
    loose = {"logprob_median_tol": 0.08, "logprob_rms_tol": 0.5,
             "logprob_p99_tol": 1.0}

    def loss_under(check):
        config = dict(tiny, program_check=check) if check else tiny
        with jax.default_matmul_precision("highest"):
            return float(jax.jit(lambda p, t: smallthinker.reference_loss(
                p, t, config))(params, tokens))
    plain = loss_under(None)
    assert loss_under(loose) == plain
    for bound in loose:
        assert np.isnan(loss_under(dict(loose, **{bound: 1e-6}))), bound


# ---------------------------------------------------------------------------
# (b) the routing is worked out once, ahead of the mixer
# ---------------------------------------------------------------------------

def _primitives(jax, jaxpr, name):
    """Equations of primitive `name` anywhere in jaxpr."""
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _primitives(jax, sub, name)
    return found


@pytest.mark.parametrize("route_from", ["input", "mixed"])
def test_the_router_runs_once_a_layer_wherever_it_reads(jax_cpu, tiny,
                                                        route_from):
    """One top-k a layer in the forward, under either setting: `_route` is
    called once, with the tensor the configuration names, and there is no
    second sparse block."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_forward
    cfg, params, tokens = _program(jax, tiny, "reference",
                                   route_from=route_from)
    jaxpr = jax.make_jaxpr(lambda p, t: gpt_forward(p, t, cfg))(
        params, tokens[:, :-1]).jaxpr
    assert _primitives(jax, jaxpr, "top_k") == cfg.n_layers


def test_routing_from_the_input_is_not_routing_from_the_mixed_stream(
        jax_cpu, tiny, reference):
    """The switch changes the result: the same weights routed from the
    stream after the mixer (today's default) give other logits, those of
    the reference's matching fault."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import gpt_forward
    cfg, params, tokens = _program(jax, tiny, "reference",
                                   route_from="mixed")
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, t: gpt_forward(p, t, cfg))(
            params, tokens[:, :-1])
    assert float(jnp.abs(logits - reference[0]).max()) > 1e-3
    patch, config = faulty_reference("router_fed_the_mixed_stream", tiny)
    with patch:
        faulty = _reference_logits(jax, config, params, tokens)
    np.testing.assert_allclose(logits, faulty, atol=5e-5)


def test_the_route_ahead_scope_reaches_the_compiled_step(jax_cpu, tiny):
    """`route_ahead` is a region of the trace's vocabulary, holds the
    router's product, its top-k and the slots' order (their sorts), and
    `moe_route` keeps what needs the rows; under remat_policy="full" the
    step differentiates through the carried routing."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import smallthinker
    from ray_tpu.models.gpt import gpt_init, gpt_loss
    from ray_tpu.util import profiling
    assert "route_ahead" in profiling.REGIONS
    cfg = smallthinker._train_config(tiny)
    assert cfg.remat_policy == "full" and cfg.route_from == "input"
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    text = jax.jit(jax.grad(lambda p, t: gpt_loss(p, {"tokens": t}, cfg))
                   ).lower(params, jnp.zeros((2, 129), jnp.int32)
                           ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    regions = {profiling._last_of(n, profiling.REGIONS) for n in names}
    assert {"route_ahead", "moe", "moe_route", "attn_window", "attn_core",
            "attn_proj", "attn_out"} <= regions
    ahead = {n for n in names
             if profiling._last_of(n, profiling.REGIONS) == "route_ahead"}
    assert any("bsd,de->bse" in n for n in ahead)       # the router
    assert any("top_k" in n for n in ahead)
    assert any("sort" in n for n in ahead)              # the slots' order
    later = {n for n in names
             if profiling._last_of(n, profiling.REGIONS) == "moe_route"}
    assert later and not any("top_k" in n for n in later)


# ---------------------------------------------------------------------------
# (c) the share: the parts add up to the whole
# ---------------------------------------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(jax_cpu):
    """model-configs guide, section 4: a whole sliding-window layer,
    routing ahead of attention, attention and residual included. Every chip
    computes the router, attention and the residual alike, so they count
    once; what the four ranks' experts add (0..3, 4..7, 8..11, 12..15: each
    the routed part of its own four) adds up with them to the uncut
    reference's layer."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import smallthinker as st
    from ray_tpu.models.gpt import GPTConfig, Setting, gpt_init, layer_fn
    tiny = _read("benchmark", "rehearsal", "configs",
                 "tiny-smallthinker.json")
    whole = copy.deepcopy(tiny)
    del whole["share"]
    whole["moe_num_primary_experts"] = 16
    full_cfg = GPTConfig(**st.gpt_config_kwargs(whole), dtype=jnp.float32,
                         attention="reference", remat_policy="none")
    assert full_cfg.experts_held is None
    layer = gpt_init(jax.random.PRNGKey(7), full_cfg)["layers"][2]
    assert sorted(layer) == ["ln1", "ln2", "moe", "window_attn"]
    layer["moe"]["router"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(8), (128, 16))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 128), jnp.float32)

    def before_the_experts(h):
        n1 = st._norm(h, layer["ln1"]["scale"], 1e-6)
        return h + st.reference_attention(layer["window_attn"], n1, whole,
                                          1, 1)

    with jax.default_matmul_precision("highest"):
        alike = jax.vmap(before_the_experts)(x)
        want = jax.vmap(lambda h: st.reference_layer(layer, h, whole, 1, 1))(x)
        parts, held_share = [], 0.0
        for rank in range(4):
            cut = dict(tiny, share=dict(tiny["share"], rank=rank))
            cfg = GPTConfig(**st.gpt_config_kwargs(cut), dtype=jnp.float32,
                            attention="reference", remat_policy="none")
            assert cfg.experts_held == (4 * rank, 4)
            mine = dict(layer, moe=dict(layer["moe"], **{
                name: layer["moe"][name][4 * rank:4 * rank + 4]
                for name in ("w_gate", "w_up", "w_down")}))
            out, stats = layer_fn(cfg, 64, Setting())(x, mine)
            # attention and the residual, the same on every chip, taken off
            parts.append(out - alike)
            held_share += float(stats["expert_slots_held_share"])
    np.testing.assert_allclose(alike + sum(parts), want, atol=5e-5)
    assert abs(held_share - 1.0) < 1e-6
    # and a part is not the whole: the absent experts' sum is left out
    assert float(jnp.abs(alike + parts[0] - want).max()) > 1e-2


# ---------------------------------------------------------------------------
# (d) statistics, arithmetic, rules, refusals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held", [None, (4, 4)], ids=["all", "a_share"])
def test_hidden_zero_share_is_a_count_by_hand(jax_cpu, held):
    """`expert_hidden_zero_share`: of the hidden units relu(gate) of the
    token-slots whose expert is here, the share that is exactly 0, counted
    slot by slot with numpy; the padding rows of the row space (zeros all)
    are not in it. Rows of x are zeroed so that whole slots count."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import GPTConfig, Setting, _moe_block, gpt_init
    cfg = GPTConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                    d_ff=16, n_experts=8, expert_top_k=2, experts_held=held,
                    gate_activation="relu", dtype=jnp.float32,
                    remat_policy="none")
    layer = gpt_init(jax.random.PRNGKey(0), cfg)["layers"][0]
    layer["moe"]["router"] = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32), jnp.float32)
    x = x.at[:, ::5].set(0.0)            # a zero row: every unit of it is 0
    _, stats = jax.jit(lambda l, x: _moe_block(l, x, cfg, Setting()))(
        layer, x)
    logits = np.asarray(x).reshape(-1, 32) @ np.asarray(
        layer["moe"]["router"])
    chosen = np.argsort(-logits, axis=1, kind="stable")[:, :2]
    first, count = held or (0, 8)
    zeros = units = 0
    for token, experts in enumerate(chosen):
        for e in experts:
            if first <= e < first + count:
                gate = np.asarray(x).reshape(-1, 32)[token] @ np.asarray(
                    layer["moe"]["w_gate"][e - first])
                zeros += int(np.sum(np.maximum(gate, 0.0) == 0.0))
                units += gate.size
    assert units and 0.5 < zeros / units < 0.8
    assert float(stats["expert_hidden_zero_share"]) == pytest.approx(
        zeros / units, abs=1e-6)


def test_a_silu_layer_has_no_such_statistic(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import GPTConfig, Setting, _moe_block, gpt_init
    cfg = GPTConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                    d_ff=16, n_experts=4, dtype=jnp.float32)
    layer = gpt_init(jax.random.PRNGKey(0), cfg)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 32), jnp.float32)
    _, stats = _moe_block(layer, x, cfg, Setting())
    assert "expert_hidden_zero_share" not in stats
    assert stats["expert_rows_bounded"] == 1.0


def test_the_dense_mlp_takes_the_configurations_activation(jax_cpu):
    """`_mlp_block` shares the line: relu(gate) * up under "relu"."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import GPTConfig, Setting, _mlp_block, gpt_init
    cfg = GPTConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                    d_ff=48, gate_activation="relu", dtype=jnp.float32)
    m = gpt_init(jax.random.PRNGKey(0), cfg)["layers"][0]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y = _mlp_block(m, x, cfg, Setting())
        want = (jnp.maximum(x @ m["w_gate"], 0.0) * (x @ m["w_up"])
                ) @ m["w_down"]
    np.testing.assert_allclose(y, want, atol=1e-6)
    # the derivative at 0 is 0: a zero input moves nothing through the gate
    g = jax.grad(lambda x: jnp.sum(_mlp_block(m, x, cfg, Setting())))(
        jnp.zeros_like(x))
    assert not np.any(np.asarray(g))


def test_param_count_is_the_published_model_and_the_programs_tree(jax_cpu,
                                                                  tiny):
    jax = jax_cpu
    from benchmark.families import smallthinker as st
    from ray_tpu.models.gpt import GPTConfig, count_params, gpt_init
    cell = _read("benchmark", "configs", "smallthinker-21b-a3b.json")
    assert st.param_count(cell) == 656_529_920
    assert st.share(cell) == (0, 16, 64)
    for config in (cell, tiny):
        cfg = GPTConfig(**st.gpt_config_kwargs(config))
        assert st.param_count(config) == count_params(
            jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg)))
    # the published model: 21.5B, of which 3.7B a token with the embedding
    # and the head (0.78B) and 2.9B without: its name (21B-A3B)
    published = {k: v for k, v in cell.items() if k != "share"}
    published.update(cell["published"])
    assert round(st.param_count(published) / 1e9, 1) == 21.5
    active = st.active_param_count(published)
    assert round(active / 1e9, 1) == 3.7
    assert round((active - 2 * 151936 * 2560) / 1e9, 1) == 2.9


def test_flops_and_kernel_arithmetic_count_what_is_computed_here():
    from benchmark.families import smallthinker as st
    from benchmark.kernels import gqa_attention, window_attention
    cell = _read("benchmark", "configs", "smallthinker-21b-a3b.json")
    mix = _read("benchmark", "traffic", "train_b1_s16384_dp.json")
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


def test_sharded_step_equals_one_device(jax_cpu, tiny):
    """One step of the two-period model on fsdp=2 x tensor=2 (a key/value
    head with its seven query heads on a shard of `tensor`, the slots'
    order worked out per shard ahead of the mixer and handed to the
    experts' shard_map after it) equals the one-device step."""
    jax = jax_cpu
    import jax.numpy as jnp
    import optax
    from benchmark.families import smallthinker
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import init_train_state, make_train_step
    cfg = GPTConfig(**smallthinker.gpt_config_kwargs(tiny),
                    dtype=jnp.float32, attention="flash")
    assert cfg.remat_policy == "full"
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


def test_a_scanned_stack_carries_the_routing_as_the_loop_does(jax_cpu, tiny):
    """What a stage of parallel/pipeline.py does with the block: one
    `layer_fn` with no mesh, scanned over stacked layers. A stack of full
    layers alone (the pipeline has no window layers), routed from the
    input, under remat_policy="full": outputs and gradients are the loop's
    over the layers' list."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import smallthinker
    from ray_tpu.models.gpt import GPTConfig, Setting, gpt_init, layer_fn
    kwargs = dict(smallthinker.gpt_config_kwargs(tiny), n_layers=3,
                  layer_kinds=None)
    cfg = GPTConfig(**kwargs, dtype=jnp.float32, attention="flash")
    layers = gpt_init(jax.random.PRNGKey(3), cfg)["layers"]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 128), jnp.float32)
    block = layer_fn(cfg, 64, Setting())

    def looped(layers, x):
        for layer in layers:
            x, _ = block(x, layer)
        return jnp.sum(x * x)

    def scanned(stacked, x):
        x, _ = jax.lax.scan(lambda x, layer: (block(x, layer)[0], None),
                            x, stacked)
        return jnp.sum(x * x)

    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(looped))(layers, x)
        got, got_grads = jax.jit(jax.value_and_grad(scanned))(stacked, x)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    want_grads = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                        *want_grads)
    for g, w in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("change,says", [
    ({}, "layer 1's parameters are not layer 0's"),
    ({"layer_kinds": ("window",) * 8}, "hands back statistics"),
    ({"layer_kinds": ("window",) * 8, "n_experts": 0, "experts_held": None},
     "no sliding-window layers"),
    ({"layer_kinds": None}, "hands back statistics .*expert_rows_bounded"),
], ids=["the_period", "window_experts", "window_layers", "experts"])
def test_pipeline_refuses_by_name(jax_cpu, tiny, change, says):
    """The refusals parallel/pipeline.py gives today, kept: a period of
    kinds is layers that are not alike; window layers it refuses by name;
    a sparse stack hands back the router's statistics, wherever it routes
    from."""
    jax = jax_cpu
    from benchmark.families import smallthinker
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import make_gpt_pp_loss
    cfg = GPTConfig(**dict(smallthinker.gpt_config_kwargs(tiny), **change))
    mesh = build_mesh(MeshConfig(data=1, pipeline=1),
                      devices=jax.devices()[:1])
    with pytest.raises(ValueError, match=says):
        make_gpt_pp_loss(cfg, mesh, num_microbatches=2)


@pytest.mark.parametrize("change,says", [
    ({"route_from": "attention"}, "route_from='attention'"),
    ({"gate_activation": "gelu"}, "gate_activation='gelu'"),
    ({"index_topk": 8, "index_heads": 2, "index_head_dim": 16,
      "layer_kinds": None}, "rotate nothing.*not for an indexer"),
    ({"kv_latent_dim": 32, "qk_nope_dim": 16, "qk_rope_dim": 16,
      "v_head_dim": 16, "layer_kinds": None, "n_kv_heads": 0},
     "rotate nothing.*not for a latent block"),
], ids=["route_from", "activation", "indexer", "latent"])
def test_the_configuration_refuses_by_name(tiny, change, says):
    from benchmark.families import smallthinker
    from ray_tpu.models.gpt import GPTConfig
    with pytest.raises(ValueError, match=says):
        GPTConfig(**dict(smallthinker.gpt_config_kwargs(tiny), **change))


def test_a_kinds_rotation_may_be_absent_beside_one_that_is_there():
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.ops.rope import RopeSpec
    none, some = RopeSpec(theta=1.5e6, rotated=0.0), RopeSpec(theta=1.5e6)
    cfg = GPTConfig(rope=none, window_rope=some)
    assert cfg.rope_of("attention") is None
    assert cfg.rope_of("window") == some
    cfg = GPTConfig(rope=some, window_rope=none)
    assert cfg.rope_of("attention") == some and cfg.rope_of("window") is None
    assert GPTConfig(use_rope=False, window_rope=some).rope_of(
        "window") is None
    # a partial rotation is still one
    assert GPTConfig(rope=RopeSpec(rotated=0.5)).rope_of(
        "attention").rotated == 0.5


def test_the_unrotated_kind_builds_no_table_and_rotates_nothing(jax_cpu,
                                                                tiny):
    """No cos / sin is computed for a stack of full layers alone (no table
    is built), one table for the window layers beside them; on the flash
    path the full layers' q and k are split into heads with no table
    handed to `rope_split`."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import smallthinker
    from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init
    kwargs = smallthinker.gpt_config_kwargs(tiny)
    tokens = jnp.zeros((1, 64), jnp.int32)

    def trig(**change):
        cfg = GPTConfig(**dict(kwargs, **change), dtype=jnp.float32,
                        attention="flash", remat_policy="none")
        params = jax.eval_shape(
            lambda: gpt_init(jax.random.PRNGKey(0), cfg))
        jaxpr = jax.make_jaxpr(lambda p, t: gpt_forward(p, t, cfg))(
            params, tokens).jaxpr
        return (_primitives(jax, jaxpr, "cos"),
                _primitives(jax, jaxpr, "sin"))
    assert trig(n_layers=2, layer_kinds=None) == (0, 0)
    assert trig() == (1, 1)
    assert trig(n_layers=2, layer_kinds=("window",) * 2) == (1, 1)


def test_configuration_file_keeps_the_catalog_and_states_the_cut():
    cell = _read("benchmark", "configs", "smallthinker-21b-a3b.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cell["source"])
    changed = {k for k, v in row["config"].items() if cell.get(k, "?") != v}
    assert changed == set(cell["reduced"]) == {
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
        "sliding_window_layout", "rope_layout"}
    assert cell["published"] == {k: row["config"][k] for k in cell["reduced"]}
    # published layers 0..3: one whole period
    for key in ("sliding_window_layout", "rope_layout"):
        assert cell[key] == row["config"][key][:4] == [0, 1, 1, 1]
    share = cell["share"]
    assert share["chips_per_layer"] * cell["moe_num_primary_experts"] \
        == share["moe_num_primary_experts"] == 64
    assert share["chips_per_layer"] * cell["vocab_size"] \
        == share["vocab_size"] == 151936
    assert {"router_input", "router_score", "expert_activation", "rope",
            "sequence_length", "embedding_init_std"} <= set(cell["assumed"])
    for key in ("reduced_why", "departures", "deployment", "train",
                "program_check"):
        assert key in cell, key
    bench = _read("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["name"])
    assert entry["reduced"] == cell["reduced"]
    assert entry["source"] == cell["source"]
    peak = cell["reduced_why"]["memory_peak_bytes"]
    assert 0.25 * 16.91e9 < peak["chip"] < 16.91e9
    workload = next(w for w in bench["workloads"]
                    if w["name"] == "smallthinker_train_1chip")
    assert workload["traffic"] == "train_b1_s16384_dp"
    assert workload["chips"] == 1
    listed = {m["name"] for m in bench["per_layer"]
              if "smallthinker_train_1chip" in m.get("workloads", ())}
    assert {"train_route_ahead_pct", "train_attn_window_pct",
            "train_moe_route_pct", "swa_fwd_roofline", "swa_bwd_dq_roofline",
            "swa_bwd_dkv_roofline", "gqa_fwd_roofline",
            "gqa_bwd_dq_roofline", "gqa_bwd_dkv_roofline"} <= listed


# ---------------------------------------------------------------------------
# (e) the benchmark's own check of the cell that needs no chip
# ---------------------------------------------------------------------------

@pytest.mark.timeout(600)
def test_the_cell_rehearses():
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # rehearse.py asks for its own devices
    proc = subprocess.run(
        [sys.executable, "benchmark/rehearse.py", "smallthinker_train_1chip",
         "--seconds", "2"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "rehearsal passed" in proc.stdout
