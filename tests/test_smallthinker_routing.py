"""What smallthinker brings to models/gpt.py, each mechanism alone: a router
that reads the layer's normed input ahead of the mixer (`route_from="input"`,
scope `route_ahead`), ReLU-gated experts (`gate_activation="relu"`) and a
kind of attention layer that rotates nothing beside one that does; and what
smallthinker_train_1chip hands the chip's compiler, for a described v5e: the
flash kernels in groups of 7, its two kinds of attention layer and its whole
step. The family's program against the reference of
benchmark/families/smallthinker.py: tests/test_smallthinker.py."""

import re

import numpy as np
import pytest

from helpers.described_chip import (  # noqa: F401 — fixtures
    attention_layer_gradients, cell_configuration, cell_step, kernel_ops,
    v5e)
from helpers.families import family, tiny  # noqa: F401
from test_smallthinker import FAMILY  # noqa: F401


# ---------------------------------------------------------------------------
# (a) the routing is worked out once, ahead of the mixer
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
    cfg, params, tokens = FAMILY.program(jax, tiny, "reference",
                                   route_from=route_from)
    jaxpr = jax.make_jaxpr(lambda p, t: gpt_forward(p, t, cfg))(
        params, tokens[:, :-1]).jaxpr
    assert _primitives(jax, jaxpr, "top_k") == cfg.n_layers


def test_a_scanned_stack_carries_the_routing_as_the_loop_does(jax_cpu, tiny):
    """What a stage of parallel/pipeline.py does with the block: one
    `layer_fn` with no mesh, scanned over stacked layers. A stack of full
    layers alone (the pipeline has no window layers), routed from the
    input, under remat_policy="full": outputs and gradients are the loop's
    over the layers' list."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import GPTConfig, Setting, gpt_init, layer_fn
    kwargs = dict(FAMILY.module.gpt_config_kwargs(tiny), n_layers=3,
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


# ---------------------------------------------------------------------------
# (b) ReLU-gated experts and their statistic
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


# ---------------------------------------------------------------------------
# (c) a kind that rotates nothing
# ---------------------------------------------------------------------------


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
    from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init
    kwargs = FAMILY.module.gpt_config_kwargs(tiny)
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


# ---------------------------------------------------------------------------
# (d) for a described v5e: the flash kernels in groups of 7, the two kinds
# of attention layer and (imported) the whole step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_flash_kernels_compile_at_16384_positions_in_groups_of_7(v5e,
                                                                  window):
    """smallthinker_train_1chip's two calls, [1, 28 on 4, 16384, 128]: the
    causal kernels and the window kernels at a band of 4096 = two major
    blocks of 2048 (three steps a grid row: the block wholly inside the
    band runs unmasked), groups of 7 query heads a key/value head through
    the index maps and `flash_bwd_dkv`'s walk, o written tokens first at 28
    heads of 128; dK and dV leave at the key/value heads' count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops import attention

    def shape(h):
        return jax.ShapeDtypeStruct((1, h, 16384, 128), jnp.bfloat16,
                                    sharding=SingleDeviceSharding(v5e[0]))
    if window:
        outer, major, _ = attention._block_sizes(16384, 16384, 128).fwd
        assert attention._band_steps(outer, major, window) == 3
    grads = jax.jit(jax.grad(lambda q, k, v: attention.flash_attention_native(
        q, k, v, causal=True, window=window,
        interpret=False).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    compiled = grads.lower(shape(28), shape(4), shape(4)).compile()
    text = compiled.as_text()
    name = "flash_win_" if window else "flash_"
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert len(kernel_ops(text, name + kernel)) == 1, kernel
    dq, dk, dv = compiled.out_info
    assert dq.shape == (1, 28, 16384, 128)
    assert dk.shape == dv.shape == (1, 4, 16384, 128)
    # the forward's output leaves the kernel tokens first: [1, 16384, 28 x 128]
    assert "bf16[1,16384,3584]" in kernel_ops(text, name + "fwd")[0]


@pytest.mark.parametrize("kind,rotates", [("attention", False),
                                          ("window", True)])
def test_a_kind_that_rotates_nothing_compiles_without_a_rotation(
        v5e, monkeypatch, kind, rotates):
    """smallthinker_train_1chip's two kinds of attention layer, [1, 28 on
    4, 16384, 128], value and gradient for one described chip. The full
    layer rotates nothing: no cosine or sine is computed for it, and its
    head splits (`rope_split`, `rope_merge`) take no table. The window layer
    beside it builds one table and hands it to q's and k's, not to v's."""
    from jax.sharding import SingleDeviceSharding
    cfg = cell_configuration(FAMILY.cell, attention="flash")
    seq = 16384
    assert (cfg.rope_of(kind) is not None) == rotates
    text = attention_layer_gradients(monkeypatch, cfg, kind, 1, seq,
                                     SingleDeviceSharding(v5e[0]),
                                     remat=False)
    table = f"f32[{seq},128]"
    splits = kernel_ops(text, "rope_split")
    merges = kernel_ops(text, "rope_merge")
    assert len(splits) == len(merges) == 3                 # q, k, v
    with_table = [op for op in splits + merges if table in op]
    assert len(with_table) == (4 if rotates else 0)        # q and k, each way
    trig = re.findall(r" (?:cosine|sine)\(", text)
    assert len(trig) == (2 if rotates else 0), trig

# Imported last: a module's names are collected in the order they are bound,
# so the chip's compiler gets this file's programs after its own tests have
# run, at another minute of a run than the other families' files.
from helpers.described_chip import (  # noqa: E402,F401
    test_the_cells_that_were_there_lower_to_the_same_step,
    test_cell_step_compiles_under_the_chips_memory,
    test_cell_step_makes_a_heads_dw_where_its_logits_are)


def test_the_route_ahead_scope_reaches_the_compiled_step(cell_step):
    """`route_ahead` is a region of the trace's vocabulary, holds the
    router's product, its top-k and the slots' order (their sorts), and
    `moe_route` keeps what needs the rows; under remat_policy="full" the
    step differentiates through the carried routing. Read off the cell's
    whole step as the chip's compiler leaves it (until PR 73 a compile of
    the tiny step's gradient on the CPU)."""
    from ray_tpu.util import profiling
    assert "route_ahead" in profiling.REGIONS
    cfg = FAMILY.module._train_config(FAMILY.cell_config())
    assert cfg.remat_policy == "full" and cfg.route_from == "input"
    names = set(re.findall(r'op_name="([^"]*)"', cell_step.text))
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
