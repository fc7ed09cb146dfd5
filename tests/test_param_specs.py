"""How a transformer's parameters are divided over the mesh, leaf for leaf:
every parameter of the benchmark's architectures (the eleven rehearsal
configurations here hold every parameter name of their cells) under `tp` and
`tp_fsdp` on fsdp=2 x tensor=2, and the dense one, stacked, under `pp` and
`pp_tp`. The expectations were recorded at PR 42, before
parallel/sharding.py's rule lists became one table (a delta-rule layer's
`kda/*` at PR 46, with its rows): a change of that table
that moves a leaf's PartitionSpec shows here, whichever architecture the
leaf belongs to."""

import json
import os
import re

import pytest

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "rehearsal", "configs")
F, T, E, PL = "fsdp", "tensor", "expert", "pipeline"

# path (a layer's number as *) -> the spec under `tp`, under `tp_fsdp`
COLUMN = ((None, T), (F, T))
ROW = ((T, None), (T, F))
WHOLE_MATRIX = ((None, None), (None, None))
WHOLE_VECTOR = ((None,), (None,))
DEFAULTED = ((None,), (F,))     # no row: P() under tp, FSDP_LARGEST
GSPMD = {
    "embed/table": ((T, None), ((T, F), None)),
    "lm_head": COLUMN,
    "final_norm/scale": DEFAULTED,
    "layers/*/ln1/scale": DEFAULTED,
    "layers/*/ln2/scale": DEFAULTED,
    # (a half under a norm either side: its second scale, and a looped
    # stack's exit gate, one column and its bias: rows of their own, whole)
    "layers/*/ln1_after/scale": WHOLE_VECTOR,
    "layers/*/ln2_after/scale": WHOLE_VECTOR,
    "exit_gate/w": WHOLE_MATRIX, "exit_gate/b": WHOLE_VECTOR,
    "layers/*/attn/wq": COLUMN, "layers/*/attn/wk": COLUMN,
    "layers/*/attn/wv": COLUMN, "layers/*/attn/wo": ROW,
    "layers/*/attn/wg": COLUMN,
    "layers/*/attn/q_norm/scale": DEFAULTED,
    "layers/*/attn/k_norm/scale": DEFAULTED,
    "layers/*/attn/q_head_norm/scale": WHOLE_VECTOR,
    "layers/*/attn/k_head_norm/scale": WHOLE_VECTOR,
    "layers/*/attn/w_kva": WHOLE_MATRIX, "layers/*/attn/w_kvb": COLUMN,
    "layers/*/attn/kv_norm/scale": WHOLE_VECTOR,
    "layers/*/attn/index/wq": COLUMN,
    "layers/*/attn/index/wk": ((None, None), (F, None)),
    "layers/*/attn/index/ww": ((None, None), (F, None)),
    "layers/*/attn/index/k_norm/scale": WHOLE_VECTOR,
    "layers/*/attn/index/k_norm/bias": WHOLE_VECTOR,
    "layers/*/window_attn/wq": COLUMN, "layers/*/window_attn/wk": COLUMN,
    "layers/*/window_attn/wv": COLUMN, "layers/*/window_attn/wo": ROW,
    "layers/*/window_attn/wg": COLUMN,
    "layers/*/conv/w_in": ((None, None, T), (None, F, T)),
    "layers/*/conv/filter": ((T, None), (T, None)),
    "layers/*/conv/w_out": ROW,
    "layers/*/kda/wq": COLUMN, "layers/*/kda/wk": COLUMN,
    "layers/*/kda/wv": COLUMN, "layers/*/kda/w_beta": COLUMN,
    "layers/*/kda/wo": ROW,
    # (a layer under GPTConfig.delta: one projection and one filter for a
    # head's [q | k | v], a decay a head, a gate from a full matrix)
    "layers/*/kda/w_qkv": COLUMN, "layers/*/kda/wg": COLUMN,
    "layers/*/kda/w_decay": COLUMN,
    "layers/*/kda/qkv_conv": ((T, None), (T, None)),
    "layers/*/kda/wf_up": ((None, T), (None, T)),
    "layers/*/kda/wg_up": ((None, T), (None, T)),
    "layers/*/kda/wf_down": ((None, None), (F, None)),
    "layers/*/kda/wg_down": ((None, None), (F, None)),
    "layers/*/kda/q_conv": ((T, None), (T, None)),
    "layers/*/kda/k_conv": ((T, None), (T, None)),
    "layers/*/kda/v_conv": ((T, None), (T, None)),
    "layers/*/kda/a_log": ((T,), (T,)),
    "layers/*/kda/dt_bias": ((T,), (T,)),
    "layers/*/kda/o_norm/scale": WHOLE_VECTOR,
    "layers/*/mlp/w_gate": COLUMN, "layers/*/mlp/w_up": COLUMN,
    "layers/*/mlp/w_down": ROW,
    "layers/*/moe/router": WHOLE_MATRIX,
    "layers/*/moe/router_bias": WHOLE_VECTOR,
    "layers/*/moe/w_gate": ((E, None, T), (E, F, T)),
    "layers/*/moe/w_up": ((E, None, T), (E, F, T)),
    "layers/*/moe/w_down": ((E, T, None), (E, T, F)),
    "layers/*/moe/shared/w_gate": COLUMN, "layers/*/moe/shared/w_up": COLUMN,
    "layers/*/moe/shared/w_down": ROW,
}
# the stacked layout (parallel/pipeline.py) -> under `pp`, under `pp_tp`.
# With a q/k norm, which tiny.json has not: inside a stage's shard_map its
# scale is cut as the projection's columns are.
STACKED_COLUMN = ((PL, None, None), (PL, None, T))
STACKED_ROW = ((PL, None, None), (PL, T, None))
STACKED = {
    "embed/table": ((None, None), (None, None)),
    "final_norm/scale": ((None,), (None,)),
    "stacked/ln1/scale": ((PL, None), (PL, None)),
    "stacked/ln2/scale": ((PL, None), (PL, None)),
    "stacked/attn/wq": STACKED_COLUMN, "stacked/attn/wk": STACKED_COLUMN,
    "stacked/attn/wv": STACKED_COLUMN, "stacked/attn/wo": STACKED_ROW,
    "stacked/attn/q_norm/scale": ((PL, None), (PL, T)),
    "stacked/attn/k_norm/scale": ((PL, None), (PL, T)),
    "stacked/mlp/w_gate": STACKED_COLUMN, "stacked/mlp/w_up": STACKED_COLUMN,
    "stacked/mlp/w_down": STACKED_ROW,
}
# What has no row of its own and falls to the strategy's default: scales of
# d_model and the q/k norm's over a projection's columns; under `pp` and
# `pp_tp` what is not stacked, whole on every stage. A matrix of a layer
# that lands here was forgotten, not decided.
NO_ROW = {"final_norm/scale", "layers/*/ln1/scale", "layers/*/ln2/scale",
          "layers/*/attn/q_norm/scale", "layers/*/attn/k_norm/scale"}
NO_ROW_STACKED = {"embed/table", "final_norm/scale"}
EXPECTED = {"tp": (GSPMD, 0, NO_ROW), "tp_fsdp": (GSPMD, 1, NO_ROW),
            "pp": (STACKED, 0, NO_ROW_STACKED),
            "pp_tp": (STACKED, 1, NO_ROW_STACKED)}

CASES = [(name, strategy)
         for name in ("tiny", "tiny-olmoe", "tiny-kanana", "tiny-lfm2",
                      "tiny-laguna", "tiny-keye", "tiny-solar",
                      "tiny-smallthinker", "tiny-kimi-linear",
                      "tiny-olmo-hybrid", "tiny-ouro")
         for strategy in ("tp", "tp_fsdp")] + [("tiny", "pp"),
                                               ("tiny", "pp_tp")]


def _leaves(jax, name, **changed):
    """The configuration's GPTConfig and its parameters' shapes."""
    from benchmark import model
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        config = json.load(f)
    cfg = GPTConfig(**dict(model.family(config).gpt_config_kwargs(config),
                           **changed))
    return jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))


def _starred(path):
    from ray_tpu.parallel.sharding import _path_str
    return re.sub(r"^layers/\d+/", "layers/*/", _path_str(path))


@pytest.mark.parametrize("name,strategy", CASES,
                         ids=[f"{n}-{s}" for n, s in CASES])
def test_every_leaf_keeps_its_spec(jax_cpu, name, strategy):
    jax = jax_cpu
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import gpt_params_to_pp
    from ray_tpu.parallel.sharding import strategy_from_name
    stacked = strategy.startswith("pp")
    if stacked:
        params = jax.eval_shape(gpt_params_to_pp,
                                _leaves(jax, name, qk_norm=True))
    else:
        params = _leaves(jax, name)
    mesh = build_mesh(
        MeshConfig(data=1, **(dict(pipeline=2, tensor=2) if stacked
                              else dict(fsdp=2, tensor=2))),
        devices=jax.devices()[:4])
    preset = strategy_from_name(strategy)
    table, column, may_have_no_row = EXPECTED[strategy]
    seen, no_row = set(), set()
    leaves = jax.tree_util.tree_flatten_with_path(
        preset.param_shardings(mesh, params))[0]
    for path, sharding in leaves:
        path = _starred(path)
        assert path in table, f"{path}: a parameter this test has no spec for"
        assert sharding.spec == P(*table[path][column]), path
        seen.add(path)
        if not any(re.search(pattern, path)
                   for pattern, _ in preset.param_rules.rules):
            no_row.add(path)
    assert no_row <= may_have_no_row, no_row - may_have_no_row
    if stacked:
        assert seen == set(STACKED)


def test_the_configurations_hold_every_expected_leaf(jax_cpu):
    """The table above has no row that no configuration reaches: an
    expectation nothing checks is not one."""
    seen = set()
    for name in sorted({name for name, _ in CASES}):
        seen |= {_starred(path) for path, _ in jax_cpu.tree_util
                 .tree_flatten_with_path(_leaves(jax_cpu, name))[0]}
    assert seen == set(GSPMD)
