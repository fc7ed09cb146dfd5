"""Pipeline parallelism: GPipe schedule correctness vs dense reference.

Runs on the 8-virtual-CPU-device mesh (conftest). Reference substrate being
matched capability-wise: python/ray/dag/compiled_dag_node.py:141.
"""

import functools

import numpy as np
import pytest


@functools.lru_cache(maxsize=None)
def _setup(qk_norm=False):
    """A tiny config, its parameters, a batch and the unsharded loss. In
    float32: a q/k norm over a tensor shard's own columns alone moves the
    loss by 3e-3, which bf16 rounding (1e-3) would hide."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss

    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=4, n_heads=4,
                    d_ff=128, max_seq=64, attention="reference",
                    remat_policy="none", qk_norm=qk_norm,
                    dtype=jnp.float32)
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    if qk_norm:
        # scales that differ by column, so that a tensor shard which took
        # the wrong slice of them, or none, reads another loss
        for i, layer in enumerate(params["layers"]):
            for j, name in enumerate(("q_norm", "k_norm")):
                layer["attn"][name]["scale"] = 1.0 + 0.5 * jax.random.normal(
                    jax.random.PRNGKey(100 + 2 * i + j), (cfg.d_model,))
    tokens = jnp.array(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 33)),
        jnp.int32)
    batch = {"tokens": tokens}
    return dict(cfg=cfg, params=params, batch=batch,
                dense_loss=float(gpt_loss(params, batch, cfg)))


@pytest.fixture(scope="module")
def env(jax_cpu):
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import (gpt_params_to_pp,
                                           make_gpt_pp_loss,
                                           pp_params_to_gpt)
    return dict(_setup(),
                gpt_params_to_pp=gpt_params_to_pp,
                pp_params_to_gpt=pp_params_to_gpt,
                make_gpt_pp_loss=make_gpt_pp_loss,
                MeshConfig=MeshConfig, build_mesh=build_mesh)


@pytest.mark.parametrize("qk_norm", [False, True],
                         ids=["plain", "qk_norm"])
@pytest.mark.parametrize("axes", [dict(data=2, pipeline=4),
                                  dict(data=2, pipeline=2, tensor=2)],
                         ids=["pp", "pp_tp"])
def test_pp_loss_matches_dense(env, axes, qk_norm):
    """The stage runs models/gpt.py's block on its shard: with the q/k norm
    too, whose mean square a tensor shard has to finish over 'tensor'."""
    case = _setup(qk_norm=True) if qk_norm else env
    mesh = env["build_mesh"](env["MeshConfig"](**axes))
    pp_params = env["gpt_params_to_pp"](case["params"])
    loss_fn = env["make_gpt_pp_loss"](case["cfg"], mesh, num_microbatches=2)
    got = float(loss_fn(pp_params, case["batch"]))
    assert abs(got - case["dense_loss"]) < 1e-4, (got, case["dense_loss"])


@pytest.mark.parametrize("axes,qk_norm", [
    (dict(data=1, pipeline=4, tensor=1), False),
    (dict(data=2, pipeline=2, tensor=2), True)], ids=["pp", "pp_tp_qk_norm"])
def test_pp_grads_match_dense(env, axes, qk_norm):
    """Through the schedule, and through the block's psums over 'tensor'
    (both row-parallel matmuls, the q/k norm's mean square)."""
    import jax

    from ray_tpu.models.gpt import gpt_loss
    case = _setup(qk_norm=True) if qk_norm else env
    mesh = env["build_mesh"](env["MeshConfig"](**axes))
    cfg = case["cfg"]
    pp_params = env["gpt_params_to_pp"](case["params"])
    loss_fn = env["make_gpt_pp_loss"](cfg, mesh, num_microbatches=4)
    # (each side ONE program: taken op by op, the schedule's shard_map and
    # the dense backward were some hundred small compiles, 33 s a case)
    g_pp = jax.jit(jax.grad(loss_fn))(pp_params, case["batch"])
    g_dense = jax.jit(jax.grad(lambda p, b: gpt_loss(p, b, cfg)))(
        case["params"], case["batch"])
    g_pp_as_dense = env["pp_params_to_gpt"](g_pp, cfg.n_layers)

    flat_pp = jax.tree_util.tree_leaves(g_pp_as_dense)
    flat_dense = jax.tree_util.tree_leaves(g_dense)
    assert len(flat_pp) == len(flat_dense)
    for a, b in zip(flat_pp, flat_dense):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-3, atol=1e-4)


def test_pp_round_trip_params(env):
    import jax
    pp = env["gpt_params_to_pp"](env["params"])
    back = env["pp_params_to_gpt"](pp, env["cfg"].n_layers)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(env["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pp_training_step_decreases_loss(env):
    import jax
    import optax

    from ray_tpu.train.train_step import init_train_state, make_train_step

    cfg = env["cfg"]
    mesh = env["build_mesh"](env["MeshConfig"](data=2, pipeline=4))
    loss_fn = env["make_gpt_pp_loss"](cfg, mesh, num_microbatches=2)
    opt = optax.adam(1e-2)
    init = lambda: env["gpt_params_to_pp"](env["params"])  # noqa: E731
    state = init_train_state(init, opt, mesh, "pp")
    step = make_train_step(loss_fn, opt, mesh, "pp",
                           sample_params=state.params)
    batch = env["batch"]
    state, m0 = step(state, batch)
    for _ in range(5):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])


def test_pp_tp_training_step(env):
    import optax

    from ray_tpu.train.train_step import init_train_state, make_train_step

    cfg = env["cfg"]
    mesh = env["build_mesh"](env["MeshConfig"](data=1, pipeline=2, tensor=2,
                                               fsdp=2))
    loss_fn = env["make_gpt_pp_loss"](cfg, mesh, num_microbatches=2)
    opt = optax.adam(1e-2)
    init = lambda: env["gpt_params_to_pp"](env["params"])  # noqa: E731
    state = init_train_state(init, opt, mesh, "pp_tp")
    step = make_train_step(loss_fn, opt, mesh, "pp_tp",
                           sample_params=state.params)
    state, m = step(state, env["batch"])
    assert np.isfinite(float(m["loss"]))


# The stage is models/gpt.py's block: it runs the cells' kernels under the
# cells' scopes (read as tests/test_device_regions.py reads them).

@pytest.fixture(scope="module")
def pp_tp_flash(jax_cpu, env):
    """The pp_tp loss with flash attention, traced only, at the smallest
    width whose tensor shard (two heads of 64) tiles for the rope kernel."""
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, gpt_init

    jax = jax_cpu
    cfg = GPTConfig(vocab_size=256, d_model=256, n_layers=2, n_heads=4,
                    d_ff=256, max_seq=32)
    mesh = env["build_mesh"](env["MeshConfig"](data=2, pipeline=2, tensor=2))
    pp_params = jax.eval_shape(lambda: env["gpt_params_to_pp"](
        gpt_init(jax.random.PRNGKey(0), cfg)))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 33), jnp.int32)}
    return jax.jit(env["make_gpt_pp_loss"](cfg, mesh, 2)).trace(
        pp_params, batch)


def test_pp_tp_stage_runs_the_cells_kernels(pp_tp_flash):
    jaxpr = str(pp_tp_flash.jaxpr)
    for kernel in ("rope_split", "flash_fwd"):
        assert f"name={kernel}" in jaxpr, kernel


def test_pp_tp_stage_names_the_cells_regions(pp_tp_flash):
    import re

    from ray_tpu.util import profiling
    names = re.findall(r'loc\("([^"]*)"',
                       pp_tp_flash.lower().as_text(debug_info=True))
    regions = {profiling._last_of(n, profiling.REGIONS) for n in names}
    assert {"attn_proj", "attn_core", "mlp", "head"} <= regions, regions


# How a Mosaic kernel meets the mesh, asserted where the rule lives
# (models/gpt.py:_per_shard).

def _kernel_calls(jax, jaxpr, inside=None):
    """(kernel name, the parameters of the shard_map it is called in, or
    None) for every pallas_call under jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], inside
        here = eqn.params if eqn.primitive.name == "shard_map" else inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_calls(jax, sub, here)


def test_gspmd_enters_every_kernel_per_shard(jax_cpu, env):
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss

    jax = jax_cpu
    mesh = env["build_mesh"](env["MeshConfig"](data=1, fsdp=2, tensor=2),
                             devices=jax.devices()[:4])
    batch_axes = ("data", "fsdp")
    batch = {"tokens": jax.ShapeDtypeStruct((4, 33), jnp.int32)}
    for sparse, expected in ((dict(), {"rope_split", "flash_fwd"}),
                             (dict(n_experts=4, expert_top_k=2),
                              {"rope_split", "flash_fwd", "moe_gmm"})):
        cfg = GPTConfig(vocab_size=256, d_model=256, n_layers=1, n_heads=4,
                        d_ff=128, max_seq=32, remat_policy="none", **sparse)
        params = jax.eval_shape(
            lambda: gpt_init(jax.random.PRNGKey(0), cfg))
        jaxpr = jax.make_jaxpr(
            lambda p, b: gpt_loss(p, b, cfg, mesh=mesh))(params, batch)
        calls = list(_kernel_calls(jax, jaxpr.jaxpr))
        assert {name for name, _ in calls} == expected
        for name, entered in calls:
            assert entered is not None, f"{name} outside any shard_map"
            assert entered["mesh"].shape == mesh.shape
            assert not entered["check_vma"]
            specs = [*entered["in_specs"], *entered["out_specs"]]
            if name == "moe_gmm":
                # tokens and their weights, the slots' order (each shard's
                # own six arrays, handed over from the routing's
                # shard_map), then the experts whole
                assert specs == [P(batch_axes, None, None)] * 2 \
                    + [P(batch_axes)] * 5 + [P(batch_axes, None)] \
                    + [P()] * 3 + [P(batch_axes, None, None)], specs
            else:
                # columns of heads, then the table; four heads of 64 are
                # two a tensor shard, a pair to a lane tile, so the heads'
                # outputs leave as columns too (attention.tokens_first)
                assert specs == [P(batch_axes, None, "tensor")] * 3 \
                    + [P()] * 2 + [P(batch_axes, None, "tensor")], specs
