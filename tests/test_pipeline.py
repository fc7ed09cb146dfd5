"""Pipeline parallelism: GPipe schedule correctness vs dense reference.

Runs on the 8-virtual-CPU-device mesh (conftest). Reference substrate being
matched capability-wise: python/ray/dag/compiled_dag_node.py:141.
"""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def env(jax_cpu):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import (gpt_params_to_pp,
                                           make_gpt_pp_loss,
                                           pp_params_to_gpt)

    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=4, n_heads=4,
                    d_ff=128, max_seq=64, attention="reference", remat=False)
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.array(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 33)),
        jnp.int32)
    batch = {"tokens": tokens}
    dense_loss = float(gpt_loss(params, batch, cfg))
    return dict(cfg=cfg, params=params, batch=batch, dense_loss=dense_loss,
                gpt_params_to_pp=gpt_params_to_pp,
                pp_params_to_gpt=pp_params_to_gpt,
                make_gpt_pp_loss=make_gpt_pp_loss,
                MeshConfig=MeshConfig, build_mesh=build_mesh)


def test_pp_loss_matches_dense(env):
    mesh = env["build_mesh"](env["MeshConfig"](data=2, pipeline=4))
    pp_params = env["gpt_params_to_pp"](env["params"])
    loss_fn = env["make_gpt_pp_loss"](env["cfg"], mesh, num_microbatches=2)
    got = float(loss_fn(pp_params, env["batch"]))
    assert abs(got - env["dense_loss"]) < 5e-2, (got, env["dense_loss"])


def test_pp_tp_loss_matches_dense(env):
    mesh = env["build_mesh"](env["MeshConfig"](data=2, pipeline=2, tensor=2))
    pp_params = env["gpt_params_to_pp"](env["params"])
    loss_fn = env["make_gpt_pp_loss"](env["cfg"], mesh, num_microbatches=2)
    got = float(loss_fn(pp_params, env["batch"]))
    assert abs(got - env["dense_loss"]) < 5e-2, (got, env["dense_loss"])


def test_pp_grads_match_dense(env):
    import jax

    from ray_tpu.models.gpt import gpt_loss
    mesh = env["build_mesh"](env["MeshConfig"](data=1, pipeline=4,
                                               tensor=1))
    cfg = env["cfg"]
    pp_params = env["gpt_params_to_pp"](env["params"])
    loss_fn = env["make_gpt_pp_loss"](cfg, mesh, num_microbatches=4)
    g_pp = jax.grad(loss_fn)(pp_params, env["batch"])
    g_dense = jax.grad(lambda p, b: gpt_loss(p, b, cfg))(
        env["params"], env["batch"])
    g_pp_as_dense = env["pp_params_to_gpt"](g_pp, cfg.n_layers)

    flat_pp = jax.tree_util.tree_leaves(g_pp_as_dense)
    flat_dense = jax.tree_util.tree_leaves(g_dense)
    assert len(flat_pp) == len(flat_dense)
    for a, b in zip(flat_pp, flat_dense):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0.1, atol=2e-2)


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
