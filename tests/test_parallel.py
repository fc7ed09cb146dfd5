"""Parallel layer tests: mesh, sharding strategies, attention kernels, and
the sharded train step — all on the virtual 8-device CPU mesh (SURVEY.md §4
fake-accelerator pattern)."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def jx(jax_cpu):
    return jax_cpu


class TestMesh:
    def test_build_mesh_axes(self, jx):
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
        assert mesh.shape["data"] == 2
        assert mesh.shape["tensor"] == 2
        assert len(mesh.devices.flatten()) == 8

    def test_auto_data_axis(self, jx):
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        mesh = build_mesh(MeshConfig(tensor=4))
        assert mesh.shape["data"] == 2

    def test_bad_factorization(self, jx):
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        with pytest.raises(ValueError):
            build_mesh(MeshConfig(data=3, tensor=3))

    def test_slice_bundles(self):
        from ray_tpu.parallel.mesh import SliceInfo, slice_bundles
        s = SliceInfo(name="v4-16", generation="v4", num_chips=16,
                      num_hosts=4, chips_per_host=4)
        bundles = slice_bundles(s)
        assert len(bundles) == 4
        assert bundles[0]["TPU-v4-16-head"] == 1.0
        assert all(b["TPU"] == 4.0 for b in bundles)


class TestShardingRules:
    def test_tp_rules_match_gpt_paths(self, jx):
        import jax
        from ray_tpu.models.gpt import GPTConfig, gpt_init
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        from ray_tpu.parallel.sharding import ShardingStrategy
        mesh = build_mesh(MeshConfig(data=2, tensor=4))
        params = gpt_init(jax.random.PRNGKey(0), GPTConfig.tiny())
        sh = ShardingStrategy.tp_transformer().param_shardings(mesh, params)
        wq = sh["layers"][0]["attn"]["wq"]
        assert "tensor" in str(wq.spec)
        ln = sh["layers"][0]["ln1"]["scale"]
        assert ln.spec == jax.sharding.PartitionSpec(None)

    def test_fsdp_shards_largest_dim(self, jx):
        import jax
        from jax.sharding import PartitionSpec as P
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        from ray_tpu.parallel.sharding import ShardingStrategy
        mesh = build_mesh(MeshConfig(data=2, fsdp=4))
        params = {"w": np.zeros((128, 64)), "b": np.zeros((7,))}
        sh = ShardingStrategy.fsdp().param_shardings(mesh, params)
        assert sh["w"].spec == P("fsdp", None)
        assert sh["b"].spec == P()  # 7 not divisible by 4 -> replicated


class TestAttention:
    def test_flash_matches_reference(self, jx):
        import jax
        import jax.numpy as jnp
        from ray_tpu.ops.attention import flash_attention, mha_reference
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(k1, (2, 2, 128, 32))
        k = jax.random.normal(k2, (2, 2, 128, 32))
        v = jax.random.normal(k3, (2, 2, 128, 32))
        for causal in (True, False):
            ref = mha_reference(q, k, v, causal=causal)
            out = flash_attention(q, k, v, causal=causal,
                                  block_q=64, block_k=64)
            assert float(jnp.abs(ref - out).max()) < 2e-5

    def test_flash_grad_matches(self, jx):
        import jax
        import jax.numpy as jnp
        from ray_tpu.ops.attention import flash_attention, mha_reference
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(k1, (1, 2, 64, 16))
        k = jax.random.normal(k2, (1, 2, 64, 16))
        v = jax.random.normal(k3, (1, 2, 64, 16))
        for causal in (True, False):
            g_ref = jax.grad(
                lambda q, k, v: (mha_reference(q, k, v, causal=causal)
                                 * v.sum(2, keepdims=True)).sum(),
                argnums=(0, 1, 2))(q, k, v)
            g_fl = jax.grad(
                lambda q, k, v: (flash_attention(q, k, v, causal=causal,
                                                 block_q=32, block_k=32)
                                 * v.sum(2, keepdims=True)).sum(),
                argnums=(0, 1, 2))(q, k, v)
            for name, a, b in zip("qkv", g_ref, g_fl):
                assert float(jnp.abs(a - b).max()) < 2e-4, name

    def test_flash_grad_cross_lengths(self, jx):
        """seq_q != seq_k exercises the bottom-right causal offset in the
        backward kernels too."""
        import jax
        import jax.numpy as jnp
        from ray_tpu.ops.attention import flash_attention, mha_reference
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(k1, (1, 2, 32, 16))
        k = jax.random.normal(k2, (1, 2, 96, 16))
        v = jax.random.normal(k3, (1, 2, 96, 16))
        g_ref = jax.grad(
            lambda q, k, v: mha_reference(q, k, v, causal=True).sum(),
            argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            block_q=32, block_k=32).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_ref, g_fl):
            assert float(jnp.abs(a - b).max()) < 2e-4, name

    def test_ring_attention_matches(self, jx):
        import jax
        import jax.numpy as jnp
        from ray_tpu.ops.attention import mha_reference, ring_attention
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        mesh = build_mesh(MeshConfig(data=1, sequence=8))
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(k1, (1, 2, 128, 16))
        k = jax.random.normal(k2, (1, 2, 128, 16))
        v = jax.random.normal(k3, (1, 2, 128, 16))
        ref = mha_reference(q, k, v, causal=True)
        out = ring_attention(q, k, v, mesh=mesh, causal=True)
        assert float(jnp.abs(ref - out).max()) < 2e-5


class TestTrainStep:
    @pytest.mark.parametrize("strategy,axes", [
        ("dp", dict(data=8)),
        ("fsdp", dict(data=2, fsdp=4)),
        ("tp", dict(data=2, tensor=4)),
        ("tp_fsdp", dict(data=2, fsdp=2, tensor=2)),
    ])
    def test_strategies_train(self, jx, strategy, axes):
        import jax
        import jax.numpy as jnp
        import optax
        from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        from ray_tpu.train.train_step import (init_train_state,
                                              make_train_step)
        cfg = GPTConfig.tiny()
        mesh = build_mesh(MeshConfig(**axes))
        opt = optax.adamw(1e-3)
        state = init_train_state(
            lambda: gpt_init(jax.random.PRNGKey(0), cfg), opt, mesh, strategy)
        step = make_train_step(lambda p, b: gpt_loss(p, b, cfg), opt, mesh,
                               strategy, sample_params=state.params)
        toks = jnp.array(np.random.randint(0, 512, (8, 65)), jnp.int32)
        losses = []
        for _ in range(3):
            state, m = step(state, {"tokens": toks})
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        assert np.isfinite(losses[-1])

    def test_moe_expert_parallel(self, jx):
        import jax
        import jax.numpy as jnp
        import optax
        from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        from ray_tpu.parallel.sharding import ShardingStrategy
        from ray_tpu.train.train_step import (init_train_state,
                                              make_train_step)
        cfg = GPTConfig(vocab_size=512, d_model=64, n_layers=2, n_heads=4,
                        d_ff=128, max_seq=64, n_experts=4)
        mesh = build_mesh(MeshConfig(data=2, expert=4))
        strategy = ShardingStrategy.tp_transformer()  # has moe rules
        opt = optax.adamw(1e-3)
        state = init_train_state(
            lambda: gpt_init(jax.random.PRNGKey(0), cfg), opt, mesh, strategy)
        step = make_train_step(lambda p, b: gpt_loss(p, b, cfg), opt, mesh,
                               strategy, sample_params=state.params)
        toks = jnp.array(np.random.randint(0, 512, (4, 33)), jnp.int32)
        state, m = step(state, {"tokens": toks})
        assert np.isfinite(float(m["loss"]))


class TestGraftEntry:
    # Compile-heavy (three sharded meshes + a 2-process gang + the
    # unsharded-equivalence program): needs headroom beyond the 180 s
    # default when the XLA cache is cold or the box is loaded.
    @pytest.mark.timeout(600)
    def test_entry_and_dryrun(self, jx):
        import sys
        sys.path.insert(0, "/root/repo")
        import importlib
        ge = importlib.import_module("__graft_entry__")
        import jax
        fn, args = ge.entry()
        out = jax.jit(fn)(*args)
        assert out.shape[0] == args[1].shape[0]
        ge.dryrun_multichip(8)
