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


# (q shape [batch, heads, seq_q, head_dim], seq_k, causal, dtype, explicit
# (block_q, block_k) or None for the blocks derived from the shape)
FLASH_CASES = {
    # the three tests this file had, with the blocks they forced
    "blocks64_s128_d32_causal": ((2, 2, 128, 32), 128, True, "float32", (64, 64)),
    "blocks64_s128_d32_full": ((2, 2, 128, 32), 128, False, "float32", (64, 64)),
    "blocks32_s64_d16_causal": ((1, 2, 64, 16), 64, True, "float32", (32, 32)),
    "blocks32_s64_d16_full": ((1, 2, 64, 16), 64, False, "float32", (32, 32)),
    "blocks32_q32_k96_causal": ((1, 2, 32, 16), 96, True, "float32", (32, 32)),
    # blocks that are not square take the kernels' general path
    "blocks128x64_s256_causal": ((1, 1, 256, 32), 256, True, "float32", (128, 64)),
    "blocks64x128_s256_causal": ((1, 1, 256, 32), 256, True, "float32", (64, 128)),
    "blocks32_q96_k32_causal": ((1, 2, 96, 16), 32, True, "float32", (32, 32)),
    # derived blocks, over the shapes the rule distinguishes
    "s64_d16_causal": ((1, 2, 64, 16), 64, True, "float32", None),
    "s128_d64_causal": ((1, 2, 128, 64), 128, True, "float32", None),
    "s128_d64_full": ((1, 1, 128, 64), 128, False, "float32", None),
    "s640_d64_causal": ((1, 1, 640, 64), 640, True, "float32", None),
    "s640_d16_full_bf16": ((1, 1, 640, 16), 640, False, "bfloat16", None),
    "s1024_d64_causal": ((1, 2, 1024, 64), 1024, True, "float32", None),
    "s1024_d64_full": ((1, 1, 1024, 64), 1024, False, "float32", None),
    "s1024_d128_causal": ((1, 1, 1024, 128), 1024, True, "float32", None),
    "s1024_d64_causal_bf16": ((1, 2, 1024, 64), 1024, True, "bfloat16", None),
    "s2048_d64_causal": ((1, 1, 2048, 64), 2048, True, "float32", None),
    "s2048_d128_causal_bf16": ((1, 1, 2048, 128), 2048, True, "bfloat16", None),
    "s4096_d16_causal": ((1, 1, 4096, 16), 4096, True, "float32", None),
    "q32_k96_causal": ((1, 2, 32, 16), 96, True, "float32", None),
    "q96_k32_causal": ((1, 2, 96, 16), 32, True, "float32", None),
    "q128_k384_causal": ((1, 1, 128, 64), 384, True, "float32", None),
    "q384_k128_causal": ((1, 1, 384, 64), 128, True, "float32", None),
}
# max |difference| from mha_reference in fp32, (forward, gradients): fp32
# inputs at the tolerances this file always had; bf16 inputs (bf16 dot
# operands and outputs, fp32 accumulation and softmax) against the fp32
# reference on the same bf16 values.
FLASH_TOL = {"float32": (2e-5, 2e-4), "bfloat16": (2e-2, 5e-2)}


class TestAttention:
    @pytest.mark.parametrize("case", FLASH_CASES)
    def test_flash_matches_reference(self, jx, case):
        """Forward and all three gradients against mha_reference. Where
        seq_q > seq_k a causal query row may have no key at all: the kernel
        gives zeros there (mha_reference's softmax over nothing gives the
        mean of v), so the reference is zeroed on those rows."""
        import jax
        import jax.numpy as jnp
        from ray_tpu.ops.attention import flash_attention, mha_reference
        (b, h, sq, d), sk, causal, dtype, blocks = FLASH_CASES[case]
        kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(1), 4)
        q = jax.random.normal(kq, (b, h, sq, d), dtype)
        k = jax.random.normal(kk, (b, h, sk, d), dtype)
        v = jax.random.normal(kv, (b, h, sk, d), dtype)
        w = jax.random.normal(kw, (b, h, sq, d), jnp.float32)
        has_key = (jnp.arange(sq) + sk - sq >= 0) | (not causal)
        kwargs = dict(zip(("block_q", "block_k"), blocks or ()))

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=causal, **kwargs)

        def reference(q, k, v):
            out = mha_reference(q, k, v, causal=causal)
            return jnp.where(has_key[:, None], out, 0.0)

        def run(attn, *args):
            out, vjp = jax.vjp(
                lambda *a: attn(*a).astype(jnp.float32), *args)
            return (out,) + vjp(w)

        got = jax.jit(lambda *a: run(flash, *a))(q, k, v)
        want = jax.jit(lambda *a: run(reference, *a))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
        tol_out, tol_grad = FLASH_TOL[dtype]
        for name, tol, a, e in zip(("out", "dq", "dk", "dv"),
                                   (tol_out,) + (tol_grad,) * 3, got, want):
            assert a.dtype == (jnp.float32 if name == "out" else q.dtype)
            err = float(jnp.abs(a.astype(jnp.float32) - e).max())
            assert err < tol, (name, err)

    @pytest.mark.parametrize("seq,fwd,bwd", [
        (1024, (1024, 1024, 256), (1024, 1024, 128)),   # gpt2s_train_1chip
        (2048, (2048, 2048, 256), (2048, 2048, 128)),   # smollm17_train_4chip
    ])
    def test_block_sizes_of_the_benchmark_cells(self, seq, fwd, bwd):
        """The blocks PERF.md prints for the two cells' per-chip shapes."""
        from ray_tpu.ops.attention import _block_sizes
        blocks = _block_sizes(seq, seq, 64)
        assert (blocks.fwd, blocks.dq, blocks.dkv) == (fwd, bwd, bwd)

    def test_block_sizes_divide_every_sequence(self):
        from ray_tpu.ops.attention import _block_sizes
        for seq in range(128, 4096 + 1, 128):
            for head_dim in (64, 256):
                for outer, major, group in _block_sizes(seq, seq, head_dim):
                    assert seq % outer == 0 and seq % major == 0, seq
                    assert outer % group == 0, seq
                    assert min(outer, major, group) % 128 == 0, seq
                    assert max(outer, major) <= 2048
        for seq in (8, 16, 64, 96, 127):
            assert set(_block_sizes(seq, seq, 64)) == {(seq, seq, seq)}
        # a short sequence against a long one: one block each
        assert _block_sizes(32, 96, 16).fwd == (32, 96, 32)
        assert _block_sizes(32, 96, 16).dkv == (96, 32, 96)
        assert _block_sizes(128, 384, 64).fwd == (128, 128, 128)

    def test_flash_refuses_a_ragged_sequence(self, jx):
        import jax.numpy as jnp
        from ray_tpu.ops.attention import flash_attention
        x = jnp.zeros((1, 1, 200, 16))
        with pytest.raises(ValueError, match="multiples of 128"):
            flash_attention(x, x, x)
        with pytest.raises(ValueError, match="block_q=128"):
            flash_attention(x, x, x, block_q=128, block_k=128)

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
