"""Where the flash kernels put the heads' outputs (ops/attention.py:
tokens_first), for the test files that each hold one kind of call:
test_conv_gqa.py (grouped queries), test_window_attention.py (a window),
test_selected_attention.py (a selection), test_latent_moe.py (q.k wider than
v) and test_flash_remat.py (under jax.checkpoint)."""

import hashlib

import numpy as np


def operands(jax, dtype, heads, kv_heads, seq, dqk, dv):
    """q [2, heads, seq, dqk], k [2, kv_heads, seq, dqk], v [2, kv_heads,
    seq, dv] and a cotangent for the tokens-first output [2, seq, heads *
    dv]."""
    keys = jax.random.split(jax.random.PRNGKey(heads * seq + dqk), 4)
    shapes = [(2, heads, seq, dqk), (2, kv_heads, seq, dqk),
              (2, kv_heads, seq, dv), (2, seq, heads * dv)]
    return [jax.random.normal(key, shape, dtype)
            for key, shape in zip(keys, shapes)]


def check_tokens_first(jax, dtype, *, heads=6, kv_heads=2, seq=256, dqk=128,
                       dv=128, block=128, keep=False, **kind):
    """flash_attention_native at heads of whole lane tiles, several blocks a
    row: the output [B, S, H * Dv] and all three gradients (under a cotangent
    that tells every head, row and column apart) against mha_reference,
    turned. kind: window= or selected=. keep: under a jax.checkpoint whose
    policy keeps FLASH_OUT and FLASH_LSE, as a layer's does."""
    import jax.numpy as jnp
    from ray_tpu.ops import attention
    assert attention.tokens_first(dv)
    q, k, v, g = operands(jax, dtype, heads, kv_heads, seq, dqk, dv)

    def flash(q, k, v):
        return attention.flash_attention_native(
            q, k, v, block_q=block, block_k=block, **kind)
    if keep:
        flash = jax.checkpoint(
            flash, policy=jax.checkpoint_policies.save_only_these_names(
                attention.FLASH_OUT, attention.FLASH_LSE))

    def oracle(q, k, v):
        return attention.mha_reference(q, k, v, **kind).transpose(
            0, 2, 1, 3).reshape(2, seq, heads * dv)

    def loss(attend):
        return lambda *a: jnp.sum(attend(*a).astype(jnp.float32)
                                  * g.astype(jnp.float32))
    out = flash(q, k, v)
    assert out.shape == (2, seq, heads * dv) and out.dtype == dtype
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    # the oracle in float32 on the same (rounded) operands
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    want = jax.grad(loss(oracle), (0, 1, 2))(q32, k32, v32)
    exact = dtype == jnp.float32
    np.testing.assert_allclose(out.astype(jnp.float32), oracle(q32, k32, v32),
                               atol=3e-6 if exact else 2e-2)
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for a, b in zip(got, want):
        # bf16: a gradient is rounded once, from float32 accumulators
        np.testing.assert_allclose(a.astype(jnp.float32), b,
                                   atol=2e-5 if exact else 0.15,
                                   rtol=0 if exact else 2e-2)


def traced_sha(jax, attend, heads, kv_heads, seq, dim):
    """sha256 of the jaxpr of attend's value and gradients at float32
    operands of one head width."""
    import jax.numpy as jnp
    q = jnp.zeros((1, heads, seq, dim), jnp.float32)
    k = jnp.zeros((1, kv_heads, seq, dim), jnp.float32)
    jaxpr = str(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: attend(q, k, v).sum(), (0, 1, 2)))(q, k, k))
    return hashlib.sha256(jaxpr.encode()).hexdigest()
