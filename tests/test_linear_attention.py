"""The delta rule, chunked (ops/linear_attention.py), and the plain filter
(ops/short_conv.py) against a token a step on the CPU (the kernels in
interpret mode), and what solar2_train_1chip hands the chip's compiler, for
a described v5e: the filter's and the delta rule's kernels at the cell's
shapes, a delta-rule layer on the four-chip mesh and the whole step. The
family's program against the reference of benchmark/families/solar.py:
tests/test_linear_attention_model.py."""

import re

import numpy as np
import pytest

from helpers.described_chip import (  # noqa: F401 — fixtures
    cell_step, layer_on_four_chips, v5e)
from helpers.families import family  # noqa: F401
from helpers.jaxprs import (dots_of, pallas_calls, pallas_operands,
                            passes_of)
from test_linear_attention_model import FAMILY  # noqa: F401


# ---------------------------------------------------------------------------
# (a) the delta rule: chunked against a token a step
# ---------------------------------------------------------------------------


def _delta_inputs(jax, seq, dim, decay, beta_top, seed=0):
    """Normalised q and k, log-decays of about -decay a token and channel,
    beta near beta_top."""
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (2, 3, seq, dim)) for key in keys[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dim ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    log_decay = -decay * jax.nn.softplus(
        jax.random.normal(keys[3], (2, 3, seq, dim)))
    beta = beta_top * jax.nn.sigmoid(
        jax.random.normal(keys[4], (2, 3, seq)) + 3.0)
    return q, k, v, log_decay, beta


@pytest.mark.parametrize("seq,chunk,decay,beta_top", [
    (128, 64, 0.05, 2.0),       # whole chunks, beta near 2
    (100, 64, 1e-3, 2.0),       # a ragged tail, decays near 1
    (192, 64, 8.0, 1.0),        # a chunk's decay underflows float32
    (64, 64, 0.0, 2.0),         # no decay at all: the plain delta rule
    (40, 16, 0.3, 1.5),         # another chunk size, a ragged tail
], ids=["whole", "ragged_slow", "underflow", "no_decay", "chunk16"])
def test_chunked_delta_rule_matches_the_recurrence(jax_cpu, seq, chunk, decay,
                                                   beta_top):
    """Values and all five gradients. No chunk divides by a decay: where a
    chunk's cumulative log-decay passes float32's range (8 a token over 64
    tokens) the chunked form still has the recurrence's numbers."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.linear_attention import (chunk_log_decay, kda,
                                              kda_reference)
    args = _delta_inputs(jax, seq, 32, decay, beta_top)
    if decay == 8.0:
        assert float(chunk_log_decay(args[3]).min()) < -200.0
    weight = jnp.cos(0.37 * jnp.arange(seq * 32).reshape(seq, 32))

    def run(fn):
        def scalar(*a):
            out = fn(*a)
            return jnp.sum(out * weight), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            scalar, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        return out, grads
    out, grads = run(lambda *a: kda(*a, chunk=chunk))
    ref, ref_grads = run(kda_reference)
    assert out.shape == (2, 3, seq, 32) and np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, atol=5e-6)
    for name, g, r in zip(("q", "k", "v", "log_decay", "beta"), grads,
                          ref_grads):
        assert np.any(np.asarray(r)), name
        np.testing.assert_allclose(
            g, r, atol=1e-5 * max(1.0, float(np.abs(r).max())), err_msg=name)


def test_delta_rule_keeps_the_inputs_type_and_refuses_an_odd_chunk(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.linear_attention import kda, kda_reference
    q, k, v, log_decay, beta = _delta_inputs(jax, 64, 32, 0.1, 2.0)
    half = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    out = kda(*half, log_decay, beta)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        out.astype(jnp.float32),
        kda_reference(*half, log_decay, beta).astype(jnp.float32), atol=2e-2)
    with pytest.raises(ValueError, match="power of two"):
        kda(q, k, v, log_decay, beta, chunk=48)


@pytest.mark.parametrize("seq,decay,beta_top,dtype", [
    (128, 0.05, 2.0, "float32"),     # whole chunks, a mild decay, beta near 2
    (128, 8.0, 2.0, "float32"),      # past -87 inside a chunk, beta near 2
    (80, 0.05, 1.0, "bfloat16"),     # a ragged tail, two-byte q, k and v
    (80, 8.0, 2.0, "bfloat16"),      # all of it at once
], ids=["mild", "underflow", "ragged_bf16", "underflow_ragged_bf16"])
def test_delta_rule_gradients_are_the_recurrences(jax_cpu, seq, decay,
                                                  beta_top, dtype):
    """All five gradients of the kernels (`kda_bwd`: the chunk function's
    transpose, written out, walked from the last chunk to the first) against
    jax.grad of the recurrence on the same inputs, one head of two, under a cotangent of its
    own: the decay mild and underflowing inside a chunk, beta up to 2, a
    sequence that is not whole chunks, inputs of four bytes and of two."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.linear_attention import (chunk_log_decay, kda,
                                              kda_reference)
    q, k, v, log_decay, beta = (x[:1, :2] for x in _delta_inputs(
        jax, seq, 32, decay, beta_top, seed=1))
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    if decay == 8.0:
        assert float(chunk_log_decay(log_decay).min()) < -87.0
    ct = jax.random.normal(jax.random.PRNGKey(7), (1, 2, seq, 32))

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * ct),
            argnums=(0, 1, 2, 3, 4)))(q, k, v, log_decay, beta)
    # the two kernels are all of it: no scan is left beside them
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(kda(
        q, k, v, log_decay, beta).astype(jnp.float32))))(q))
    assert re.findall(r"name=(kda_fwd|kda_bwd)\b", text) == ["kda_fwd",
                                                             "kda_bwd"]
    assert " scan[" not in text and " while[" not in text
    tol = 1e-5 if dtype == "float32" else 2e-2
    for name, g, r in zip(("q", "k", "v", "log_decay", "beta"), grads(kda),
                          grads(kda_reference)):
        g, r = (np.asarray(x.astype(jnp.float32)) for x in (g, r))
        assert g.dtype == r.dtype and np.isfinite(g).all() and np.any(r), name
        np.testing.assert_allclose(g, r, atol=tol * max(1.0, np.abs(r).max()),
                                   err_msg=name)


def _chunks_differentiated(q, k, v, log_decay, beta, *, chunk):
    """`linear_attention._chunk` a chunk under a `lax.scan`, as plain jnp,
    on operands padded to whole chunks as `kda` pads them: differentiated
    by JAX this is `jax.vjp(_chunk)` a chunk from the last to the first,
    which is what `kda_bwd` was until PR 66 wrote the transpose out. Its
    oracle."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.linear_attention import _chunk
    f32 = jnp.float32
    b, h, s, dk = q.shape
    pad = -s % chunk
    n = (s + pad) // chunk

    def cut(x):                  # [B, H, S, d] -> [chunks, B H, chunk, d]
        x = jnp.pad(x.astype(f32), ((0, 0), (0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(x.reshape(b * h, n, chunk, -1), 1, 0)
    def rows(x):                 # [B, H, S] -> [chunks, B H, 1, chunk]
        x = jnp.pad(x.astype(f32), ((0, 0), (0, 0), (0, pad)))
        return jnp.moveaxis(x.reshape(b * h, n, 1, chunk), 1, 0)
    # ONE decay a head: the chunk's cumulative log-decay, a row like beta's
    decay = (jnp.cumsum(rows(log_decay[..., 0]), axis=3)
             if log_decay.shape[-1] == 1 else cut(log_decay))

    def a_chunk(state, xs):
        o, state, _kept = _chunk(*xs, state)
        return state, o
    _, o = jax.lax.scan(
        a_chunk, jnp.zeros((b * h, v.shape[-1], dk), f32),
        (cut(q), cut(k), cut(v), decay, rows(beta)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, h, n * chunk, -1)
    return o[:, :, :s].astype(q.dtype)


@pytest.mark.parametrize("dtype,seq,chunk,decay,beta_top,a_step,a_head", [
    ("float32", 64, 32, 0.05, 2.0, 4, False),
    ("bfloat16", 64, 64, 0.05, 1.0, 4, False),
    ("float32", 40, 16, 8.0, 2.0, 1, False),
    ("bfloat16", 80, 32, 8.0, 2.0, 1, False),
    ("float32", 40, 16, 8.0, 2.0, 2, True),
    ("bfloat16", 80, 32, 0.05, 2.0, 4, True),
], ids=["float32_beta_near_2", "bfloat16_four_heads_a_step",
        "float32_underflow_ragged_a_head_a_step",
        "bfloat16_underflow_ragged_a_head_a_step",
        "float32_underflow_ragged_a_decay_a_head",
        "bfloat16_ragged_a_decay_a_head"])
def test_the_written_transpose_equals_the_chunks_vjp(
        jax_cpu, monkeypatch, dtype, seq, chunk, decay, beta_top, a_step,
        a_head):
    """`kda_bwd`'s body is `_chunk`'s transpose written by hand (PR 66): it
    reads A, Aqk and the inverse the forward made, sends the inverse's
    cotangent back in closed form (dM = -T^T dT T^T), walks the tree once
    on stacked cotangents and leaves out the pairs of terms that are
    exactly zero (dO and v that arrive as bfloat16 are ONE term). Held
    here, all five gradients, to JAX's own transpose of the same `_chunk`
    on the same values (`_chunks_differentiated`): the float32 ones at
    float32 rounding, those that leave in bfloat16 at one rounding of
    theirs. The cases: q / k / v (and with them the cotangent) float32 or
    bfloat16, beta near 2, a decay past -87 inside a chunk, a ragged tail,
    four heads a grid step and one; and ONE decay a head (the log-decay's
    last dimension 1: A and Aqk one masked product each under the matrix of
    the pairs' decays, their transposes two stacked products, and g's
    gradient a row)."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import linear_attention
    from ray_tpu.ops.linear_attention import chunk_log_decay, kda
    monkeypatch.setattr(linear_attention, "_FWD_HEADS", a_step)
    monkeypatch.setattr(linear_attention, "_BWD_HEADS", a_step)
    linear_attention._make_kda_fn.cache_clear()
    bf16, f32 = jnp.bfloat16, jnp.float32
    q, k, v, log_decay, beta = (jnp.concatenate([x[0], x[1, :1]])[None]
                                for x in _delta_inputs(
                                    jax, seq, 32, decay, beta_top, seed=3))
    q, k, v = (x.astype(dtype) for x in (q, k, v))            # four heads
    if a_head:
        log_decay = log_decay[..., :1]
    if decay == 8.0:
        assert float(chunk_log_decay(log_decay, chunk).min()) < -87.0
    ct = jax.random.normal(jax.random.PRNGKey(11), (1, 4, seq, 32))

    def gradients(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a, chunk=chunk).astype(f32) * ct),
            argnums=(0, 1, 2, 3, 4)))(q, k, v, log_decay, beta)
    try:
        got = gradients(kda)
    finally:
        linear_attention._make_kda_fn.cache_clear()
    want = gradients(_chunks_differentiated)
    for name, g, w, like in zip(("q", "k", "v", "log_decay", "beta"), got,
                                want, (q, k, v, log_decay, beta)):
        assert g.dtype == w.dtype == like.dtype, name
        assert np.any(np.asarray(w.astype(f32))), name
        top = max(float(jnp.max(jnp.abs(w.astype(f32)))), 1.0)
        # read when this was written (of each one's largest value): the
        # float32 ones at most 2.8e-7, those in bfloat16 1.2e-4
        tol = 2.0 ** -8 if like.dtype == bf16 else 4e-6
        worst = float(jnp.max(jnp.abs(g.astype(f32) - w.astype(f32))))
        assert worst < tol * top, (name, worst, top)


@pytest.mark.parametrize("dtype,passes", [("bfloat16", 156),
                                          ("float32", 174)])
def test_the_backward_multiplies_the_terms_its_operands_have(jax_cpu, dtype,
                                                             passes):
    """`LOWERED` does not see a kernel's body, so the count stands here:
    the bfloat16 passes of the matrix unit that `kda_bwd` traces to at a
    chunk of 64 (`passes_of`: a float32 product at HIGHEST is six, a
    bfloat16 one one). Its 29 products: Wv, Wk, Wk S; the tail's transposes
    (Aqk^T dO, Ktilde dS, dO U^T, dO S, U dS, dU S, dO^T Qbar, dU^T Wk);
    T^T dWv, T^T dWk, dT's two, dM's two; two a level of the tree's six.
    float32 q / k / v and cotangent: six passes each, 174. bfloat16: the
    four products against dO and the two against the bare v (Wv, and dT's
    dWv v^T) are three bfloat16 matmuls each, 156; a three-term dO or v
    would make them six again, a one-term cotangent by nature fewer."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.linear_attention import kda
    shape = lambda *dims, dtype=dtype: jax.ShapeDtypeStruct(dims, dtype)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kda(*a, interpret=False).astype(jnp.float32)),
        argnums=tuple(range(5))))(
        shape(1, 4, 128, 128), shape(1, 4, 128, 128), shape(1, 4, 128, 128),
        shape(1, 4, 128, 128, dtype="float32"),
        shape(1, 4, 128, dtype="float32")).jaxpr
    bodies = pallas_calls(jaxpr)
    assert sorted(bodies) == ["kda_bwd", "kda_fwd"]
    assert passes_of(bodies["kda_bwd"]) == passes
    assert dots_of(bodies["kda_bwd"]) == {"bfloat16": 23 + 6 * 3,
                                          "float32": 29}[dtype]
    # the forward's, each ONE product at Precision.HIGHEST whatever the
    # types: two a level, the inverse's two at five levels, the tail's six
    assert dots_of(bodies["kda_fwd"]) == 12 + 10 + 6


@pytest.mark.parametrize("dtype,heads,dk,dv,seq,decay", [
    ("float32", 15, 96, 192, 128, 0.05),
    ("float32", 3, 24, 48, 100, 3.0),
    ("bfloat16", 6, 96, 192, 80, 0.5),
], ids=["float32_15_heads_of_96_and_192", "float32_underflow_ragged",
        "bfloat16_ragged_96_and_192"])
def test_a_decay_a_head_is_the_decay_broadcast_and_the_recurrence(
        jax_cpu, dtype, heads, dk, dv, seq, decay):
    """Gated DeltaNet's layer: a key of 96 and a value of 192 (one lane tile
    and two, a state of [256, 128] in the kernels), 15 heads (5 a grid step
    forward and 3 backward: the largest divisors under `_FWD_HEADS` /
    `_BWD_HEADS`), ONE log-decay a head and token. `kda` handed the decay
    with a last dimension of 1 runs the chunk for a decay a head; that equals
    the same decay broadcast to the key's channels through the tree, and the
    recurrence a token at a time: values and all five gradients (the
    log-decay's summed over the channels where it was broadcast), float32
    at float32's rounding, bfloat16 at one rounding of theirs; a decay past
    -87 inside a chunk, beta to 2 and a ragged tail among the cases."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.linear_attention import (chunk_log_decay, kda,
                                              kda_reference)
    f32 = jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    q, k = (jax.random.normal(key, (1, heads, seq, dk)) for key in keys[:2])
    q = (q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
         ).astype(dtype)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(dtype)
    v = jax.random.normal(keys[2], (1, heads, seq, dv)).astype(dtype)
    a_head = -decay * jax.nn.softplus(
        jax.random.normal(keys[3], (1, heads, seq, 1)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (1, heads, seq)))
    ct = jax.random.normal(keys[5], (1, heads, seq, dv))
    if decay == 3.0:
        assert float(chunk_log_decay(a_head).min()) < -87.0

    def run(fn, log_decay):
        def scalar(*a):
            out = fn(*a)
            return jnp.sum(out.astype(f32) * ct), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            scalar, argnums=(0, 1, 2, 3, 4), has_aux=True))(
            q, k, v, log_decay, beta)
        # a broadcast decay's gradient, summed back to the head's number
        return out, grads[:3] + (
            jnp.sum(grads[3], axis=-1, keepdims=True), grads[4])
    broadcast = jnp.broadcast_to(a_head, q.shape)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(kda(
        q, k, v, a_head, beta).astype(f32))))(q).jaxpr
    bodies = pallas_calls(jaxpr)
    assert sorted(bodies) == ["kda_bwd", "kda_fwd"]
    # the head's form walks no tree: two plain products forward beside the
    # inverse's ten and the tail's six where the tree makes twelve
    assert dots_of(bodies["kda_fwd"]) == 2 + 10 + 6
    out, grads = run(kda, a_head)
    same, same_grads = run(kda, broadcast)
    ref, ref_grads = run(kda_reference, broadcast)
    assert out.shape == (1, heads, seq, dv) and out.dtype == q.dtype
    tol = 1e-5 if dtype == "float32" else 2e-2
    for got, want in ((out, same), (out, ref)):
        np.testing.assert_allclose(got.astype(f32), want.astype(f32),
                                   atol=tol)
    for name, g, b, r in zip(("q", "k", "v", "log_decay", "beta"), grads,
                             same_grads, ref_grads):
        g, b, r = (np.asarray(x.astype(f32)) for x in (g, b, r))
        assert np.isfinite(g).all() and np.any(r), name
        top = tol * max(1.0, np.abs(r).max())
        np.testing.assert_allclose(g, r, atol=top, err_msg=name)
        np.testing.assert_allclose(g, b, atol=top, err_msg=name)


def test_fifteen_heads_run_five_and_three_a_grid_step(jax_cpu):
    """`specs` takes the largest divisor of the heads under `_FWD_HEADS` 8 /
    `_BWD_HEADS` 4: at 15 heads 5 forward and 3 backward, a grid of (3,
    chunks) and (5, chunks); the decay a head rides in as rows [heads,
    chunks, 1, chunk] like beta's, its gradient leaves the same way."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.linear_attention import kda
    shape = lambda *dims, dtype="bfloat16": jax.ShapeDtypeStruct(dims, dtype)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kda(*a, interpret=False).astype(jnp.float32)),
        argnums=tuple(range(5))))(
        shape(1, 15, 256, 96), shape(1, 15, 256, 96), shape(1, 15, 256, 192),
        shape(1, 15, 256, 1, dtype="float32"),
        shape(1, 15, 256, dtype="float32")).jaxpr
    grid, ins, outs = pallas_operands(jaxpr, "kda_fwd")
    assert grid == (3, 4)
    assert ins == [(15, 256, 128), (15, 256, 128), (15, 256, 256),
                   (15, 4, 1, 64), (15, 4, 1, 64)]
    # o, the chunks' states [256, 128] and their kept matrices
    assert outs == [(15, 256, 256), (15, 4, 256, 128), (15, 4, 64, 128)]
    grid, ins, outs = pallas_operands(jaxpr, "kda_bwd")
    assert grid == (5, 4) and outs[3] == outs[4] == (15, 4, 1, 64)


@pytest.mark.parametrize("batch,heads,seq,dk,dv,a_head,dtype,steps", [
    (1, 8, 64, 128, 128, False, "bfloat16", (1, 2)),
    (1, 4, 64, 128, 128, False, "float32", (1, 1)),
    (2, 4, 64, 128, 128, True, "bfloat16", (2, 2)),
    (2, 2, 100, 128, 256, False, "bfloat16", (2, 2)),
    (1, 3, 64, 96, 192, True, "bfloat16", None),
], ids=["a_full_forward_group", "four_heads", "a_decay_a_head",
        "a_ragged_tail_of_two_rows", "96_192_goes_by_head"])
def test_operands_by_token_are_the_by_head_call_bit_for_bit(
        jax_cpu, batch, heads, seq, dk, dv, a_head, dtype, steps):
    """`kda` on q, k, v and the log-decay as a layer's projections write
    them, [B, S, H w], beta and ONE decay a head [B, S, H], against the same
    numbers turned by head, [B, H, S, w]: o and all five gradients have the
    same bits. At heads of whole lane tiles nothing is turned: the kernels
    take the operands as they are and a grid step's heads (8 forward, 4
    backward, or every head of a row that has fewer) are a block of a
    token's columns, `steps` = (forward, backward) grid rows; at 96 / 192
    the operands are turned by head and padded, the call the by-head one.

    Both programs are compiled with LLVM's optimisations off: XLA's CPU
    backend contracts a product and a sum into one fused multiply-add
    wherever its own fusion puts the two in one loop, which the shape of
    the block that is written decides (the log-decay's gradient at a decay
    a channel differed in its last bit on every other token), and at level
    0 nothing is contracted: what is compared is the program's sums."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.linear_attention import by_token, kda
    keys = jax.random.split(jax.random.PRNGKey(heads * seq + dv), 6)

    def unit(x):
        x = x.reshape(batch, seq, heads, dk)
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            batch, seq, heads * dk)
    q = (unit(jax.random.normal(keys[0], (batch, seq, heads * dk)))
         * dk ** -0.5).astype(dtype)
    k = unit(jax.random.normal(keys[1], (batch, seq, heads * dk))).astype(
        dtype)
    v = jax.random.normal(keys[2], (batch, seq, heads * dv)).astype(dtype)
    log_decay = -0.1 * jax.nn.softplus(jax.random.normal(
        keys[3], (batch, seq, heads if a_head else heads * dk)))
    beta = 2.0 * jax.nn.sigmoid(
        jax.random.normal(keys[4], (batch, seq, heads)) + 1.0)
    ct = jax.random.normal(keys[5], (batch, seq, heads * dv)).astype(dtype)
    args = (q, k, v, log_decay, beta)

    def turned(x):
        return x.reshape(batch, seq, heads, -1).transpose(0, 2, 1, 3)

    def by_head(q, k, v, log_decay, beta):
        o = kda(turned(q), turned(k), turned(v), turned(log_decay),
                beta.transpose(0, 2, 1))
        return o.transpose(0, 2, 1, 3).reshape(batch, seq, heads * dv)

    def run(fn):
        def all_of(*a):
            o, vjp = jax.vjp(fn, *a)
            return (o,) + vjp(ct)
        traced = jax.jit(all_of).trace(*args)
        return traced.jaxpr.jaxpr, traced.lower().compile(
            compiler_options={"xla_backend_optimization_level": 0})(*args)
    names = ("o", "q", "k", "v", "log_decay", "beta")
    (jaxpr, got), (_, want) = run(kda), run(by_head)
    for name, mine, theirs in zip(names, got, want):
        assert mine.shape == theirs.shape and mine.dtype == theirs.dtype, name
        assert np.any(np.asarray(theirs, np.float32)), name
        np.testing.assert_array_equal(
            np.asarray(mine, np.float32), np.asarray(theirs, np.float32),
            err_msg=name)
    # which operands the kernels were handed
    chunks = -(-seq // 64)
    for kernel, at in (("kda_fwd", 0), ("kda_bwd", 1)):
        grid, ins, _ = pallas_operands(jaxpr, kernel)
        if by_token(dk, dv):
            assert grid == (steps[at], chunks), (kernel, grid)
            assert ins[:3] == [(batch, chunks * 64, heads * dk)] * 2 + [
                (batch, chunks * 64, heads * dv)], (kernel, ins)
            assert ins[3] == ((batch * heads, chunks, 1, 64) if a_head
                              else (batch, chunks * 64, heads * dk))
        else:
            assert ins[:3] == [(batch * heads, chunks * 64, 128)] * 2 + [
                (batch * heads, chunks * 64, 256)], (kernel, ins)
        assert ins[4] == (batch * heads, chunks, 1, 64)


def test_a_negative_eigenvalue_flips_what_the_state_holds(jax_cpu):
    """beta = 2 on a unit key reflects the state along it: reading the same
    key back gives the value written less the value held, and not, as at
    beta = 1, the value written."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.linear_attention import kda
    k = jnp.zeros((1, 1, 2, 8)).at[..., 0].set(1.0)
    v = jnp.stack([jnp.full((8,), 3.0), jnp.full((8,), 5.0)])[None, None]
    zeros = jnp.zeros((1, 1, 2, 8))
    for beta, second in ((1.0, 5.0), (2.0, 2.0 * 5.0 - 2.0 * 3.0)):
        out = kda(k, k, v, zeros, jnp.full((1, 1, 2), beta), chunk=2)
        np.testing.assert_allclose(out[0, 0, 0], beta * 3.0, atol=1e-6)
        np.testing.assert_allclose(out[0, 0, 1], second, atol=1e-6)


# ---------------------------------------------------------------------------
# (b) the plain filter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,shape,kernels", [
    ("float32", (2, 1024, 256), True), ("float32", (1, 64, 128), True),
    ("bfloat16", (1, 2048, 1024), True), ("bfloat16", (2, 96, 128), True),
    ("float32", (2, 50, 96), False),
], ids=["f32", "f32_one_block", "bf16_cell_width", "bf16_small", "jnp_form"])
def test_plain_filter_matches_jnp(jax_cpu, dtype, shape, kernels):
    """silu of a causal 4-tap depthwise filter, forward and both gradients,
    the kernels against jnp written out here; a shape that does not tile
    takes the jnp form."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import short_conv
    dt = jnp.dtype(dtype)
    x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dt)
    taps = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (shape[2], 4))
    ct = jax.random.normal(jax.random.PRNGKey(2), shape)
    tiled = short_conv._conv_blocks(shape[1], shape[2], 4, dt.itemsize)
    assert (tiled is not None) == kernels

    def plain(x, taps):
        # y_t = silu(sum_j w_j x_{t - 3 + j}), zeros before the start
        seq = x.shape[1]
        padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (3, 0), (0, 0)))
        return jax.nn.silu(sum(taps[:, j] * padded[:, j:j + seq]
                               for j in range(4))).astype(x.dtype)

    def run(fn):
        def scalar(x, taps):
            out = fn(x, taps)
            return jnp.sum(out.astype(jnp.float32) * ct), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            scalar, argnums=(0, 1), has_aux=True))(x, taps)
        return out, grads
    out, (dx, dw) = run(short_conv.silu_conv)
    ref, (rx, rw) = run(plain)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(out.astype(np.float32), ref.astype(np.float32),
                               atol=tol)
    np.testing.assert_allclose(dx.astype(np.float32), rx.astype(np.float32),
                               atol=tol)
    np.testing.assert_allclose(dw, rw, rtol=1e-4,
                               atol=1e-4 * float(np.abs(rw).max()))
    names = re.findall(r"name=(conv_silu_\w+)", str(jax.make_jaxpr(
        jax.grad(lambda x: jnp.sum(short_conv.silu_conv(x, taps).astype(
            jnp.float32))))(x)))
    assert names == (["conv_silu_fwd", "conv_silu_bwd"] if kernels else [])


# ---------------------------------------------------------------------------
# (c) for a described v5e: the kernels, a delta-rule layer on four chips and
# (imported) the whole step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_plain_filter_kernels_compile_for_v5e(v5e, backward):
    """ops/short_conv.py's plain pair (silu of a 4-tap filter) at one
    projection of solar2_train_1chip, [1, 8192, 8 heads x 128]: the halo
    after a block filtered from the block's own last rows lays out too."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.short_conv import silu_conv

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(v5e[0]))

    def fn(x, w, g):
        out, vjp = jax.vjp(lambda *a: silu_conv(*a, interpret=False), x, w)
        return vjp(g) if backward else out
    x = shape((1, 8192, 1024))
    text = jax.jit(fn).lower(x, shape((1024, 4), jnp.float32),
                             x).compile().as_text()
    assert ("conv_silu_bwd" if backward else "conv_silu_fwd") in text


@pytest.mark.parametrize("heads", [8, 32], ids=["solar_8_heads",
                                                "kimi_32_heads"])
def test_delta_rule_compiles_at_8192_positions_of_128(v5e, heads):
    """ops/linear_attention.py's two kernels at a delta-rule layer of
    solar2_train_1chip, [1, 8, 8192, 128], and of kimilinear_train_1chip,
    [1, 32, 8192, 128]: `kda_fwd` and `kda_bwd` (the chunk's transpose
    written out: the stacked products of the tree, the rotations back)
    compile inside the VMEM limit each asks for, one Mosaic call each and no
    XLA loop beside them, neither over the 128 chunks nor the 8192 tokens;
    the temporaries are the chunks' kept states (67 MB at 8 heads) and their
    kept matrices (A, Aqk and the inverse packed into [64, 128]: 34 MB,
    which the compiler may hold in VMEM at 8 heads), far under the gigabyte
    and a half the XLA form was held to."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops import linear_attention
    from ray_tpu.ops.linear_attention import kda

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(v5e[0]))
    x = shape((1, heads, 8192, 128))
    args = (x, x, x, shape((1, heads, 8192, 128), jnp.float32),
            shape((1, heads, 8192), jnp.float32))
    compiled = jax.jit(jax.grad(
        lambda *a: jnp.sum(kda(*a, interpret=False).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(\S*kda_(?:fwd|bwd)\S*) = .*custom-call\(", text)
    assert len(calls) == 2 and "fwd" in calls[0] and "bwd" in calls[1], calls
    assert text.count("tpu_custom_call") == 2
    assert " while(" not in text
    # what XLA keeps free of VMEM across each call is what its body asked
    # for (the first size of a call's line; the second is what the body
    # takes), at most half of what `jax.vjp` of the chunk was given
    limits = [int(re.search(r'"size":"(\d+)"', line).group(1))
              for line in text.splitlines()
              if re.search(r"%\S*kda_(?:fwd|bwd)\S* = .*custom-call\(", line)]
    assert limits == [linear_attention._FWD_PARAMS.vmem_limit_bytes,
                      linear_attention._BWD_PARAMS.vmem_limit_bytes]
    assert max(limits) <= 32 << 20
    # the chunks' states, [heads, 128, 128, 128] float32, their matrices,
    # [heads, 128, 64, 128], and little else (at 8 heads the compiler may
    # hold either in VMEM between the two calls)
    states = heads * 128 * 128 * 128 * 4
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert states // 2 <= temporaries < 1.1 * (states + states // 2)


def test_a_heads_decay_compiles_at_8192_positions_of_96_and_192(v5e):
    """The two kernels at a delta-rule layer of olmohybrid_train_1chip, [1,
    15, 8192, 96 / 192] under ONE decay a head: 5 heads a grid step forward
    and 3 backward compile inside the VMEM limits solar's and kimi's shapes
    ask for (their settings are not this shape's to move), one Mosaic call
    each and no XLA loop beside them; the temporaries are the chunks' kept
    states at [256, 128] (the widths padded to lane tiles: 252 MB), their
    matrices (63 MB) and the padded operands."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops import linear_attention
    from ray_tpu.ops.linear_attention import kda

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(v5e[0]))
    args = (shape((1, 15, 8192, 96)), shape((1, 15, 8192, 96)),
            shape((1, 15, 8192, 192)), shape((1, 15, 8192, 1), jnp.float32),
            shape((1, 15, 8192), jnp.float32))
    compiled = jax.jit(jax.grad(
        lambda *a: jnp.sum(kda(*a, interpret=False).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    lines = [line for line in text.splitlines()
             if re.search(r"%\S*kda_(?:fwd|bwd)\S* = .*custom-call\(", line)]
    assert len(lines) == 2 and text.count("tpu_custom_call") == 2
    assert " while(" not in text
    asked, taken = zip(*(map(int, re.findall(r'"size":"(\d+)"', line)[:2])
                         for line in lines))
    assert list(asked) == [linear_attention._FWD_PARAMS.vmem_limit_bytes,
                           linear_attention._BWD_PARAMS.vmem_limit_bytes]
    assert all(t < a for t, a in zip(taken, asked)), (taken, asked)
    states = 15 * 128 * 256 * 128 * 4
    assert states <= compiled.memory_analysis().temp_size_in_bytes \
        < 2.5 * states


def test_delta_rule_layer_compiles_on_four_chip_mesh(v5e, monkeypatch):
    """A delta-rule layer at solar2_train_1chip's widths (8 heads of 128 on
    4096) under tp_fsdp on fsdp=2 x tensor=2, forward and backward: the two
    kernels run per shard (`gpt.py:_per_shard`: a batch row and four whole
    heads a device), as the filters beside them do; GSPMD would refuse the
    Mosaic calls as they stand."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    widths = dict(FAMILY.module.gpt_config_kwargs(FAMILY.cell_config()),
                  n_layers=1, layer_kinds=("kda",), n_experts=0,
                  experts_held=None, n_shared_experts=0, max_seq=2048)
    cfg, mesh, _, layer, x = layer_on_four_chips(v5e, monkeypatch, widths,
                                                  2, 2048)

    def loss(layer, x):
        out, _stats = gpt._kda_block(layer["kda"], x, cfg, gpt.Setting(mesh))
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        layer, x).compile().as_text()
    calls = re.findall(r"%(\S*kda_(?:fwd|bwd)\S*) = .*custom-call\(", text)
    assert len(calls) == 2, calls
    # a shard's own slice: a batch row, four heads' columns of a token
    assert re.search(r"kda_fwd\S* = .*bf16\[1,2048,512\]", text)
    assert "conv_silu_fwd" in text and "all-reduce" in text

# Imported last: a module's names are collected in the order they are bound,
# so the chip's compiler gets this file's programs after its own tests have
# run, at another minute of a run than the other families' files.
from helpers.described_chip import (  # noqa: E402,F401
    test_the_cells_that_were_there_lower_to_the_same_step,
    test_cell_step_compiles_under_the_chips_memory,
    test_cell_step_keeps_the_delta_rule_by_token,
    test_cell_step_makes_a_heads_dw_where_its_logits_are,
    test_the_new_scopes_are_regions_and_reach_the_compiled_step)
