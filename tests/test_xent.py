"""models/gpt.py: chunked_xent, the head's loss, whose gradient is taken in
its forward pass (a jax.custom_vjp: three vocabulary matmuls a chunk and one
softmax, nothing recomputed), against a plain unchunked float32
cross-entropy, against the function as it stood before (autodiff through a
jax.checkpoint: kept here as the reference for the forward's bits and for
the bf16 rounding), and chunked_xent_recompute, the formulation a caller
inside a differentiated scan keeps."""

import numpy as np
import pytest

# rows, chunk_rows, mask
SHAPES = {
    "one_chunk": (256, 256, "some"),
    # what eight of the ten cells run: the rows are fewer than the default
    # chunk and are the chunk, so the scan is one iteration, which the
    # compiler inlines into the step; at 144 rows of bf16 under D = 32 the
    # rule ties dW to dx, as at the cells of 8192 rows
    "the_rows_are_the_one_chunk": (144, 16384, "some"),
    "several_chunks": (512, 128, "some"),
    "rows_not_a_multiple_of_the_chunk": (300, 128, "some"),
    "fewer_rows_than_the_smallest_chunk": (100, 16384, "ones"),
    "every_row_masked": (256, 128, "zeros"),
}
D, V = 32, 257


def _plain_xent(jax, jnp, x, w, targets, mask):
    """The whole [N, V] logits in float32, no chunk, no custom rule."""
    logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0])
    return jnp.sum(nll * mask), jnp.sum(mask)


def _xent_before(jax, jnp, x, w_head, targets, mask, chunk_rows=16384):
    """chunked_xent as it stood before its gradient moved into the forward
    pass, line for line."""
    n, d = x.shape
    chunk_rows = min(chunk_rows, max(128, n))
    pad = (-n) % chunk_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    n_chunks = (n + pad) // chunk_rows
    xc = x.reshape(n_chunks, chunk_rows, d)
    tc = targets.reshape(n_chunks, chunk_rows)
    mc = mask.reshape(n_chunks, chunk_rows)

    @jax.checkpoint
    def body(carry, args):
        xk, tk, mk = args
        logits = (xk @ w_head).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tk[:, None], axis=-1)[:, 0]
        nll = lse - picked
        return (carry[0] + jnp.sum(nll * mk), carry[1] + jnp.sum(mk)), None

    (total, denom), _ = jax.lax.scan(body, (0.0, 0.0), (xc, tc, mc))
    return total, denom


def _operands(jax, jnp, shape, dtype):
    rows, chunk_rows, mask = SHAPES[shape]
    kx, kw, kt, km = jax.random.split(jax.random.PRNGKey(rows), 4)
    x = jax.random.normal(kx, (rows, D), jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, (D, V), jnp.float32) * 0.3).astype(dtype)
    targets = jax.random.randint(kt, (rows,), 0, V)
    mask = {"ones": jnp.ones((rows,), jnp.float32),
            "zeros": jnp.zeros((rows,), jnp.float32),
            "some": (jax.random.uniform(km, (rows,)) > 0.3
                     ).astype(jnp.float32)}[mask]
    return x, w, targets, mask, chunk_rows


def _grads(jax, xent, operands, scale=3.0):
    """Value and gradients (x, w_head, mask) of the mean the model takes,
    times a scale: the cotangent that reaches the rule is scale / denom and
    never 1."""
    x, w, targets, mask, chunk_rows = operands

    def loss(x, w, mask):
        total, denom = xent(x, w, targets, mask, chunk_rows)
        return scale * total / denom.clip(1.0)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(x, w, mask)


@pytest.mark.parametrize("shape", SHAPES)
def test_gradients_are_the_plain_float32_ones(jax_cpu, shape):
    """x, w_head and the mask's gradients of the rule, through a scaled mean
    (a cotangent other than 1), against jax.grad of the unchunked loss."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import chunked_xent
    operands = _operands(jax, jnp, shape, jnp.float32)
    loss, grads = _grads(jax, chunked_xent, operands)
    want_loss, want = _grads(
        jax, lambda x, w, t, m, _rows: _plain_xent(jax, jnp, x, w, t, m),
        operands)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for got, ref in zip(grads, want):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        np.testing.assert_allclose(got, ref, atol=1e-6, rtol=0)
    if SHAPES[shape][2] == "zeros":
        assert not np.any(grads[0]) and not np.any(grads[1])


@pytest.mark.parametrize("shape", SHAPES)
def test_bf16_gradients_within_one_rounding_of_autodiff(jax_cpu, shape):
    """In the model dtype the rule rounds where autodiff through the old
    body rounded (d logits to bf16, each product's result to bf16), so it
    stays within one bf16 step, at the tensor's largest magnitude, of
    jax.grad of that body, and is no farther from the float32 gradient."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import chunked_xent
    operands = _operands(jax, jnp, shape, jnp.bfloat16)
    _, grads = _grads(jax, chunked_xent, operands)
    _, before = _grads(
        jax, lambda *a: _xent_before(jax, jnp, *a), operands)
    x, w, targets, mask, chunk_rows = operands
    _, exact = _grads(
        jax, lambda x, w, t, m, _rows: _plain_xent(jax, jnp, x, w, t, m),
        (x.astype(jnp.float32), w.astype(jnp.float32), targets, mask,
         chunk_rows))
    for got, ref, true in zip(grads[:2], before[:2], exact[:2]):
        assert got.dtype == jnp.bfloat16
        got, ref, true = (np.asarray(a, np.float32) for a in (got, ref, true))
        # bf16 keeps 8 significant bits: the spacing at the largest entry
        step = 2.0 ** (np.floor(np.log2(max(np.abs(ref).max(), 1e-30))) - 7)
        assert np.abs(got - ref).max() <= step
        assert np.abs(got - true).max() <= np.abs(ref - true).max() + step


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", SHAPES)
def test_forward_bits_are_the_function_before(jax_cpu, shape, dtype):
    """(sum of nll, sum of mask) evaluated alone, evaluated under
    value_and_grad (the forward rule's own scan), and of
    chunked_xent_recompute: one number, and in float32 the bits of the
    function as it stood before. In bf16 that function read the target's
    logit from an fp32 copy of the logits which XLA fills with the matmul's
    unrounded result (the TPU's fusion does, and this backend's excess
    precision does), while its logsumexp saw the rounded ones; the target's
    logit is now read from the bf16 result too, so the sums differ by one
    bf16 rounding a row (PERF.md, PR 30: 3.8e-6 and 3.4e-5 of a first loss
    of 11.3-11.4 on the chip)."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import chunked_xent, chunked_xent_recompute
    x, w, targets, mask, chunk_rows = _operands(jax, jnp, shape,
                                                jnp.dtype(dtype))
    want = jax.jit(lambda *a: _xent_before(jax, jnp, *a, chunk_rows))(
        x, w, targets, mask)
    values = []
    for xent in (chunked_xent, chunked_xent_recompute):
        values.append(jax.jit(lambda *a, f=xent: f(*a, chunk_rows))(
            x, w, targets, mask))
        values.append(jax.jit(jax.value_and_grad(
            lambda x, f=xent: f(x, w, targets, mask, chunk_rows),
            has_aux=True))(x)[0])
    for total, denom in values:
        assert total.dtype == denom.dtype == jnp.float32
        assert (float(total), float(denom)) == tuple(map(float, values[0]))
    if dtype == "float32":
        assert tuple(map(float, values[0])) == tuple(map(float, want))
    else:
        np.testing.assert_allclose(values[0], want, rtol=1e-4)


@pytest.mark.parametrize("shape", ["several_chunks",
                                   "rows_not_a_multiple_of_the_chunk",
                                   "the_rows_are_the_one_chunk"])
def test_recompute_formulation_has_the_same_gradients(jax_cpu, shape):
    """What parallel/pipeline.py's last rank calls: autodiff through the
    rematted body, equal to the rule's gradients in float32."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import chunked_xent, chunked_xent_recompute
    operands = _operands(jax, jnp, shape, jnp.float32)
    loss, grads = _grads(jax, chunked_xent, operands)
    want_loss, want = _grads(jax, chunked_xent_recompute, operands)
    assert float(loss) == float(want_loss)
    for got, ref in zip(grads, want):
        np.testing.assert_allclose(got, ref, atol=1e-6, rtol=0)


@pytest.mark.parametrize("rows,d,dtype,chunk_rows,together", [
    (128, 32, "bfloat16", 16384, True),     # 256 B of logits a column < 384
    (256, 32, "bfloat16", 16384, False),    # 512 B: the compiler's own place
    (128, 32, "float32", 16384, False),     # 512 B in float32 too
    (128, 64, "float32", 16384, True),      # 512 B < 768
    (256, 64, "bfloat16", 128, False),      # two chunks: a real loop
], ids=["8192_rows_of_bf16_at_olmoe", "16384_rows", "float32",
        "float32_and_wider", "a_real_loop"])
def test_the_gradients_leave_together_where_a_lone_chunks_logits_are_smaller(
        jax_cpu, rows, d, dtype, chunk_rows, together):
    """The rule's backward ties dW to dx (one optimization_barrier) exactly
    where the scan is one chunk whose logits, a vocabulary column, are
    fewer bytes than the three float32 results of w_head's update: the
    shapes at which the chip's compiler would hold the logits to the end of
    the step (at a cell's scale: 8192 rows of bf16 under a model 2048 or
    4096 wide, and not 16 384 rows under 2048 or 2560). Everywhere else the
    step is the program it was."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import chunked_xent
    x = jnp.ones((rows, d), jnp.dtype(dtype))
    w = jnp.ones((d, V), jnp.dtype(dtype))
    targets = jnp.zeros((rows,), jnp.int32)
    mask = jnp.ones((rows,), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, w: chunked_xent(x, w, targets, mask, chunk_rows)[0],
        argnums=(0, 1)))(x, w))
    assert text.count("optimization_barrier") == int(together)


# ------------------------------------------------------- through the model
def _tiny(tie, **over):
    import jax.numpy as jnp
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(vocab_size=384, d_model=64, n_layers=2, n_heads=2,
                     d_ff=128, max_seq=64, dtype=jnp.float32,
                     attention="reference", tie_embeddings=tie, **over)


def _logits_loss(params, batch, cfg):
    """gpt_loss without chunked_xent: the whole logits through gpt_forward,
    negative targets left out."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.gpt import gpt_forward
    tokens = batch["tokens"]
    logits, _ = gpt_forward(params, tokens[:, :-1], cfg)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    mask = (targets >= 0).astype(jnp.float32)
    return -jnp.sum(picked * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _tokens(cfg, batch, seed=0):
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, cfg.max_seq + 1)).astype(np.int32)
    tokens[:, -5:] = -1     # a padded tail: targets the loss leaves out
    tokens[:, 0] = 1
    return tokens


def _assert_trees_close(got, want, atol):
    import jax
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, leaf in jax.tree_util.tree_flatten_with_path(got)[0]:
        np.testing.assert_allclose(leaf, flat_want[path], atol=atol, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("tie", [False, True], ids=["untied", "tied"])
def test_gpt_loss_gradients_tied_and_untied(jax_cpu, tie):
    """Through gpt_loss: an untied head's gradient lands on lm_head, a tied
    one's on the embedding table, with the lookup's gradient added."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_init, gpt_loss
    cfg = _tiny(tie)
    params = gpt_init(jax.random.PRNGKey(1), cfg)
    assert ("lm_head" in params) != tie
    batch = {"tokens": _tokens(cfg, 4)}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: gpt_loss(p, batch, cfg)))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: _logits_loss(p, batch, cfg)))(params)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    _assert_trees_close(grads, want, atol=2e-6)


@pytest.mark.parametrize("tie", [False, True], ids=["untied", "tied"])
def test_gpt_loss_gradients_on_an_fsdp_tensor_mesh(jax_cpu, tie):
    """The rule is plain jnp under GSPMD: on a fake fsdp=2 x tensor=2 mesh,
    parameters cut as tp_fsdp cuts them, the gradients are the one-device
    ones."""
    jax = jax_cpu
    from jax.sharding import NamedSharding
    from ray_tpu.models.gpt import gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    cfg = _tiny(tie)
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                      devices=jax.devices()[:4])
    strategy = strategy_from_name("tp_fsdp")
    params = gpt_init(jax.random.PRNGKey(2), cfg)
    batch = {"tokens": _tokens(cfg, 4, seed=1)}
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: _logits_loss(p, batch, cfg)))(params)

    shardings = strategy.param_shardings(mesh, params)
    sharded = jax.device_put(params, shardings)
    tokens = jax.device_put(batch["tokens"],
                            NamedSharding(mesh, strategy.batch_spec))
    act = strategy.activation_sharding(mesh)
    loss, grads = jax.jit(
        jax.value_and_grad(
            lambda p, t: gpt_loss(p, {"tokens": t}, cfg, mesh, act)),
        out_shardings=(None, shardings))(sharded, tokens)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    _assert_trees_close(grads, want, atol=2e-6)


@pytest.mark.parametrize("accum_steps", [0, 2])
def test_train_step_applies_the_rules_gradient(jax_cpu, accum_steps):
    """make_train_step differentiates inside each microbatch of its
    accumulation scan and averages: with sgd at rate 1 the parameters move
    by the mean of the microbatches' plain gradients."""
    jax = jax_cpu
    import optax
    from ray_tpu.models.gpt import gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.train.train_step import init_train_state, make_train_step
    cfg = _tiny(False)
    mesh = build_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    optimizer = optax.sgd(1.0)
    state = init_train_state(lambda: gpt_init(jax.random.PRNGKey(3), cfg),
                             optimizer, mesh, "dp")
    before = jax.tree_util.tree_map(np.asarray, state.params)
    micro = [_tokens(cfg, 2, seed=s) for s in range(max(accum_steps, 1))]
    want = [jax.jit(jax.grad(lambda p, t=t: _logits_loss(
        p, {"tokens": t}, cfg)))(before) for t in micro]
    want = jax.tree_util.tree_map(lambda *g: sum(g) / len(g), *want)

    step = make_train_step(lambda p, b: gpt_loss(p, b, cfg), optimizer, mesh,
                           "dp", sample_params=state.params, donate=False,
                           accum_steps=accum_steps)
    tokens = np.stack(micro) if accum_steps else micro[0]
    state, _ = step(state, {"tokens": tokens})
    moved = jax.tree_util.tree_map(lambda a, b: a - np.asarray(b), before,
                                   state.params)
    _assert_trees_close(moved, want, atol=2e-6)


# --------------------------------------- does it engage: the lowered step
def _vocab_dots(jaxpr, vocab, stack=""):
    """(name stack, phase) of every dot_general with a dimension of the
    vocabulary's size, through every nested jaxpr, named as the lowering
    names them: the enclosing equations' stacks joined."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "dot_general" and any(
                vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars)):
            yield here
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _vocab_dots(
                        sub, vocab, f"{here}/{eqn.primitive.name}")


@pytest.mark.parametrize("model,axes,strategy", [
    (dict(), dict(data=1), "dp"),
    (dict(tie_embeddings=True), dict(data=1, fsdp=2, tensor=2), "tp_fsdp"),
    (dict(n_experts=8, expert_top_k=2, qk_norm=True, d_ff=64),
     dict(data=1), "dp"),
], ids=["dense_dp", "dense_tied_tp_fsdp", "sparse_dp"])
def test_step_holds_three_vocabulary_matmuls_and_recomputes_none(
        jax_cpu, model, axes, strategy):
    """The engagement check that needs no chip: the step make_train_step
    builds over gpt_loss, at a rehearsal size, has exactly three
    dot_generals with the vocabulary dimension (logits, dx, dW), all under
    scope head, none under a rematted_computation. Before the rule there
    were four, one of them recomputed."""
    jax = jax_cpu
    import jax.numpy as jnp
    import optax
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import TrainState, make_train_step
    vocab = 384   # no other dimension of the step has this size
    cfg = GPTConfig(**{**dict(vocab_size=vocab, d_model=128, n_layers=2,
                              n_heads=4, d_ff=256, max_seq=128), **model})
    chips = int(np.prod(list(axes.values())))
    mesh = build_mesh(MeshConfig(**axes), devices=jax.devices()[:chips])
    strategy = strategy_from_name(strategy)
    optimizer = optax.adamw(1e-3)
    params = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))
    state = TrainState(params, jax.eval_shape(optimizer.init, params),
                       jax.ShapeDtypeStruct((), jnp.int32))
    batch = {"tokens": jax.ShapeDtypeStruct((8, cfg.max_seq + 1), jnp.int32)}
    act = strategy.activation_sharding(mesh)
    step = make_train_step(lambda p, b: gpt_loss(p, b, cfg, mesh, act),
                           optimizer, mesh, strategy, sample_params=params)
    dots = list(_vocab_dots(jax.make_jaxpr(step)(state, batch).jaxpr, vocab))
    assert len(dots) == 3, dots
    assert all("head" in stack for stack in dots), dots
    assert not any("remat" in stack or "checkpoint" in stack
                   for stack in dots), dots
