"""The token-embedding lookup (ops/embedding.py) on the CPU, its kernel in
the Pallas interpreter: the forward's bits are those of rounding the table
and gathering, the gradient is the float32 sum of each vocabulary row's
tokens rounded once, and which path runs is read off the mesh alone."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WIDTH = 128


def _operands(jax, vocab, batch, seq, tokens=None, seed=0):
    import jax.numpy as jnp
    table = jax.random.normal(jax.random.PRNGKey(seed), (vocab, WIDTH),
                              jnp.float32)
    if tokens is None:
        tokens = np.random.default_rng(seed + 1).integers(
            0, vocab, (batch, seq), dtype=np.int32)
    # the rows' cotangent, in the model's type as the first layer hands it
    g = jax.random.normal(jax.random.PRNGKey(seed + 2),
                          (batch, seq, WIDTH), jnp.float32
                          ).astype(jnp.bfloat16)
    return table, jnp.asarray(tokens), g


def _gradient(jax, table, tokens, g, jit=True):
    import jax.numpy as jnp
    from ray_tpu.ops.embedding import embed_lookup

    def pulled(table):
        _rows, pull = jax.vjp(
            lambda t: embed_lookup(t, tokens, jnp.bfloat16), table)
        return pull(g)[0]
    return (jax.jit(pulled) if jit else pulled)(table)


def _rounded_once(jax, vocab, tokens, g):
    """The float32 one-hot sum of g's rows a vocabulary row, rounded once
    to g's type and widened."""
    import jax.numpy as jnp
    onehot = jax.nn.one_hot(tokens.reshape(-1), vocab, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        exact = onehot.T @ g.reshape(tokens.size, -1).astype(jnp.float32)
    return exact.astype(g.dtype).astype(jnp.float32), exact


# 37 984 / 8 and 50 257: no multiple of a group of 256 or 512 rows
@pytest.mark.parametrize("vocab", [4748, 50257])
@pytest.mark.parametrize("batch,seq", [(1, 96), (2, 56)],
                         ids=["1xS", "2xS"])
def test_forward_bits_and_the_gradient_rounded_once(jax_cpu, vocab, batch,
                                                    seq):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.embedding import embed_lookup
    table, tokens, g = _operands(jax, vocab, batch, seq)
    tokens = tokens.at[0, 0].set(0).at[0, 1].set(vocab - 1)
    rows = jax.jit(lambda t: embed_lookup(t, tokens, jnp.bfloat16))(table)
    assert rows.dtype == jnp.bfloat16
    assert bool(jnp.all(rows == table.astype(jnp.bfloat16)[tokens]))
    got = _gradient(jax, table, tokens, g)
    want, _exact = _rounded_once(jax, vocab, tokens, g)
    assert got.dtype == table.dtype and got.shape == table.shape
    assert bool(jnp.all(got == want))
    # ids at both ends of the table got their rows, and nothing else did
    assert bool(jnp.any(got[0] != 0)) and bool(jnp.any(got[vocab - 1] != 0))
    named = np.zeros(vocab, bool)
    named[np.asarray(tokens).reshape(-1)] = True
    assert not bool(jnp.any(got[~named]))


def test_one_id_repeated_is_summed_in_float32(jax_cpu):
    """Every token the same id: one row of the gradient holds the float32
    sum of all T rows, rounded once, where a bf16 scatter-add rounds after
    every addend."""
    jax = jax_cpu
    import jax.numpy as jnp
    vocab, seq = 4748, 160
    table, tokens, g = _operands(
        jax, vocab, 1, seq, tokens=np.full((1, seq), 4321, np.int32))
    got = _gradient(jax, table, tokens, g)
    want, exact = _rounded_once(jax, vocab, tokens, g)
    assert bool(jnp.all(got == want))
    today = jax.grad(lambda t: jnp.sum(
        (t.astype(jnp.bfloat16)[tokens] * g).astype(jnp.float32)))(table)
    assert (float(jnp.max(jnp.abs(got - exact)))
            <= float(jnp.max(jnp.abs(today - exact))))


def test_fewer_tokens_than_groups_and_without_jit(jax_cpu):
    """8 tokens over 197 groups: nearly every group is an all-zero tile."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import embedding
    vocab = 50257
    table, tokens, g = _operands(jax, vocab, 1, 8)
    assert tokens.size < -(-vocab // embedding._GROUP_ROWS)
    got = _gradient(jax, table, tokens, g, jit=False)
    want, _exact = _rounded_once(jax, vocab, tokens, g)
    assert bool(jnp.all(got == want))


def test_an_id_outside_the_table_adds_nothing(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.embedding import table_gradient
    vocab = 4748
    _table, tokens, g = _operands(jax, vocab, 1, 64)
    ids = tokens.reshape(-1)
    stray = ids.at[3].set(vocab).at[9].set(-1).at[20].set(vocab + 700)
    kept = jnp.ones(64, bool).at[jnp.array([3, 9, 20])].set(False)
    got = table_gradient(stray, g[0], vocab)
    want = table_gradient(ids, jnp.where(kept[:, None], g[0], 0), vocab)
    assert got.shape == (vocab, WIDTH) and got.dtype == g.dtype
    assert bool(jnp.all(got == want))


def test_the_tile_rule(jax_cpu):
    """Rows a tile from the shape: whole sublane tiles, at most the MXU's
    128, and a grid in the hundreds of steps at the cells' shapes."""
    import jax.numpy as jnp
    from ray_tpu.ops import embedding
    for vocab, ids in [(37984, 16384), (50304, 8192), (50304, 65536),
                       (18992, 16384), (8192, 16384), (4748, 96)]:
        n_groups = -(-vocab // embedding._GROUP_ROWS)
        rows = embedding.tile_rows(ids, n_groups, jnp.bfloat16)
        assert rows % 16 == 0 and rows <= 128
        assert -(-ids // rows) + n_groups < 1000


def _tiny(jax, tied):
    import jax.numpy as jnp
    from ray_tpu.models.gpt import GPTConfig, gpt_init
    cfg = GPTConfig(vocab_size=600, d_model=64, n_layers=1, n_heads=2,
                    d_ff=128, max_seq=32, dtype=jnp.bfloat16,
                    attention="reference", remat_policy="none",
                    tie_embeddings=tied)
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    tokens = np.random.default_rng(1).integers(0, 600, (2, 25),
                                               dtype=np.int32)
    return cfg, params, {"tokens": jnp.asarray(tokens)}


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_under_the_loss_the_tables_gradient_is_the_lookups(jax_cpu, tied):
    """gpt_loss's value_and_grad at a tiny configuration: the loss is that
    of the parent's expression to the bit, and the table's gradient is the
    lookup's part (the one-hot sum of the rows' cotangent, which jax.vjp of
    the layers gives, rounded once) plus, tied, the head's part."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    cfg, params, batch = _tiny(jax, tied)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: gpt.gpt_loss(p, batch, cfg)))(params)

    # the same model with the lookup's rows as an input of their own
    inputs = batch["tokens"][:, :-1]
    rows = params["embed"]["table"].astype(cfg.dtype)[inputs]

    def by_rows(params, rows):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(gpt, "embed_lookup", lambda *_a: rows)
            return gpt.gpt_loss(params, batch, cfg)
    loss_rows, (rest, g_rows) = jax.jit(jax.value_and_grad(
        by_rows, argnums=(0, 1)))(params, rows)
    assert float(loss) == float(loss_rows)
    lookup, _exact = _rounded_once(jax, cfg.vocab_size, inputs, g_rows)
    want = lookup + (rest["embed"]["table"] if tied else 0.0)
    np.testing.assert_array_equal(np.asarray(grads["embed"]["table"]),
                                  np.asarray(want))


def test_the_kernel_runs_under_its_own_name(jax_cpu):
    """`embed_grad`, never `moe_tgmm`: benchmark/xplane.py counts an event
    under the kernel whose name its op_name ends in, and olmoe's
    `moe_tgmm_roofline` would average a call of another shape in."""
    jax = jax_cpu
    cfg, params, batch = _tiny(jax, False)
    from ray_tpu.models import gpt
    from ray_tpu.util.profiling import KERNELS
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: gpt.gpt_loss(p, batch, cfg)))(params))
    assert "embed_grad" in text and "embed_grad" in KERNELS
    assert "moe_tgmm" not in text


def test_under_a_mesh_the_step_is_the_parents_form(jax_cpu):
    """fsdp=2 x tensor=2 on the CPU: the table is cut over `vocab`, GSPMD
    places the gradient's sum, and the lookup is today's expression: no
    `embed_grad` in the lowered step, and the very text of a step whose
    lookup is written out as the parent wrote it."""
    jax = jax_cpu
    import optax
    from ray_tpu.models import gpt
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import init_train_state, make_train_step
    cfg, _params, batch = _tiny(jax, True)
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                      devices=jax.devices()[:4])
    strategy = strategy_from_name("tp_fsdp")
    optimizer = optax.adamw(1e-3)
    state = init_train_state(
        lambda: gpt.gpt_init(jax.random.PRNGKey(0), cfg), optimizer, mesh,
        strategy)

    def lowered():
        return make_train_step(
            lambda p, b: gpt.gpt_loss(p, b, cfg, mesh,
                                      strategy.activation_sharding(mesh)),
            optimizer, mesh, strategy, sample_params=state.params
        ).lower(state, batch).as_text(debug_info=False)
    text = lowered()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(gpt, "embed_lookup",
                      lambda table, tokens, dtype, mesh:
                      table.astype(dtype)[tokens])
        assert lowered() == text

    # the interpreter leaves no name in a lowered text; the jaxpr has it
    def jaxpr(*where):
        return str(jax.make_jaxpr(jax.grad(
            lambda p: gpt.gpt_loss(p, batch, cfg, *where)))(state.params))
    assert "embed_grad" not in jaxpr(mesh, strategy.activation_sharding(mesh))
    assert "embed_grad" in jaxpr()
    one_device = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    assert "embed_grad" in jaxpr(one_device)
