"""The MLP's matmul results through the remat (models/gpt.py:MLP_OUT,
layer_fn's `keep_mlp(n)`, `mlp_products_kept`; parallel/memory.py): how many
of them every layer keeps is reckoned from what the step's builder reports
and the shapes, the arithmetic is the same work done once instead of twice,
and a step that keeps none is the program it was."""

import functools
import re

import numpy as np
import pytest

from helpers.described_chip import V5E_BYTES as V5E
from helpers.described_chip import v5e  # noqa: F401 — a fixture
from helpers.families import read

# cell -> (how many of its products every MLP keeps on a v5e, the layers
# that have an MLP, prediction modules' included). gpt2s: its MLP is SwiGLU
# (three matrices), so `up x` in twelve layers is 4.83 GB, ISSUE 63's
# figure, and both products would be 9.66 GB of the 9.3 the chip has free.
# Four chips: `up x` in 24 layers is 6.4 GB a chip. solar and nemotron are
# reckoned over the ceiling as they are; three cells have no `_mlp_block`.
CELLS = {
    "gpt2s_train_1chip": (1, 12),
    "smollm17_train_4chip": (0, 24),
    "olmoe_train_1chip": (0, 0),
    "kanana2_train_1chip": (2, 5),
    "lfm2_train_1chip": (2, 1),
    "laguna_train_1chip": (2, 5),
    "keye2_train_1chip": (0, 0),
    "solar2_train_1chip": (0, 4),
    "smallthinker_train_1chip": (0, 0),
    "nemotron3s_train_1chip": (0, 6),
    "granite4hm_train_1chip": (1, 10),
    # the dense MLP of 9216 under the first delta-rule layer and four
    # shared experts of 1024: 0.37 GB for both products of all five
    "kimilinear_train_1chip": (2, 5),
    # four MLPs of 11008 beside 12.26 GB of state: `up x` alone is 0.72 GB
    "olmohybrid_train_1chip": (1, 4),
}


@functools.lru_cache(maxsize=None)
def _cell(cell):
    """The cell's loss traced over shapes once, with what `mlp_products_kept`
    was handed: (its arguments, the budget the step's builder would give
    on devices of `limit` bytes)."""
    import jax
    import jax.numpy as jnp
    import optax
    from benchmark import model
    from ray_tpu.models import gpt
    from ray_tpu.parallel import memory
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train import train_step as ts

    bench = read("BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    config = read(next(c for c in bench["configs"]
                       if c["name"] == entry["config"])["file"])
    mix = read("benchmark", "traffic", entry["traffic"] + ".json")
    program = model.family(config).program(config)
    mesh = build_mesh(MeshConfig(**mix["mesh"]),
                      devices=jax.devices()[:entry["chips"]])
    strategy = strategy_from_name(mix["strategy"])
    params = jax.eval_shape(lambda: program.init(jax.random.PRNGKey(0)))
    optimizer = optax.adamw(1e-4)
    shardings = strategy.param_shardings(mesh, params)
    held = memory.tree_bytes(params, shardings)
    state = held + memory.tree_bytes(
        jax.eval_shape(optimizer.init, params),
        ts._opt_state_shardings(optimizer, params, shardings, mesh))
    seen = []
    real = gpt.mlp_products_kept

    def spy(*args):
        seen.append(args)
        return real(*args)
    gpt.mlp_products_kept = spy
    try:
        jax.eval_shape(
            lambda p, b: program.loss(p, b, mesh,
                                      strategy.activation_sharding(mesh)),
            params, {"tokens": jax.ShapeDtypeStruct(
                (mix["global_batch"], mix["seq"] + 1), jnp.int32)})
    finally:
        gpt.mlp_products_kept = real
    return seen[0], functools.partial(
        memory.Budget, state=state, share=held / memory.tree_bytes(params))


def _kept(cell, limit):
    """(products every MLP keeps, the layers that have one, the reckoned
    peak) for the cell's step on devices of `limit` bytes, as the traced
    step reports them."""
    from ray_tpu.models import gpt
    from ray_tpu.parallel import memory
    args, budget = _cell(cell)
    said = []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(memory, "report", lambda *a: said.append(a))
        with memory.told(budget(limit=limit)):
            n = gpt.mlp_products_kept(*args)
    (products, of, kept_bytes, peak, _limit, _passes), = said
    assert products == n and (kept_bytes > 0) == (n > 0)
    return n, of, peak


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_cells_products_kept_on_a_described_v5e(jax_cpu, cell):
    """The reckoning on each cell's shapes and a device of 16.9 GB: what
    the table says (0 at solar and nemotron, over the ceiling as they are,
    and in the three cells without an `_mlp_block`), in every layer that
    has an MLP or in none, the reckoned peak under the ceiling wherever
    anything is kept, 0 where the platform reports no limit (the CPU's
    `memory_stats()` is None) and where nobody reports, and never falling
    as the limit rises."""
    from ray_tpu.models import gpt
    from ray_tpu.parallel import memory
    products, having = CELLS[cell]
    n, of, peak = _kept(cell, V5E)
    assert (n, of) == (products, having)
    assert n == 0 or peak <= memory.CEILING * V5E
    assert _kept(cell, None)[0] == 0
    assert memory.device_limit(jax_cpu.devices()[:1]) is None
    assert gpt.mlp_products_kept(*_cell(cell)[0]) == 0      # nobody told
    ns = [_kept(cell, int(V5E * x))[0]
          for x in (0.25, 0.5, 0.75, 0.9, 1.0, 1.25, 2.0, 8.0)]
    # (a cell whose MLPs have no gate, nemotron, has one product to keep)
    assert ns == sorted(ns) and ns[-1] == (
        0 if not having else 1 if cell.startswith("nemotron") else 2), ns


def test_the_walk_finds_the_fullest_moment():
    """memory.reckoned_peak over a backward pass by hand: where the layers
    keep less than their gradients weigh the end of the pass is fullest,
    where they keep more the first layer's turn is, or the head's; and
    memory.most_kept takes the fullest choice under the ceiling."""
    from ray_tpu.parallel import memory
    peak = functools.partial(memory.reckoned_peak, 100, 5, [10, 10, 10], 7,
                             working=0, head=0)
    bare = 100 + memory.OVERHEAD
    assert peak(held=[1, 1, 1]) == bare + 5 + 30 + 7
    assert peak(held=[1, 1, 1], head=50) == bare + 3 + 50
    assert peak(held=[20, 20, 20]) == bare + 5 + 10 + 60
    assert peak(held=[1, 1, 20]) == bare + 5 + 30 + 7
    assert peak(held=[20, 1, 1]) == bare + 5 + 30 + 20
    assert peak(held=[1, 1, 1], working=9) == bare + 5 + 30 + 1 + 9
    assert memory.most_kept(100, [50, 80, 120], ceiling=0.9) == 1
    assert memory.most_kept(100, [50, 80, 120], ceiling=0.7) == 0
    assert memory.most_kept(100, [95, 96, 97], ceiling=0.9) == 0
    assert memory.most_kept(None, [50, 80, 120]) == 0


def _tiny(form):
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=256, d_model=64, n_layers=3, n_heads=2,
                        d_ff=128, max_seq=32, dtype=jnp.float32,
                        expert_form=form)
    params = gpt.gpt_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                          cfg.vocab_size)}
    return cfg, params, batch


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_keeping_is_the_same_arithmetic(jax_cpu, monkeypatch, gated):
    """Loss and every gradient of a small stack at float32 activations with
    every MLP keeping none, one and both of its products: to the bit (the
    kept value is the product the forward pass computed). The keeping
    step's jaxpr names what it keeps, the other names nothing; under
    remat_policy "none" no block carries the name and the program is the
    one it was at any n."""
    import dataclasses
    jax = jax_cpu
    from ray_tpu.models import gpt
    cfg, params, batch = _tiny(
        None if gated else gpt.ExpertForm(matrices=2, activation="relu2"))

    def step(cfg, k):
        monkeypatch.setattr(gpt, "mlp_products_kept", lambda *a: k)
        return jax.jit(jax.value_and_grad(
            lambda p: gpt.gpt_loss(p, batch, cfg)))

    plain = step(cfg, 0)(params)
    for k in (1, 2):
        kept = step(cfg, k)(params)
        assert all(np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(plain), jax.tree_util.tree_leaves(kept)))
        names = re.findall(r"name=(\w+)", str(step(cfg, k).trace(params).jaxpr))
        assert names.count(gpt.MLP_OUT) == cfg.n_layers * (
            k if gated else 1), names
    assert gpt.MLP_OUT not in str(step(cfg, 0).trace(params).jaxpr)
    bare = dataclasses.replace(cfg, remat_policy="none")
    texts = {k: step(bare, k).lower(params).as_text() for k in (0, 2)}
    assert texts[0] == texts[2] and gpt.MLP_OUT not in texts[0]


def test_what_a_layer_keeps_is_what_the_checkpoint_saves(jax_cpu):
    """`_layer_bytes` (the closed forms the reckoning uses) against what
    jax.checkpoint saves of a layer, for every kind of layer the rehearsal
    configurations have: the input, the kernels' named results and, in the
    keeping blocks, one and both of the MLP's products."""
    jax = jax_cpu
    import jax.numpy as jnp
    from jax._src.ad_checkpoint import saved_residuals
    from benchmark import model
    from ray_tpu.models import gpt
    kinds = set()
    for name in ("tiny", "tiny-granite-hybrid", "tiny-kanana", "tiny-keye",
                 "tiny-kimi-linear", "tiny-laguna", "tiny-lfm2",
                 "tiny-olmo-hybrid",
                 "tiny-nemotron-h", "tiny-solar"):
        config = read("benchmark", "rehearsal", "configs", name + ".json")
        cfg = model.family(config)._train_config(config) if hasattr(
            model.family(config), "_train_config") else None
        if cfg is None:
            from benchmark.families.gpt_dense import gpt_config_kwargs
            cfg = gpt.GPTConfig(**gpt_config_kwargs(config))
        layers = jax.eval_shape(
            lambda: gpt.gpt_init(jax.random.PRNGKey(0), cfg))["layers"]
        batch, seq = 2, min(cfg.max_seq, 256)
        block = gpt.layer_fn(cfg, seq, gpt.Setting())
        x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype)
        for layer in layers:
            kind = (name, tuple(sorted(layer)),
                    gpt._mlp_of(layer) is not None)
            if kind in kinds:
                continue
            kinds.add(kind)
            kept, products = gpt._layer_bytes(layer, batch, seq, cfg,
                                              gpt.Setting())
            for n in range(3):
                fn, want = block.keep_mlp(n), kept + products[n]
                saved = sum(
                    aval.size * aval.dtype.itemsize
                    for aval, why in saved_residuals(fn, x, layer)
                    if "the argument layer" not in why
                    and "constant" not in why)
                assert saved == want, (kind, n, saved, want)
    assert len(kinds) >= 15, kinds


def test_a_pipeline_stage_keeps_what_it_is_told(jax_cpu, monkeypatch):
    """The pipeline's stage with every layer keeping both of its MLP's
    products against none: loss and gradients to the bit, and the keeping
    stage names them where the other does not."""
    jax = jax_cpu
    import dataclasses
    from ray_tpu.models import gpt
    from ray_tpu.parallel import pipeline
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    cfg, params, _ = _tiny(None)
    cfg = dataclasses.replace(cfg, n_layers=4, attention="reference")
    params = gpt.gpt_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                          cfg.vocab_size)}
    mesh = build_mesh(MeshConfig(data=2, pipeline=2), jax.devices()[:4])
    stacked = pipeline.gpt_params_to_pp(params)
    results = {}
    for keep in (0, 2):
        monkeypatch.setattr(pipeline, "mlp_products_kept",
                            lambda *a, keep=keep: keep)
        step = jax.jit(jax.value_and_grad(
            pipeline.make_gpt_pp_loss(cfg, mesh, num_microbatches=2)))
        results[keep] = step(stacked, batch)
        assert (gpt.MLP_OUT in str(step.trace(stacked, batch).jaxpr)) \
            == bool(keep)
    assert all(np.array_equal(a, b) for a, b in zip(
        *map(jax.tree_util.tree_leaves, (results[0], results[2]))))


@pytest.mark.timeout(600)
@pytest.mark.parametrize("name,layers", [("gpt2s", 12)])
def test_a_step_keeps_up_x_under_the_chips_memory(v5e, name, layers):  # noqa: F811
    """One of the two cells with the most to gain, its whole step compiled
    as the chip runs it: helpers/described_chip.py:a_step_keeps_up_x. (The
    other, granite, has a family's file, whose one compile of the cell is
    this step: tests/test_hybrid_mixer.py holds its case.)"""
    from helpers.described_chip import CellStep, a_step_keeps_up_x
    a_step_keeps_up_x(CellStep(v5e, name, limit=V5E), layers)
