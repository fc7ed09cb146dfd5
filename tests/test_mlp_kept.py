"""A layer's products through the remat (models/gpt.py:LADDER: the MLP's
matmul results, MLP_OUT, then what a delta-rule or state-space mixer's
filters read and write, MIXER_OUT; layer_fn's `keeping(n)`,
`products_kept`; parallel/memory.py): how many rungs every layer keeps is
reckoned from what the step's builder reports and the shapes, the
arithmetic is the same work done once instead of twice, and a step that
keeps none is the program it was."""

import functools
import re

import numpy as np
import pytest

from helpers.described_chip import V5E_BYTES as V5E
from helpers.described_chip import v5e  # noqa: F401 — a fixture
from helpers.families import read

# cell -> (the rung of gpt.LADDER its layers keep on a v5e: 1 and 2 an MLP's
# products, 3 and 4 what a mixer's filters read and write; the layers that
# have an MLP, prediction modules' included). gpt2s: its MLP is SwiGLU
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
    # shared experts of 1024: 0.44 GB for both products of all five; what the
    # three filters of its four delta-rule layers read, 0.81 GB, and what
    # they write, as much again: 14.76 GB reckoned of the ceiling's 14.88
    "kimilinear_train_1chip": (4, 5),
    # four MLPs of 11008 beside 12.26 GB of state: `up x` alone is 0.72 GB
    "olmohybrid_train_1chip": (1, 4),
    # a looped stack keeps nothing more (gpt.products_kept: PR 71)
    "ouro26_train_1chip": (0, 8),
}

# The cells whose stacks have a delta-rule or state-space mixer: their
# ladders reach rung 4 where the memory allows.
FILTERED = ("solar2", "nemotron3s", "granite4hm", "kimilinear", "olmohybrid")


@functools.lru_cache(maxsize=None)
def _cell(cell):
    """The cell's loss traced over shapes once, with what `products_kept`
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
    real = gpt.products_kept

    def spy(*args):
        seen.append(args)
        return real(*args)
    gpt.products_kept = spy
    try:
        jax.eval_shape(
            lambda p, b: program.loss(p, b, mesh,
                                      strategy.activation_sharding(mesh)),
            params, {"tokens": jax.ShapeDtypeStruct(
                (mix["global_batch"], mix["seq"] + 1), jnp.int32)})
    finally:
        gpt.products_kept = real
    return seen[0], functools.partial(
        memory.Budget, state=state, share=held / memory.tree_bytes(params))


def _kept(cell, limit):
    """(the rung every layer keeps, the layers that have an MLP, the
    layers whose mixer keeps something, the reckoned peak) for the cell's
    step on devices of `limit` bytes, as the traced
    step reports them."""
    from ray_tpu.models import gpt
    from ray_tpu.parallel import memory
    args, budget = _cell(cell)
    said = []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(memory, "report", lambda *a: said.append(a))
        with memory.told(budget(limit=limit)):
            n = gpt.products_kept(*args)
    (products, of, mixers, kept_bytes, peak, _limit, _passes), = said
    assert products == n and (kept_bytes > 0) == (n > 0)
    assert (mixers > 0) == (n > 2)
    return n, of, mixers, peak


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_cells_products_kept_on_a_described_v5e(jax_cpu, cell):
    """The reckoning on each cell's shapes and a device of 16.9 GB: what
    the table says (0 at solar and nemotron, over the ceiling as they are,
    and in the three cells without an `_mlp_block`), in every layer that
    has an MLP or in none, the reckoned peak under the ceiling wherever
    anything is kept, 0 where the platform reports no limit (the CPU's
    `memory_stats()` is None) and where nobody reports, and never falling
    as the limit rises, up to the top rung the cell's layers have: 4 where
    the stack has a delta-rule or state-space mixer (kimi's four such layers
    keep at 3 and 4 on the v5e itself), 2 where it has gated MLPs alone, 0
    without an `_mlp_block` and in a looped stack."""
    from ray_tpu.models import gpt
    from ray_tpu.parallel import memory
    products, having = CELLS[cell]
    n, of, mixers, peak = _kept(cell, V5E)
    assert (n, of) == (products, having)
    assert mixers == (4 if cell.startswith("kimilinear") else 0)
    assert n == 0 or peak <= memory.CEILING * V5E
    assert _kept(cell, None)[0] == 0
    assert memory.device_limit(jax_cpu.devices()[:1]) is None
    assert gpt.products_kept(*_cell(cell)[0]) == 0      # nobody told
    ns = [_kept(cell, int(V5E * x))[0]
          for x in (0.25, 0.5, 0.75, 0.9, 1.0, 1.25, 2.0, 8.0)]
    top = (0 if cell.startswith("ouro26") else 4 if cell.startswith(FILTERED)
           else 2 if having else 0)
    assert ns == sorted(ns) and ns[-1] == top, ns


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


def _tiny(form, **more):
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    cfg = gpt.GPTConfig(**{**dict(
        vocab_size=256, d_model=64, n_layers=3, n_heads=2, d_ff=128,
        max_seq=32, dtype=jnp.float32, expert_form=form), **more})
    # (one program: op by op a delta-rule layer's init takes 5 s)
    params = jax.jit(lambda key: gpt.gpt_init(key, cfg))(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (2, cfg.max_seq + 1), 0, cfg.vocab_size)}
    return cfg, params, batch


# form -> (what `_tiny` builds, the rung the others are compared with, the
# others, how many values a layer names at each). delta_rule: one
# delta-rule layer over an MLP at a width whose three filters are the
# kernels (two heads of 64: 128 channels), both of the MLP's products
# against the top of the ladder.
_FORMS = {
    "gated": (dict(form=None), 0, (1, 2), {0: (0, 0), 1: (1, 0), 2: (2, 0)}),
    "ungated": (dict(form="relu2"), 0, (1, 2),
                {0: (0, 0), 1: (1, 0), 2: (1, 0)}),
    "delta_rule": (dict(form=None, d_model=128, n_layers=1, max_seq=64,
                        layer_kinds=("kda",)), 2, (4,),
                   {2: (2, 0), 4: (2, 6)}),
}


@pytest.mark.parametrize("form", list(_FORMS))
def test_keeping_is_the_same_arithmetic(jax_cpu, monkeypatch, form):
    """Loss and every gradient of a small stack at float32 activations with
    every layer keeping none of its products and the first rungs of them
    (an MLP's one and both; a delta-rule layer's filters' operands, read
    and written): to the bit (the kept value is the value the forward pass
    computed). The keeping step's jaxpr names what it keeps, the other
    names nothing; under remat_policy "none" no block carries a name and
    the program is the one it was at any n. (The delta-rule case is
    compiled without XLA's fusion pass: on the CPU an interpreted kernel's
    ops are fused with their neighbours and a fused loop contracts its
    products and sums as it likes, so the last bit of a FILTER's gradient
    follows what surrounds it; on the chip a kernel is a call.)"""
    import dataclasses
    jax = jax_cpu
    from ray_tpu.models import gpt
    more, base, rungs, named = _FORMS[form]
    more = dict(more)
    if more["form"]:
        more["form"] = gpt.ExpertForm(matrices=2, activation=more["form"])
    cfg, params, batch = _tiny(**more)

    def step(cfg, k):
        monkeypatch.setattr(gpt, "products_kept", lambda *a: k)
        return jax.jit(jax.value_and_grad(
            lambda p: gpt.gpt_loss(p, batch, cfg)))

    def run(cfg, k):
        options = ({"xla_disable_hlo_passes": "fusion"}
                   if cfg.layer_kinds else {})
        traced = step(cfg, k).trace(params)
        names = re.findall(r"name=(\w+)", str(traced.jaxpr))
        assert (names.count(gpt.MLP_OUT), names.count(gpt.MIXER_OUT)) == tuple(
            cfg.n_layers * count for count in named[k]), names
        return traced.lower().compile(compiler_options=options)(params)

    plain = run(cfg, base)
    for k in rungs:
        assert all(np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(plain),
            jax.tree_util.tree_leaves(run(cfg, k))))
    bare = dataclasses.replace(cfg, remat_policy="none")
    top = str(step(bare, rungs[-1]).trace(params).jaxpr)
    assert gpt.MLP_OUT not in top and gpt.MIXER_OUT not in top
    if base == 0:
        texts = [step(bare, k).lower(params).as_text() for k in (0, rungs[-1])]
        assert texts[0] == texts[1] and gpt.MLP_OUT not in texts[0]


# rehearsal configuration -> the kinds of layer it has (by what they hold)
# and the highest rung at which one of them keeps more than at the rung
# below: 4 where a layer's mixer has `silu_conv` filters (granite's and
# nemotron's state-space layers; kimi's, solar's and olmo's delta-rule
# layers, kimi's three filters a layer and olmo's one)
_LAYERS = {"tiny": (1, 2), "tiny-granite-hybrid": (2, 4), "tiny-kanana": (2, 2),
           "tiny-keye": (1, 0), "tiny-kimi-linear": (3, 4),
           "tiny-laguna": (3, 2), "tiny-lfm2": (3, 2),
           "tiny-olmo-hybrid": (2, 4), "tiny-nemotron-h": (3, 4),
           "tiny-solar": (2, 4)}


@pytest.mark.parametrize("name", list(_LAYERS))
def test_what_a_layer_keeps_is_what_the_checkpoint_saves(jax_cpu, name):
    """`_layer_bytes` (the closed forms the reckoning uses) against what
    jax.checkpoint saves of a layer, for every kind of layer the rehearsal
    configuration has, at each of the ladder's five choices: the input, the
    kernels' named results and, in the keeping blocks, one and both of the
    MLP's products, then what a delta-rule or state-space mixer's filters
    read, then what they write."""
    jax = jax_cpu
    from jax._src.ad_checkpoint import saved_residuals
    from benchmark import model
    from ray_tpu.models import gpt
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
    kinds, top = set(), 0
    for layer in layers:
        kind = (tuple(sorted(layer)), gpt._mlp_of(layer) is not None)
        if kind in kinds:
            continue
        kinds.add(kind)
        kept, products = gpt._layer_bytes(layer, batch, seq, cfg,
                                          gpt.Setting())
        assert len(products) == len(gpt.LADDER) + 1
        assert list(products) == sorted(products)
        top = max([top] + [n for n in range(1, len(products))
                           if products[n] > products[n - 1]])
        for n, more in enumerate(products):
            if n > 2 and more == products[2]:
                continue          # no such mixer: the rung-2 block's values
            saved = sum(
                aval.size * aval.dtype.itemsize
                for aval, why in saved_residuals(block.keeping(n), x, layer)
                if "the argument layer" not in why
                and "constant" not in why)
            assert saved == kept + more, (kind, n, saved, kept + more)
    assert (len(kinds), top) == _LAYERS[name], kinds


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
        monkeypatch.setattr(pipeline, "products_kept",
                            lambda *a, keep=keep: keep)
        step = jax.jit(jax.value_and_grad(
            pipeline.make_gpt_pp_loss(cfg, mesh, num_microbatches=2)))
        results[keep] = step(stacked, batch)
        assert (gpt.MLP_OUT in str(step.trace(stacked, batch).jaxpr)) \
            == bool(keep)
    assert all(np.array_equal(a, b) for a, b in zip(
        *map(jax.tree_util.tree_leaves, (results[0], results[2]))))


@pytest.mark.timeout(600)
@pytest.mark.parametrize("cell,layers", [
    pytest.param("gpt2s_train_1chip", 12, id="gpt2s-12")])
def test_a_step_keeps_up_x_under_the_chips_memory(v5e, cell, layers):  # noqa: F811
    """One of the two cells with the most to gain, its whole step compiled
    as the chip runs it: helpers/described_chip.py:a_step_keeps_up_x. (The
    other, granite, has a family's file, whose one compile of the cell is
    this step: tests/test_hybrid_mixer.py holds its case.)"""
    from helpers.described_chip import CellStep, a_step_keeps_up_x
    a_step_keeps_up_x(CellStep(cell, v5e, axes=("data",)), layers)
