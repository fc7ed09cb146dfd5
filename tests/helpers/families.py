"""What the families' test files share: one body for every check that each
`model_config` PR used to write again by copying the last family's file.

A family's file holds a `Family` subclass (FAMILY = ...) with what differs
between families as data and small hooks (its module under
benchmark/families/, its tiny configuration, its tolerances with their
reasons, its faults, its new leaves and scopes), the tests only it has, and
it imports the checks below by name: pytest collects an imported test under
the importing module, the module-scoped fixtures here read that module's
FAMILY, and tests/conftest.py's `pytest_generate_tests` gives a check its
cases from `FAMILY.cases`. A new architecture is a row in FILES, its class,
and its own kernels' tests.

Which programs a family's two files compile, and which check reads which
(PR 70; a new family brings no other). The model file: the reference's
logits, loss and gradients (the `reference` fixture: one program), the tiny
program on one device in float32 once an attention path (`programmed`:
logits, loss and gradients in one program, read by the float32 check AND, at
`flash`, by the sharded step as its one-device twin: `flash_twin`, params -
0.1 gradients, so no one-device step is compiled unless the family's mesh
needs another configuration than its tiny one), the sharded step, the bf16
check's one program, and ONE rehearsal of the cell in a subprocess, traced (`--trace 1`: the untraced run is a subset of it, and
kanana's own case keeps both). The kernels file: the cell's whole step for
the described chip, lowered and compiled once (`cell_step`,
helpers/described_chip.py:CellStep, the one builder of a cell's step under
tests/), as the chip runs it, and read by every check of that file: its
memory, kernel calls and layout, the text tests/test_lowered_steps.py
records, the family's scopes among its op names (until PR 73 a compile of
the tiny step's gradient on the CPU, in the model file) and a share's sparse
blocks (until PR 73 a compile of one block alone).
tests/conftest.py runs the files that take `cell_step` first and the
families' other files next, by what it collects: no list names them.

pytest does not collect this module (no test_ prefix).
"""

import contextlib
import copy
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# module under benchmark/families/ -> the file under tests/ whose FAMILY is
# its row of data (tests/test_static_analysis.py holds every configuration's
# family to this table). gpt_dense, the control, predates the template: its
# checks are tests/test_parallel.py, test_rope.py, test_xent.py and
# test_paired_heads.py, and it has no class.
FILES = {
    "gpt_dense": None,
    "olmoe": "test_moe.py",
    "kanana": "test_latent_moe_model.py",
    "lfm2": "test_conv_gqa_model.py",
    "laguna": "test_window_attention_model.py",
    "keye": "test_selected_attention_model.py",
    "solar": "test_linear_attention_model.py",
    "smallthinker": "test_smallthinker.py",
    "nemotron_h": "test_state_space.py",
    "granite_hybrid": "test_hybrid_mixer_model.py",
    "kimi_linear": "test_kimi_linear_model.py",
    "olmo_hybrid": "test_olmo_hybrid_model.py",
    "ouro": "test_ouro_model.py",
}

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@contextlib.contextmanager
def patched(module, **names):
    """`module` with `names` replaced, and put back."""
    kept = {name: getattr(module, name) for name in names}
    for name, value in names.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in kept.items():
            setattr(module, name, value)


def case(value, id, *marks):
    return pytest.param(value, id=id, marks=marks)


class Family:
    """A family's row: what the shared checks take as data. A subclass sets
    the attributes it needs and overrides the hooks its checks call; its
    docstring says what its tiny configuration is."""

    name = None         # benchmark/families/<name>.py
    tiny = None         # benchmark/rehearsal/configs/<tiny>.json
    cell = None         # benchmark/configs/<cell>.json
    workload = None     # BENCHMARK.json's cell, which rehearse.py rehearses

    # the program the checks build (`program` below)
    remat = "none"      # None: GPTConfig's own
    tokens_seed = 5
    # (`reference`, models/gpt.py's jnp attention held to the same
    # reference, is no path a cell runs: out of tier-1 since PR 70)
    attentions = (case("reference", "reference", pytest.mark.slow), "flash")

    # test_logits_loss_and_gradients_match_the_reference
    logits_atol = None
    grads_atol = None

    # test_bfloat16_step_passes_the_per_token_check: the bounds that hold
    # the bf16 program, and those of them that are each broken in turn
    bf16_bounds = None
    bf16_broken = ("logprob_median_tol",)

    # test_the_shares_of_a_layer_add_up_to_the_uncut_reference
    experts_key = None  # the configuration's count of experts
    shared_layer = None     # which of the uncut model's layers

    # test_param_count_is_the_..._and_the_programs_tree
    cell_params = None
    cell_share = None

    # test_configuration_file_keeps_the_catalog_and_states_the_cut
    reduced = None
    in_benchmark_json = True
    states_its_peak = True

    # test_the_configuration_refuses_by_name, test_pipeline_refuses_by_name:
    # [case((change, says), id)], [case((change, mesh axes, says), id)]
    refusals = ()
    pipeline_refusals = ()

    # tests/helpers/described_chip.py: the cell's whole step for one
    # described chip, as the chip runs it ({kernel: calls}; (low, high)
    # share of 16.91 GB; the rung of models/gpt.py:LADDER every layer keeps;
    # the GB by which the builder's reckoned peak stands (under compiled +
    # overhead, over its own ceiling) where `the_reckoning_holds` does not
    # hold, each with its reason in the family's row) and a share's sparse
    # block ((rows a tile, tiles of the bounded row space, tiles for every
    # slot))
    cell_kernel_calls = None
    cell_memory_share = None
    cell_rung = 0
    cell_reckoned = (0.0, 0.0)
    row_spaces = None

    @property
    def cases(self):
        """argument name -> the cases of the shared checks that take it
        (the described-chip checks take the cell's configuration as their
        one case, the id they had in tests/test_chip_compile.py, and the
        recorded-text check the cell's name, the id it had in
        tests/test_lowered_steps.py). Either may be the first to read the
        file's one whole step, so each has the limit a whole-step compile
        needs: 100-150 s in a whole run, of conftest.py's 180."""
        whole_step = pytest.mark.timeout(600)
        return {
            "attention": self.attentions,
            "refusal": self.refusals,
            "pipeline_refusal": self.pipeline_refusals,
            "cell": [pytest.param(self.cell, marks=whole_step)],
            "lowered": [pytest.param(self.workload, marks=whole_step)],
            "sparse_cell": [self.cell],
        }

    @property
    def module(self):
        return importlib.import_module("benchmark.families." + self.name)

    def tiny_config(self):
        """The tiny configuration as its file has it."""
        return read("benchmark", "rehearsal", "configs", self.tiny + ".json")

    def shaped(self, config):
        """What this family's checks run of it (the `tiny` fixture)."""
        return config

    def cell_config(self):
        return read("benchmark", "configs", self.cell + ".json")

    def config(self, config, **fields):
        """GPTConfig of a configuration under the family's keys."""
        from ray_tpu.models.gpt import GPTConfig
        return GPTConfig(**dict(self.module.gpt_config_kwargs(config),
                                **fields))

    def opinion(self, jax, cfg, params):
        """A router with an opinion: at the init's 0.02 every score is 1/2
        (every softmax probability the same)."""
        for i, layer in enumerate(params["layers"]):
            if "moe" in layer:
                layer["moe"]["router"] = 0.3 * jax.random.normal(
                    jax.random.PRNGKey(100 + i), layer["moe"]["router"].shape)

    def program(self, jax, config, attention, dtype=None, **change):
        """-> (cfg, params, tokens [2, 129]): the configuration's program in
        float32 (or dtype) without remat, seeded."""
        import jax.numpy as jnp
        from ray_tpu.models.gpt import gpt_init
        if self.remat is not None:
            change = dict(change, remat_policy=self.remat)
        cfg = self.config(config, attention=attention,
                          dtype=dtype or jnp.float32, **change)
        params = gpt_init(jax.random.PRNGKey(3), cfg)
        self.opinion(jax, cfg, params)
        tokens = np.random.default_rng(self.tokens_seed).integers(
            0, config["vocab_size"], (2, 129), dtype=np.int32)
        return cfg, params, jnp.asarray(tokens)

    # -- hooks of the shared checks, each a no-op unless a family says --

    def reference_more(self, jax, params, tokens, config):
        """What the `reference` fixture holds after logits, loss, grads."""
        return None

    def built(self, cfg, params):
        """The tree the tiny configuration builds."""

    def statistics(self, aux, loss, reference):
        """What the step hands back beside the loss."""

    def moves(self, name):
        """Whether the reference's gradient of leaf `name` has a non-zero
        element (None: not held)."""
        return None

    def gradients(self, grads):
        """What else holds of the whole tree of gradients."""

    def other_configurations(self, tiny):
        """{fault: the configuration that has it}"""
        return {}

    def faults(self, jax, tiny, params):
        """[(fault, {attribute of the family's module: replacement},
        whether the reference stays what it was)]"""
        return []

    def told_apart(self, gap):
        return gap > 1e-3

    def uncut_layer(self, jax, layer, x, whole):
        """-> (what every chip computes alike, the uncut reference's
        layer), both [2, 64, 128]."""
        raise NotImplementedError

    def shared_layer_is(self, layer):
        pass

    def shares_statistics(self, stats):
        pass

    def sharded_step(self, jax, tiny, twin):
        """twin: () -> `flash_twin`, called by a family that steps its tiny
        configuration as it stands."""
        steps_agree(jax, self, tiny, twin())

    def tree(self, jax, config):
        """The shapes of the program's parameters."""
        from ray_tpu.models.gpt import gpt_init
        cfg = self.config(config)
        return jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))

    def published(self, cell, tiny_tree):
        """The published model's counts, after the cut's and the trees'."""

    def rules(self, specs, column, row):
        """The new leaves' PartitionSpecs under tp (column (None,
        "tensor"), row ("tensor", None)) and tp_fsdp."""
        raise NotImplementedError

    def scopes(self, names, regions):
        """The new scopes among the op names of the cell's compiled step
        (helpers/described_chip.py:
        test_the_new_scopes_are_regions_and_reach_the_compiled_step)."""
        raise NotImplementedError

    def cut(self, cell, row, bench):
        """What the configuration file says of its own cut."""


# ---------------------------------------------------------------------------
# Fixtures: a family's tiny program is built once a file
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def family(request):
    return request.module.FAMILY


@pytest.fixture(scope="module")
def tiny(family):
    return family.shaped(family.tiny_config())


@pytest.fixture(scope="module")
def seeded(jax_cpu, family, tiny):
    """attention -> (cfg, params, tokens) of the tiny program in float32,
    initialised once a path; nobody writes into the tree."""
    made = {}

    def of(attention):
        if attention not in made:
            made[attention] = family.program(jax_cpu, tiny, attention)
        return made[attention]
    return of


@pytest.fixture(scope="module")
def reference(jax_cpu, family, tiny, seeded):
    """The reference's (logits, loss, gradients, what the family adds),
    float32 at full matmul precision."""
    jax = jax_cpu
    module = family.module
    _cfg, params, tokens = seeded("reference")
    with jax.default_matmul_precision("highest"):
        # one program, as `programmed`'s: the layers' forward compiled once
        logits, (loss, grads) = jax.jit(lambda p, t: (
            module.reference_logits(p, t[:, :-1], tiny), jax.value_and_grad(
                lambda p: module.reference_loss(p, t, tiny))(p)))(
                    params, tokens)
        more = family.reference_more(jax, params, tokens, tiny)
    return logits, loss, grads, more


@pytest.fixture(scope="module")
def programmed(jax_cpu, seeded):
    """attention -> (logits, (loss, aux), gradients) of the tiny program on
    one device, float32 at full matmul precision: compiled once a path, held
    to the reference below and, at `flash`, the one-device twin that the
    sharded step is compared with (`flash_twin`)."""
    jax = jax_cpu
    from ray_tpu.models.gpt import gpt_forward, gpt_loss_and_aux
    made = {}

    def of(attention):
        if attention not in made:
            cfg, params, tokens = seeded(attention)
            with jax.default_matmul_precision("highest"):
                # one program: the layers' forward is compiled once for both
                made[attention] = jax.jit(lambda p, t: (
                    gpt_forward(p, t[:, :-1], cfg)[0], jax.value_and_grad(
                        lambda p: gpt_loss_and_aux(p, {"tokens": t}, cfg),
                        has_aux=True)(p)))(params, tokens)
        return made[attention]
    return of


def flash_twin(seeded, programmed):
    """(params, tokens, loss, gradients) of the flash path's program: what a
    sharded step starts from and what one float32 SGD step on one device
    makes of it, params - 0.1 gradients, with no compile of its own
    (`steps_agree`)."""
    _cfg, params, tokens = seeded("flash")
    _logits, ((loss, _aux), grads) = programmed("flash")
    return params, tokens, loss, grads


# ---------------------------------------------------------------------------
# The program against the reference
# ---------------------------------------------------------------------------

def test_logits_loss_and_gradients_match_the_reference(jax_cpu, family,
                                                       seeded, programmed,
                                                       reference, attention):
    """In float32: every logit, the loss and the whole tree of gradients;
    the family's class says what the tree is (`built`), what the step
    hands back (`statistics`) and which gradients move."""
    jax = jax_cpu
    cfg, params, _tokens = seeded(attention)
    family.built(cfg, params)
    logits, ((loss, aux), grads) = programmed(attention)
    ref_logits, ref_loss, ref_grads = reference[:3]
    np.testing.assert_allclose(logits, ref_logits, atol=family.logits_atol)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    family.statistics(aux, loss, reference)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        moves = family.moves(name)
        if moves is not None:
            assert bool(np.any(np.asarray(r))) == moves, name
        np.testing.assert_allclose(
            g, r, atol=family.grads_atol * max(1.0, float(np.abs(r).max())),
            err_msg=name)
    family.gradients(grads)


def test_the_programs_gradient_moves_where_the_references_does(jax_cpu,
                                                               programmed,
                                                               reference):
    """Of the flash path's own gradients, leaf by leaf: a non-zero element
    exactly where the reference's has one. The float32 check above holds a
    leaf to the reference's within `grads_atol` x its largest element, so a
    leaf that the program leaves out of its loss (all zeros: a stop_gradient,
    a branch not taken) passes there wherever the reference's gradient is
    smaller than that bound, and `moves` is asked of the reference's tree
    alone."""
    jax = jax_cpu
    _logits, (_loss, grads) = programmed("flash")
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(reference[2])):
        assert bool(np.any(np.asarray(g))) == bool(np.any(np.asarray(r))), \
            jax.tree_util.keystr(path)


def test_the_reference_tells_each_mechanism_apart(jax_cpu, family, tiny,
                                                  seeded, reference):
    """What `program_check` rests on: the reference with one mechanism
    changed (another configuration, or one function of the family's module
    replaced) gives other logits."""
    jax = jax_cpu
    import jax.numpy as jnp
    module = family.module
    _cfg, params, tokens = seeded("reference")
    sound = reference[0]

    def gap(config):
        with jax.default_matmul_precision("highest"):
            logits = jax.jit(lambda p, t: module.reference_logits(
                p, t[:, :-1], config))(params, tokens)
        return float(jnp.abs(logits - sound).max())
    for name, config in family.other_configurations(tiny).items():
        assert family.told_apart(gap(config)), name
    for name, replaced, same in family.faults(jax, tiny, params):
        with patched(module, **replaced):
            found = gap(tiny)
        assert (found < 1e-5) if same else family.told_apart(found), name


def test_bfloat16_step_passes_the_per_token_check(jax_cpu, family, tiny,
                                                  seeded):
    """reference_loss with a `program_check` answers the loss where the
    program's own forward (bf16, the flash path's kernels, the
    grouped-matmul kernels) agrees with the reference token by token, and
    nan where one of `bf16_broken`'s bounds is broken. The bounds reach the
    one compiled program, which gives the unchecked loss beside the checked
    one, as numbers."""
    jax = jax_cpu
    import jax.numpy as jnp
    module = family.module
    _cfg, params, tokens = seeded("flash")
    names = tuple(family.bf16_bounds)

    @jax.jit
    def checked(params, tokens, bounds):
        return (module.reference_loss(params, tokens, tiny),
                module.reference_loss(params, tokens, dict(
                    tiny, program_check=dict(zip(names, bounds)))))

    def under(**change):
        plain, held = checked(params, tokens, jnp.asarray(
            [dict(family.bf16_bounds, **change)[name] for name in names],
            jnp.float32))
        return float(plain), float(held)
    with jax.default_matmul_precision("highest"):
        plain, held = under()
        broken = [under(**{name: 1e-6})[1] for name in family.bf16_broken]
    assert held == plain and np.isfinite(plain) and np.isnan(broken).all()


# ---------------------------------------------------------------------------
# The share
# ---------------------------------------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(jax_cpu,
                                                             family):
    """model-configs guide, section 4: a whole sparse layer, mixer and
    residual included. Every chip computes what `uncut_layer` hands back
    first alike, so it counts once; what the four shares' experts add (each
    the routed part of its own four experts) adds up with it to the uncut
    reference's layer."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.models.gpt import Setting, gpt_init, layer_fn
    tiny = family.tiny_config()
    whole = copy.deepcopy(tiny)
    del whole["share"]
    whole[family.experts_key] = 16

    def config(c):
        return family.config(c, dtype=jnp.float32, attention="reference",
                             remat_policy="none")
    full_cfg = config(whole)
    assert full_cfg.experts_held is None
    layer = jax.jit(lambda key: gpt_init(key, full_cfg)["layers"][
        family.shared_layer])(jax.random.PRNGKey(7))
    family.shared_layer_is(layer)
    layer["moe"]["router"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(8), (128, 16))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 128), jnp.float32)

    @jax.jit
    def layers(layer, x):
        """(the uncut layer's two results, every share's layer and its
        statistics): one program, where op by op each product, norm and
        sort was a program of its own (16-35 s a family in a whole run)."""
        shares = []
        for rank in range(4):
            cfg = config(dict(tiny, share=dict(tiny["share"], rank=rank)))
            assert cfg.experts_held == (4 * rank, 4)
            mine = dict(layer, moe=dict(layer["moe"], **{
                name: layer["moe"][name][4 * rank:4 * rank + 4]
                for name in ("w_gate", "w_up", "w_down")}))
            shares.append(layer_fn(cfg, 64, Setting())(x, mine))
        return family.uncut_layer(jax, layer, x, whole), shares

    with jax.default_matmul_precision("highest"):
        (alike, want), shares = layers(layer, x)
    # what is the same on every chip, taken off
    parts = [out - alike for out, _stats in shares]
    seen = [stats for _out, stats in shares]
    held_share = sum(float(stats["expert_slots_held_share"])
                     for stats in seen)
    np.testing.assert_allclose(alike + sum(parts), want, atol=5e-5)
    assert abs(held_share - 1.0) < 1e-6
    family.shares_statistics(seen)
    # and a part is not the whole: the absent experts' sum is left out
    assert float(jnp.abs(alike + parts[0] - want).max()) > 1e-2


# ---------------------------------------------------------------------------
# Arithmetic, rules, refusals, names
# ---------------------------------------------------------------------------

def test_param_count_is_the_published_model_and_the_programs_tree(jax_cpu,
                                                                  family,
                                                                  tiny):
    """The arithmetic counts the cut, the program's own tree (at the cell
    and at the tiny size), and what the family's class says of the
    published model."""
    jax = jax_cpu
    from ray_tpu.models.gpt import count_params
    module, cell = family.module, family.cell_config()
    assert module.param_count(cell) == family.cell_params
    if family.cell_share is not None:
        assert module.share(cell) == family.cell_share
    trees = [family.tree(jax, config) for config in (tiny, cell)]
    for config, tree in zip((tiny, cell), trees):
        assert module.param_count(config) == count_params(tree)
    family.published(cell, trees[0])


@pytest.mark.parametrize("strategy,column,row", [
    ("tp", (None, "tensor"), ("tensor", None)),
    ("tp_fsdp", ("fsdp", "tensor"), ("tensor", "fsdp"))],
    ids=["tp", "tp_fsdp"])
def test_every_new_leaf_gets_its_rule(jax_cpu, family, tiny, strategy, column,
                                      row):
    jax = jax_cpu
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    params = family.tree(jax, tiny)
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                      devices=jax.devices()[:4])
    specs = jax.tree_util.tree_map(
        lambda s: s.spec,
        strategy_from_name(strategy).param_shardings(mesh, params))
    family.rules(specs, column, row)


def steps_agree(jax, family, config, twin=None, rows=4, strategy="tp_fsdp",
                axes=None, atol=1e-6, **fields):
    """One step (float32, flash, sgd at 0.1) of `config` on a mesh of `axes`
    under `strategy` equals the one-device step: the loss and every
    parameter. twin: `flash_twin`'s (params, tokens, loss, gradients),
    for a `config` that is the family's tiny one as it stands: the step
    starts from those parameters and tokens and is held to that loss and to
    params - 0.1 gradients, which the float32 check holds to the reference.
    Without it (a family whose mesh takes fewer layers or other heads than
    its tiny configuration has) the one-device step is compiled here, on
    `rows` rows of tokens.
    -> the function that takes one step (name, axes, devices), for a caller
    that has more to ask."""
    import jax.numpy as jnp
    import optax
    from ray_tpu.models.gpt import gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name
    from ray_tpu.train.train_step import init_train_state, make_train_step
    axes = axes or {"data": 1, "fsdp": 2, "tensor": 2}
    cfg = family.config(config, dtype=jnp.float32, attention="flash",
                        **fields)
    if twin is None:
        def init():
            return gpt_init(jax.random.PRNGKey(3), cfg)
        tokens = jnp.asarray(np.random.default_rng(5).integers(
            0, 512, (rows, 129), dtype=np.int32))
    else:
        start, tokens, ref_loss, grads = twin

        def init():
            return start

    def one_step(name, axes, n):
        mesh = build_mesh(MeshConfig(**axes), devices=jax.devices()[:n])
        strategy = strategy_from_name(name)
        optimizer = optax.sgd(0.1)
        state = init_train_state(init, optimizer, mesh, strategy)
        step = make_train_step(
            lambda p, b: gpt_loss(
                p, b, cfg, mesh=mesh,
                act_sharding=strategy.activation_sharding(mesh)),
            optimizer, mesh, strategy, sample_params=state.params)
        with jax.default_matmul_precision("highest"):
            state, metrics = step(state, {"tokens": tokens})
        return float(metrics["loss"]), jax.device_get(state.params)

    if twin is None:
        ref_loss, ref_params = one_step("dp", {"data": 1}, 1)
    else:
        ref_params = jax.tree_util.tree_map(
            lambda p, g: np.asarray(p) - np.float32(0.1) * np.asarray(g),
            start, grads)
    loss, params = one_step(strategy, axes, int(np.prod(list(axes.values()))))
    assert abs(loss - float(ref_loss)) < 1e-5
    for (path, p), r in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_allclose(p, r, rtol=1e-4, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))
    return cfg, one_step


def test_sharded_step_equals_one_device(request, jax_cpu, family, tiny):
    """One step of the tiny model on a mesh (`sharded_step` of the family's
    class says which, and what lies on a shard) equals the one-device
    step: `flash_twin` where the family asks for it (the file then has
    `seeded` and `programmed`)."""
    family.sharded_step(jax_cpu, tiny, lambda: flash_twin(
        request.getfixturevalue("seeded"),
        request.getfixturevalue("programmed")))


def test_the_configuration_refuses_by_name(family, tiny, refusal):
    change, says = refusal
    with pytest.raises(ValueError, match=says):
        family.config(tiny, **(change() if callable(change) else change))


def test_pipeline_refuses_by_name(jax_cpu, family, tiny, pipeline_refusal):
    """The refusals parallel/pipeline.py gives, by what it observes."""
    jax = jax_cpu
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import make_gpt_pp_loss
    change, mesh_axes, says = pipeline_refusal
    cfg = family.config(tiny, **change)
    n = int(np.prod(list(mesh_axes.values())))
    mesh = build_mesh(MeshConfig(data=1, **mesh_axes),
                      devices=jax.devices()[:n])
    with pytest.raises(ValueError, match=says):
        make_gpt_pp_loss(cfg, mesh, num_microbatches=2)


def kernel_calls(jax, jaxpr, rematted=False):
    """(kernel name, whether it runs in a layer's recompute pass: under a
    checkpoint equation of the backward) for every pallas_call of jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], rematted
        # jax.checkpoint's equation, as the backward pass holds it
        inner = rematted or eqn.params.get("differentiated", False)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from kernel_calls(jax, sub, inner)


def step_kernel_calls(jax, family, config):
    """-> (the train configuration, Counter of `kernel_calls` over the
    gradient of its loss at [2, 129] tokens, the jaxpr)."""
    from collections import Counter
    from ray_tpu.models.gpt import gpt_init, gpt_loss
    cfg = family.module._train_config(config)
    params = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))
    tokens = np.zeros((2, 129), np.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: gpt_loss(p, {"tokens": tokens}, cfg)))(params)
    return cfg, Counter(kernel_calls(jax, jaxpr.jaxpr)), jaxpr


def test_configuration_file_keeps_the_catalog_and_states_the_cut(family):
    """The cell's file differs from the catalog's row in what `reduced`
    lists and nothing else, keeps the published values, and says of its cut
    what the family's class holds it to."""
    cell = family.cell_config()
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cell["source"])
    changed = {k for k, v in row["config"].items() if cell.get(k, "?") != v}
    assert changed == set(cell["reduced"]) == family.reduced
    assert cell["published"] == {k: row["config"][k] for k in cell["reduced"]}
    bench = read("BENCHMARK.json")
    family.cut(cell, row, bench)
    if family.in_benchmark_json:
        entry = next(c for c in bench["configs"] if c["name"] == cell["name"])
        assert entry["reduced"] == cell["reduced"]
        assert entry["source"] == cell["source"]
    if family.states_its_peak:
        peak = cell["reduced_why"]["memory_peak_bytes"]
        assert 0.25 * 16.91e9 < peak["chip"] < 16.91e9


# ---------------------------------------------------------------------------
# The benchmark's own checks that need no chip, through their commands
# ---------------------------------------------------------------------------

def benchmark_command_says(command, says):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # rehearse.py asks for its own devices
    proc = subprocess.run([sys.executable] + command, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert says in proc.stdout


@pytest.mark.timeout(600)
def test_the_cell_rehearses(family):
    """The cell once, traced: run_cell's traced run goes through all the
    untraced one does and the trace's readers besides (kanana's own case,
    tests/test_latent_moe_model.py, keeps both runs)."""
    benchmark_command_says(["benchmark/rehearse.py", family.workload,
                            "--seconds", "2", "--trace", "1"],
                           "rehearsal passed")
