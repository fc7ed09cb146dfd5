"""Compiles for a TPU v5e that is described, not attached (the chip's own
compiler is installed with libtpu): what tests/test_chip_compile.py (the
kernels every cell shares) and each family's file (its kernels, its layers,
its cell's whole step) use to hand the chip's compiler a program. Nothing
runs; what the compiler would refuse on the chip — a tile it cannot lay out,
a Mosaic call it cannot partition — it refuses here, at no chip time.

A family's kernels file imports the `v5e` and `cell_step` fixtures and the
whole-step checks at the end by name; its FAMILY (tests/helpers/families.py)
holds their numbers. `CellStep` is the one builder of a cell's step at its
real sizes under tests/. pytest does not collect this module.
"""

import functools
import hashlib
import os
import re

import pytest

from helpers.families import read


@pytest.fixture(scope="module")
def v5e(jax_cpu):
    """The four devices of a described v5e 2x2 host. The persistent compile
    cache is off around these compiles: a TPU entry written without a chip
    cannot be read back and only warns on the next run. Every file that
    compiles for the described chip asks for the topology in its own pytest
    process, and one process at a time may load libtpu unless
    ALLOW_MULTIPLE_LIBTPU_LOAD=1 is set from outside, as the driver's test
    command sets it (/root/TESTS_LAST_RUN.json): run several workers with
    it, or one process without."""
    jax = jax_cpu
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this box
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def windows(text):
    """The window sizes ("1x1x255") of the compiled text's reduce-windows."""
    return re.findall(r"reduce-window\([^\n]*window=\{size=([0-9x]+)", text)


def kernel_call(kernel):
    """What a line that defines a call of `kernel` starts with."""
    return rf"\s*%?(?:\w+_)?{kernel}_*[.\d]* = "


def kernel_ops(text, kernel):
    """The compiled text's lines that define a call of a Mosaic kernel (the
    instruction takes the kernel's name, inside the transforms it was
    traced under: `transpose_jvp_moe_gmm__.24`)."""
    return [line for line in text.splitlines()
            if re.match(kernel_call(kernel), line)]


def placed(tree, sharding):
    """tree's leaves as shapes on `sharding`: one for all, or a tree of
    them."""
    import jax
    if sharding is None or isinstance(sharding, jax.sharding.Sharding):
        sharding = jax.tree_util.tree_map(lambda x: sharding, tree)
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding)


def compiled_on_chip(monkeypatch, fn, *shapes):
    """jit(fn) compiled for where `shapes` lie, the kernels on their TPU
    branch (jax.default_backend() is the CPU here)."""
    import jax
    from ray_tpu.ops import attention
    monkeypatch.setattr(attention, "_default_interpret", lambda: False)
    return jax.jit(fn).lower(*shapes).compile()


def cell_configuration(name, **fields):
    """The GPTConfig of benchmark/configs/<name>.json as its family maps
    it."""
    from benchmark import model
    from ray_tpu.models import gpt
    config = read("benchmark", "configs", name + ".json")
    return gpt.GPTConfig(**model.family(config).gpt_config_kwargs(config),
                         **fields)


def mixer_layer(cfg, kind, sharding):
    """({group: the first layer's parameters of `kind`'s group} as shapes
    on `sharding`, the group's name)."""
    import jax
    from ray_tpu.models import gpt
    layers = jax.eval_shape(
        lambda: gpt.gpt_init(jax.random.PRNGKey(0), cfg))["layers"]
    group = gpt._GROUP[kind]
    return placed({group: next(layer[group] for layer in layers
                               if group in layer)}, sharding), group


def layer_on_four_chips(v5e, monkeypatch, widths, batch, seq):
    """One layer's parameters and input as shapes under tp_fsdp on the
    described fsdp=2 x tensor=2 mesh -> (cfg, mesh, strategy, layer, x)."""
    import jax
    from ray_tpu.models import gpt
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import strategy_from_name

    # jax.default_backend() is the CPU here; take the kernel's TPU branch.
    monkeypatch.setattr(attention, "_default_interpret", lambda: False)
    cfg = gpt.GPTConfig(**widths)
    mesh = build_mesh(MeshConfig(data=1, fsdp=2, tensor=2), devices=v5e)
    strategy = strategy_from_name("tp_fsdp")

    layer = jax.eval_shape(
        lambda: gpt.gpt_init(jax.random.PRNGKey(0), cfg))["layers"][0]
    layer_sh = strategy.param_shardings(mesh, {"layers": [layer]})["layers"][0]
    layer = jax.tree_util.tree_map(
        lambda leaf, sh: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=sh), layer, layer_sh)
    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype,
                             sharding=strategy.activation_sharding(mesh))
    return cfg, mesh, strategy, layer, x


def written_in_entry(text, ops="copy|transpose|reshape"):
    """(the dimensions, the line) of every instruction of the entry
    computation that writes a tensor by one of `ops`: what is written to
    memory (an instruction inside a fused computation lives in registers)."""
    written = re.compile(
        rf"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* ({ops})\(")
    for line in text[text.index("\nENTRY "):].splitlines():
        made = written.match(line)
        if made:
            yield [int(d) for d in made.group(1).split(",")], line


def attention_layer_gradients(monkeypatch, cfg, kind, batch, seq, sharding,
                              mesh=None, layer=None, x=None, remat=True):
    """The compiled text of one attention layer of `kind`, value and
    gradient (under the layer's remat policy, which keeps the flash
    forward's output and lse, unless remat is False), at [batch, seq]."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    from ray_tpu.ops import attention
    from ray_tpu.ops.rope import rope_table
    if layer is None:
        layer, _ = mixer_layer(cfg, kind, sharding)
        x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype,
                                 sharding=sharding)
    rope = cfg.rope_of(kind)

    def loss(layer, x):
        # layer_fn's own rule: no table for a kind that does not rotate
        table = rope_table(seq, cfg.head_dim, rope) if rope else ()

        def block(layer, x):
            return gpt._attention_block(layer, x, cfg, table,
                                        gpt.Setting(mesh), kind)[0]
        if remat:
            block = jax.checkpoint(
                block, policy=jax.checkpoint_policies.save_only_these_names(
                    attention.FLASH_OUT, attention.FLASH_LSE))
        return block(layer, x).astype(jnp.float32).sum()

    return compiled_on_chip(monkeypatch, jax.grad(loss, argnums=(0, 1)),
                            layer, x).as_text()


def heads_of_64_stay_by_token(text, rows, seq, heads, kv_heads):
    """Of a compiled text that holds ONE attention layer whose heads are 64
    wide, value and gradient under the layer's remat policy (`rows`, `heads`
    and `kv_heads` a shard's, under a mesh). The heads fill lane tiles in
    pairs (`ops/attention.py:tokens_first`), so q, k, v, o and their
    cotangents stay [B, S, heads * 64] from the projections' matmuls to
    `wo` and back: the three flash kernels once each, `rope_split` twice
    forward (q's and k's; v takes none) and twice recomputed, `rope_merge`
    twice, every one of them on [B, S, heads * 64] operands, and in the
    entry computation no `copy`, `transpose` or `reshape` under `attn_core`
    or `attn_out` writes a tensor the size of the key/value heads or larger
    (the parent's steps turned [B, H, S, 64] under `attn_out` forward,
    recomputed and backward). The twin of
    test_heads_of_128_reach_wo_without_a_layout_pass
    (tests/test_window_attention.py)."""
    import math
    by_token = {n: f"bf16[{rows},{seq},{n * 64}]" for n in (heads, kv_heads)}
    for kernel, calls in (("flash_fwd", 1), ("flash_bwd_dq", 1),
                          ("flash_bwd_dkv", 1), ("rope_split", 4),
                          ("rope_merge", 2)):
        ops = kernel_ops(text, kernel)
        assert len(ops) == calls, (kernel, len(ops))
        for op in ops:
            assert by_token[heads] in op or by_token[kv_heads] in op, op
            assert not re.search(rf"\[{rows},\d+,{seq},64\]", op), op
    for dims, line in written_in_entry(text):
        if re.search(r'op_name="[^"]*attn_(core|out)', line):
            assert math.prod(dims) < rows * seq * kv_heads * 64, line


# ---------------------------------------------------------------------------
# A cell's whole step, built once a file, as the chip runs it
# ---------------------------------------------------------------------------

# A v5e's `bytes_limit`, which neither a described device nor the CPU reports.
V5E_BYTES = 16909336064


def masked(lowered):
    """A lowering's text as LOWERED (tests/test_lowered_steps.py) hashes it:
    the Mosaic calls in it, their backend_config masked, locations
    stripped."""
    text = re.sub(r"loc\([^)]*\)", "", lowered.as_text(debug_info=False))
    return re.sub(r'backend_config = "[^"]*"', 'backend_config = "..."', text)


class CellStep:
    """A cell's train step at its real sizes, as the chip runs it: the ONE
    place under tests/ that turns a cell's name (BENCHMARK.json's workload)
    into `make_train_step`'s lowered step
    (tests/test_static_analysis.py::test_a_cells_whole_step_has_one_builder).
    Configuration and traffic are the cell's own, the optimizer adamw over
    fp32 masters, parameters, optimizer state and batch are shapes placed as
    the strategy places them, the kernels take their TPU branch, and the
    step's builder reads a v5e's memory limit (parallel/memory.py:
    device_limit; no device here reports one), so a cell that keeps
    products through the remat on the chip keeps them here.

    devices, axes: what it is lowered for: one described v5e under a mesh of
    `axes` (("data",): the families' files), or, axes None, the traffic's
    own mesh over `devices` (the CPU's, for a cell nobody compiles here:
    tests/test_lowered_steps.py). Lowered once for the TPU: `lowered` is the
    hash LOWERED records; `kept` is what the traced step reported
    (memory.report's arguments); `text` and `memory` are the compiled
    step's, compiled when first read."""

    def __init__(self, cell, devices, axes=None):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from benchmark import model
        from ray_tpu.ops import attention
        from ray_tpu.parallel import memory
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        from ray_tpu.parallel.sharding import strategy_from_name
        from ray_tpu.train import train_step as ts

        bench = read("BENCHMARK.json")
        entry = next(w for w in bench["workloads"] if w["name"] == cell)
        self.config = config = read(next(
            c for c in bench["configs"] if c["name"] == entry["config"])["file"])
        self.mix = mix = read("benchmark", "traffic",
                              entry["traffic"] + ".json")
        program = model.family(config).program(config)
        mesh = (Mesh(np.array(devices[:1]), axes) if axes else build_mesh(
            MeshConfig(**mix["mesh"]), devices=devices[:entry["chips"]]))
        strategy = strategy_from_name(mix["strategy"])
        optimizer = optax.adamw(config["train"]["learning_rate"])
        self.params = params = jax.eval_shape(
            lambda: program.init(jax.random.PRNGKey(0)))
        shardings = strategy.param_shardings(mesh, params)
        state = ts.TrainState(
            placed(params, shardings),
            placed(jax.eval_shape(optimizer.init, params),
                   ts._opt_state_shardings(optimizer, params, shardings,
                                           mesh)),
            jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(mesh, P())))
        batch = {"tokens": jax.ShapeDtypeStruct(
            (mix["global_batch"], mix["seq"] + 1), jnp.int32,
            sharding=NamedSharding(mesh, strategy.batch_spec))}
        step = ts.make_train_step(
            lambda p, b: program.loss(p, b, mesh,
                                      strategy.activation_sharding(mesh)),
            optimizer, mesh, strategy, sample_params=params)
        said = []
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(attention, "_default_interpret", lambda: False)
            patch.setattr(memory, "device_limit", lambda devices: V5E_BYTES)
            patch.setattr(memory, "report", lambda *a: said.append(a))
            self._lowered = step.trace(state, batch).lower(
                lowering_platforms=("tpu",))
        self.kept, = said
        self.lowered = hashlib.sha256(
            masked(self._lowered).encode()).hexdigest()[:16]

    @functools.cached_property
    def _compiled(self):
        return self._lowered.compile()

    @functools.cached_property
    def text(self):
        return self._compiled.as_text()

    @functools.cached_property
    def memory(self):
        return self._compiled.memory_analysis()

    @property
    def peak(self):
        """Arguments + temporaries + what is handed back and not aliased."""
        memory = self.memory
        return (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + memory.output_size_in_bytes - memory.alias_size_in_bytes)


@pytest.fixture(scope="module")
def cell_step(v5e, family):
    """The whole step of the file's cell (FAMILY.workload) for one described
    chip: lowered and compiled once, read by every case of the file that
    asserts on its texts."""
    return CellStep(family.workload, v5e, axes=("data",))


def the_reckoning_holds(step, under=0.0, over=0.0):
    """The memory relation every compiled whole step is held to: the step's
    builder reckoned a peak (parallel/memory.py, models/gpt.py:memory_plan)
    that the compiled step, with the runtime's overhead, fits under, and
    that stays under the ceiling it holds itself to; and whatever the layers
    keep more through the remat is in the compiled step's temporaries.
    under, over: a cell's recorded exceptions, in GB: by how much its
    reckoned peak stands UNDER compiled + overhead, and OVER the ceiling (a
    cell at rung 0 has nothing left to drop). A recorded gap is held to
    0.05 GB, so that a PR on `_working_set` moves the row with it."""
    from ray_tpu.parallel import memory
    _products, _of, _mixers, kept_bytes, reckoned, limit, _passes = step.kept
    assert limit == V5E_BYTES
    for recorded, gap in ((under, step.peak + memory.OVERHEAD - reckoned),
                          (over, reckoned - memory.CEILING * limit)):
        if recorded:
            assert abs(gap / 1e9 - recorded) < 0.05, (gap, recorded)
        else:
            assert gap <= 0, gap
    if kept_bytes:
        assert step.memory.temp_size_in_bytes > kept_bytes


def a_step_keeps_up_x(step, layers):
    """Of a cell with much to gain, its whole step compiled for one
    described chip: `products_kept` keeps `up x` in every layer's MLP (both
    products are reckoned over the ceiling); of the gate and up products
    ONE a layer stands a second time in the entry computation's backward
    pass (`rematted_computation` in its op_name) where the step that keeps
    none has two; and the reckoning holds (the step that keeps nothing more
    holds that much less: 12.42 GB at granite for 13.63)."""
    products, of = step.kept[:2]
    assert (products, of) == (1, layers)
    entry = step.text[step.text.index("\nENTRY "):].splitlines()
    again = [at for at, line in enumerate(entry) if re.search(
        r'op_name="[^"]*rematted_computation[^"]*/mlp/bsd,df->bsf/'
        r'dot_general', line)]
    assert len(again) == layers, again
    the_reckoning_holds(step)


def test_the_cells_that_were_there_lower_to_the_same_step(cell_step, family,
                                                          lowered):
    """The file's one lowering is the text that was recorded
    (tests/test_lowered_steps.py: LOWERED, and what a PR that means to
    change a cell's program does there)."""
    from test_lowered_steps import LOWERED
    assert lowered == family.workload
    assert cell_step.lowered == LOWERED[lowered]


def test_cell_step_compiles_under_the_chips_memory(cell_step, family, cell):
    """A one-chip cell's whole step (the cell's own traffic: 2 x 8192
    tokens, 2 x 4096 at olmoe; adamw over fp32 masters) for one described
    chip, as the chip runs it: every layer keeps the rung of the ladder the
    family's row says (`cell_rung`: models/gpt.py:LADDER), every Mosaic
    call lays out, the kernels are called as often as the layers say
    (`embed_grad` once a step, the embedding lookup's backward, and never
    under `moe_tgmm`'s name), arguments + temporaries stand within the
    row's band of the chip's 16.91 GB, and the builder's reckoning of that
    memory holds (`the_reckoning_holds`, with the row's recorded
    exceptions). Under grouped queries k and v exist at the key/value
    heads' count alone: no tensor of the step has them at the query
    heads'."""
    assert cell == family.cell
    config, mix = cell_step.config, cell_step.mix
    kernel_calls, share = family.cell_kernel_calls, family.cell_memory_share
    memory, text = cell_step.memory, cell_step.text
    assert cell_step.kept[0] == family.cell_rung
    assert share[0] * 16.91e9 < cell_step.peak < share[1] * 16.91e9, \
        cell_step.peak
    the_reckoning_holds(cell_step, *family.cell_reckoned)
    if family.cell_rung >= 2:
        # both products of every MLP are kept: the backward pass makes none
        # a second time
        assert not re.findall(
            r'op_name="[^"]*rematted_computation[^"]*/mlp/bsd,df->bsf/'
            r'dot_general', text[text.index("\nENTRY "):])
    for kernel, calls in kernel_calls.items():
        found = kernel_ops(text, kernel)
        assert len(found) == calls, (kernel, len(found))
        if kernel.startswith("index_") or kernel in ("kda_fwd", "ssd_fwd") \
                or (kernel == "conv_silu_fwd" and family.cell_rung >= 4):
            # once a layer: the walk's results, a state's output and its
            # chunks' states are kept through the remat, and at the ladder's
            # top what a mixer's filters write
            assert not any("rematted_computation" in op for op in found)
    if "index_kl" in kernel_calls:
        # the indexer's walk left no row of 8192 keys to XLA: no window
        # reduction over them (the router's own is 255 wide), and the
        # step's temporaries are at or under those of PR 40's jnp walk
        assert all(int(w.split("x")[-1]) < 512 for w in windows(text))
        assert memory.temp_size_in_bytes <= 6.39e9
    kv_heads = config.get("num_key_value_heads")
    if kv_heads != config["num_attention_heads"]:
        # dK and dV leave their kernel at the key/value heads' count, and
        # the one tensor at the query heads' that enters it is q (with dO)
        heads = config["num_attention_heads"]
        dim = config.get("head_dim", config["hidden_size"] // heads)
        b, s = mix["global_batch"], mix["seq"]
        from ray_tpu.ops.attention import LANES, tokens_first
        in_pairs = dim < LANES and tokens_first(dim, heads, kv_heads)
        # by head, or (lfm2's heads of 64, in pairs) as the projections
        # wrote them
        at_kv_heads = (f"bf16[{b},{s},{kv_heads * dim}]" if in_pairs
                       else f"bf16[{b * kv_heads},{s},{dim}]")
        for kernel in ("flash_bwd_dkv", "flash_win_bwd_dkv",
                       "flash_sel_bwd_dkv"):
            for dkv in kernel_ops(text, kernel):
                assert dkv.count(at_kv_heads) >= 4, dkv
        by_head = (f"bf16[{b},{s},{heads * dim}]" if in_pairs
                   else f"bf16[{b},{heads},{s},{dim}]")
        made = [line for line in kernel_ops(text, "rope_split")
                if f" = {by_head}" in line]
        # q alone is split at the query heads' count: forward, and
        # recomputed, in every full-attention layer (and in every window
        # layer where both kinds have the one head count)
        layers = kernel_calls["flash_fwd"] or kernel_calls["flash_sel_fwd"]
        if "num_attention_heads_per_layer" not in config:
            layers += kernel_calls.get("flash_win_fwd", 0)
        # (heads in pairs that rotate nothing reach the kernels as their
        # projections wrote them: no split at any count)
        if kernel_calls.get("rope_split") == 0:
            layers = 0
        assert len(made) == 2 * layers, made


def test_cell_step_keeps_the_delta_rule_by_token(cell_step, family, cell):
    """A cell whose delta-rule heads are whole lane tiles (solar 8 x 128 /
    128, kimi 32 x 128 / 128): under scope `kda` the compiled step holds no
    tensor by head, forward, recomputed or backward: no instruction of the
    entry computation makes a [B, S, H, w], [B, H, S, w] or [B H, S, w]
    result there, nothing the size of q is written by a `copy` or a
    `transpose` (the parent wrote 25 a layer, float32 at the norms and the
    decay), and `kda_fwd` / `kda_bwd` take q, k, v, the decay a channel and
    dO, and write o and the four wide gradients, as [B, S, H w]: what the
    filters wrote and `wo` reads. (How often each kernel is called is
    `test_cell_step_compiles_under_the_chips_memory`'s, from the family's
    `cell_kernel_calls`.)"""
    import math
    from ray_tpu.ops.linear_attention import by_token
    assert cell == family.cell
    cfg = cell_configuration(family.cell)
    size, heads = cfg.delta_rule, cfg.n_heads
    assert by_token(size.key_dim, size.value_dim)
    rows, seq = cell_step.mix["global_batch"], cell_step.mix["seq"]
    text = cell_step.text
    entry = [line for line in text[text.index("\nENTRY "):].splitlines()
             if re.search(r'op_name="[^"]*[/(]kda[/)]', line)]
    assert len(entry) > 100 * len(kernel_ops(text, "kda_fwd"))
    by_head = "|".join(
        rf"\[{rows},{seq},{heads},{w}\]|\[{rows},{heads},{seq},{w}\]"
        rf"|\[{rows * heads},{seq},{w}\]"
        for w in {size.key_dim, size.value_dim})
    assert not [line for line in entry if re.search(by_head, line)]
    wide = rows * seq * heads * min(size.key_dim, size.value_dim)
    for line in entry:
        made = re.match(r"\s*%(\S+) = \(?\w+\[([\d,]+)\]", line)
        if made and re.search("copy|transpose", made.group(1)):
            assert math.prod(map(int, made.group(2).split(","))) < wide, line
    by_token_q = f"bf16[{rows},{seq},{heads * size.key_dim}]"
    by_token_v = f"bf16[{rows},{seq},{heads * size.value_dim}]"
    for call in kernel_ops(text, "kda_fwd") + kernel_ops(text, "kda_bwd"):
        # q, k, v in and o out at the least
        assert call.count(by_token_q) + call.count(by_token_v) >= 4, call


def fused_computation(text, instruction):
    """The body of the computation that the fusion `instruction` (a line of
    the compiled text) calls."""
    called = re.search(r"calls=(%[\w.\-]+)", instruction).group(1)
    body = text[text.index(f"\n{called} ("):]
    return body[:body.index("\n}")]


def test_cell_step_makes_a_heads_dw_where_its_logits_are(cell_step, family,
                                                         cell):
    """The same compiled step, its `head`: a head's three vocabulary matmuls
    stand together. The text holds one logits matmul a head (the main one
    and, where the model has a prediction module, its own), bf16[rows a
    chunk, vocabulary]; no instruction under `head` is one the compiler
    made again under memory pressure (`.remat`: its own rematerialisation,
    which no jaxpr shows); and each head's dW product stands within a
    hundred instructions of its logits, of the step's thousands, so no
    logits live through the backward to reach it. Where the rule ties dW
    to dx (a lone chunk whose logits are smaller than the update's three
    float32 results: ray_tpu/models/gpt.py:_chunked_xent_bwd), the update
    of `lm_head`, the fusion that reads its first moment, holds no matmul;
    elsewhere the compiler puts the product into that fusion right after
    dx by itself, and a cell that fails here says its scheduler no longer
    does. (Tied to the embedding table the product waits for the lookup's
    gradient at the end of the backward whatever is done here: lfm2, whose
    place for it is not held.)"""
    assert cell == family.cell
    mix, text, params = cell_step.mix, cell_step.text, cell_step.params
    tied = "lm_head" not in params
    d, vocab = (params["embed"]["table"].shape[::-1] if tied
                else params["lm_head"].shape)
    rows = min(mix["global_batch"] * mix["seq"], 16384)
    # one chunk: the scan is inlined, so the head's instructions that run
    # as ops of their own are the entry computation's
    entry = text[text.index("\nENTRY "):].splitlines()

    def under_head(tail):
        return [at for at, line in enumerate(entry) if re.search(
            rf'op_name="[^"]*[(/]head[)/][^"]*{tail}"', line)]
    logits = [at for at in under_head("closed_call/dot_general")
              if f"bf16[{rows},{vocab}]" in entry[at].split(" fusion(")[0]]
    assert len(logits) == 1 + ("mtp" in params), [entry[at] for at in logits]
    again = [line for line in entry if re.match(
        r"\s*(ROOT )?%[\w.\-]*\.remat[^=]* = .*op_name=\"[^\"]*[(/]head[)/]",
        line)]
    assert not again, again
    if tied:
        return
    products = under_head("nd,nv->dv/dot_general")
    assert len(products) == len(logits)
    assert all(0 < dw - at < 100 for at, dw in zip(logits, products)), (
        logits, products, len(entry))
    if rows * 2 < 3 * 4 * d:
        updates = [line for line in entry if re.search(
            r" fusion\([^)]*%state_1__0__mu__lm_head__", line)]
        assert len(updates) == 1, updates
        assert not re.search(r" (convolution|dot)\(", fused_computation(
            text, updates[0])), updates[0][:300]


def test_the_new_scopes_are_regions_and_reach_the_compiled_step(cell_step,
                                                                family):
    """The family's scopes (its hook says which) among the op names of the
    cell's step as the chip's compiler leaves it: each ends in a region of
    the trace's vocabulary (util/profiling.py:REGIONS), and the kernels
    stand under the scope the trace charges them to."""
    from ray_tpu.util import profiling
    names = set(re.findall(r'op_name="([^"]*)"', cell_step.text))
    family.scopes(names, {profiling._last_of(n, profiling.REGIONS)
                          for n in names})


def branches_of(text, wanted):
    """For every line of the compiled text that `wanted` (a regular
    expression) finds: which branch of a conditional its computation is, or
    is called from (a fusion's body is a computation of its own): 0, 1, or
    None outside every conditional."""
    inside, calls, branch, found = None, {}, {}, []
    for line in text.splitlines():
        opened = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", line)
        if opened:
            inside = opened.group(1)
            continue
        for called in re.findall(r"(?:calls|to_apply)=(%[\w.\-]+)", line):
            calls[called] = inside
        for both in re.findall(r"branch_computations=\{([^}]*)\}", line):
            for at, name in enumerate(re.findall(r"%[\w.\-]+", both)):
                branch[name] = at
        if re.search(wanted, line):
            found.append(inside)

    def of(name):
        while name is not None and name not in branch:
            name = calls.get(name)
        return branch.get(name)
    return [of(name) for name in found]


def test_sparse_layer_compiles_with_both_row_spaces(cell_step, family,
                                                    sparse_cell):
    """The sparse blocks of a cell that holds a share of the experts, in the
    cell's whole step for one described chip (until PR 73 one block alone,
    its gradients under the layer's remat, compiled a second time): the
    text holds every block over the bounded row space and over every
    slot's, one conditional forward and one backward a block (the forward
    one's recomputation under the remat is dead code, unless a latent
    projection reads the block's result: then it runs again, a third
    pass), the kernels once a branch; and the token side sized by the slots
    in every slot's branch alone, at any number of experts a token. Tokens
    a step are the cell's own (BENCHMARK.json's traffic), the rows' width
    the experts' own."""
    assert sparse_cell == family.cell
    tile, bounded, every = family.row_spaces
    cfg = cell_configuration(family.cell)
    batch, seq = cell_step.mix["global_batch"], cell_step.mix["seq"]
    text = cell_step.text
    def sparse_blocks(tree):
        """Every layer's, a prediction module's included."""
        if isinstance(tree, dict) and "moe" in tree:
            yield tree["moe"]
        elif isinstance(tree, (dict, list)):
            for sub in (tree.values() if isinstance(tree, dict) else tree):
                yield from sparse_blocks(sub)
    blocks = list(sparse_blocks(cell_step.params))
    sparse = blocks[0]
    # the rows' width: the model's, or the latent one the experts work in
    d = sparse["w_down"].shape[-1]
    matrices = sum(key in sparse for key in ("w_gate", "w_up", "w_down"))
    # forward, backward and, where a latent projection's gradient needs the
    # block's result, the forward again under the remat
    passes = 3 if "w_latent_out" in sparse else 2
    assert len(re.findall(r" conditional\(", text)) == passes * len(blocks)
    # a branch and matrix: one product a forward pass, two in the backward's
    # branch (the forward again, then the rows' gradient), and one tgmm
    assert len(kernel_ops(text, "moe_gmm")) \
        == 2 * matrices * (passes + 1) * len(blocks)
    assert len(kernel_ops(text, "moe_tgmm")) == 2 * matrices * len(blocks)
    for tiles in (bounded, every):
        # the table of rows by tiles; the dispatched rows and the experts'
        # outputs, forward and backward
        assert f"s32[{tiles},{tile}]" in text, tiles
        assert text.count(f" = bf16[{tiles * tile},{d}]") >= 4 * len(blocks)
    # the token side (combine forward, dispatch backward) moves every slot's
    # row, bf16[T, k, d], over every slot's row space only: once a
    # conditional, in the branch the predicate's false picks. The bounded
    # branch gathers its own rows in token order and the tokens' run heads
    # out of moe_run_sum's result, which has a tile of zeros appended.
    per_slot = branches_of(text, (
        rf" = bf16\[{batch * seq},{cfg.expert_top_k},{d}\]\S* gather\("))
    assert per_slot == [0] * (passes * len(blocks)), per_slot
    runs = kernel_ops(text, "moe_run_sum")
    assert len(runs) == passes * len(blocks)
    assert all(f"bf16[{(bounded + 1) * tile},{d}]" in line for line in runs)
    assert branches_of(text, "^" + kernel_call("moe_run_sum")) \
        == [1] * len(runs)
    # and no element gather or scatter-add of the kept weights
    assert not re.search(r" scatter\(", text)
