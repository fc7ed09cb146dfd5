"""The state-space scan (ops/state_space.py), the plain filter's bias
(ops/short_conv.py), and a stack whose layers are ONE half each (a Mamba-2
mixer, latent relu^2 experts, grouped-query attention without rotation) with
a multi-token prediction module (models/gpt.py) against the plain float32
reference of benchmark/families/nemotron_h.py, at a small size on the CPU:
seeded random weights, the kernels in interpret mode. The checks every
family has are tests/helpers/families.py's, given this file's FAMILY; the
cell's sparse block and its whole step compile for a described chip at the
end."""

import copy

import numpy as np
import pytest

from helpers.described_chip import cell_step, v5e  # noqa: F401 — fixtures
from helpers.families import (  # noqa: F401 — fixtures and shared checks
    Family, case, family, read, steps_agree,
    test_configuration_file_keeps_the_catalog_and_states_the_cut,
    test_every_new_leaf_gets_its_rule,
    test_param_count_is_the_published_model_and_the_programs_tree
    as test_param_count_is_the_cut_and_the_programs_tree,
    test_sharded_step_equals_one_device,
    test_the_configuration_refuses_by_name, tiny)
from helpers.jaxprs import dots_of, pallas_calls


def _expert_form_of_four():
    from ray_tpu.models.gpt import ExpertForm
    return {"expert_form": ExpertForm(matrices=4)}


class NemotronH(Family):
    """benchmark/rehearsal/configs/tiny-nemotron-h.json: the pattern MEM*E
    and a prediction module *E; Mamba heads 4..7 of 8 at 32 with group 1 of
    2, state 64; query heads 2 on 1 of 4 on 2 at 32; experts 4..7 of 16
    held, 4 a token, width 96 in a latent 64, beside a shared one of 192."""

    name, tiny, cell = ("nemotron_h", "tiny-nemotron-h",
                        "nemotron-3-super-120b-a12b")
    workload = "nemotron3s_train_1chip"

    def tree(self, jax, config):
        return jax.eval_shape(
            lambda: self.module.program(config).init(jax.random.PRNGKey(0)))

    cell_params = 838_249_968       # 838M +- 1 %

    def published(self, cell, tiny_tree):
        family = self.module
        assert abs(family.param_count(cell) - 838.2e6) < 0.01 * 838.2e6
        # the "A12B" of the name: at the published sizes, every layer and the
        # module, 22 experts a token
        whole = {**cell, **cell["published"], "share": None}
        assert 120e9 < family.param_count(whole) < 125e9
        assert 12e9 < family.active_param_count(whole) < 13.5e9

    def rules(self, specs, column, row):
        from jax.sharding import PartitionSpec as P
        ssm, moe = specs["layers"][0]["ssm"], specs["layers"][1]["moe"]
        assert ssm["w_z"] == ssm["w_dt"] == P(*column)
        assert ssm["w_out"] == specs["layers"][3]["attn"]["wo"] == P(*row)
        assert ssm["w_xbc"] == P(column[0], None)
        assert ssm["conv"] == P(None, None) and ssm["conv_bias"] == P(None)
        assert ssm["a_log"] == ssm["dt_bias"] == ssm["d"] == P("tensor")
        assert ssm["norm"]["scale"] == P("tensor")
        assert moe["w_latent_in"] == P(column[0], None)
        assert moe["w_latent_out"] == P(None, column[0])
        assert moe["shared"]["w_up"] == P(*column)
        assert "w_gate" not in moe and "w_gate" not in moe["shared"]
        module = specs["mtp"]
        assert module["proj"] == P(column[0], None)
        assert module["layers"][0]["attn"]["wq"] == P(*column)
        assert module["layers"][1]["moe"]["w_latent_in"] == P(column[0], None)

    def sharded_step(self, jax, tiny, twin):
        """A state-space layer and the module (attention, latent experts,
        its projection and second loss) on tensor=2: the `ssm/*`,
        `moe/w_latent_*` and `mtp/*` rows of parallel/sharding.py's table (a
        CPU mesh: no chip claim)."""
        steps_agree(jax, self, dict(
            tiny, num_hidden_layers=1, hybrid_override_pattern="M",
            num_attention_heads=4, num_key_value_heads=2), rows=2,
            strategy="tp", axes={"data": 1, "tensor": 2}, atol=2e-6)

    refusals = [
        case(({"attention": "ring"}, "'ssm' layer's state.*attention='ring'"),
             "ring"),
        case(({"ssm": None}, "'ssm' layers need their sizes"), "no_sizes"),
        case(({"route_from": "input"}, "an 'ff' layer has none"),
             "route_ahead"),
        case(({"layer_kinds": ("ssm", "mamba", "ff", "ff", "ff")},
              "'attention_alone'"), "kinds_names"),
        case((_expert_form_of_four, "matrices 2"), "form"),
    ]


    def scopes(self, names, regions):
        from ray_tpu.util import profiling
        assert {"ssm", "ssm_core", "moe_latent", "mtp"} <= set(
            profiling.REGIONS)
        assert {"ssm", "ssm_core", "moe_latent", "mtp", "moe", "moe_route",
                "moe_shared", "attn_proj", "attn_core", "attn_out", "head",
                "embed"} <= regions
        for n in names:
            if "conv_silu" in n:
                assert profiling._last_of(n, profiling.REGIONS) == "ssm"
        assert {"ssd_fwd", "ssd_bwd"} <= set(profiling.KERNELS)
        # the scan's two kernels are ssm_core's, the forward's in phase
        # forward and the backward's in the transpose; the forward is not
        # run a second time under the remat, and no loop of XLA's is left
        core = [n for n in names
                if profiling._last_of(n, profiling.REGIONS) == "ssm_core"]
        assert any("/ssd_fwd/" in n and "transpose(" not in n for n in core)
        assert any("/ssd_bwd/" in n and "transpose(" in n for n in core)
        for n in names:
            if "ssd_fwd" in n or "ssd_bwd" in n:
                assert profiling._last_of(n, profiling.REGIONS) == "ssm_core"
            assert not ("ssd_fwd" in n and "rematted_computation" in n), n
            # (here the interpreter walks a kernel's grid in a loop of its
            # own, inside the kernel's name)
            assert not ("/ssm_core/" in n and "while" in n
                        and "/ssd_fwd/" not in n and "/ssd_bwd/" not in n), n

    reduced = {"num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "vocab_size", "num_attention_heads",
               "num_key_value_heads", "mamba_num_heads", "n_groups"}

    def cut(self, cell, row, bench):
        # published layers 26..36: one whole period in the published 5 : 5 : 1
        pattern = row["config"]["hybrid_override_pattern"]
        assert cell["hybrid_override_pattern"] == pattern[26:37] \
            == "EMEMEMEMEM*"
        assert [pattern.count(c) for c in "ME*"] == [40, 40, 8]
        share = cell["share"]
        assert share["expert_parallel"] == share["chips_per_layer"] == 64
        assert share["expert_parallel"] * cell["n_routed_experts"] \
            == share["n_routed_experts"] == 512
        for key in ("vocab_size", "num_attention_heads", "mamba_num_heads",
                    "n_groups"):
            assert share["tensor_parallel"] * cell[key] == share[key] \
                == row["config"][key], key
        # a key/value head is repeated over four chips of the group
        assert cell["num_key_value_heads"] == 1 \
            and share["num_key_value_heads"] == row["config"][
                "num_key_value_heads"] == 2
        assert cell["expand"] * cell["hidden_size"] \
            == share["mamba_num_heads"] * cell["mamba_head_dim"]
        assert {"no_rotation", "latent_experts", "mtp", "ssm_init",
                "sequence_length"} <= set(cell["assumed"])
        for key in ("source", "share", "reduced", "published", "reduced_why",
                    "distorts", "assumed", "departures", "deployment",
                    "train", "program_check"):
            assert key in cell or key in cell["reduced_why"], key

    # 1 x 8192 tokens x 22 a token, 8 of 512 held, in a latent width of
    # 1024: 2816 expected in 128-row tiles, 2 x 22 + 8 = 52 tiles (6656 rows)
    # against 1416 (181 248). k - 1 = 21 rows past a block are two sublane
    # tiles of bfloat16: the run sum's halo follows k (ops/moe.py:_run_halo)
    row_spaces = (128, 52, 1416)

    # the whole step at 1 x 8192 tokens: one attention layer (`*`) of the
    # cut and one of the module, five Mamba layers, six sparse blocks, two
    # lookups. 14.75 GB when this was written: 10.06 of state, 4.69 of
    # temporaries, the tightest cell (75-130 s alone here): the compiler made
    # the main head's logits three times to fit until dW was made beside dx
    cell_kernel_calls = {"flash_fwd": 2, "flash_bwd_dq": 2,
                         "flash_bwd_dkv": 2, "rope_split": 12,
                         "rope_merge": 6, "conv_silu_fwd": 10,
                         "conv_silu_bwd": 5, "ssd_fwd": 5, "ssd_bwd": 5,
                         "moe_gmm": 96, "moe_tgmm": 24, "moe_run_sum": 18,
                         "embed_grad": 2}
    cell_memory_share = (0.80, 0.92)
    # rung 0 is the floor: the reckoning reads 16.24 GB where its ceiling is
    # 14.88 and has nothing left to drop; the step compiles to 14.96 (+
    # OVERHEAD 15.38) and the chip reads 15.06 (89.047 %, ledger PR 72), so
    # `_working_set` reads this cell ~0.9 GB high (ROADMAP D29)
    cell_reckoned = (0.0, 1.36)


FAMILY = NemotronH()
CELL = FAMILY.cell


def _worst(jax, got, want):
    import jax.numpy as jnp
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), got, want)))


# ---------------------------------------------------------------------------
# (a) the scan: chunked against a token a step
# ---------------------------------------------------------------------------


def _scan_inputs(jax, seq, groups, step, seed=0, heads=4):
    """x [2, seq, heads, 8], steps of about `step`, rates over 1..16."""
    import jax.numpy as jnp
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (2, seq, heads, 8))
    dt = step * jax.nn.softplus(jax.random.normal(k[1], (2, seq, heads)))
    a_log = jnp.log(jax.random.uniform(k[2], (heads,), minval=1.0,
                                       maxval=16.0))
    b = jax.random.normal(k[3], (2, seq, groups, 16))
    c = jax.random.normal(k[4], (2, seq, groups, 16))
    return x, dt, a_log, b, c, 1.0 + 0.1 * jax.random.normal(k[5], (heads,))


def _value_and_gradients(jax, fn, args):
    """(fn's output in float32, the six gradients of its sum under fixed
    weights), jitted."""
    import jax.numpy as jnp
    weight = jnp.cos(0.37 * jnp.arange(args[0].size // 2).reshape(
        args[0].shape[1:]))

    def scalar(*a):
        out = fn(*a).astype(jnp.float32)
        return jnp.sum(out * weight), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=tuple(range(6)), has_aux=True))(*args)
    return out, grads


@pytest.mark.parametrize("seq,chunk,groups,step,dtype,heads", [
    (128, 32, 2, 0.05, "float32", 4),   # whole chunks
    (100, 32, 1, 0.05, "float32", 4),   # a ragged tail, one group
    (70, 16, 2, 1e-3, "float32", 4),    # another chunk size, decays near 1
    (96, 32, 4, 3.0, "float32", 4),     # a chunk's decay underflows float32
    (64, 32, 1, 0.05, "float32", 4),    # one group serves every head
    (64, 32, 1, 0.05, "bfloat16", 4),   # the cell's types: x, B, C bfloat16
    # granite's call, small: chunks of 256 and more heads on ONE group than
    # a grid step takes, so B's and C's gradients are summed over the
    # group's blocks of heads outside the kernel
    (300, 256, 1, 0.01, "float32", 32),
    (256, 256, 1, 0.01, "bfloat16", 32),
    (64, 32, 2, 0.05, "float32", 64),   # two blocks of 16 in each of 2 groups
], ids=["whole", "ragged", "chunk16_slow", "underflow", "one_group",
        "cell_types", "chunk256_blocks_of_a_group", "chunk256_cell_types",
        "two_groups_of_two_blocks"])
def test_chunked_scan_matches_the_recurrence(jax_cpu, seq, chunk, groups,
                                             step, dtype, heads):
    """The two kernels, interpreted: values and all six gradients. No chunk
    divides by a decay: where a chunk's cumulative log-decay passes
    float32's range the chunked form still has the recurrence's numbers.
    At the cell's types (bfloat16 x, B and C are exact operands, one term
    of three; dt float32) what comes out as bfloat16 is held to a rounding
    of its type and the float32 gradients to float32's tolerance."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.state_space import (_heads_a_step, chunk_log_decay, ssd,
                                         ssd_reference)
    args = list(_scan_inputs(jax, seq, groups, step, heads=heads))
    for at in (0, 3, 4):
        args[at] = args[at].astype(dtype)
    if heads > 4:
        assert _heads_a_step(chunk, heads // groups) < heads // groups
    assert chunk_log_decay(args[1], args[2], chunk).shape == (
        2, -(-seq // chunk), heads, chunk)
    if step == 3.0:
        assert float(chunk_log_decay(args[1], args[2], chunk).min()) < -200.0
    out, grads = _value_and_gradients(jax, lambda *a: ssd(*a, chunk=chunk),
                                      args)
    want, want_grads = _value_and_gradients(jax, ssd_reference, args)
    scale = float(jnp.max(jnp.abs(want)))
    rounding = {"float32": 2e-5, "bfloat16": 2 ** -8}
    assert float(jnp.max(jnp.abs(out - want))) < rounding[dtype] * max(
        scale, 1.0)
    for g, w, like in zip(grads, want_grads, args):
        assert np.isfinite(np.asarray(g, np.float32)).all()
        assert g.dtype == like.dtype
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        top = max(float(jnp.max(jnp.abs(w))), 1.0)
        assert float(jnp.max(jnp.abs(g - w))) < 10 * rounding[
            str(like.dtype)] * top


def _xla_chunked(x, dt, a_log, b, c, d, *, chunk):
    """The chunked form as XLA einsums and one `lax.scan`, every product
    at Precision.HIGHEST: what `ssd` was before its kernels (PR 56), kept
    here as their oracle. Whole chunks only."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.state_space import chunk_log_decay
    hi = jax.lax.Precision.HIGHEST
    batch, seq, heads, width = x.shape
    groups, n = b.shape[-2:]
    per, chunks = heads // groups, seq // chunk
    xc, dtc, bc, cc = (t.astype(jnp.float32).reshape(
        batch, chunks, chunk, *t.shape[2:]) for t in (x, dt, b, c))
    g = chunk_log_decay(dt, a_log, chunk)                     # [B,c,H,C]
    u = dtc[..., None] * xc                                   # [B,c,C,H,P]
    at = jnp.arange(chunk)
    between = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                                g[..., :, None] - g[..., None, :], -jnp.inf))
    cb = jnp.einsum("bktgn,bksgn->bkgts", cc, bc, precision=hi)
    y = jnp.einsum("bkhts,bkshp->bkthp",
                   between * jnp.repeat(cb, per, axis=2), u, precision=hi)
    to_end = jnp.exp(g[..., -1:] - g).transpose(0, 1, 3, 2)   # [B,c,C,H]
    added = jnp.einsum(
        "bksgrp,bksgn->bkgrpn",
        (to_end[..., None] * u).reshape(batch, chunks, chunk, groups, per,
                                        width),
        bc, precision=hi).reshape(batch, chunks, heads, width, n)

    def step(state, inputs):
        decay, add = inputs
        return decay[..., None, None] * state + add, state
    _, starts = jax.lax.scan(
        step, jnp.zeros((batch, heads, width, n), jnp.float32),
        (jnp.moveaxis(jnp.exp(g[..., -1]), 1, 0), jnp.moveaxis(added, 1, 0)))
    carried = jnp.einsum(
        "bktgn,bkgrpn->bktgrp", cc,
        jnp.moveaxis(starts, 0, 1).reshape(batch, chunks, groups, per, width,
                                           n),
        precision=hi).reshape(batch, chunks, chunk, heads, width)
    y = (y + jnp.exp(g).transpose(0, 1, 3, 2)[..., None] * carried
         + d.astype(jnp.float32)[:, None] * xc)
    return y.reshape(batch, seq, heads, width)


@pytest.mark.parametrize("exact,heads", [(True, 4), (False, 4), (True, 32)],
                         ids=["bfloat16_values", "float32_values",
                              "bfloat16_values_two_blocks_of_a_group"])
def test_kernels_equal_the_xla_form_at_float32_rounding(jax_cpu, exact,
                                                        heads):
    """`LOWERED` (tests/test_lowered_steps.py) does not see a kernel's body,
    so this does: y and the six gradients of the kernels against the XLA
    form they replaced, all in float32, so that nothing hides under a
    bfloat16 rounding. With x, B and C holding bfloat16 values and declared
    exact (the cell's types: ONE term of an operand's three enters its
    products, `state_space._dot`), the sum is the all-HIGHEST form's; with
    float32 values every product takes its six passes."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import state_space
    args = list(_scan_inputs(jax, 64, 1, 0.05, seed=3, heads=heads))
    if exact:
        for at in (0, 3, 4):
            args[at] = args[at].astype(jnp.bfloat16).astype(jnp.float32)
    out, grads = _value_and_gradients(jax, lambda *a: state_space._scan(
        *a, chunk=32, exact=(exact,) * 3, interpret=True), args)
    want, want_grads = _value_and_gradients(
        jax, lambda *a: _xla_chunked(*a, chunk=32), args)
    assert out.dtype == jnp.float32
    # of each one's largest value. The kernels read 0.9-3.1e-7 on y, dx,
    # ddt, db, dc when this was written, and with the pairs of order two
    # left out (Precision.HIGH's three passes) 2.6-8.5e-6; the two sums
    # over a head's whole sequence, a_log's and d's, 3.1e-6 and 5.3e-7
    # either way (the XLA form is as far from the recurrence)
    for name, g, w in zip(("y", "x", "dt", "a_log", "b", "c", "d"),
                          (out,) + grads, (want,) + want_grads):
        top = max(float(jnp.max(jnp.abs(w))), 1.0)
        tol = 1e-5 if name in ("a_log", "d") else 1e-6
        assert float(jnp.max(jnp.abs(g - w))) < tol * top, (
            name, float(jnp.max(jnp.abs(g - w))), top)


def _chunks_differentiated(x, dt, a_log, b, c, d, *, chunk, exact):
    """`state_space._chunk` a chunk and group under a `lax.scan`, as plain
    jnp, on operands laid out as the kernels' (tokens last), with g
    `chunk_log_decay`'s: differentiated by JAX this is `jax.vjp(_chunk)` a
    chunk (`_product`'s and `_spread`'s rules: every cotangent three
    terms) and XLA's transpose of the cumulative sum, which is what
    `ssd_bwd` was until PR 64 wrote the transpose out. Its oracle."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.state_space import _chunk, chunk_log_decay
    f32 = jnp.float32
    batch, seq, heads, width = x.shape
    groups, n = b.shape[-2:]
    per, pad = heads // groups, -seq % chunk
    chunks = (seq + pad) // chunk

    def cut(t):      # [B, S, ...] -> [chunks, B, chunk, ...], whole chunks
        t = jnp.pad(t.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape(batch, chunks, chunk, *t.shape[2:]), 1, 0)
    g = jnp.moveaxis(chunk_log_decay(dt, a_log, chunk), 1, 0)   # [c, B, H, C]
    skip = d.astype(f32).reshape(groups, per, 1, 1)

    def a_group(state, xs):
        x_c, b_c, c_c, dt_c, g_c, skip_g = xs
        return _chunk(x_c.transpose(1, 2, 0), b_c, c_c, dt_c.T[:, None],
                      g_c[:, None], skip_g, state, exact)

    def a_chunk(states, xs):
        x_c, b_c, c_c, dt_c, g_c = xs             # [B, C, H, P], .., [B, H, C]
        y, states = jax.vmap(jax.vmap(a_group, in_axes=(0, (1, 1, 1, 1, 0, 0)),
                                      out_axes=(0, 0)),
                             in_axes=(0, (0, 0, 0, 0, 0, None)))(
            states, (x_c.reshape(batch, chunk, groups, per, width), b_c, c_c,
                     dt_c.reshape(batch, chunk, groups, per),
                     g_c.reshape(batch, groups, per, chunk), skip))
        return states, y                           # y [B, G, per, P, C]
    _, y = jax.lax.scan(
        a_chunk, jnp.zeros((batch, groups, per, width, n), f32),
        (cut(x), cut(b), cut(c), cut(dt), g))
    y = y.transpose(1, 0, 5, 2, 3, 4).reshape(batch, chunks * chunk, heads,
                                              width)
    return y[:, :seq].astype(x.dtype)


@pytest.mark.parametrize("types,declared,chunk,seq,heads,groups,a_step", [
    ("bfloat16", (True,) * 3, 128, 200, 4, 1, None),
    ("bfloat16", (True,) * 3, 256, 300, 8, 1, 2),
    ("float32", (True,) * 3, 128, 256, 8, 1, 2),
    ("float32", (False,) * 3, 256, 512, 4, 1, None),
    ("float32", (False,) * 3, 128, 200, 16, 2, 2),
    ("x_bfloat16", (True, False, False), 128, 256, 4, 2, None),
], ids=["cell_types_chunk128_ragged", "cell_types_chunk256_four_blocks_ragged",
        "exact_values_float32_dy_four_blocks", "float32_chunk256",
        "float32_two_groups_of_four_blocks_ragged", "bfloat16_x_alone"])
def test_the_written_transpose_equals_the_chunks_vjp(
        jax_cpu, monkeypatch, types, declared, chunk, seq, heads, groups,
        a_step):
    """`ssd_bwd`'s body is `_chunk`'s transpose written by hand (PR 64): it
    leaves out the pairs of terms that are exactly zero (dy that arrives as
    bfloat16 is ONE term), sums g's cotangent back onto dt and a_log itself
    and a group's dB and dC over its blocks of heads. Held here, all six
    gradients, to JAX's own transpose of the same `_chunk` on the same
    values (`_chunks_differentiated`): the float32 ones at float32
    rounding, those that leave in bfloat16 at one rounding of theirs. The
    cases: x / B / C exact or not, dy bfloat16 or float32, chunks of 128
    and 256, one block of heads a group and four (`_HEADS` lowered: the
    block follows from the shape), a ragged tail."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import state_space
    if a_step:
        monkeypatch.setattr(state_space, "_HEADS", a_step)
        assert heads // groups == 4 * state_space._heads_a_step(
            chunk, heads // groups)
    state_space._make_ssd_fn.cache_clear()
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = list(_scan_inputs(jax, seq, groups, 0.05, seed=5, heads=heads))
    args[0], args[1] = args[0][:1], args[1][:1]              # one sequence
    args[3], args[4] = args[3][:1], args[4][:1]
    for at, exact in zip((0, 3, 4), declared):
        if exact:                                  # bfloat16 VALUES
            args[at] = args[at].astype(bf16).astype(f32)
        if types == "bfloat16" or (types == "x_bfloat16" and at == 0):
            args[at] = args[at].astype(bf16)
    weight = jnp.cos(0.37 * jnp.arange(args[0].size).reshape(args[0].shape))

    def gradients(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a).astype(f32)
                                                   * weight),
                                argnums=tuple(range(6))))(*args)
    try:
        got = gradients(lambda *a: state_space._scan(
            *a, chunk=chunk, exact=declared, interpret=True))
    finally:
        state_space._make_ssd_fn.cache_clear()
    want = gradients(lambda *a: _chunks_differentiated(
        *a, chunk=chunk, exact=declared))
    # read when this was written (of each one's largest value): x, b, c, d
    # and dt at most 4.7e-7, a_log (a head's sum over the sequence of
    # terms that cancel) 1.9e-6 to 3.4e-5
    for name, g, w, like in zip(("x", "dt", "a_log", "b", "c", "d"), got,
                                want, args):
        assert g.dtype == w.dtype == like.dtype, name
        top = max(float(jnp.max(jnp.abs(w.astype(f32)))), 1.0)
        tol = (2.0 ** -8 if like.dtype == bf16
               else 1e-4 if name == "a_log" else 2e-6)
        worst = float(jnp.max(jnp.abs(g.astype(f32) - w.astype(f32))))
        assert worst < tol * top, (name, worst, top)


@pytest.mark.parametrize("dtype,dots", [("bfloat16", 35), ("float32", 63)])
def test_the_backward_multiplies_the_terms_its_operands_have(jax_cpu, dtype,
                                                             dots):
    """`LOWERED` does not see a kernel's body (D24), so the count stands
    here: `ssd_bwd` at granite's block (16 heads of 64 on one group of 128,
    chunks of 256) traces to one bfloat16 matmul a pair of terms that
    `_dot` keeps. x / B / C and dy bfloat16: C B^T 1, dy^T x 1 and dy W 3
    (dy is ONE term: three-term cotangents would make them 3 and 6, 40 in
    all), C S_0 3, the cotangent of x^T B's operand 3, the state's
    cotangent 3, g's reverse cumulative sum against the triangle of ones 3,
    dB and dC 3 + 6 each. Float32 operands take every product's six
    passes (the triangle's three)."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.state_space import ssd
    shape = lambda *dims, dtype=dtype: jax.ShapeDtypeStruct(dims, dtype)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssd(*a, chunk=256, interpret=False)),
        argnums=tuple(range(6))))(
        shape(1, 512, 64, 64), shape(1, 512, 64, dtype="float32"),
        shape(64, dtype="float32"), shape(1, 512, 1, 128),
        shape(1, 512, 1, 128), shape(64, dtype="float32")).jaxpr

    bodies = pallas_calls(jaxpr)
    assert sorted(bodies) == ["ssd_bwd", "ssd_fwd"]
    assert dots_of(bodies["ssd_bwd"]) == dots
    # the forward's: C B^T, x W, C S_0, x^T B
    assert dots_of(bodies["ssd_fwd"]) == {
        "bfloat16": 1 + 3 + 3 + 3, "float32": 24}[dtype]


def test_scan_keeps_the_inputs_type_and_names_what_remat_keeps(jax_cpu):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops.state_space import SSD_OUT, ssd
    x, *rest = _scan_inputs(jax, 64, 2, 0.05)
    assert jax.eval_shape(lambda *a: ssd(*a, chunk=32),
                          x.astype(jnp.bfloat16), *rest).dtype == jnp.bfloat16
    # the names are the custom_vjp's forward rule's: what a gradient traces
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssd(*a, chunk=32))))(x, *rest))
    assert text.count(f"name[name={SSD_OUT}]") == 2     # y, the chunk states
    assert text.count("name=ssd_fwd") == text.count("name=ssd_bwd") == 1


def test_the_block_of_heads_follows_the_chunk_and_the_group():
    """A grid step takes the most heads that divide a group's, stay within
    16 and keep a chunk's [h, C, C] float32 tiles within the bytes
    `_TILE_BYTES` gives one: nemotron's call (16 heads on a group, chunks of
    128) is what it was, one block of 16; granite's 64 heads on one group at
    chunks of 256 are four blocks of 16, and chunks of 512 would be blocks of 4."""
    from ray_tpu.ops.state_space import _TILE_BYTES, _heads_a_step
    assert _heads_a_step(128, 16) == 16 and _heads_a_step(128, 64) == 16
    assert _heads_a_step(128, 12) == 12 and _heads_a_step(64, 24) == 12
    assert _heads_a_step(256, 64) == 16 \
        == _TILE_BYTES // (4 * 256 * 256)
    assert _heads_a_step(512, 64) == 4 and _heads_a_step(512, 6) == 3
    assert _heads_a_step(2048, 64) == 1             # never none
    # the backward walks a step's [C, C] tiles a megabyte at a time: all
    # sixteen heads at chunks of 128, four at 256, what divides the block
    from ray_tpu.ops.state_space import _tile_heads
    assert _tile_heads(128, 16) == 16 and _tile_heads(256, 16) == 4
    assert _tile_heads(128, 12) == 12 and _tile_heads(256, 6) == 3
    assert _tile_heads(1024, 4) == 1


def test_scan_refuses_what_the_chips_tiles_cannot_hold(jax_cpu):
    """No second path: a compiled chunk is whole lane tiles of tokens and a
    head whole sublane tiles of channels, and the groups divide the heads
    (the small shapes of this file run interpreted, where any shape
    does)."""
    jax = jax_cpu
    from ray_tpu.ops.state_space import ssd
    args = _scan_inputs(jax, 64, 2, 0.05)
    with pytest.raises(ValueError, match="whole lane tiles"):
        ssd(*args, chunk=32, interpret=False)
    with pytest.raises(ValueError, match="whole sublane tiles"):
        ssd(*args, chunk=128, interpret=False)
    x, dt, a_log, b, c, d = args
    with pytest.raises(ValueError, match="do not divide"):
        ssd(x, dt, a_log, b[:, :, :1].repeat(3, 2), c[:, :, :1].repeat(3, 2),
            d, chunk=32)


# ---------------------------------------------------------------------------
# (b) the plain filter with a bias a channel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,shape,kernels", [
    ("float32", (2, 64, 256), True),
    ("bfloat16", (1, 128, 128), True),
    ("float32", (2, 40, 96), False),     # does not tile: the jnp form
], ids=["f32_kernels", "bf16_kernels", "jnp"])
def test_plain_filter_with_a_bias_matches_jnp(jax_cpu, dtype, shape, kernels):
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import short_conv
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(k[0], shape).astype(dtype)
    taps = 0.5 * jax.random.normal(k[1], (shape[2], 4))
    bias = 0.3 * jax.random.normal(k[2], (shape[2],))
    weight = jax.random.normal(k[3], shape)
    blocks = short_conv._conv_blocks(shape[1], shape[2], 4,
                                     jnp.dtype(dtype).itemsize)
    assert (blocks is not None) == kernels

    def run(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight),
            argnums=(0, 1, 2)))(x, taps, bias)
    got, grads = run(lambda *a: short_conv.silu_conv(*a, interpret=True))
    want, want_grads = run(short_conv.silu_conv_reference)
    tol = 1e-5 if dtype == "float32" else 2e-2
    assert abs(float(got - want)) < tol * max(abs(float(want)), 1.0) * 10
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=tol * float(jnp.max(jnp.abs(w.astype(jnp.float32)))),
            rtol=tol)
    # a bias of zeros is the filter without one
    np.testing.assert_array_equal(
        np.asarray(short_conv.silu_conv(x, taps, jnp.zeros_like(bias),
                                        interpret=True), np.float32),
        np.asarray(short_conv.silu_conv(x, taps, interpret=True), np.float32))


# ---------------------------------------------------------------------------
# (c) the program against the family's reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small(tiny):
    """The tiny configuration at the pattern ME under its module *E: all
    three kinds of layer and the module, in four layers."""
    return dict(tiny, num_hidden_layers=2, hybrid_override_pattern="ME")


@pytest.fixture(scope="module")
def seeded(jax_cpu, small):
    """(params, tokens [2, 129]) of the small configuration."""
    jax = jax_cpu
    from benchmark.families import nemotron_h
    tiny = small
    params = nemotron_h.program(tiny).init(jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0,
                                tiny["vocab_size"])
    return params, tokens


@pytest.fixture(scope="module")
def referred(jax_cpu, small, seeded):
    """The reference's (logits, both heads' log-probabilities, the main
    head's, loss, gradients) of `seeded`, float32 at full matmul precision:
    one program, compiled once for both attention paths' cases."""
    jax = jax_cpu
    from benchmark.families import nemotron_h as family
    params, tokens = seeded
    tiny = small

    @jax.jit
    def reference(params):
        loss, grads = jax.value_and_grad(
            lambda p: family.reference_loss(p, tokens, tiny))(params)
        return (family.reference_logits(params, tokens[:, :-1], tiny),
                family.reference_both_logprobs(params, tokens, tiny),
                family.reference_logprobs(params, tokens[:, :-1], tiny),
                loss, grads)
    with jax.default_matmul_precision("highest"):
        return reference(params)


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_logits_loss_and_gradients_match_the_reference(jax_cpu, small,
                                                       seeded, referred,
                                                       attention):
    """float32 on both sides: every logit of both heads' passes to 5e-5,
    the loss (both cross-entropies) and the whole tree of gradients."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import nemotron_h as family
    from ray_tpu.models.gpt import (GPTConfig, gpt_forward, gpt_forward_both,
                                    gpt_loss, gpt_loss_and_aux)
    params, tokens = seeded
    tiny = small
    cfg = GPTConfig(**family.gpt_config_kwargs(tiny), attention=attention,
                    remat_policy="full", dtype=jnp.float32)
    batch = {"tokens": tokens}

    @jax.jit
    def program(params):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: gpt_loss_and_aux(p, batch, cfg), has_aux=True)(params)
        return (gpt_forward_both(params, tokens, cfg),
                gpt_forward(params, tokens[:, :-1], cfg)[0],
                gpt_loss(params, batch, cfg), loss, aux, grads)

    def logprob(z, t):
        return jnp.take_along_axis(jax.nn.log_softmax(z), t[..., None],
                                   -1)[..., 0]
    with jax.default_matmul_precision("highest"):
        (logits, ahead), again, alone, loss, aux, grads = program(params)
    want_logits, (first, second), plain, want_loss, want = referred
    assert float(jnp.max(jnp.abs(logits - want_logits))) < 5e-5
    assert float(jnp.max(jnp.abs(again - want_logits))) < 5e-5
    assert float(jnp.max(jnp.abs(
        logprob(logits, tokens[:, 1:]) - first))) < 5e-5
    assert float(jnp.max(jnp.abs(
        logprob(ahead[:, :-1], tokens[:, 2:]) - second))) < 5e-5
    np.testing.assert_allclose(plain, first[:, :-1], atol=1e-6)
    assert float(loss) == float(alone) == pytest.approx(
        float(aux["xent"]) + 0.3 * float(aux["mtp_xent"]), abs=1e-6)
    assert float(loss) == pytest.approx(float(want_loss), abs=2e-5)
    assert 0.3 < float(aux["expert_hidden_zero_share"]) < 0.7
    assert float(aux["expert_rows_bounded"]) == 1.0
    assert float(aux["ssm_log_decay_min"]) < 0 < float(aux["ssm_dt_mean"])
    assert _worst(jax, grads, want) < 2e-6
    moved = {jax.tree_util.keystr(path) for path, g
             in jax.tree_util.tree_flatten_with_path(grads)[0]
             if float(jnp.max(jnp.abs(g))) > 0}
    still = {jax.tree_util.keystr(path) for path, _
             in jax.tree_util.tree_flatten_with_path(grads)[0]} - moved
    assert all("router_bias" in path for path in still), still


def _gap(jax, params, tokens, sound, faulty):
    """(median, root mean square) of |the faulty reference's per-token
    log-probabilities less the sound one's| over both heads' passes."""
    import jax.numpy as jnp
    from benchmark.families import nemotron_h as family
    with jax.default_matmul_precision("highest"):
        a = jax.jit(lambda p, t: family.reference_both_logprobs(p, t, sound)
                    )(params, tokens)
        b = jax.jit(lambda p, t: family.reference_both_logprobs(
            p, t, faulty[2]))(*faulty[:2])
    gap = jnp.concatenate([(x - y).reshape(-1) for x, y in zip(a, b)])
    return float(jnp.median(jnp.abs(gap))), float(jnp.sqrt(jnp.mean(gap ** 2)))


@pytest.mark.parametrize("fault", ["no_decay", "dt_raw", "no_skip",
                                   "gate_after_norm", "relu", "unscaled",
                                   "fp8"])
def test_the_reference_tells_each_mechanism_apart(jax_cpu, small, seeded,
                                                  fault):
    """Each of the chip controls' faults, in the reference alone, moves the
    per-token log-probabilities far past what float32 leaves between
    program and reference (5e-5 a logit)."""
    jax = jax_cpu
    import jax.numpy as jnp
    params, tokens = seeded
    tiny = small
    if fault == "fp8":
        low = jax.tree_util.tree_map(
            lambda w: w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            if w.ndim >= 2 else w, params)
        median, rms = _gap(jax, params, tokens, tiny, (low, tokens, tiny))
    else:
        median, rms = _gap(jax, params, tokens, tiny,
                           (params, tokens, dict(tiny, fault=fault)))
    # (a step that is not through its softplus is negative for most tokens:
    # the state grows without bound and the reading is nan, outside)
    assert not (median <= 2e-3 or rms <= 5e-3), (median, rms)


def test_the_modules_term_is_part_of_the_loss(jax_cpu, small, seeded):
    jax = jax_cpu
    from benchmark.families import nemotron_h as family
    params, tokens = seeded
    tiny = small
    with jax.default_matmul_precision("highest"):
        whole, without = (float(jax.jit(
            lambda p, t: family.reference_loss(p, t, c))(params, tokens))
            for c in (tiny, dict(tiny, fault="no_mtp")))
    assert whole - without > 0.2 * whole / 1.3
    with pytest.raises(ValueError, match="fault"):
        family.reference_loss(params, tokens, dict(tiny, fault="typo"))


def test_bfloat16_step_passes_the_per_token_check(jax_cpu, small, seeded):
    """reference_loss with a `program_check`: the bf16 program's own forward
    (both heads' passes) within the bounds gives the number, outside them
    nan."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import nemotron_h as family
    params, tokens = seeded
    tiny = small

    @jax.jit
    def checked(params, tokens, slack):
        first, second = family.reference_both_logprobs(params, tokens, tiny)
        gap = family.program_logprob_gap(params, tokens, tiny, first, second)
        check = dict(zip(("logprob_median_tol", "logprob_rms_tol",
                          "logprob_p99_tol"), (g * w for g, w
                                               in zip(gap, slack))))
        return gap, family.reference_loss(
            params, tokens, dict(tiny, program_check=check))
    with jax.default_matmul_precision("highest"):
        (median, rms, tail), loose = checked(params, tokens,
                                             jnp.array([2.0, 2.0, 2.0]))
        _, tight = checked(params, tokens, jnp.array([2.0, 0.5, 2.0]))
    # (a near-tied expert that swaps under bf16 moves a few tokens much:
    # the root mean square may pass the 99th percentile)
    assert 0 < float(median) < 0.02 and 0 < float(tail) < 0.2
    assert float(median) < float(rms) < 0.2
    assert np.isfinite(float(loose)) and np.isnan(float(tight))


# ---------------------------------------------------------------------------
# (d) the share ties to the model
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["ssm", "attention", "experts"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(jax_cpu, tiny,
                                                             kind):
    """model-configs guide, section 4: a whole layer of 8 Mamba heads in 2
    groups, 4 query heads on 2 key/value heads, 16 experts, over tensor
    parallel 2 (4 Mamba heads with their group, 2 query heads on their
    key/value head) or expert parallel 4 (4 experts each). A head share's
    mixer output is its heads' rows of the output projection's sum and the
    gated norm is a group's own, so the two head shares add up to the uncut
    mixer; the four expert shares' routed sums, through the latent
    projection that every chip holds whole, add up with the shared expert
    counted once to the uncut reference's layer."""
    jax = jax_cpu
    import jax.numpy as jnp
    from benchmark.families import nemotron_h as family
    from ray_tpu.models import gpt
    from ray_tpu.models.gpt import GPTConfig, Setting, gpt_init
    letter = {"ssm": "M", "attention": "*", "experts": "E"}[kind]
    whole = copy.deepcopy(tiny)
    del whole["share"]
    whole.update(n_routed_experts=16, num_attention_heads=4,
                 num_key_value_heads=2, mamba_num_heads=8, n_groups=2,
                 num_hidden_layers=1, hybrid_override_pattern=letter,
                 num_nextn_predict_layers=0)

    def config(c):
        return GPTConfig(**family.gpt_config_kwargs(c), dtype=jnp.float32,
                         attention="reference", remat_policy="none")
    full = config(whole)
    assert full.experts_held is None and full.mtp is None
    layer = gpt_init(jax.random.PRNGKey(7), full)["layers"][0]
    group = {"ssm": "ssm", "attention": "attn", "experts": "moe"}[kind]
    assert sorted(layer) == sorted([group, "ln1"])       # ONE norm, one half
    if kind == "ssm":
        # seeds that tell the heads and the columns apart
        layer["ssm"]["conv_bias"] = 0.2 * jax.random.normal(
            jax.random.PRNGKey(5), (512,))
        layer["ssm"]["norm"]["scale"] = 1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(6), (256,))
    if kind == "experts":
        layer["moe"]["router"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(8), (128, 16))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 128), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(
            lambda h: family.reference_layer(layer, h, whole)))(x)

    def columns(leaf, rank, parts=2, axis=-1):
        return jnp.split(leaf, parts, axis=axis)[rank]

    def ssm_share(rank):
        m = layer["ssm"]
        # [x of 8 heads | B of 2 groups | C of 2 groups]: a rank's parts
        def xbc(leaf, axis):
            x_, b_, c_ = jnp.split(leaf, [256, 384], axis=axis)
            return jnp.concatenate([columns(t, rank, axis=axis)
                                    for t in (x_, b_, c_)], axis=axis)
        return {"w_z": columns(m["w_z"], rank), "w_xbc": xbc(m["w_xbc"], 1),
                "w_dt": columns(m["w_dt"], rank), "conv": xbc(m["conv"], 0),
                "conv_bias": xbc(m["conv_bias"], 0),
                "a_log": columns(m["a_log"], rank),
                "dt_bias": columns(m["dt_bias"], rank),
                "d": columns(m["d"], rank),
                "norm": {"scale": columns(m["norm"]["scale"], rank)},
                "w_out": columns(m["w_out"], rank, axis=0)}

    def attention_share(rank):
        a = layer["attn"]
        return {"wq": columns(a["wq"], rank), "wk": columns(a["wk"], rank),
                "wv": columns(a["wv"], rank),
                "wo": columns(a["wo"], rank, axis=0)}

    def experts_share(rank):
        m = layer["moe"]
        return dict(m, w_up=columns(m["w_up"], rank, 4, 0),
                    w_down=columns(m["w_down"], rank, 4, 0))

    with jax.default_matmul_precision("highest"):
        if kind == "experts":
            shared = jax.vmap(lambda h: family._relu2_mlp(
                layer["moe"]["shared"],
                family._norm(h, layer["ln1"]["scale"], 1e-5), whole))(x)
            total = x + shared
            for rank in range(4):
                held = dict(whole, n_routed_experts=4, share={
                    "rank": rank, "n_routed_experts": 16})
                cfg = config(held)
                assert cfg.experts_held == (4 * rank, 4)
                part = {"ln1": layer["ln1"], "moe": experts_share(rank)}
                y, stats = jax.jit(gpt.layer_fn(cfg, 64, Setting()))(x, part)
                np.testing.assert_allclose(
                    y, jax.jit(jax.vmap(lambda h: family.reference_layer(
                        part, h, held)))(x), atol=2e-5)
                total = total + (y - x - shared)
        else:
            held = dict(whole, num_attention_heads=2, num_key_value_heads=1,
                        mamba_num_heads=4, n_groups=1,
                        share=dict(whole, rank=0))
            cfg = config(held)
            share_of = ssm_share if kind == "ssm" else attention_share
            total = x
            for rank in range(2):
                part = {"ln1": layer["ln1"], group: share_of(rank)}
                y, _ = jax.jit(gpt.layer_fn(cfg, 64, Setting()))(x, part)
                np.testing.assert_allclose(
                    y, jax.jit(jax.vmap(lambda h: family.reference_layer(
                        part, h, held)))(x), atol=2e-5)
                total = total + (y - x)
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_flops_and_kernel_arithmetic_count_what_is_computed_here():
    from benchmark.families import nemotron_h as family
    from benchmark.kernels import gqa_attention, kda, ssd
    cell = read("benchmark", "configs", CELL + ".json")
    mix = read("benchmark", "traffic", "train_b1_s8192_dp.json")
    forward = family.train_flops_per_token(cell, 8192) / 3.0
    assert forward == pytest.approx(1.19e9, rel=0.01)
    assert family.forward_flops_per_token(cell, 8192) == forward
    assert family.attention_call(cell, mix) == {
        "batch": 1, "heads": 4, "kv_heads": 1, "seq": 8192, "head_dim": 128}
    assert family.ssd_call(cell, mix) == {
        "batch": 1, "seq": 8192, "heads": 16, "head_dim": 64, "groups": 1,
        "state": 128, "chunk": 128}
    # the ONE filter call of a layer: [1, 8192, 1280], 4 taps
    flops, moved = kda.conv_silu_fwd(cell, mix)
    assert moved == 2 * 8192 * 1280 * 2 and flops == 11 * 8192 * 1280
    assert kda.conv_silu_bwd(cell, mix)[1] == 3 * 8192 * 1280 * 2
    assert gqa_attention.flash_fwd(cell, mix)[0] > 0
    # the scan, a token and head: a brute-force count of the chunked form
    chunk, width, state, per_group = 128, 64, 128, 16
    intra = sum(2 * state / per_group + 2 * width for t in range(chunk)
                for s in range(t + 1)) / chunk
    carried = 2 * width * state + 2 * width * state
    assert ssd.ssd_flops_per_token(chunk, width, state, per_group) \
        == pytest.approx(intra + carried, rel=0.01)
    flops, moved = ssd.ssd(cell, mix)
    assert flops == 8192 * 16 * ssd.ssd_flops_per_token(128, 64, 128, 16)
    assert flops / 197e12 < moved / 819e9            # bound by bytes


# ---------------------------------------------------------------------------
# (e) refusals
# ---------------------------------------------------------------------------


def test_pipeline_refuses_a_state_and_a_second_stream(jax_cpu, tiny):
    jax = jax_cpu
    from benchmark.families import nemotron_h as family
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel import pipeline
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    mesh = build_mesh(MeshConfig(data=1, pipeline=2),
                      devices=jax.devices()[:2])
    kwargs = family.gpt_config_kwargs(
        dict(tiny, num_hidden_layers=2, hybrid_override_pattern="MM",
             num_nextn_predict_layers=0))
    with pytest.raises(ValueError, match="state-space layer's state"):
        pipeline.make_gpt_pp_loss(GPTConfig(**kwargs), mesh, 2)


def test_the_existing_configurations_have_none_of_it():
    """The sub-records are None and the seeded weights what they were for a
    configuration that asks for nothing new."""
    from ray_tpu.models.gpt import ExpertForm, GPTConfig
    cfg = GPTConfig.tiny()
    assert cfg.ssm is None and cfg.expert_form is None and cfg.mtp is None
    assert cfg.feed_forward == ExpertForm(matrices=3, activation="silu")
    relu = GPTConfig(gate_activation="relu")
    assert relu.feed_forward.activation == "relu"


# ---------------------------------------------------------------------------
# (f) for a described v5e: the scan's kernels, a state-space layer on four
# chips and (imported) the sparse block and the whole step
# ---------------------------------------------------------------------------


def test_scan_compiles_at_8192_positions_of_16_heads_of_64(v5e):
    """ops/state_space.py's two kernels at a state-space layer of
    nemotron3s_train_1chip, [1, 8192, 16, 64] on one group of 128 in chunks
    of 128, the cell's types: `ssd_fwd` and `ssd_bwd` (the chunk function's
    transpose, written out) compile inside their VMEM limit, one Mosaic
    call each and no XLA loop beside them, neither over the 64 chunks nor
    the 8192 tokens.
    All sixteen heads of the group a grid step: x, y, dy and dx blocks of
    [16, 64, 128] bfloat16 (256 KB each), B, C, dB and dC of [128, 128] (32
    KB each), a chunk's states [16, 64, 128]
    float32 (512 KB) and as much scratch, each block twice for the
    pipeline: under 4 MB; the [16, 128, 128] float32 decay-and-score tiles
    (1 MB each) are values inside the body, under the 32 MB the call may
    use. What is kept between the two calls is the chunks' states (34 MB,
    which the compiler may hold in VMEM: no lower bound here), where the
    XLA form's decay-and-score tensors were 67 MB each."""
    import re
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.state_space import ssd

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(v5e[0]))
    args = (shape((1, 8192, 16, 64)), shape((1, 8192, 16), jnp.float32),
            shape((16,), jnp.float32), shape((1, 8192, 1, 128)),
            shape((1, 8192, 1, 128)), shape((16,), jnp.float32))
    compiled = jax.jit(jax.grad(
        lambda *a: jnp.sum(ssd(*a, interpret=False).astype(jnp.float32)),
        argnums=tuple(range(6)))).lower(*args).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(\S*ssd_(?:fwd|bwd)\S*) = .*custom-call\(", text)
    assert len(calls) == 2 and "fwd" in calls[0] and "bwd" in calls[1], calls
    assert text.count("tpu_custom_call") == 2
    assert " while(" not in text
    states = 16 * 64 * 64 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * states


def test_state_space_layer_compiles_on_four_chip_mesh(v5e, monkeypatch):
    """A state-space layer at two chips' worth of nemotron3s_train_1chip's
    share (32 heads of 64 in 2 groups of 128, on 4096) under tp_fsdp on
    fsdp=2 x tensor=2, forward and backward: the two kernels run per shard
    (`gpt.py:_per_shard`: a batch row, sixteen heads and their group a
    device), as the filter beside them does; GSPMD would refuse the Mosaic
    calls as they stand."""
    import re
    import jax
    import jax.numpy as jnp
    from helpers.described_chip import layer_on_four_chips
    from ray_tpu.models import gpt
    widths = dict(FAMILY.module.gpt_config_kwargs(FAMILY.cell_config()),
                  n_layers=1, layer_kinds=("ssm",), n_experts=0,
                  experts_held=None, n_shared_experts=0, expert_form=None,
                  mtp=None, max_seq=2048,
                  ssm=gpt.StateSpace(heads=32, head_dim=64, groups=2,
                                     state=128, chunk=128))
    cfg, mesh, _, layer, x = layer_on_four_chips(v5e, monkeypatch, widths,
                                                  2, 2048)

    def loss(layer, x):
        out, _stats = gpt._ssm_block(layer["ssm"], x, cfg, gpt.Setting(mesh))
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        layer, x).compile().as_text()
    calls = re.findall(r"%(\S*ssd_(?:fwd|bwd)\S*) = .*custom-call\(", text)
    assert len(calls) == 2, calls
    # a shard's own slice: a batch row of sixteen heads, tokens last
    assert re.search(r"ssd_fwd\S* = .*bf16\[16,64,2048\]", text)
    assert "conv_silu_fwd" in text and "all-reduce" in text


# Imported last: a module's names are collected in the order they are bound,
# so the chip's compiler gets this file's programs after its own tests have
# run, at another minute of a run than the other families' files.
from helpers.described_chip import (  # noqa: E402,F401
    test_the_cells_that_were_there_lower_to_the_same_step,
    test_cell_step_compiles_under_the_chips_memory,
    test_cell_step_makes_a_heads_dw_where_its_logits_are,
    test_sparse_layer_compiles_with_both_row_spaces,
    test_the_new_scopes_are_regions_and_reach_the_compiled_step)
