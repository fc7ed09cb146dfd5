"""Every cell's train step, lowered for the TPU at the real sizes, is the
text that was recorded: a PR that means to change a cell's program records
its new hash here and says so; one that does not finds out here."""

import pytest

from helpers.described_chip import CellStep


# sha256 (16 digits) of each cell's train step lowered for the TPU at the
# real sizes, Mosaic calls in it (their backend_config masked, locations
# stripped), nothing compiled: the parent's (708aa58), read before this
# PR's first edit. A PR that means to change a cell's step replaces its
# line; one that does not finds out here. PR 39 (parent 8427b12) meant to
# change kanana's (f5d072a05f3156d1 there: its latent block reaches the
# flash kernels through ops/rope.py's latent kernels) and no other; laguna's
# is its parent's.
LOWERED = {
    # PR 73 recorded the twelve one-chip cells that have a family's file
    # anew and changed NO cell's program (`git diff` of ray_tpu/ and
    # benchmark/ against its parent is empty; the table as it stood passes
    # on the parent): each is now the hash of its family's ONE lowering
    # (helpers/described_chip.py:CellStep, for one described v5e under a
    # mesh of ("data",)), and that text is not the one the same step lowers
    # to over the CPU's devices under build_mesh's axes, which this file
    # lowered a second time until then (the shardings name other axes and
    # devices). Lowered over the CPU's devices, as gpt2s and the four-chip
    # cell still are below, they were: lfm2 6d6427f3b7e0a9c6, kanana2
    # 6799c0d15ba47c04, laguna 48168fa787fdbf01, keye2 b478ecfb41cd7a16,
    # smallthinker 4fa221fa21dffe03, olmoe 857d8b4221a8d29b, solar2
    # e24649581d1b74e7, nemotron3s 81727bededde4c7c, granite4hm
    # 5936a9f19a88e1fd, kimilinear d134dd2cc6a86574, olmohybrid
    # 485e120577e83a31, ouro26 75376408a21b0486. The histories below are of
    # those.
    # PR 63 lowers every cell as the chip runs it (the builder reads a
    # v5e's memory limit: see the test) and means to change exactly the
    # five cells whose MLPs then keep matmul results through the remat
    # (`MLP_OUT`; models/gpt.py:products_kept): `up x` in every layer
    # at gpt2s (301b04a77398ad85 before it) and granite
    # (4ea5a3064d7f262b), both products at lfm2 (fe8bdb7954ee2c8e), kanana
    # (8e6cd298cd0a8c16) and laguna (e425c199e1b22258). The other six are
    # the parent's lines, with or without a limit: olmoe, keye and
    # smallthinker have no `_mlp_block`, four chips have no room for `up x`
    # in 24 layers (6.4 GB a chip), solar and nemotron are reckoned over
    # the ceiling as they are. With no limit reported (the CPU) all eleven
    # lower to the parent's text.
    # PR 51 recorded every one-chip cell anew and the four-chip cell not:
    # on one device the embedding's lookup is ops/embedding.py's (a gather
    # of the master rows, `embed_grad` backward), under the four-chip mesh
    # it is the parent's expression and the step the parent's text. Before
    # it: gpt2s 0a5e354a41f2d309, lfm2 6d8075c1983c7f5a, olmoe
    # de1ac5dddca614d0, kanana ccd30b735ee79374, laguna 2c9cca064dffa677,
    # keye c7b4fffd346aa0f2, solar 830c2fd63a15f131.
    # heads of 64, recorded anew by PR 55, which means to change exactly
    # these three: the heads fill lane tiles in pairs, q and k are rotated
    # where they lie (two `rope_split` calls a layer forward, none for v),
    # the flash kernels read and write [B, S, heads * 64] and nothing is
    # turned under `attn_out` (e83fa754d169a879, 3d347ff7870a2d4a and
    # ee4dcfeb681f3aa3 before it, the parent's per-head steps since PR 51;
    # PR 48 left them alone)
    "gpt2s_train_1chip": "cd3268c7f55100d0",
    "smollm17_train_4chip": "dfab71739954d841",
    "lfm2_train_1chip": "9a1823087a251da4",
    # heads (v's) of 128, recorded anew by PR 48: the flash kernels write o
    # and read dO as [B, S, H * 128], `wo` reads that as it is, delta and a
    # gate a head go through `head_columns` (a875c8421b01b065,
    # 64dedc5a37df64cf, a13b1de35328fc71, 2420d0b4f00749c5 and
    # aa90d217a4905e58 before it: PR 42's masters in `moe_gmm`, and at
    # solar PR 47's `kda_fwd` / `kda_bwd`)
    "kanana2_train_1chip": "2abe146d8471d434",
    "laguna_train_1chip": "b5f2573ce042cd98",
    "keye2_train_1chip": "507fa1e1d6110904",
    # not in the table until PR 59, which leaves it alone (the parent's,
    # .proof/lower_text.py)
    "smallthinker_train_1chip": "bb1a0cb999926a9b",
    # recorded anew by PR 59, which means to change exactly these three,
    # the cells whose one chunk of 8192 rows holds logits smaller than the
    # head matrix's update writes (models/gpt.py:_chunked_xent_bwd): the
    # head's backward rule hands dx and dW on through one
    # `optimization_barrier` a head and nothing else differs (the products,
    # their operands and their rounding are the parent's), so that the
    # chip's compiler makes dW beside dx and not inside `lm_head`'s update
    # at the end of the step. Before it olmoe 37f86a82ad7f1fa7 and solar
    # 42d57e72e853172f (since PR 48), nemotron 8e65faacc0b38e23 (since PR
    # 57, not in the table).
    "olmoe_train_1chip": "f0e1a758c256ffe8",
    # solar's and kimi's recorded anew by PR 66, which means to change
    # exactly these two, the cells with `kda` layers (3d034416b94d0f0e since
    # PR 59 and 54748505aae61025 since PR 65 before it): `kda_fwd` hands out
    # a third result, a chunk's A, Aqk and inverse packed into [64, 128]
    # float32 under the name KDA_OUT, and `kda_bwd` reads it. The kernel's
    # body, now the chunk's transpose written by hand, is held by
    # tests/test_linear_attention.py::test_the_written_transpose_equals_the_chunks_vjp.
    # Both recorded anew by PR 68, which means to change exactly these two,
    # the cells whose delta-rule heads are whole lane tiles (01d97333f72f6be1
    # and fd6f12d5a5c9dedc since PR 66 before it): q, k, v, the log-decay, o
    # and their gradients stay [B, S, H x 128] from the filters to `wo`,
    # `kda_fwd` / `kda_bwd` take them so (a grid step's heads are a block of
    # a token's columns), and the unit norm of q and k and `o_norm` sum a
    # head's squares through `head_columns` at full precision: no transpose
    # and no [.., H, 128] reshape is left under `kda`. olmohybrid's 96 / 192
    # keep the by-head operands and the parent's line. The kernels' bodies
    # (a head is a column slice of the block) are held to the by-head call
    # bit for bit by
    # tests/test_linear_attention.py::test_operands_by_token_are_the_by_head_call_bit_for_bit.
    "solar2_train_1chip": "72edbf236f09f8b7",
    # recorded anew by PR 61, which means to change exactly this one, the
    # only cell with `ssm` layers: the scan is two Mosaic calls a layer,
    # `ssd_fwd` / `ssd_bwd` (ops/state_space.py), where it was XLA einsums
    # around a `lax.scan` (9b951c1470dfbaa6 before it, since PR 59). The
    # hash does not see a kernel's body:
    # tests/test_state_space.py::test_kernels_equal_the_xla_form_at_float32_rounding
    # holds the kernels to the form they replaced.
    # Both recorded anew by PR 64, which means to change exactly these two,
    # the cells with `ssm` layers (69b2dfb18851e2f7 since PR 61 and
    # e36f498a7a0f3560 since PR 63 before it): `ssd_bwd` takes a_log and
    # hands back dt's gradient with g's transpose applied, a_log's a chunk
    # and a group's dB and dC once, in B's type, so the `reduce-window`
    # transpose of `chunk_log_decay` and the sums over a group's blocks of
    # heads leave the step. The kernel's body, now the chunk's transpose
    # written by hand, is held by
    # tests/test_state_space.py::test_the_written_transpose_equals_the_chunks_vjp.
    "nemotron3s_train_1chip": "62032aae0ea5e0e3",
    # new with PR 62, which leaves the ten above alone (their lines are the
    # parent's): every layer a mixer and a gated MLP (`ssm_ff`), the four
    # multipliers' products, `sm_scale` 1/64 on the paired flash kernels,
    # `ssd_fwd` / `ssd_bwd` over four blocks of 16 heads at chunks of 256
    "granite4hm_train_1chip": "cedfa65fdb887046",
    # new with PR 65, which leaves the eleven above alone (their lines are
    # the parent's): four `kda` layers of 32 heads beside a latent layer
    # whose four layout kernels are handed no table (nothing is rotated and
    # no table is built), the first layer a `kda` mixer over the dense MLP,
    # both products of every MLP kept through the remat.
    # Recorded anew by PR 72, which means to change exactly this one
    # (93d3b120f5a4eedf since PR 68 before it): the ladder of what a layer
    # keeps through the remat goes past the MLP's products
    # (models/gpt.py:LADDER), and on a v5e kimi's step is reckoned to hold
    # its top rung, so the four `kda` layers' x wq, x wk and x wv and the
    # three filters' results carry MIXER_OUT and the backward pass neither
    # multiplies nor filters them again. The other thirteen stop at the rung
    # they stopped at (granite, gpt2s and olmohybrid at 1, where `gate x`
    # does not fit; solar and nemotron over the ceiling at 0) or have no
    # such mixer, and their lines are the parent's.
    "kimilinear_train_1chip": "911ca533d51ba968",
    # new with PR 67, which leaves the twelve above alone (their lines are
    # the parent's: a configuration without `GPTConfig.delta` and
    # `norm_after` traces the block and the delta rule as it did): three
    # `kda` layers of 15 heads at key 96 / value 192 under ONE decay a head
    # (the kernels take the chunks' cumulative log-decay as rows, like
    # beta's), one projection and one filter over [q | k | v], a full layer
    # of 15 heads of 128 through `rope_split` without a table under a q/k
    # norm of 1920 columns, the norm after each half, `up x` of every MLP
    # kept through the remat
    "olmohybrid_train_1chip": "b31d45f3fb36f722",
    # new with PR 71, which leaves the thirteen above alone (their lines are
    # the parent's: a configuration without `GPTConfig.loop` walks its
    # layers once and calls `chunked_xent` without its third result): eight
    # layers under four norms each inside ONE scan of four passes, so the
    # text holds 8 `flash_fwd` calls and not 32, the final norm under a
    # checkpoint inside it, the passes' rows through the head in one call
    # of two chunks under the exit gate's weights, nothing more kept
    "ouro26_train_1chip": "365cd81a00e9e2ba",
}


# The two cells without a family's file are lowered here, over the CPU's
# devices under the traffic's own mesh; the twelve others are read off their
# family's one whole step (helpers/described_chip.py:
# test_the_cells_that_were_there_lower_to_the_same_step, imported by each
# kernels file under the id it had here).
ON_THE_CPU = ("gpt2s_train_1chip", "smollm17_train_4chip")


@pytest.mark.timeout(600)
@pytest.mark.parametrize("cell", ON_THE_CPU)
def test_the_cells_that_were_there_lower_to_the_same_step(jax_cpu, cell):
    """The cells' programs are the text that was recorded: a PR that means
    to change a cell's program records its new hash above and says so."""
    assert CellStep(cell, jax_cpu.devices()).lowered == LOWERED[cell]
