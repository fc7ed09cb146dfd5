"""Attention kernels: Pallas flash attention + ring attention (context
parallelism over the ICI ring).

Net-new relative to the reference, which has no sequence-parallel support
(SURVEY.md §5 "Long-context"): ring attention moves K/V shards around the
'sequence' mesh axis with lax.ppermute while each device accumulates
blockwise-softmax partials for its local Q shard — compute overlaps the
ICI transfer, HBM never holds the full sequence.

Layouts: q and the output are [batch, num_heads, seq, D], k and v
[batch, num_kv_heads, seq, D]: q.k over Dqk columns, v and the output Dv
wide. The two widths may differ (latent attention: q.k over 192 columns, v
128 wide); the scale defaults to 1 / sqrt(Dqk). num_kv_heads divides
num_heads (grouped-query attention): query head h reads key/value head
h // (num_heads // num_kv_heads), through the kernels' index maps, so k and
v are never repeated in HBM and dK, dV leave at num_kv_heads.

Where a head's output is whole lane tiles wide (`tokens_first`: Dv a
multiple of 128) the kernels write it, and read its cotangent, as [batch,
seq, num_heads * Dv], a head's block placed by the block maps: the layout
the output projection reads, with no transpose on either side
(`flash_attention_native`). `flash_attention` turns that back to [batch,
num_heads, seq, Dv].

A sliding window (`window`: query i sees keys j with 0 <= i - j < window)
runs the same three kernel bodies under names of their own (flash_win_*)
on a grid whose reduced dimension covers only the blocks the band touches.

A selection (`selected`: [batch, seq, seq] int8, 1 where a query may see a
key, the same for every head; ops/indexer.py makes one from the data) runs
them as flash_sel_*: softmax, lse and delta over the selected keys alone.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30

# What the flash forward's two results are called under a jax.checkpoint
# (jax.ad_checkpoint.checkpoint_name): a policy that saves both names keeps
# the kernel from running a second time in the backward pass. FLASH_OUT names
# the output as the kernels wrote it (`tokens_first`), so what a policy keeps
# is what the output projection reads.
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"


# ---------------------------------------------------------------------------
# Reference implementation (small seqs, correctness baseline)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, *, causal: bool = True,
                  sm_scale: Optional[float] = None,
                  window: Optional[int] = None, selected=None):
    """window (causal only): query i sees keys j with 0 <= i - j < window,
    itself and the window - 1 before it. selected ([B, Sq, Sk], not zero
    where query i may see key j, for every head alike): the softmax runs
    over those keys, under the causal mask if there is one."""
    if window is not None and not causal:
        raise ValueError("a window is a band under the causal mask")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if k.shape[1] != q.shape[1]:
        # grouped queries: a key/value head for each of its query heads
        k, v = (jnp.repeat(t, q.shape[1] // k.shape[1], axis=1)
                for t in (k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        qlen, klen = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((qlen, klen), dtype=bool), klen - qlen)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((qlen, klen), dtype=bool),
                              klen - qlen - window)
        logits = jnp.where(mask, logits, NEG_INF)
    if selected is not None:
        logits = jnp.where(selected[:, None] != 0, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Pallas flash attention (single device)
# ---------------------------------------------------------------------------
#
# Three kernels, FlashAttention-2: flash_fwd, flash_bwd_dq, flash_bwd_dkv.
# Each runs on a grid (batch*head, outer block, major block). The last grid
# dimension is the reduction — k blocks for the forward and dQ, q blocks for
# dK/dV (under grouped queries: batch*kv_head first, and the q blocks of each
# of the group's query heads in turn last) — with the accumulators in VMEM
# scratch, zeroed at its first step and written out at its last, so VMEM
# holds blocks and never a whole sequence, and the next major block is
# fetched while this one is computed. A grid
# step costs about as much as a 128 x 128 tile's work, so blocks are large,
# and inside a step a statically unrolled loop walks the outer block in row
# groups, one score tile [group, major block] each: wide tiles, because the
# per-row statistics cost a pass per 128 columns whatever the width.
#
# Under a causal mask a step whose major block lies past the diagonal is
# skipped (and its fetch elided: the index map repeats the last block
# needed), and one wholly under the diagonal runs as straight-line code with
# no mask. On the diagonal, square blocks (outer == major, the rule's
# choice) make the geometry static: row group i meets only the columns up
# to its own, and only the group x group corner the diagonal crosses is
# masked. Blocks that are not square (a test's explicit block_q != block_k)
# take the general path: every tile whole, masked by position, a group the
# mask leaves nothing of skipped.
#
# Dots take their operands in the INPUT dtype (bf16 on the model path) and
# accumulate in fp32: an fp32 x fp32 MXU matmul is several times slower on
# v5e. Softmax statistics, exp, lse and delta are fp32. The forward's
# running max and sum are kept lane-replicated ([rows, 128], all lanes of a
# row equal); dK/dV works on the transposed tile ([keys, queries]), where
# lse and delta are rows as they lie in memory and no matmul needs a
# transposed left operand.

LANES = 128

# (outer, major, group) per kernel: the block of the sequence that is not
# reduced over (q for the forward and dQ, k for dK/dV), the block of the
# reduced sequence a grid step holds, and the rows of the outer block one
# score tile covers.
_FlashBlocks = collections.namedtuple("_FlashBlocks", "fwd dq dkv")


def lane_divisor(n: int, cap: int) -> int:
    """The largest multiple of 128 that divides n and is <= cap; n itself
    below 128 (one block)."""
    if n < LANES:
        return n
    return max(b for b in range(LANES, min(cap, n) + 1, LANES) if n % b == 0)


def tokens_first(v_dim: int) -> bool:
    """Whether the kernels write the heads' outputs (and read their
    cotangent) as [batch, seq, heads * v_dim] rather than [batch * heads,
    seq, v_dim]: where a head's block of v_dim columns is whole lane tiles
    of that array. A narrower head (64) would share a lane tile with its
    neighbour, which a block map cannot address."""
    return v_dim % LANES == 0


def _block_sizes(seq_q: int, seq_k: int, head_dim: int,
                 v_dim: Optional[int] = None) -> _FlashBlocks:
    """Blocks of the three kernels, from the shape alone (PERF.md has the
    sweep on a v5e behind the numbers). head_dim is q's and k's width,
    v_dim v's and the output's (head_dim where it is not given): the wider
    of the two decides."""
    wide = max(head_dim, v_dim or head_dim)
    for seq in (seq_q, seq_k):
        if seq > LANES and seq % LANES:
            raise ValueError(
                f"flash_attention needs sequence lengths up to {LANES} or "
                f"multiples of {LANES}, got {seq}; pad the sequence or call "
                "mha_reference")
    if seq_q != seq_k and min(seq_q, seq_k) < LANES:
        # no square block fits both: one block each
        return _FlashBlocks(fwd=(seq_q, seq_k, seq_q), dq=(seq_q, seq_k, seq_q),
                            dkv=(seq_k, seq_q, seq_k))
    # Square blocks as large as the sequence, up to 2048: a whole row of
    # the score matrix in one grid step where it fits. The backward kernels
    # stop at 1024 where q, k or v is wider than 128 (six blocks x their
    # widths, two buffers each, beside the score tiles: at 256 / 128 wide
    # they take 1.5 x as long with 2048, the forward 0.9 x; PERF.md, PR 31).
    # Row groups of 256 for the forward, whose per-row statistics want a
    # wide tile, and of 128 for the backward kernels, which have none and
    # two score-sized products a tile.
    seq = math.gcd(seq_q, seq_k)
    square = lane_divisor(seq, 2048)
    back = square if wide <= LANES else lane_divisor(seq, 1024)
    return _FlashBlocks(fwd=(square, square, lane_divisor(square, 256)),
                        dq=(back, back, lane_divisor(back, LANES)),
                        dkv=(back, back, lane_divisor(back, LANES)))


def _band_steps(outer: int, major: int, window: int) -> int:
    """Major blocks the band of one outer block touches (major divides
    outer): the outer block's own and those the window reaches into."""
    return outer // major + -(-(window - 1) // major)


def _lanes(x, n: int):
    """A lane-replicated [rows, 128] statistic as [rows, n]."""
    if n <= LANES:
        return x[:, :n]
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _for_tiles(causal, upper, delta, outer, major, group, tile):
    """Run tile(rows, cols, mask) for every score tile of this grid step
    that the causal mask leaves something of: rows a slice of the outer
    block (one group), cols a slice of the major block.

    Row a of the outer block and column b of the major block stand in the
    score matrix `delta` apart: with keys as columns (`upper` false: the
    forward, dQ) the pair is kept iff b <= a + delta, with queries as
    columns (`upper`: dK/dV) iff b >= a + delta. mask is None (keep all),
    "corner" (the tile's last — `upper`: first — group columns are the
    square the diagonal crosses, delta 0 there) or the tile's own delta.
    """
    n = outer // group
    groups = [slice(i * group, (i + 1) * group) for i in range(n)]
    whole = slice(0, major)

    def unmasked():
        for rows in groups:
            tile(rows, whole, None)

    if not causal:
        unmasked()
    elif outer == major:
        # delta is a multiple of the block (flash_attention checks): the
        # step is under the diagonal, on it, or past it
        pl.when(delta <= -outer if upper else delta >= outer)(unmasked)

        @pl.when(delta == 0)
        def _on_the_diagonal():
            for rows in groups:
                cols = (slice(rows.start, major) if upper
                        else slice(0, rows.stop))
                tile(rows, cols, "corner")
    else:
        for rows in groups:
            d = delta + rows.start
            if upper:
                full, some = d + group - 1 <= 0, d <= major - 1
            else:
                full, some = d >= major - 1, d + group - 1 >= 0
            pl.when(full)(functools.partial(tile, rows, whole, None))
            pl.when(some & jnp.logical_not(full))(
                functools.partial(tile, rows, whole, d))


# A score tile's place in the band: query position less key position at the
# tile's first row and first column, and the window.
_Band = collections.namedtuple("_Band", "diff window")


def _for_band(upper, step, first, blocks, outer, major, group, window, tile):
    """_for_tiles under a sliding window: run tile(rows, cols, mask) for
    the score tiles of this grid step that the band 0 <= query - key <
    window leaves something of. The reduced grid dimension walks only the
    major blocks the band of an outer block touches (`_band_steps`), so
    which step this is says statically where the band lies in it: one
    branch a step, each with the column range of every row group cut to
    the band at lane tiles, and the mask (`_Band`) on the tiles an edge
    crosses alone.

    Keys as columns (`upper` false: the forward, dQ): step j holds major
    block first - j, the outer block's last one first, so that the first
    tile a row group meets holds its diagonal and every row has a key
    before a tile in which the window leaves it none. Queries as columns
    (`upper`: dK/dV): step j holds major block first + j. A block before
    the sequence's start or past its end (of `blocks`) is skipped."""
    groups = [slice(i * group, (i + 1) * group)
              for i in range(outer // group)]
    align = LANES if major % LANES == 0 else major
    for j in range(_band_steps(outer, major, window)):
        # query - key at row 0, column 0 of the step's [outer, major]
        at_origin = j * major if upper else (j + 1) * major - outer
        tiles = []
        for rows in groups:
            if upper:       # diff = at_origin + col - row
                lo = rows.start - at_origin
                hi = rows.stop - 1 - at_origin + window - 1
            else:           # diff = at_origin + row - col
                lo = rows.start + at_origin - window + 1
                hi = rows.stop - 1 + at_origin
            lo, hi = max(lo, 0), min(hi, major - 1)
            if lo > hi:
                continue
            cols = slice(lo // align * align,
                         min(-(-(hi + 1) // align) * align, major))
            diff = at_origin + (cols.start - rows.start if upper
                                else rows.start - cols.start)
            tiles.append((rows, cols, _Band(diff, window)))
        block = first + j if upper else first - j

        def run(tiles=tiles):
            for t in tiles:
                tile(*t)
        if tiles:
            pl.when((step == j) & (block >= 0) & (block < blocks))(run)


def _band_mask(s, band, upper):
    """s [rows, width] with NEG_INF outside the band, masked a lane tile of
    columns at a time and only where an edge crosses: the causal edge
    (query - key >= 0), the window's (query - key < window), or both."""
    rows, width = s.shape
    chunk = LANES if width % LANES == 0 else width
    pieces = []
    for x0 in range(0, width, chunk):
        x1 = x0 + chunk
        if upper:
            least, most = band.diff + x0 - (rows - 1), band.diff + x1 - 1
        else:
            least, most = band.diff - (x1 - 1), band.diff + rows - 1 - x0
        edges = (least < 0, most >= band.window)
        if pieces and pieces[-1][0] == edges:
            pieces[-1][2] = x1
        else:
            pieces.append([edges, x0, x1])
    out = []
    for (causal_edge, window_edge), x0, x1 in pieces:
        part = s[:, x0:x1]
        if causal_edge or window_edge:
            col = jax.lax.broadcasted_iota(jnp.int32, part.shape, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, part.shape, 0)
            diff = band.diff + x0 + col - row if upper \
                else band.diff - x0 + row - col
            keep = None
            if causal_edge:
                keep = diff >= 0
            if window_edge:
                inside = diff < band.window
                keep = inside if keep is None else keep & inside
            part = jnp.where(keep, part, NEG_INF)
        out.append(part)
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _mask(s, mask, upper):
    """(s with NEG_INF where the causal mask says so, what was kept or None
    where no row can have lost every column)."""
    def kept(shape, delta):
        diff = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                - jax.lax.broadcasted_iota(jnp.int32, shape, 0))
        return diff >= delta if upper else diff <= delta

    if mask is None:
        return s, None
    if isinstance(mask, _Band):
        # no row can have lost every column it has MET: _for_band's order
        return _band_mask(s, mask, upper), None
    if isinstance(mask, str):
        group, width = s.shape
        corner = s[:, :group] if upper else s[:, width - group:]
        corner = jnp.where(kept(corner.shape, 0), corner, NEG_INF)
        if width == group:
            return corner, None
        return jnp.concatenate([corner, s[:, group:]] if upper
                               else [s[:, :width - group], corner],
                               axis=1), None
    keep = kept(s.shape, mask)
    return jnp.where(keep, s, NEG_INF), keep


def _select(s, sel_ref, rows, cols):
    """(s with NEG_INF where the selection's tile says so, what was kept):
    the tile of a [1, outer, major] int8 block that lies like s."""
    keep = sel_ref[0, rows, cols].astype(jnp.int32) != 0
    return jnp.where(keep, s, NEG_INF), keep


def _walk(upper, band, causal, delta, step, at, outer, major, group, tile):
    """The tiles of one grid step: under the causal mask alone (or none),
    or, with band = (window, major blocks in the sequence), in the band of
    outer block `at`, whose first step holds its own last major block
    (keys as columns) or its own first (`upper`)."""
    if band is None:
        _for_tiles(causal, upper, delta, outer, major, group, tile)
    else:
        first = at * (outer // major) if upper \
            else (at + 1) * (outer // major) - 1
        _for_band(upper, step, first, band[1], outer, major, group, band[0],
                  tile)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *, sm_scale, causal, group,
                      offset, band=None, sel_ref=None):
    """Grid (batch*head, q block, k major block): online softmax over the k
    blocks of one q block (with `band`, over those its window touches:
    `_for_band`).

    offset = seq_k - seq_q: masking is bottom-right aligned, matching
    mha_reference (query i attends keys <= i + offset). Also emits the
    per-row logsumexp (lse) the backward kernels consume; a row with no key
    to attend gives zeros and lse = NEG_INF. With `sel_ref` (a selection's
    [1, bq, bk] block, a subset of the causal pairs) the walk is the causal
    one and a tile's mask is the selection's: a row may meet its first key
    in any tile, so what is not kept is 0 by decree there too.
    """
    bq, d = o_ref.shape[1:]                 # d: v's and the output's width
    bk = k_ref.shape[1]
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(rows, cols, mask):
        # scaling q ([group, d]) is the scores' scaling ([group, width])
        # done on the small side
        q = q_ref[0, rows, :] * sm_scale
        k, v = k_ref[0, cols, :], v_ref[0, cols, :]
        s = _dot(q, k, (1, 1))                            # [group, width]
        if sel_ref is None:
            s, keep = _mask(s, mask, False)
        else:
            s, keep = _select(s, sel_ref, rows, cols)
        m_prev, l_prev = m_ref[rows, :], l_ref[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, s.shape[1]))
        if keep is not None and (offset < 0 or sel_ref is not None):
            # a row with no key at all (seq_q > seq_k) has m = NEG_INF and
            # exp(NEG_INF - NEG_INF) = 1: its p is 0 by decree
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[rows, :] = m_new
        l_ref[rows, :] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[rows, :] = (acc_ref[rows, :] * _lanes(alpha, d)
                            + _dot(p.astype(v.dtype), v, (1, 0)))

    _walk(False, band, causal, qi * bq + offset - ki * bk, ki, qi, bq, bk,
          group, tile)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / _lanes(l, d)).astype(o_ref.dtype)
        lse = m_ref[...] + jnp.log(l)                     # [bq, 128]
        if bq % LANES == 0:
            # all lanes of a row are equal, so a row of the transpose is
            # the column as lse lies in memory; turning a [bq] reduction
            # result into that row cost a fifth of the kernel
            lse_ref[0] = lse.T[:1, :]
        else:
            lse_ref[0, 0] = jnp.max(lse, axis=1)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, sm_scale, causal, group,
                         offset, band=None, sel_ref=None):
    """Grid (batch*head, q block, k major block): dQ of one q block.

    p = exp(s - lse); dS = p * (dO·Vᵀ - delta); dQ = scale · dS·K
    (FlashAttention-2 backward).
    """
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(rows, cols, mask):
        q, do = q_ref[0, rows, :] * sm_scale, do_ref[0, rows, :]
        k, v = k_ref[0, cols, :], v_ref[0, cols, :]
        # lse and delta lie in memory as rows; here they are columns
        lse, delta = lse_ref[0, 0, rows][:, None], delta_ref[0, 0, rows][:, None]
        s = _dot(q, k, (1, 1))                            # [group, width]
        if sel_ref is None:
            s, keep = _mask(s, mask, False)
        else:
            # every row has a key: exp(NEG_INF - lse) is 0
            s, keep = _select(s, sel_ref, rows, cols)[0], None
        p = jnp.exp(s - lse)
        if keep is not None and offset < 0:
            p = jnp.where(keep, p, 0.0)     # lse = NEG_INF: see the forward
        dp = _dot(do, v, (1, 1))                          # [group, width]
        ds = p * (dp - delta)
        acc_ref[rows, :] += _dot(ds.astype(k.dtype), k, (1, 0))

    _walk(False, band, causal, qi * bq + offset - ki * bk, ki, qi, bq, bk,
          group, tile)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = (acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, sm_scale,
                          causal, group, offset, q_blocks=None, band=None,
                          sel_ref=None):
    """Grid (batch*kv_head, k block, q major block): dK and dV of one k
    block, on transposed tiles [keys, queries].

    dV = Pᵀ·dO; dK = scale · dSᵀ·Q. q_blocks: None where a key/value head
    has one query head; else the q blocks of one query head, and the last
    grid dimension walks them once for each query head of the group, all
    into the same accumulators. With `band` the q blocks of one query head
    are the q_blocks steps of the band (`_for_band`), not all of them.
    `sel_ref`: a [1, bk, bq] block of the selection TRANSPOSED (keys first).
    """
    bk = k_ref.shape[1]
    bq = q_ref.shape[1]
    ki, step = pl.program_id(1), pl.program_id(2)
    qi = step if q_blocks is None else step % q_blocks

    @pl.when(step == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def tile(rows, cols, mask):
        k, v = k_ref[0, rows, :] * sm_scale, v_ref[0, rows, :]
        q, do = q_ref[0, cols, :], do_ref[0, cols, :]
        lse, delta = lse_ref[0, :, cols], delta_ref[0, :, cols]  # [1, width]
        st = _dot(k, q, (1, 1))                           # [group, width]
        if sel_ref is None:
            st, keep = _mask(st, mask, True)
        else:
            st, keep = _select(st, sel_ref, rows, cols)[0], None
        pt = jnp.exp(st - lse)
        if keep is not None and offset < 0:
            pt = jnp.where(keep, pt, 0.0)   # lse = NEG_INF: see the forward
        dv_acc_ref[rows, :] += _dot(pt.astype(do.dtype), do, (1, 0))
        dpt = _dot(v, do, (1, 1))                         # [group, width]
        dst = pt * (dpt - delta)
        dk_acc_ref[rows, :] += _dot(dst.astype(q.dtype), q, (1, 0))

    _walk(True, band, causal, ki * bk - (qi * bq + offset), qi, ki, bk, bq,
          group, tile)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = (dk_acc_ref[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_sel_fwd_kernel(q_ref, k_ref, v_ref, sel_ref, *refs, **static):
    _flash_fwd_kernel(q_ref, k_ref, v_ref, *refs, sel_ref=sel_ref, **static)


def _flash_sel_bwd_kernel(body, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, sel_ref, *refs, **static):
    """body: _flash_bwd_dq_kernel | _flash_bwd_dkv_kernel, with the
    selection's block (for dK/dV the transposed selection's) as the last
    operand."""
    body(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
         sel_ref=sel_ref, **static)


_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
# Heads of 128 and wider (q, k or v): blocks of 2048 x 128 beside the score
# tiles need 16.7 MB, over the 16 MB of VMEM a kernel is given unless it asks.
_GRID_SEMANTICS_WIDE = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=48 << 20)


def _compiler_params(head_dim: int, seq: int, block: int):
    """Narrower heads fit the default 16 MB in one block a row (the dense
    cells: 1024 and 2048 positions); several blocks of 2048 a row do not
    (16.9 MB at a head of 64 and 8192 positions: the straight-line and the
    masked branch of a step both hold their tiles)."""
    if head_dim < LANES and (seq == block or block < 2048):
        return _GRID_SEMANTICS
    return _GRID_SEMANTICS_WIDE


def _kv_index(causal, offset, bq, bkm, num_k, rep):
    """Index map of a K/V major block on a (b, q block, k block) grid, b
    over batch*head and the block of key/value head b // rep (rep query
    heads a key/value head). Past the diagonal it repeats the last block
    the q block needs: Pallas does not fetch a block whose index did not
    change."""
    def head(b):
        return b if rep == 1 else b // rep

    if not causal:
        return lambda b, i, j: (head(b), j, 0)

    def index(b, i, j):
        last = jnp.maximum((i + 1) * bq - 1 + offset, 0) // bkm
        return (head(b), jnp.minimum(j, jnp.minimum(last, num_k - 1)), 0)
    return index


def _band_index(outer, major, blocks, rep):
    """_kv_index under a window: step j of the reduced dimension holds the
    major block `_for_band` says (keys: the outer block's last one less
    j), held inside the sequence so that a skipped step fetches nothing
    new."""
    def head(b):
        return b if rep == 1 else b // rep

    def index(b, i, j):
        block = (i + 1) * (outer // major) - 1 - j
        return (head(b), jnp.clip(block, 0, blocks - 1), 0)
    return index


def _query_heads_a_kv_head(q, k, v) -> int:
    heads, kv_heads = q.shape[1], k.shape[1]
    if v.shape[1] != kv_heads or heads % kv_heads:
        raise ValueError(
            f"flash_attention needs k and v of one head count that divides "
            f"q's, got q {heads}, k {kv_heads}, v {v.shape[1]}")
    return heads // kv_heads


def head_columns(width: int, dim: int):
    """[width, width // dim] float32, 1 where a column lies in a head (of
    dim columns each): a product with it sums each head's columns, one with
    its transpose hands a number a head to the head's columns, and both
    leave the columns where they are. A [.., heads, dim] view does not: the
    chip tiles 8 rows x 128 lanes, so a head of 64 lies on 128 lanes, and
    at 128 the view's tile is 8 heads of a token where the columns' is 8
    tokens of a head; either way the compiler copies the tensor into the
    other layout and back (PERF.md, PRs 33 and 48)."""
    return (jnp.arange(width)[:, None] // dim
            == jnp.arange(width // dim)[None, :]).astype(jnp.float32)


def _head_dots(a, b, heads: int):
    """a, b [B, S, heads * D] -> each head's sum of a * b over its D
    columns, [B, S, heads] float32, the columns left where they are
    (`head_columns`). Three bf16 passes carry 16 bits of a product, every
    bit of one of two bf16 numbers; float32 factors take six."""
    exact = (jax.lax.Precision.HIGH if a.dtype.itemsize <= 2
             else jax.lax.Precision.HIGHEST)
    return jnp.einsum("bsw,wh->bsh",
                      a.astype(jnp.float32) * b.astype(jnp.float32),
                      head_columns(a.shape[-1], a.shape[-1] // heads),
                      precision=exact)


def _kernel_name(kernel: str, window, selected) -> str:
    """flash_<kernel>, flash_win_<kernel> under a window, flash_sel_<kernel>
    under a selection: a trace row, and a roofline, is of one shape."""
    kind = "" if window is None else "win_"
    return f"flash_{kind if selected is None else 'sel_'}{kernel}"


def _flash_forward(q, k, v, causal, sm_scale, blocks, interpret, window=None,
                   selected=None):
    """-> (the heads' outputs, [B, S, H * Dv] where `tokens_first(Dv)` and
    [B, H, S, Dv] where not; lse [B * H, 1, S])."""
    batch, heads, seq_q, d = q.shape
    seq_k, dv = k.shape[2], v.shape[3]
    bh = batch * heads
    rep = _query_heads_a_kv_head(q, k, v)
    bq, bkm, group = blocks.fwd
    offset = seq_k - seq_q
    kernel = functools.partial(_flash_fwd_kernel, sm_scale=sm_scale,
                               causal=causal, group=group, offset=offset)
    steps, kv_index = seq_k // bkm, _kv_index(causal, offset, bq, bkm,
                                              seq_k // bkm, rep)
    if window is not None:
        kernel = functools.partial(kernel, band=(window, seq_k // bkm))
        steps, kv_index = (_band_steps(bq, bkm, window),
                           _band_index(bq, bkm, seq_k // bkm, rep))
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bkm, d), kv_index),
        pl.BlockSpec((1, bkm, dv), kv_index),
    ]
    operands = (q.reshape(bh, seq_q, d), k.reshape(bh // rep, seq_k, d),
                v.reshape(bh // rep, seq_k, dv))
    if selected is not None:
        # one tile for all the heads of a batch row, the key block k's
        kernel = functools.partial(_flash_sel_fwd_kernel, **kernel.keywords)
        in_specs.append(pl.BlockSpec(
            (1, bq, bkm),
            lambda b, i, j: (b // heads, i, kv_index(b, i, j)[1])))
        operands += (selected,)
    if tokens_first(dv):
        # grid row b's [bq, dv] block is head b % heads' columns of batch
        # row b // heads: the body writes o_ref[0] either way
        out_shape = (batch, seq_q, heads * dv)

        def out_index(b, i, j):
            return b // heads, i, b % heads
    else:
        out_shape = (bh, seq_q, dv)

        def out_index(b, i, j):
            return b, i, 0
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, seq_q // bq, steps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, dv), out_index),
            # lse rides as [bh, 1, seq_q]: TPU Pallas needs the last two
            # block dims divisible by (8, 128) or equal to the array dims.
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(out_shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=_compiler_params(max(d, dv), seq_k, bkm),
        interpret=interpret,
        name=_kernel_name("fwd", window, selected),
    )(*operands)
    return (out if tokens_first(dv)
            else out.reshape(batch, heads, seq_q, dv)), lse


def _flash_backward(q, k, v, out, lse, g, causal, sm_scale, blocks,
                    interpret, window=None, selected=None):
    """out and its cotangent g in the layout the forward wrote
    (`tokens_first`)."""
    batch, heads, seq_q, d = q.shape
    seq_k, dv = k.shape[2], v.shape[3]
    bh = batch * heads
    rep = _query_heads_a_kv_head(q, k, v)
    qr = q.reshape(bh, seq_q, d)
    kr = k.reshape(bh // rep, seq_k, d)
    vr = v.reshape(bh // rep, seq_k, dv)
    # delta_i = rowsum(dO_i * O_i): cheap elementwise, fused by XLA.
    if tokens_first(dv):
        # dO stays where the output projection's backward wrote it, a
        # head's block found by the block maps (`do_index`); only delta
        # [B, S, H], float32, is turned to the rows the kernels read
        gr = g
        delta = _head_dots(g, out, heads).transpose(0, 2, 1).reshape(
            bh, 1, seq_q)

        def do_index(head, block):
            return head // heads, block, head % heads
    else:
        gr = g.reshape(bh, seq_q, dv)
        delta = jnp.sum(gr.astype(jnp.float32)
                        * out.reshape(bh, seq_q, dv).astype(jnp.float32),
                        axis=-1).reshape(bh, 1, seq_q)

        def do_index(head, block):
            return head, block, 0
    offset = seq_k - seq_q
    params = _compiler_params(max(d, dv), seq_k, blocks.dq[1])

    # q, k and dq, dk move in blocks d wide; v, dO and dv in blocks dv wide
    bq, bkm, group = blocks.dq
    steps, kv_index = seq_k // bkm, _kv_index(causal, offset, bq, bkm,
                                              seq_k // bkm, rep)
    if window is not None:
        steps, kv_index = (_band_steps(bq, bkm, window),
                           _band_index(bq, bkm, seq_k // bkm, rep))

    def band(blocks_of_major):
        """The kernels' `band`, where there is a window."""
        return {} if window is None else {"band": (window, blocks_of_major)}

    def q_spec(width):
        return pl.BlockSpec((1, bq, width), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
    kernel = functools.partial(_flash_bwd_dq_kernel, sm_scale=sm_scale,
                               causal=causal, group=group, offset=offset,
                               **band(seq_k // bkm))
    in_specs = [q_spec(d), pl.BlockSpec((1, bkm, d), kv_index),
                pl.BlockSpec((1, bkm, dv), kv_index),
                pl.BlockSpec((1, bq, dv), lambda b, i, j: do_index(b, i)),
                row_spec, row_spec]
    operands = (qr, kr, vr, gr, lse, delta)
    if selected is not None:
        kernel = functools.partial(_flash_sel_bwd_kernel,
                                   _flash_bwd_dq_kernel, **kernel.keywords)
        in_specs.append(pl.BlockSpec(
            (1, bq, bkm),
            lambda b, i, j: (b // heads, i, kv_index(b, i, j)[1])))
        operands += (selected,)
    dq = pl.pallas_call(
        kernel,
        grid=(bh, seq_q // bq, steps),
        in_specs=in_specs,
        out_specs=q_spec(d),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name=_kernel_name("bwd_dq", window, selected),
    )(*operands)

    # dK, dV: a grid row a key/value head. Its rep query heads' q blocks
    # follow one another on the last grid dimension (step -> query head
    # step // num_q, q block step % num_q), summed in the same scratch, so
    # dK and dV leave the kernel at the key/value heads' count.
    bk, bqm, group = blocks.dkv
    num_q = seq_q // bqm
    # the steps of one query head: every q block, or the band's
    steps = num_q if window is None else _band_steps(bk, bqm, window)

    def q_head(b, j):
        return b if rep == 1 else b * rep + j // steps

    def q_of(j):
        return j if rep == 1 else j % steps
    if window is not None:
        def q_block(i, j):
            return jnp.minimum(i * (bk // bqm) + q_of(j), num_q - 1)
    elif causal:
        # q major blocks above the diagonal are skipped, and not fetched:
        # the index repeats the first one this k block needs
        def q_block(i, j):
            first = jnp.maximum(i * bk - offset, 0) // bqm
            return jnp.maximum(q_of(j), jnp.minimum(first, num_q - 1))
    else:
        def q_block(i, j):
            return q_of(j)
    def q_spec(width):
        return pl.BlockSpec((1, bqm, width),
                            lambda b, i, j: (q_head(b, j), q_block(i, j), 0))

    def kv_spec(width):
        return pl.BlockSpec((1, bk, width), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec(
        (1, 1, bqm), lambda b, i, j: (q_head(b, j), 0, q_block(i, j)))
    walk = {} if rep == 1 else {"q_blocks": steps}
    kernel = functools.partial(_flash_bwd_dkv_kernel, sm_scale=sm_scale,
                               causal=causal, group=group, offset=offset,
                               **walk, **band(num_q))
    do_spec = pl.BlockSpec(
        (1, bqm, dv),
        lambda b, i, j: do_index(q_head(b, j), q_block(i, j)))
    in_specs = [q_spec(d), kv_spec(d), kv_spec(dv), do_spec,
                row_spec, row_spec]
    if selected is not None:
        # dK/dV's tiles are [keys, queries]: the selection transposed, one
        # XLA pass over a byte a pair, so that no tile is turned in VMEM
        kernel = functools.partial(_flash_sel_bwd_kernel,
                                   _flash_bwd_dkv_kernel, **kernel.keywords)
        in_specs.append(pl.BlockSpec(
            (1, bk, bqm),
            lambda b, i, j: (b // (heads // rep), i, q_block(i, j))))
        operands = operands[:-1] + (jnp.swapaxes(selected, 1, 2),)
    dk, dvalue = pl.pallas_call(
        kernel,
        grid=(bh // rep, seq_k // bk, rep * steps),
        in_specs=in_specs,
        out_specs=[kv_spec(d), kv_spec(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((bh // rep, seq_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh // rep, seq_k, dv), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name=_kernel_name("bwd_dkv", window, selected),
    )(*operands)
    return (dq.reshape(batch, heads, seq_q, d),
            dk.reshape(batch, heads // rep, seq_k, d),
            dvalue.reshape(batch, heads // rep, seq_k, dv))


@functools.lru_cache(maxsize=None)
def _make_flash_fn(causal, sm_scale, blocks, interpret, window=None):
    """Pallas forward + Pallas backward under jax.custom_vjp.

    The backward is the flash-attention recompute form (dQ kernel + dK/dV
    kernel over saved lse/delta) — O(seq) memory, no S² logits tensor in
    HBM.
    """

    @jax.custom_vjp
    def f(q, k, v):
        out, _ = _flash_forward(q, k, v, causal, sm_scale, blocks, interpret,
                                window)
        return out

    def fwd(q, k, v):
        out, lse = _flash_forward(q, k, v, causal, sm_scale, blocks,
                                  interpret, window)
        # the NAMED values are both the primal output and the residuals: a
        # remat policy that saves the two names then has all the forward
        # kernel made (q, k, v come from the rematted projections), and the
        # kernel is dead code in the recompute pass. Naming the output
        # alone, outside, would leave lse to a second run of the kernel.
        # Outside a jax.checkpoint the names are identities.
        out = checkpoint_name(out, FLASH_OUT)
        lse = checkpoint_name(lse, FLASH_LSE)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        q, k, v, out, lse = res
        return _flash_backward(q, k, v, out, lse, g, causal, sm_scale,
                               blocks, interpret, window)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _make_flash_sel_fn(sm_scale, blocks, interpret):
    """_make_flash_fn for causal self-attention under a selection, the
    fourth operand ([B, S, S] int8, no gradient): the same two names on the
    forward's results, so a policy that keeps them keeps this kernel from a
    second run too. -> (out, lse [B, H, S]: each head's log-sum-exp over the
    query's selected keys, the one the backward kernels read, handed out
    detached for the indexer's KL, ops/indexer.py:kl)."""

    def heads_apart(lse, q):
        return lse.reshape(q.shape[:3])

    @jax.custom_vjp
    def f(q, k, v, selected):
        out, lse = _flash_forward(q, k, v, True, sm_scale, blocks, interpret,
                                  selected=selected)
        return out, heads_apart(lse, q)

    def fwd(q, k, v, selected):
        out, lse = _flash_forward(q, k, v, True, sm_scale, blocks, interpret,
                                  selected=selected)
        out = checkpoint_name(out, FLASH_OUT)
        lse = checkpoint_name(lse, FLASH_LSE)
        return (out, heads_apart(lse, q)), (q, k, v, out, lse, selected)

    def bwd(res, g):
        q, k, v, out, lse, selected = res
        return _flash_backward(q, k, v, out, lse, g[0], True, sm_scale,
                               blocks, interpret, selected=selected) + (None,)

    f.defvjp(fwd, bwd)
    return f


def qk_padding(d: int) -> int:
    """Zero columns to append to q and k of width d before the kernels."""
    return -d % LANES if d > LANES else 0


def _default_interpret() -> bool:
    """Mosaic-compiled on a TPU backend, interpreted (the Pallas software
    emulator, what the CPU tests run) on every other."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, **options):
    """`flash_attention_native` with the heads' outputs [B, H, S, Dv] at
    every width, for a caller that wants them by head (the tests, the
    oracle's layout): where the kernels wrote them tokens first, turned
    back, a pass of XLA's that the model's path (models/gpt.py) never
    runs."""
    result = flash_attention_native(q, k, v, **options)
    if not tokens_first(v.shape[-1]):
        return result

    def by_head(out):
        return out.reshape(*out.shape[:2], q.shape[1], -1).transpose(
            0, 2, 1, 3)
    if options.get("with_lse"):
        return by_head(result[0]), result[1]
    return by_head(result)


def flash_attention_native(q, k, v, *, causal: bool = True,
                           sm_scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           window: Optional[int] = None, selected=None,
                           with_lse: bool = False):
    """Fused attention on the MXU; O(seq) memory via online softmax. q
    [B, H, S, D], k and v [B, Hkv, S, D | Dv] -> the heads' outputs in the
    layout the kernels write, which is the one their backward reads the
    cotangent in: [B, S, H * Dv] where a head is whole lane tiles wide
    (`tokens_first(Dv)`: its [block, Dv] tile is placed among a token's
    H * Dv columns by the block maps, so the output projection reads it as
    it is and no transpose runs forward, recomputed or backward), and
    [B, H, S, Dv] at any other width (64: the kernels write [B * H, S, Dv]
    and the caller turns it). The shape decides, nothing else. A caller
    keeps the columns as they are: a [.., H, Dv] view is another layout on
    the chip (`head_columns`).

    selected (causal self-attention only, no window): [B, S, S] int8, 1
    where query i may see key j and 0 elsewhere, a subset of the causal
    pairs, at least one key a query, the same for every head of a batch
    row (ops/indexer.py:select_and_kl makes it). The kernels run as
    flash_sel_fwd / flash_sel_bwd_dq / flash_sel_bwd_dkv with the blocks of
    the shape and the causal walk: a [block, block] tile of the selection
    comes in beside a tile's K and V (the backward's dK/dV kernel reads the
    transposed selection, its tiles lying keys first), every tile at or
    under the diagonal is computed and masked by it, and softmax, lse and
    delta run over the selected keys alone. One byte a pair was chosen over
    a threshold a row with the indexer's scores recomputed in the kernel:
    the tile is 4 MB a grid step beside 2 MB of q, k, v and o, fetched
    while the step before computes, and the kernels need nothing of the
    indexer. No table of empty tiles is kept: a tile of 2048 x 2048 pairs
    under the diagonal has none selected only if 2048 queries in a row
    choose none of 2048 keys in a row. with_lse (under a selection only):
    -> (out as above, lse [B, H, S] float32, each head's log-sum-exp over
    the query's selected keys as the backward kernels read it, without a
    gradient).

    window (causal self-attention only): query i sees keys j with 0 <= i -
    j < window. The kernels then run as flash_win_fwd / flash_win_bwd_dq /
    flash_win_bwd_dkv, with the blocks of the shape (`_block_sizes`: the
    window does not move them) and a grid whose reduced dimension covers
    the blocks the band touches alone (`_band_steps`). A window
    that reaches the sequence's start from its end is the causal mask, and
    runs as that.

    The kernels' blocks follow from the shape (`_block_sizes`). A sequence
    length is below 128 (one block) or a multiple of 128: pad upstream; a
    ragged shape raises instead of silently running another kernel.
    block_q / block_k are for tests that want several blocks of a short
    sequence: every kernel then tiles by exactly these.

    q and k of a width over 128 that is not whole lane tiles (192) are
    padded with zero columns to the next tile (256: `qk_padding`): no score
    changes, and the kernels run a contraction of 256 faster than one of
    192, forward by a fifth (PERF.md, PR 31). XLA runs that pad as a pass
    of its own; a caller that assembles q and k itself appends the zero
    columns there and passes the true width's sm_scale.
    """
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    pad = qk_padding(d)
    if pad:
        widths = ((0, 0),) * 3 + ((0, pad),)
        q, k = jnp.pad(q, widths), jnp.pad(k, widths)
    seq_q, seq_k = q.shape[2], k.shape[2]
    if window is not None:
        if not causal or seq_q != seq_k or window < 1:
            raise ValueError(
                f"window={window} needs causal self-attention (seq_q == "
                f"seq_k, got {seq_q} and {seq_k}) and at least the query's "
                "own position")
        if window >= seq_k:
            window = None
    if selected is not None:
        if (not causal or window is not None or pad
                or selected.shape != (q.shape[0], seq_q, seq_k)
                or seq_q != seq_k):
            raise ValueError(
                "selected needs causal self-attention without a window, q "
                f"and k of whole lane tiles, and a selection [B, S, S]: got "
                f"causal={causal}, window={window}, q {q.shape}, k "
                f"{k.shape}, selected {selected.shape}")
    if interpret is None:
        interpret = _default_interpret()
    if block_q is None and block_k is None:
        # the window does not move them: square blocks of 2048 read as
        # fast as any at windows of 128, 512 and 2048 (PERF.md, PR 37)
        blocks = _block_sizes(seq_q, seq_k, q.shape[-1], v.shape[-1])
    else:
        bq = min(block_q or seq_q, seq_q)
        bk = min(block_k or seq_k, seq_k)
        if seq_q % bq or seq_k % bk:
            raise ValueError(
                f"flash_attention needs seq_q={seq_q} and seq_k={seq_k} to "
                f"be multiples of block_q={bq} and block_k={bk}; pad the "
                "sequence or call mha_reference")
        if window is not None and bq != bk:
            raise ValueError(f"under a window the forward's and dK/dV's "
                             f"major block each divide the outer one: "
                             f"block_q={bq} == block_k={bk}")
        blocks = _FlashBlocks(fwd=(bq, bk, bq), dq=(bq, bk, bq),
                              dkv=(bk, bq, bk))
    if selected is not None:
        out, lse = _make_flash_sel_fn(float(sm_scale), blocks, interpret)(
            q, k, v, selected.astype(jnp.int8))
        return (out, lse) if with_lse else out
    if with_lse:
        raise ValueError("with_lse is the selected kernels' alone")
    fn = _make_flash_fn(causal, float(sm_scale), blocks, interpret, window)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Ring attention (context parallelism over the 'sequence' mesh axis)
# ---------------------------------------------------------------------------

def _blockwise_partials(q, k, v, q_offset, k_offset, causal, sm_scale):
    """Unnormalized blockwise attention with running-max stats.

    Returns (acc, m, l) partials combinable across K/V chunks.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        qlen, klen = q.shape[2], k.shape[2]
        q_pos = q_offset + jnp.arange(qlen)[:, None]
        k_pos = k_offset + jnp.arange(klen)[None, :]
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [b,h,q]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return acc, m, l


def _combine(acc1, m1, l1, acc2, m2, l2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return (acc1 * a1[..., None] + acc2 * a2[..., None],
            m, l1 * a1 + l2 * a2)


def ring_attention(q, k, v, *, mesh, axis_name: str = "sequence",
                   causal: bool = True, sm_scale: Optional[float] = None):
    """Attention over a sequence sharded across `axis_name`.

    Call under the mesh with q/k/v sharded [B, H, S/n, D] on the sequence
    axis. Each of the n ring steps overlaps the blockwise compute with a
    `ppermute` of the K/V shard to the next neighbor — the XLA schedule
    hides ICI latency behind the einsums (ring attention, PAPERS.md).
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = mesh.shape[axis_name]

    def local_fn(q_loc, k_loc, v_loc):
        idx = jax.lax.axis_index(axis_name)
        chunk = q_loc.shape[2]
        q_offset = idx * chunk

        def step(i, carry):
            acc, m, l, k_cur, v_cur = carry
            # The shard currently held originated at ring position idx - i.
            src = (idx - i) % n
            k_offset = src * chunk
            a2, m2, l2 = _blockwise_partials(
                q_loc, k_cur, v_cur, q_offset, k_offset, causal, sm_scale)
            acc, m, l = _combine(acc, m, l, a2, m2, l2)
            # Rotate K/V around the ring (skip after the last step).
            k_nxt, v_nxt = jax.lax.cond(
                i < n - 1,
                lambda kv: _rotate(kv, axis_name, n),
                lambda kv: kv,
                (k_cur, v_cur))
            return acc, m, l, k_nxt, v_nxt

        b, h, s = q_loc.shape[:3]
        d = v_loc.shape[3]
        # Mark the accumulators device-varying so the loop carry's vma type
        # is stable across iterations (jax shard_map type system).
        acc0, m0, l0 = jax.lax.pvary(
            (jnp.zeros((b, h, s, d), jnp.float32),
             jnp.full((b, h, s), NEG_INF, jnp.float32),
             jnp.zeros((b, h, s), jnp.float32)),
            (axis_name,))
        init = (acc0, m0, l0, k_loc, v_loc)
        acc, m, l, _, _ = jax.lax.fori_loop(0, n, step, init)
        l = jnp.where(l == 0.0, 1.0, l)
        return (acc / l[..., None]).astype(q_loc.dtype)

    spec = P(None, None, axis_name, None)
    return shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def _rotate(kv, axis_name, n):
    perm = [(i, (i + 1) % n) for i in range(n)]
    return jax.tree_util.tree_map(
        lambda x: jax.lax.ppermute(x, axis_name, perm), kv)
