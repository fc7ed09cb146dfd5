"""The boundary between the attention projections and the flash kernels,
one pass a tensor and direction.

A projection leaves its matmul as [batch, seq, heads * head_dim], lane
dense; the kernels of ops/attention.py take [batch, heads, seq, head_dim].
`rope_split` writes the second from the first, rotating on the way (rotary
position embeddings, "rotate-half") where it is handed the table; its
transpose `rope_merge` is its backward. Two Pallas kernels, `rope_split`
and `rope_merge` (names in util/profiling.KERNELS).

The head split is the output BlockSpec's index map, not a transpose, and
the rotation is done in registers on whole 128-lane tiles (two heads of 64
or one of 128; a wider head takes a multiple):

    out = x * cos + select(first half of its head,
                           roll(x, -D/2), roll(x, +D/2)) * sin

with cos = [cos, cos] and sin = [-sin, sin] a head (`rope_table`): no
slice, no concatenate and no D/2-wide array anywhere. Products and the sum
are float32, rounded once to the input's dtype: bit for bit what the jnp
formulation (`_split_reference`, models/gpt.py:_rope) gives. The backward
is the same body with sin negated.

Blocks follow from the shape (`_rope_blocks`); a shape that does not tile
(a head width that neither divides nor is a multiple of 128, a head count
that does not fill whole lane tiles, a ragged sequence) takes the jnp
formulation.

A latent block's head of q.k is two parts, 128 columns that carry no
position and 64 that are rotated: 192 fills no whole lane tiles and
`rope_split` refuses it. `latent_split` takes the head by its parts, with
four kernels of the same kind (`latent_q_split`, `latent_kv_split` and their
transposes `latent_q_merge`, `latent_kv_merge`: the second half of this
file) wherever the parts fill whole or half lane tiles; anything else (heads
of 32 + 16, `attention="reference"`) keeps models/gpt.py's jnp assembly
(`_rope_tail`, `_latent_heads`).
"""

from __future__ import annotations

import collections
import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.ops.attention import LANES

# (rows, cols) of the [seq, heads * head_dim] plane one grid step moves.
_RopeBlocks = collections.namedtuple("_RopeBlocks", "rows cols")

# Elements of the plane a grid step moves: 1 MB of bf16 in, twice that out
# where a 64-wide head owns a 128-lane tile, the table beside them, two
# buffers each: 8 MB of VMEM at most (1024 x 2048 does not fit the 16 MB a
# kernel is given). On a v5e every block of at least 256 rows x a whole row
# of lane tiles runs within 3 % of the best; 256 rows x one lane tile a
# step takes 2.2 x as long (PERF.md, PR 28).
_STEP_ELEMENTS = 512 * 1024


def _lane_tile(head_dim: int) -> Optional[int]:
    """Lanes the rotation works on at once: whole heads in whole 128-lane
    tiles. None for a head width that fits neither way."""
    if head_dim % 2 or (LANES % head_dim and head_dim % LANES):
        return None
    return max(head_dim, LANES)


def _largest_divisor(n: int, unit: int, cap: int) -> int:
    """The largest multiple of unit that divides n and is <= cap; unit
    itself under a smaller cap (unit divides n)."""
    return max(d for d in range(unit, max(min(cap, n), unit) + 1, unit)
               if n % d == 0)


def _rope_blocks(seq: int, heads: int, head_dim: int,
                 itemsize: int) -> Optional[_RopeBlocks]:
    """Blocks of both kernels, from the shape alone; None for a shape the
    kernels do not tile (the caller then takes the jnp formulation)."""
    tile = _lane_tile(head_dim)
    sublanes = 32 // itemsize          # rows of one register: 8 fp32, 16 bf16
    if (tile is None or (heads * head_dim) % tile or seq % sublanes
            or itemsize not in (2, 4)):
        return None
    # every lane tile of a row where that leaves at least 256 rows a step
    cols = _largest_divisor(heads * head_dim, tile, _STEP_ELEMENTS // 256)
    rows = _largest_divisor(seq, sublanes, _STEP_ELEMENTS // cols)
    return _RopeBlocks(rows, cols)


@dataclass(frozen=True)
class RopeSpec:
    """How one kind of attention layer rotates q and k.

    rotated: the share of a head's columns that is rotated, counted from
      the head's first column, as halves of that part (a head of 128 at
      0.5: columns 0..31 with 32..63; 64..127 pass as they are).
    yarn: None, or (factor, original positions, beta_fast, beta_slow):
      every frequency a blend of itself and itself / factor, by how many
      turns it makes over the original positions (`yarn_frequencies`).
    attention_factor: times cos and sin both, on the rotated columns."""
    theta: float = 10000.0
    rotated: float = 1.0
    yarn: Optional[Tuple[float, int, float, float]] = None
    attention_factor: float = 1.0

    @property
    def plain(self) -> bool:
        return (self.rotated == 1.0 and self.yarn is None
                and self.attention_factor == 1.0)

    def columns(self, head_dim: int) -> int:
        """Rotated columns of a head, an even count."""
        return int(head_dim * self.rotated) // 2 * 2


def yarn_frequencies(theta: float, dim: int, factor: float, original: int,
                     beta_fast: float, beta_slow: float):
    """The dim / 2 frequencies of YaRN, float32 numpy, as the Hugging Face
    `_compute_yarn_parameters` blends them (its `truncate` default): pair i
    turns `original` x theta^(-2i/dim) / 2 pi times over the original
    positions; pairs that turn more than beta_fast times keep their
    frequency, pairs that turn fewer than beta_slow times have it divided
    by `factor`, and between the two (the pair numbers floored and ceiled)
    a linear ramp blends them."""
    def pair_of(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def as_spec(rope) -> RopeSpec:
    """A RopeSpec as it is; a bare theta as the plain rotation of every
    column at it."""
    return rope if isinstance(rope, RopeSpec) else RopeSpec(theta=rope)


def rope_frequencies(rope, head_dim: int):
    """The frequencies of a head's rotated pairs, float32 [columns / 2]."""
    spec = as_spec(rope)
    rotated = spec.columns(head_dim)
    if spec.yarn is not None:
        return jnp.asarray(yarn_frequencies(spec.theta, rotated, *spec.yarn))
    return spec.theta ** (-jnp.arange(0, rotated // 2, dtype=jnp.float32)
                          / (rotated // 2))


def halves_apart(head_dim: int, rotated: int):
    """The order of a head's columns in which a part rotated as halves
    (columns 0..rotated/2-1 with rotated/2..rotated-1, the rest passing)
    lies as the kernels rotate: partners head_dim / 2 apart. q.k does not
    change with its columns' order, so it is done to wq's and wk's columns
    (models/gpt.py), and `rope_table` lays cos 1 and sin 0 on the
    columns that pass."""
    r, passing = rotated // 2, (head_dim - rotated) // 2
    return (list(range(r)) + list(range(rotated, rotated + passing))
            + list(range(r, rotated)) + list(range(rotated + passing,
                                                   head_dim)))


def rope_table(seq: int, head_dim: int, rope):
    """(cos, sin) of positions 0..seq-1 as the rotation multiplies them,
    float32 [seq, W]: a head's columns are [cos, cos] and [-sin, sin], and
    where heads share a 128-lane tile the head repeats to fill it (W =
    128), so that the kernels load whole tiles. rope: a theta, or a
    RopeSpec: its frequencies and attention factor on the first columns of
    each half, cos 1 and sin 0 on those that pass (the head's columns in
    `halves_apart`'s order)."""
    spec = as_spec(rope)
    freqs = rope_frequencies(spec, head_dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if not spec.plain:
        passing = head_dim // 2 - freqs.shape[0]
        cos = jnp.concatenate([cos * spec.attention_factor,
                               jnp.ones((seq, passing), jnp.float32)], 1)
        sin = jnp.concatenate([sin * spec.attention_factor,
                               jnp.zeros((seq, passing), jnp.float32)], 1)
    repeat = (_lane_tile(head_dim) or head_dim) // head_dim
    return (jnp.tile(jnp.concatenate([cos, cos], axis=1), (1, repeat)),
            jnp.tile(jnp.concatenate([-sin, sin], axis=1), (1, repeat)))


def _rotate(x, cos, sin, head_dim: int):
    """x [rows, W] float32, whole heads side by side: x * cos + (the other
    half of each head) * sin."""
    width = x.shape[1]
    half = head_dim // 2
    if head_dim == width:
        other = pltpu.roll(x, half, 1)      # by half a head: either way
    else:
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        other = jnp.where(lane % head_dim < half,
                          pltpu.roll(x, width - half, 1),   # x[l + half]
                          pltpu.roll(x, half, 1))           # x[l - half]
    return x * cos + other * sin


def _split_kernel(*refs, head_dim, rotate):
    """Grid (batch, seq block, column block): x [1, rows, cols] ->
    out [1, cols / head_dim, rows, head_dim]."""
    x_ref, o_ref = refs[0], refs[-1]
    tile = _lane_tile(head_dim)
    per_tile = tile // head_dim
    for t in range(x_ref.shape[2] // tile):
        y = x_ref[0, :, t * tile:(t + 1) * tile]
        if rotate:
            y = _rotate(y.astype(jnp.float32), refs[1][...], refs[2][...],
                        head_dim).astype(o_ref.dtype)
        for i in range(per_tile):
            o_ref[0, t * per_tile + i] = y[:, i * head_dim:(i + 1) * head_dim]


def _merge_kernel(*refs, head_dim, rotate):
    """_split_kernel's transpose: g [1, cols / head_dim, rows, head_dim] ->
    out [1, rows, cols], rotated back (sin negated)."""
    g_ref, o_ref = refs[0], refs[-1]
    tile = _lane_tile(head_dim)
    per_tile = tile // head_dim
    for t in range(o_ref.shape[2] // tile):
        heads = [g_ref[0, t * per_tile + i] for i in range(per_tile)]
        y = heads[0] if per_tile == 1 else jnp.concatenate(heads, axis=1)
        if rotate:
            y = _rotate(y.astype(jnp.float32), refs[1][...], -refs[2][...],
                        head_dim).astype(o_ref.dtype)
        o_ref[0, :, t * tile:(t + 1) * tile] = y


_PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


def _call(split: bool, x, table, head_dim, blocks, interpret):
    """rope_split (x [B, S, H*D]) or rope_merge (x [B, H, S, D]) over the
    whole tensor, on a grid (batch, seq block, column block). The
    [B, S, H*D] side moves in (1, rows, cols) blocks, the [B, H, S, D] side
    in the same rows of cols / D heads."""
    if split:
        batch, seq, width = x.shape
    else:
        batch, seq, width = x.shape[0], x.shape[2], x.shape[1] * head_dim
    rows, cols = blocks
    flat = pl.BlockSpec((1, rows, cols), lambda b, i, j: (b, i, j))
    by_head = pl.BlockSpec((1, cols // head_dim, rows, head_dim),
                           lambda b, i, j: (b, j, i, 0))
    row_spec = pl.BlockSpec((rows, table[0].shape[1]),
                            lambda b, i, j: (i, 0)) if table else None
    out_shape = ((batch, width // head_dim, seq, head_dim) if split
                 else (batch, seq, width))
    return pl.pallas_call(
        functools.partial(_split_kernel if split else _merge_kernel,
                          head_dim=head_dim, rotate=bool(table)),
        grid=(batch, seq // rows, width // cols),
        in_specs=[flat if split else by_head] + [row_spec] * len(table),
        out_specs=by_head if split else flat,
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="rope_split" if split else "rope_merge",
    )(x, *table)


@functools.lru_cache(maxsize=None)
def _make_split_fn(head_dim, blocks, interpret):
    """rope_split with rope_merge as its backward; no residual but the
    table."""

    @jax.custom_vjp
    def f(x, *table):
        return _call(True, x, table, head_dim, blocks, interpret)

    def fwd(x, *table):
        return f(x, *table), table

    def bwd(table, g):
        dx = _call(False, g, table, head_dim, blocks, interpret)
        return (dx, *(jnp.zeros_like(t) for t in table))

    f.defvjp(fwd, bwd)
    return f


def _split_reference(x, table, head_dim: int):
    """The jnp formulation: reshape, transpose, rotate halves."""
    b, s, width = x.shape
    y = x.reshape(b, s, width // head_dim, head_dim).transpose(0, 2, 1, 3)
    if not table:
        return y
    half = head_dim // 2
    cos, sin = table[0][:, :half], table[1][:, half:head_dim]
    y1, y2 = y[..., :half], y[..., half:]
    return jnp.concatenate(
        [y1 * cos - y2 * sin, y1 * sin + y2 * cos], axis=-1).astype(x.dtype)


def rope_split(x, head_dim: int, table=(), *,
               interpret: Optional[bool] = None):
    """x [B, S, H * head_dim] -> [B, H, S, head_dim], rotated by `table`
    (`rope_table` of S and head_dim) if it is given: a projection's output
    as the flash kernels read it."""
    _, seq, width = x.shape
    blocks = _rope_blocks(seq, width // head_dim, head_dim, x.dtype.itemsize)
    if blocks is None:
        return _split_reference(x, table, head_dim)
    if interpret is None:
        interpret = attention._default_interpret()
    return _make_split_fn(head_dim, blocks, interpret)(x, *table)


# ---------------------------------------------------------------------------
# A latent block's q, k and v (models/gpt.py:_latent_attention)
# ---------------------------------------------------------------------------
# A head of q.k there is `nope` columns that carry no position and `rope`
# that are rotated as halves (128 + 64): no whole lane tiles a head, but a
# group of 128 / rope heads is (two heads: three tiles), and the kernels
# below move whole groups. They write what the flash kernels read, q and k
# [B, H, S, nope + rope + fill] (fill: the zero columns of
# attention.qk_padding) and v [B, H, S, dv]:
#
#   latent_q_split   q [B, S, H * (nope + rope)] -> q
#   latent_kv_split  kv [B, S, H * (nope + dv)], k_rope [B, S, rope] -> k, v
#                    (the one rotated part repeated to every head)
#   latent_q_merge, latent_kv_merge: their transposes, d k_rope the sum
#                    over the heads of dk's rotated columns, added up in
#                    float32 over the heads of a block and over the grid's
#                    head axis, then rotated back and rounded once.

# (rows, cols) of the q plane and of the kv plane one grid step moves.
_LatentBlocks = collections.namedtuple("_LatentBlocks", "q kv")


def _latent_blocks(seq: int, heads: int, nope: int, rope: int, dv: int,
                   itemsize: int) -> Optional[_LatentBlocks]:
    """Blocks of the four latent kernels, by `_rope_blocks`' rule over
    planes whose unit is a group of heads; None for a shape they do not
    tile: nope and dv whole lane tiles, the rotated parts of a group of
    heads a whole lane tile, whole groups, a sequence of whole registers."""
    tile = _lane_tile(rope)
    sublanes = 32 // itemsize
    if (tile is None or not nope or nope % LANES or dv % LANES
            or (heads * rope) % tile or seq % sublanes
            or itemsize not in (2, 4)):
        return None

    def plane(width, unit):
        cols = _largest_divisor(width, unit, _STEP_ELEMENTS // 256)
        return _RopeBlocks(
            _largest_divisor(seq, sublanes, _STEP_ELEMENTS // cols), cols)
    group = tile // rope
    return _LatentBlocks(plane(heads * (nope + rope), group * (nope + rope)),
                         plane(heads * (nope + dv), nope + dv))


def _side_by_side(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _rotated_tail(y, i: int, rope: int, fill: int):
    """Head i's rotated columns out of the group's tile y, and the zero
    columns after them."""
    tail = y[:, i * rope:(i + 1) * rope]
    if not fill:
        return tail
    return jnp.concatenate(
        [tail, jnp.zeros((y.shape[0], fill), y.dtype)], axis=1)


def _latent_q_split_kernel(x_ref, cos_ref, sin_ref, o_ref, *, nope, rope):
    """Grid (batch, seq block, head block): x [1, rows, heads * (nope +
    rope)] -> out [1, heads, rows, nope + rope + fill]."""
    head = nope + rope
    fill = o_ref.shape[3] - head
    group = _lane_tile(rope) // rope
    for g in range(o_ref.shape[1] // group):
        x = x_ref[0, :, g * group * head:(g + 1) * group * head]
        y = _side_by_side([x[:, i * head + nope:(i + 1) * head]
                           for i in range(group)])
        y = _rotate(y.astype(jnp.float32), cos_ref[...], sin_ref[...],
                    rope).astype(o_ref.dtype)
        for i in range(group):
            o_ref[0, g * group + i, :, :nope] = x[:, i * head:i * head + nope]
            o_ref[0, g * group + i, :, nope:] = _rotated_tail(y, i, rope, fill)


def _latent_q_merge_kernel(g_ref, cos_ref, sin_ref, o_ref, *, nope, rope):
    """_latent_q_split_kernel's transpose: g [1, heads, rows, nope + rope +
    fill] -> out [1, rows, heads * (nope + rope)], rotated back."""
    head = nope + rope
    group = _lane_tile(rope) // rope
    for g in range(g_ref.shape[1] // group):
        grads = [g_ref[0, g * group + i] for i in range(group)]
        y = _side_by_side([t[:, nope:head] for t in grads])
        y = _rotate(y.astype(jnp.float32), cos_ref[...], -sin_ref[...],
                    rope).astype(o_ref.dtype)
        o_ref[0, :, g * group * head:(g + 1) * group * head] = _side_by_side(
            [part for i, t in enumerate(grads)
             for part in (t[:, :nope], y[:, i * rope:(i + 1) * rope])])


def _latent_kv_split_kernel(kv_ref, kr_ref, cos_ref, sin_ref, k_ref, v_ref,
                            *, nope, rope):
    """Grid (batch, seq block, head block): kv [1, rows, heads * (nope +
    dv)], k_rope [1, rows, rope] -> k [1, heads, rows, nope + rope + fill],
    v [1, heads, rows, dv]."""
    dv = v_ref.shape[3]
    fill = k_ref.shape[3] - nope - rope
    group = _lane_tile(rope) // rope
    y = _side_by_side([kr_ref[0]] * group)
    y = _rotate(y.astype(jnp.float32), cos_ref[...], sin_ref[...],
                rope).astype(k_ref.dtype)
    tail = _rotated_tail(y, 0, rope, fill)
    for h in range(k_ref.shape[1]):
        at = h * (nope + dv)
        k_ref[0, h, :, :nope] = kv_ref[0, :, at:at + nope]
        k_ref[0, h, :, nope:] = tail
        v_ref[0, h] = kv_ref[0, :, at + nope:at + nope + dv]


def _latent_kv_merge_kernel(dk_ref, dv_ref, cos_ref, sin_ref, dkv_ref,
                            dkr_ref, sum_ref, *, nope, rope):
    """_latent_kv_split_kernel's transpose. The head block is the grid's
    last, sequential axis: sum_ref [rows, rope] float32 carries the heads'
    rotated columns of dk from block to block, and the last one rotates
    the sum back into d k_rope [1, rows, rope]."""
    dv = dv_ref.shape[3]
    group = _lane_tile(rope) // rope
    j = pl.program_id(2)
    total = jnp.where(j == 0, 0.0, sum_ref[...])
    for h in range(dk_ref.shape[1]):
        dk = dk_ref[0, h]
        at = h * (nope + dv)
        dkv_ref[0, :, at:at + nope] = dk[:, :nope]
        dkv_ref[0, :, at + nope:at + nope + dv] = dv_ref[0, h]
        total = total + dk[:, nope:nope + rope].astype(jnp.float32)
    sum_ref[...] = total

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        y = _rotate(_side_by_side([total] * group), cos_ref[...],
                    -sin_ref[...], rope)
        dkr_ref[0] = y[:, :rope].astype(dkr_ref.dtype)


def _latent_specs(rows, heads, table):
    """BlockSpecs of a grid (batch, seq block, head block), each by its
    last dimension: a plane [B, S, H * width] in `heads` heads' columns, a
    by-head tensor [B, H, S, width] in the same rows of those heads, a
    plane that every head block reads or writes whole, the table."""
    return (lambda width: pl.BlockSpec((1, rows, heads * width),
                                       lambda b, i, j: (b, i, j)),
            lambda width: pl.BlockSpec((1, heads, rows, width),
                                       lambda b, i, j: (b, j, i, 0)),
            lambda width: pl.BlockSpec((1, rows, width),
                                       lambda b, i, j: (b, i, 0)),
            [pl.BlockSpec((rows, t.shape[1]), lambda b, i, j: (i, 0))
             for t in table])


def _latent_q_call(split: bool, x, table, nope, rope, blocks, interpret):
    """latent_q_split (x [B, S, H * (nope + rope)]) or latent_q_merge
    (x [B, H, S, nope + rope + fill]) over the whole tensor."""
    head = nope + rope
    padded = head + attention.qk_padding(head)
    if split:
        batch, seq, n_heads = x.shape[0], x.shape[1], x.shape[2] // head
    else:
        batch, n_heads, seq = x.shape[:3]
    rows, cols = blocks
    plane, by_head, _, rows_of_table = _latent_specs(rows, cols // head, table)
    out_shape = ((batch, n_heads, seq, padded) if split
                 else (batch, seq, n_heads * head))
    return pl.pallas_call(
        functools.partial(
            _latent_q_split_kernel if split else _latent_q_merge_kernel,
            nope=nope, rope=rope),
        grid=(batch, seq // rows, n_heads * head // cols),
        in_specs=[plane(head) if split else by_head(padded)] + rows_of_table,
        out_specs=by_head(padded) if split else plane(head),
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="latent_q_split" if split else "latent_q_merge",
    )(x, *table)


def _latent_kv_call(split: bool, operands, table, nope, rope, dv, blocks,
                    interpret):
    """latent_kv_split (operands kv [B, S, H * (nope + dv)], k_rope [B, S,
    rope] -> k, v) or latent_kv_merge (operands dk [B, H, S, nope + rope +
    fill], dv [B, H, S, dv] -> d kv, d k_rope) over the whole tensors. The
    merge walks the head blocks in order (its sum over the heads)."""
    padded = nope + rope + attention.qk_padding(nope + rope)
    if split:
        batch, seq, n_heads = (*operands[0].shape[:2],
                               operands[0].shape[2] // (nope + dv))
    else:
        batch, n_heads, seq = operands[0].shape[:3]
    rows, cols = blocks
    plane, by_head, shared, rows_of_table = _latent_specs(
        rows, cols // (nope + dv), table)
    flat = [plane(nope + dv), shared(rope)]
    heads = [by_head(padded), by_head(dv)]
    dtype = operands[0].dtype
    flat_shapes = [jax.ShapeDtypeStruct((batch, seq, w), dtype)
                   for w in (n_heads * (nope + dv), rope)]
    head_shapes = [jax.ShapeDtypeStruct((batch, n_heads, seq, w), dtype)
                   for w in (padded, dv)]
    return pl.pallas_call(
        functools.partial(
            _latent_kv_split_kernel if split else _latent_kv_merge_kernel,
            nope=nope, rope=rope),
        grid=(batch, seq // rows, n_heads * (nope + dv) // cols),
        in_specs=(flat if split else heads) + rows_of_table,
        out_specs=heads if split else flat,
        out_shape=head_shapes if split else flat_shapes,
        scratch_shapes=[] if split else [pltpu.VMEM((rows, rope),
                                                    jnp.float32)],
        compiler_params=_PARALLEL if split else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="latent_kv_split" if split else "latent_kv_merge",
    )(*operands, *table)


@functools.lru_cache(maxsize=None)
def _make_latent_fns(nope, rope, dv, blocks, interpret):
    """(latent_q_split, latent_kv_split), each with its merge as its
    backward; no residual but the table."""
    def no_gradient(table):
        return tuple(jnp.zeros_like(t) for t in table)

    @jax.custom_vjp
    def q_split(q, *table):
        return _latent_q_call(True, q, table, nope, rope, blocks.q, interpret)

    def q_bwd(table, g):
        return (_latent_q_call(False, g, table, nope, rope, blocks.q,
                               interpret), *no_gradient(table))

    @jax.custom_vjp
    def kv_split(kv, k_rope, *table):
        return tuple(_latent_kv_call(True, (kv, k_rope), table, nope, rope,
                                     dv, blocks.kv, interpret))

    def kv_bwd(table, g):
        return (*_latent_kv_call(False, g, table, nope, rope, dv, blocks.kv,
                                 interpret), *no_gradient(table))

    q_split.defvjp(lambda q, *table: (q_split(q, *table), table), q_bwd)
    kv_split.defvjp(
        lambda kv, k_rope, *table: (kv_split(kv, k_rope, *table), table),
        kv_bwd)
    return q_split, kv_split


def latent_split(seq: int, heads: int, nope: int, rope: int, dv: int, dtype,
                 *, interpret: Optional[bool] = None):
    """The latent block's way into the flash kernels at this shape:
    (q_split, kv_split), or None where the kernels do not tile it
    (`_latent_blocks`) and the caller keeps the jnp assembly.

    q_split(q [B, S, heads * (nope + rope)], cos, sin) -> q [B, heads, S,
      nope + rope + fill]: a head's nope columns, its rope columns rotated
      as halves by (cos, sin) = rope_table(S, rope, theta), and the fill
      zero columns of attention.qk_padding.
    kv_split(kv [B, S, heads * (nope + dv)], k_rope [B, S, rope], cos, sin)
      -> (k [B, heads, S, nope + rope + fill], v [B, heads, S, dv]): k_rope
      rotated once and repeated to every head."""
    blocks = _latent_blocks(seq, heads, nope, rope, dv,
                            jnp.dtype(dtype).itemsize)
    if blocks is None:
        return None
    if interpret is None:
        interpret = attention._default_interpret()
    return _make_latent_fns(nope, rope, dv, blocks, interpret)
