"""Sparse-expert dispatch: every token's k chosen experts, and only those.

The token-slots (T tokens x k choices) are ordered by expert into a row
space in which each expert owns whole tiles of `tile_rows` rows: its group
is padded up to a tile boundary with zero rows, and an expert nobody chose
still owns one (all zero) tile. Three things follow from that layout:

  - a grouped matmul is a plain tiled matmul whose weight block is picked
    by the row tile's expert (scalar prefetch): no mask, no tile computed
    for two experts, no `[tokens, experts, width]` intermediate;
  - no token is dropped under any imbalance (there is no capacity): the
    row space has room for every row the routing at hand fills (T x k
    rows plus one tile an expert where all the experts are here; for a
    share of them see below), and the tiles past the last used one are
    skipped, not computed;
  - every data movement is a gather, forward and backward: `dispatch`
    (tokens -> rows) and `combine` (rows -> tokens, weighted) are each
    other's transposes and carry their own VJPs, because XLA's transpose
    of a row gather is a scatter-add, which a TPU serialises. (The other
    row gather of a step, the token-embedding lookup, goes by the same
    rule with these very pieces: ops/embedding.py, whose "experts" are
    groups of vocabulary rows.)

Padding rows are zeros in, zeros through SwiGLU, and never gathered back.

One chip's share of the experts (`plan_dispatch(..., partial=True)`): the
groups are the experts held here and a slot may have chosen one that is
not. Such a slot gets no row: it is not gathered in, multiplied or
combined, and no gradient passes through it (`Plan.token_held`). How many
slots fall here is known only on the device, and the kernels skip the
tiles nobody fills, but every XLA pass (the gathers, SwiGLU, the tables)
takes its shape from the row space and runs over all of it. So the row
space is sized for the rows that are expected, T x k x held / of, and
not for every slot landing here: `in_row_space` lays the plan out over
`_ROW_SPACE_FACTOR` times the tiles the expectation fills (plus one a
group) when the routing at hand fits that, which it reads off the groups'
sizes on the device, and over room for every slot when it does not
(skewed routing, a collapsed router). Both are the same arithmetic on the
same rows in the same order; the second is only slower. Nothing is
dropped, clipped or approximated either way.

The token side of a share (`rows_to_tokens`: `combine` forward and
`dispatch` backward, one operation, rows -> tokens) is sized the same way.
By the slots it gathers a row for each of the T x k slots, seven eighths
of them masked out on a chip that holds an eighth of the experts, into
`[T, k, d]` and sums over k. Where the row space and the tokens together
are fewer rows than that (R + T < T x k: a share's bounded row space),
`lay_out` adds a token-ordered view of the row space (`ByToken`: one sort
of its R keys) and the sum goes by the rows: gather the R rows in token
order, add each token's run of at most k rows onto its head
(`moe_run_sum`), gather the T heads. A run may cross into the next block
of rows by k - 1 of them, which the kernel reads as a halo of whole sublane
tiles, as many as k - 1 rows need (`_run_halo`: 16 rows of bfloat16 up to
17 experts a token, 32 at 22), so any k goes by the rows as long as that
halo divides a tile of the row space. Which of the two runs is read off
the plan's static shapes and the rows' type, nothing else: every slot's
row space, the fallback above included, a plan of all the experts (every
slot is a real row: T x k is the least there is to move) and a tile the
halo does not divide (16 rows under k = 22) go by the slots, op for op as
they did. The sum is the same float32 sum of the same rows, rounded once;
by the rows it adds them in choice order from the first held one. Every
data movement is still a gather, forward and backward: the view only
changes which rows are gathered, and how many.

Kernels (names in util/profiling.KERNELS): `moe_gmm` (rows x an expert's
matrix, forward and the gradient of the rows), `moe_tgmm` (rows^T x rows a
group, the gradient of the matrices) and `moe_run_sum` (each row plus the
rows after it that are of its token, weighted: one pass over the
token-ordered rows).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention, short_conv
from ray_tpu.ops.attention import lane_divisor

# One weight block in VMEM as the MXU reads it (in the rows' type): a whole
# 2048 x 1024 bf16 expert matrix, so the rows are read once and each matrix
# once a group. A BlockSpec double-buffers it; float32 masters land in one
# block of twice the bytes beside their rounded copy (`_gmm`).
_WEIGHT_BLOCK_BYTES = 4 << 20
_VMEM_LIMIT_BYTES = 64 << 20
_MAX_TILE_ROWS = 256
# The MXU's height: a tile of fewer rows loads every 128 x 128 weight tile
# for a fraction of a pass (`tile_rows`).
_MXU_ROWS = 128
# A share's row space, in tiles the expected rows fill (`in_row_space`).
# Random routing puts 0.11-0.14 of the slots on an eighth of the experts
# (PERF.md, PR 31); past twice the expectation the plan for every slot runs.
_ROW_SPACE_FACTOR = 2


class ByToken(NamedTuple):
    """The row space read token by token: its rows sorted by the slot they
    hold, so a token's held rows are one run of at most k, its choices in
    order, and the padding rows come last.

    rows   [R] the row that is j-th by slot
    slots  [R] the slot that row holds; T * k on a padding row
    heads  [T] where each token's run starts; R (where `moe_run_sum`
           writes zeros) for a token none of whose slots is held
    """
    rows: jax.Array
    slots: jax.Array
    heads: jax.Array


class Plan(NamedTuple):
    """Where every token-slot sits in the expert-ordered, tile-padded rows.

    row_slot    [tiles * tile_rows] flat slot (token * k + choice) held by
                each row; T * k on a padding row
    token_rows  [T, k] the row of each token's each choice
    tile_group  [tiles] the expert that owns each row tile
    tiles_used  [1] tiles up to the end of the last group; the rest are
                never computed
    token_held  [T, k] bool, under `partial` only: whether the slot's
                expert is one of the groups. Where it is not, token_rows
                is 0 (a row that is always computed, so finite) and the
                slot is masked out wherever token_rows is read
    by_token    the token-ordered view of the row space (`ByToken`), by
                which `rows_to_tokens` moves R + T rows: made where that
                is fewer than the T * k it moves through token_rows (a
                share's bounded row space), None where it is not (every
                slot's row space, all the experts held). `lay_out` reads
                that off the shapes alone; like the other tables it is
                integers and not differentiated

    `tiles` is every slot's worst case from `plan_dispatch` (T * k rows and
    a tile a group) and whatever `lay_out` was given otherwise: at least
    tiles_used, which is the caller's to see to (`in_row_space` does).
    """
    row_slot: jax.Array
    token_rows: jax.Array
    tile_group: jax.Array
    tiles_used: jax.Array
    token_held: Optional[jax.Array] = None
    by_token: Optional[ByToken] = None


class Order(NamedTuple):
    """The slots in expert order: what of a plan takes no shape from the
    row space, so it is worked out once whatever the row space will be.

    order       [T * k] the slot that is r-th by expert (stable)
    sizes       [G] slots a group
    tile_end    [G] tiles up to the end of each group; the last is
                Plan.tiles_used
    first_row, first_rank  [G] a group's first row, and its first slot's
                place in `order`
    token_rows, token_held  as in Plan
    """
    order: jax.Array
    sizes: jax.Array
    tile_end: jax.Array
    first_row: jax.Array
    first_rank: jax.Array
    token_rows: jax.Array
    token_held: Optional[jax.Array] = None


def _sublanes(dtype) -> int:
    """Rows of one register: 8 of float32, 16 of bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def tile_rows(n_slots: int, n_groups: int, dtype) -> int:
    """Rows of one tile, from the shape alone: about a quarter of the mean
    group (so padding, half a tile a group on average, stays near an
    eighth of the rows), between the type's sublane packing and 256; and
    never under the MXU's 128 rows once the mean group outgrows them: a
    group of 205 rows in tiles of 32 kept the kernels at a quarter of the
    MXU's rate (5 ms of a 310 ms step at 8 of 320 experts held, and the
    seed's share of the step 0.6 % for 0.4: PERF.md section 6, PR 46); in
    tiles of 128 it pads to 256."""
    rows = _sublanes(dtype)
    while rows * 2 <= min(_MAX_TILE_ROWS, n_slots // (4 * n_groups)):
        rows *= 2
    if rows < _MXU_ROWS < n_slots // n_groups:
        rows = _MXU_ROWS
    return rows


def order_slots(idx, n_groups: int, rows: int, partial: bool = False) -> Order:
    """idx [T, k] int: the experts each token chose. Two sorts of T x k
    keys and small dense passes; integers only, nothing differentiated.
    partial: an idx outside 0 .. n_groups - 1 is an expert that is not
    here; its slot sorts behind every group's and gets no row."""
    t, k = idx.shape
    n = t * k
    flat = idx.reshape(n).astype(jnp.int32)
    if partial:
        here = (flat >= 0) & (flat < n_groups)
        flat = jnp.where(here, flat, n_groups)
    slots = jnp.arange(n, dtype=jnp.int32)
    chose = flat[:, None] == jnp.arange(n_groups, dtype=jnp.int32)[None, :]
    sizes = jnp.sum(chose, axis=0, dtype=jnp.int32)
    # stable, so a group keeps token order: slot order[r] is r-th by expert
    _, order = lax.sort((flat, slots), num_keys=1, is_stable=True)
    _, rank = lax.sort((order, slots), num_keys=1)
    group_tiles = jnp.maximum(-(-sizes // rows), 1)
    tile_end = jnp.cumsum(group_tiles)
    first_row = rows * (tile_end - group_tiles)
    first_rank = jnp.cumsum(sizes) - sizes
    # a slot's row: its rank within its group, after the group's first row
    shift = jnp.sum(jnp.where(chose, (first_row - first_rank)[None, :], 0),
                    axis=1)
    token_rows = (rank + shift).reshape(t, k)
    if not partial:
        return Order(order, sizes, tile_end, first_row, first_rank,
                     token_rows)
    here = here.reshape(t, k)
    return Order(order, sizes, tile_end, first_row, first_rank,
                 jnp.where(here, token_rows, 0), here)


def lay_out(order: Order, rows: int, tiles: int) -> Plan:
    """The tables of a row space of `tiles` tiles of `rows` rows; it has to
    hold order.tile_end[-1] of them."""
    n = order.order.shape[0]
    n_groups = order.sizes.shape[0]
    tile_group = jnp.minimum(
        jnp.sum(jnp.arange(tiles, dtype=jnp.int32)[:, None]
                >= order.tile_end[None, :], axis=1, dtype=jnp.int32),
        n_groups - 1)
    # a row's slot: tile by tile, the group's ranks from where the tile
    # starts within its group; past the group's size the row is padding
    within = (rows * jnp.arange(tiles, dtype=jnp.int32)
              - order.first_row[tile_group])[:, None] \
        + jnp.arange(rows, dtype=jnp.int32)[None, :]
    held = jnp.clip(order.first_rank[tile_group][:, None] + within, 0, n - 1)
    row_slot = jnp.where(within < order.sizes[tile_group][:, None],
                         order.order[held], n).reshape(-1)
    return Plan(row_slot, order.token_rows, tile_group, order.tile_end[-1:],
                order.token_held, _by_token(row_slot, order.token_held))


def _by_token(row_slot, token_held) -> Optional[ByToken]:
    """The token-ordered view of a row space that is smaller than the slots
    (one sort of its R keys), None of one that is not."""
    if token_held is None:
        return None
    r, (t, k) = row_slot.shape[0], token_held.shape
    if r + t >= t * k:
        return None
    slots, rows = lax.sort((row_slot, jnp.arange(r, dtype=jnp.int32)),
                           num_keys=1)
    count = jnp.sum(token_held, axis=1, dtype=jnp.int32)
    return ByToken(rows, slots,
                   jnp.where(count > 0, jnp.cumsum(count) - count, r))


def _every_slot(n_slots: int, n_groups: int, rows: int) -> int:
    """Tiles that hold any routing of n_slots: their rows and, for the
    groups' padding, a tile a group."""
    return -(-n_slots // rows) + n_groups


def plan_dispatch(idx, n_groups: int, rows: int,
                  partial: bool = False) -> Plan:
    """order_slots laid out over room for every slot of idx [T, k]."""
    return lay_out(order_slots(idx, n_groups, rows, partial), rows,
                   _every_slot(idx.size, n_groups, rows))


def in_row_space(fn, order: Order, rows: int, expected_slots: int,
                 *operands):
    """fn(tiles, rows, order, *operands), which lays the plan out over
    `tiles` tiles (`lay_out`) and takes every shape from it, in the row
    space this routing needs -> (its result, whether the bounded row space
    held it: a float32 0. or 1., the number 1.0 where there is nothing to
    bound).

    expected_slots: how many of the order's slots are expected on its
    groups (all of them where every expert is here). The row space is
    `_ROW_SPACE_FACTOR` times the tiles they fill and a tile a group; if
    the groups at hand end past it (order.tile_end, read on the device),
    fn runs over room for every slot instead: one `lax.cond`, each
    branch whole, forward and backward. The backward pass recomputes fn
    inside its own branch, since residuals shaped by a branch would have
    to be written, as zeros, by the other one too; under a layer's remat
    that is the recomputation the layer does anyway. Two row spaces are
    twice the program to trace and lower, so a branch is a `jax.jit` of
    fn: layers of one shape then share one trace and one lowering of it
    (fn has to be the same function for them), and the compiler inlines
    the calls."""
    n = order.order.shape[0]
    n_groups = order.sizes.shape[0]
    every = _every_slot(n, n_groups, rows)
    bounded = _ROW_SPACE_FACTOR * -(-expected_slots // rows) + n_groups
    if bounded >= every:
        return fn(every, rows, order, *operands), 1.0
    run = jax.jit(fn, static_argnums=(0, 1))

    def fits(order):
        return order.tile_end[-1] <= bounded

    @jax.custom_vjp
    def either(order, *operands):
        return lax.cond(fits(order), functools.partial(run, bounded, rows),
                        functools.partial(run, every, rows), order, *operands)

    def fwd(order, *operands):
        return either(order, *operands), (order, operands)

    def bwd(res, g):
        order, operands = res

        def pull(tiles, order, operands, g):
            _, pulled = jax.vjp(functools.partial(run, tiles, rows, order),
                                *operands)
            return pulled(g)
        return (None,) + lax.cond(
            fits(order), functools.partial(pull, bounded),
            functools.partial(pull, every), order, operands, g)

    either.defvjp(fwd, bwd)
    return either(order, *operands), fits(order).astype(jnp.float32)


def _take_rows(x, index):
    """x[index], zeros where index is len(x) (a padding row): one gather
    from x with a zero row appended, and no select over the result."""
    zero = jnp.zeros((1,) + x.shape[1:], x.dtype)
    return jnp.take(jnp.concatenate([x, zero]), index, axis=0, mode="clip")


@jax.custom_vjp
def dispatch(x, plan: Plan):
    """Token rows [T, d] -> expert-ordered rows [tiles * tile_rows, d]."""
    k = plan.token_rows.shape[1]
    return _take_rows(x, plan.row_slot // k)


def _dispatch_fwd(x, plan):
    return dispatch(x, plan), plan


def _held_only(per_slot, plan: Plan):
    """per_slot [T, k, ...] with zeros in the slots whose expert is not
    here (a plan of all the experts has none)."""
    if plan.token_held is None:
        return per_slot
    held = plan.token_held.reshape(
        plan.token_held.shape + (1,) * (per_slot.ndim - 2))
    return jnp.where(held, per_slot, 0)


def _run_sum_kernel(x_ref, after_ref, c_ref, o_ref):
    """Grid (row blocks and one more, column blocks). o[j] = sum_a c[j, a]
    x[j + a], the rows past the block from `after_ref`, the next block's
    first rows; the block past the last is zeros."""
    last = pl.num_programs(0) - 1

    @pl.when(pl.program_id(0) == last)
    def _no_row():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(pl.program_id(0) < last)
    def _runs():
        x = x_ref[...].astype(jnp.float32)
        after = after_ref[...].astype(jnp.float32)
        total = jnp.zeros_like(x)
        for ahead in range(c_ref.shape[1]):
            c = c_ref[:, ahead:ahead + 1]
            # select, never multiply by zero: a row past tiles_used was
            # never computed and holds whatever the buffer held
            total = total + jnp.where(
                c != 0, c * short_conv._shifted(x, after, ahead, False), 0.0)
        o_ref[...] = total.astype(o_ref.dtype)


def _run_halo(k: int, dtype) -> int:
    """The rows `_run_sum` fetches past a block: whole sublane tiles, as
    many as the k - 1 rows after a block's last one need (16 rows of
    bfloat16 up to k = 17, 32 up to 33). k is at least 2 where a
    token-ordered view exists (R + T < T x k)."""
    sublanes = _sublanes(dtype)
    return sublanes * -(-(k - 1) // sublanes)


def _run_sum(x, c, block: int, interpret: bool):
    """x [R, d], c [R, k] float32 -> [R + block, d]: row j is the float32
    sum of c[j, a] x[j + a] over a < k, rounded once (c is zero wherever
    j + a is past the end), and the last `block` rows are zeros. One pass
    over x in blocks of `block` rows, the k - 1 rows a block needs of its
    neighbour through a second BlockSpec of `_run_halo` rows: the halo
    follows k, and it has to divide the block (the second BlockSpec counts
    x in halos), which `rows_to_tokens` sees to."""
    r, d = x.shape
    halo = _run_halo(c.shape[1], x.dtype)
    cols = lane_divisor(d, 2048)
    per, blocks = block // halo, r // block
    return pl.pallas_call(
        _run_sum_kernel,
        grid=(blocks + 1, d // cols),
        in_specs=[
            pl.BlockSpec((block, cols),
                         lambda i, j: (jnp.minimum(i, blocks - 1), j)),
            pl.BlockSpec((halo, cols),
                         lambda i, j: (jnp.minimum((i + 1) * per,
                                                   r // halo - 1), j)),
            pl.BlockSpec((block, c.shape[1]),
                         lambda i, j: (jnp.minimum(i, blocks - 1), 0)),
        ],
        out_specs=pl.BlockSpec((block, cols), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r + block, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_run_sum",
    )(x, x, c)


def rows_to_tokens(rows, plan: Plan, weights=None):
    """Expert-ordered rows [R, d] -> tokens [T, d]: the float32 sum of each
    token's held rows, weighted by weights [T, k] (float32) where given,
    rounded once. `combine` forward and `dispatch` backward.

    By the slots (plan.by_token is None: every slot is a row, or the row
    space is as large as the slots; or the run's halo, `_run_halo`, does
    not divide a tile): gather [T, k, d] through token_rows, mask what is
    not held, sum over k. By the rows (a share's bounded row space, at any
    k): gather the R rows in token order, add each run onto its head
    (`moe_run_sum`: a row's coefficients are its followers' weights as far
    as they are of its token), gather the T heads. R + T rows moved where
    T * k were, and nothing shaped [T, k, d]."""
    view = plan.by_token
    k = plan.token_rows.shape[1]
    tile = plan.row_slot.shape[0] // plan.tile_group.shape[0]
    if view is None or tile % _run_halo(k, rows.dtype):
        per_slot = rows[plan.token_rows]
        if weights is None:
            y = jnp.sum(_held_only(per_slot, plan), axis=1,
                        dtype=jnp.float32)
        else:
            y = jnp.einsum("tk,tkd->td", _held_only(weights, plan), per_slot,
                           preferred_element_type=jnp.float32)
        return y.astype(rows.dtype)
    token = view.slots // k
    # a row's weight, zero on a padding row (whose slot is T * k)
    weight = ((view.slots < plan.token_held.size).astype(jnp.float32)
              if weights is None
              else _take_rows(weights.reshape(-1), view.slots))

    def follower(ahead):
        # the row `ahead` after each: its weight if it is of the same token
        same = jnp.pad(token[ahead:], (0, ahead), constant_values=-1) == token
        return jnp.where(same, jnp.pad(weight[ahead:], (0, ahead)), 0)
    runs = _run_sum(rows[view.rows],
                    jnp.stack([follower(a) for a in range(k)], axis=1),
                    tile, attention._default_interpret())
    return jnp.take(runs, view.heads, axis=0, mode="clip")


def _dispatch_bwd(plan, g):
    return rows_to_tokens(g, plan), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(z, weights, plan: Plan):
    """Expert-ordered rows [rows, d] back to tokens [T, d]: each token's k
    rows, weighted (weights [T, k] float32) and summed in float32."""
    return rows_to_tokens(z, plan, weights)


def _combine_fwd(z, weights, plan):
    return combine(z, weights, plan), (z, weights, plan)


def _combine_bwd(res, g):
    # in row space, where g's rows come by the cheap gather (from [T, d]):
    # dz = w g and dw = <g, z> row by row, then dw back to [T, k]
    z, weights, plan = res
    k = plan.token_rows.shape[1]
    # g meets z here, and not before: left to itself the scheduler may pad
    # g as soon as it exists, ahead of the kernels that recompute z; a copy
    # that lives across them stays in HBM, and the gather below reads it
    # five times slower than from VMEM (2.87 ms for 0.52 at olmoe:
    # PERF.md, PR 42)
    g, z = lax.optimization_barrier((g, z))
    g_rows = _take_rows(g, plan.row_slot // k)
    row_weight = _take_rows(weights.reshape(-1), plan.row_slot)
    dz = g_rows * row_weight[:, None].astype(g.dtype)
    dweights = _held_only(
        jnp.sum(g_rows.astype(jnp.float32) * z.astype(jnp.float32),
                axis=1)[plan.token_rows], plan)
    return dz, dweights.astype(weights.dtype), None


combine.defvjp(_combine_fwd, _combine_bwd)


# ---------------------------------------------------------------------------
# The grouped matmuls
# ---------------------------------------------------------------------------

def _used(tile, used_ref):
    """Index maps clamp to the last used tile: Pallas does not fetch a
    block whose index did not change, nor write one back."""
    return jnp.minimum(tile, used_ref[0] - 1)


def _first_of_group(i, group_ref):
    """Whether row tile i opens its group's run of tiles (the plan keeps a
    group's tiles together), or the walk itself."""
    return (i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != group_ref[i])


def _tile_product(x_ref, w, o_ref, transposed):
    contract = (((1,), (1 if transposed else 0,)), ((), ()))
    o_ref[...] = lax.dot_general(
        x_ref[...], w, contract,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _gmm_kernel(group_ref, used_ref, x_ref, w_ref, o_ref, *, transposed):
    @pl.when(pl.program_id(1) < used_ref[0])
    def _tile():
        _tile_product(x_ref, w_ref[0], o_ref, transposed)


def _gmm_masters_kernel(group_ref, used_ref, x_ref, w_hbm, o_ref, landing,
                        block, arrived, *, transposed):
    """_gmm_kernel where the matrices are kept in another type than the
    rows' (float32 masters under bfloat16 rows) and stay in HBM: a group's
    block is fetched into `landing` as it is kept and rounded into `block`,
    the rows' type, at the group's first tile; its tiles share the one
    rounding. The next group's fetch starts as soon as `landing` has been
    read, so it has the whole group's products to arrive under (a BlockSpec
    would start it one tile ahead, and a block of twice the bytes does not
    arrive under one tile's product: 2.27 ms a call at olmoe where this
    takes 2.01; a second landing slot bought nothing: PERF.md, PR 42). The
    plan gives every group a tile, in order (`lay_out`): the group after g
    is g + 1."""
    j, i = pl.program_id(0), pl.program_id(1)
    n_groups = w_hbm.shape[0]

    def fetch(group):
        tn = block.shape[0] if transposed else block.shape[1]
        cols = pl.ds(pl.multiple_of(j * tn, tn), tn)
        return pltpu.make_async_copy(
            w_hbm.at[group, cols, :] if transposed else w_hbm.at[group, :, cols],
            landing, arrived)

    @pl.when(i < used_ref[0])
    def _tile():
        group = group_ref[i]

        @pl.when(i == 0)
        def _first_of_walk():
            fetch(group).start()

        @pl.when(_first_of_group(i, group_ref))
        def _round():
            fetch(group).wait()
            block[...] = landing[...].astype(block.dtype)

            @pl.when(group + 1 < n_groups)
            def _next():
                fetch(group + 1).start()

        _tile_product(x_ref, block[...], o_ref, transposed)


def _gmm(x, w, plan: Plan, transposed: bool, interpret: bool):
    """x [rows, K] times, row tile by row tile, its group's [K, N] matrix
    (w [G, K, N], or [G, N, K] when `transposed`) -> [rows, N]. Grid
    (column blocks, row tiles) with the tiles inside, so a group's weight
    block stays in VMEM while its tiles pass. w in the rows' type, or in
    the type it is kept in (float32 masters): the block is then rounded to
    the rows' type in VMEM (`_gmm_masters_kernel`), the same
    round-to-nearest an `astype` ahead of the call would make, without its
    pass over HBM. The block is sized by the elements the MXU reads,
    whatever w's type: a narrower block would read the rows again."""
    m, kdim = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    tiles = plan.tile_group.shape[0]
    tm = m // tiles
    tn = lane_divisor(
        n, max(128, _WEIGHT_BLOCK_BYTES // (kdim * x.dtype.itemsize)))
    block = (tn, kdim) if transposed else (kdim, tn)
    if w.dtype != x.dtype:
        kernel, w_spec = _gmm_masters_kernel, pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM(block, w.dtype), pltpu.VMEM(block, x.dtype),
                   pltpu.SemaphoreType.DMA(())]
    else:
        kernel, scratch = _gmm_kernel, []
        w_spec = pl.BlockSpec((1,) + block, lambda j, i, grp, used: (
            (grp[_used(i, used)], j, 0) if transposed
            else (grp[_used(i, used)], 0, j)))
    return pl.pallas_call(
        functools.partial(kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, tiles),
            in_specs=[
                pl.BlockSpec((tm, kdim),
                             lambda j, i, grp, used: (_used(i, used), 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, i, grp, used: (_used(i, used), j)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_gmm",
    )(plan.tile_group, plan.tiles_used, x, w)


def _tgmm_kernel(group_ref, used_ref, x_ref, g_ref, o_ref, acc_ref, *,
                 whole):
    """whole: the result's block of o_ref, the first of a [1, K, N] block
    (0) or all of a [K, N] one (...)."""
    i = pl.program_id(2)
    last_tile = used_ref[0] - 1
    group = group_ref[i]

    @pl.when(i <= last_tile)
    def _tile():
        @pl.when(_first_of_group(i, group_ref))
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot_general(
            x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when((i == last_tile)
                 | (group_ref[jnp.minimum(i + 1, last_tile)] != group))
        def _last_of_group():
            o_ref[whole] = acc_ref[...].astype(o_ref.dtype)


def _tgmm(x, g, plan: Plan, n_groups: int, interpret: bool,
          table_rows: int = 0):
    """x [rows, K], g [rows, N] -> [G, K, N] in the rows' type: x^T g over
    each group's rows (padding rows are zero; every group owns a tile, so
    every block of the result is written). Grid (K blocks, N blocks, row
    tiles), the tiles inside as the reduction.

    `table_rows` is ops/embedding.py's, where a group is K consecutive rows
    of a table of that many and x marks each row's id: the result is then
    the table's own shape, [table_rows, N], the groups' blocks one under the
    other and the last one cut where the table ends (a reshape and a slice
    after the call would stand between it and the optimizer's fusion:
    PERF.md, PR 51), in blocks of whole rows of N where the float32 block
    and its copy fit, and the call runs under a name of its own,
    `embed_grad`, so that a trace counts it apart."""
    m, kdim = x.shape
    n = g.shape[1]
    tiles = plan.tile_group.shape[0]
    tm = m // tiles
    tk = lane_divisor(kdim, 1024)
    if table_rows:
        tn = lane_divisor(n, max(128, _WEIGHT_BLOCK_BYTES // (4 * tk)))
        shape, whole = (table_rows, n), ...
        out_spec = pl.BlockSpec(
            (tk, tn), lambda a, b, i, grp, used: (
                grp[_used(i, used)] * (kdim // tk) + a, b))
    else:
        tn = lane_divisor(n, 1024)
        shape, whole = (n_groups, kdim, n), 0
        out_spec = pl.BlockSpec(
            (1, tk, tn),
            lambda a, b, i, grp, used: (grp[_used(i, used)], a, b))
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, whole=whole),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(kdim // tk, n // tn, tiles),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda a, b, i, grp, used: (_used(i, used), a)),
                pl.BlockSpec((tm, tn),
                             lambda a, b, i, grp, used: (_used(i, used), b)),
            ],
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="embed_grad" if table_rows else "moe_tgmm",
    )(plan.tile_group, plan.tiles_used, x, g)


@functools.lru_cache(maxsize=None)
def _make_grouped_matmul(interpret: bool):
    @jax.custom_vjp
    def f(x, w, plan):
        return _gmm(x, w, plan, False, interpret)

    def fwd(x, w, plan):
        return f(x, w, plan), (x, w, plan)

    def bwd(res, g):
        x, w, plan = res
        # the matrices' gradient in the rows' type, widened where they are
        # masters: XLA fuses that into whatever reads it (the optimizer's
        # update), where a float32 result of the kernel would be twice the
        # bytes written here and read there (PERF.md, PR 42)
        return (_gmm(g, w, plan, True, interpret),
                _tgmm(x, g, plan, w.shape[0], interpret).astype(w.dtype),
                None)

    f.defvjp(fwd, bwd)
    return f


def grouped_matmul(x, w, plan: Plan, *, interpret: Optional[bool] = None):
    """rows [R, K] (dispatch's order) x w [G, K, N] -> [R, N]: each row by
    its own expert's matrix, float32 accumulation, the rows' type out. w in
    the rows' type or in the type it is kept in (float32 masters): `moe_gmm`
    rounds it a block at a time as `w.astype(x.dtype)` ahead of the call
    would, and w's gradient comes back in w's type, rounded to the rows':
    the same bits either way."""
    if interpret is None:
        interpret = attention._default_interpret()
    return _make_grouped_matmul(interpret)(x, w, plan)
