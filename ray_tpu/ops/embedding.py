"""The token-embedding lookup: the rows the batch names, and only those.

`table.astype(dtype)[tokens]` rounds the whole float32 table for the rows
of one batch, and XLA's transpose of its row gather is a scatter-add into
a `[V, d]` zero table, which a TPU serialises row by row (ops/moe.py says
the same of the experts' rows). On one device `embed_lookup` does neither:

  - forward: gather the master rows, round the gathered rows. Each element
    is rounded once either way, so the bits are those of rounding the table
    and gathering; no `[V, d]` copy in the model's type is made. That is
    where the batch names no more rows than the table has: XLA's row gather
    costs by rows x columns whatever the type (16.6 ns a row of 1024
    columns out of HBM), so gathering T master rows saves the cast's pass
    over V rows and no more; with more tokens than rows (gpt2s: 65 536 of
    50 304) the rounded table is the smaller thing to make, it may fit
    VMEM, where the gather is five times faster, and today's cast stays
    (PERF.md, PR 51: 0.44 ms against 1.12);
  - backward: a grouped product, with ops/moe.py's pieces at one choice a
    token. The "experts" are groups of 256 consecutive vocabulary rows:
    the ids are ordered by group into tile-padded rows (`order_slots`,
    `lay_out` over room for every id: all groups are here, so there is no
    bounded row space and no fallback), the row gradients are gathered into
    that order (`dispatch`), and a group's `[256, d]` of the table's
    gradient is onehot^T g over its rows, where onehot `[R, 256]` marks
    each row's id within its group (all zero on a padding row): `moe_tgmm`'s
    body under the name `embed_grad`. A vocabulary row's gradient is the
    float32 sum of its tokens' rows, rounded once to the model's type and
    widened where the optimizer reads it, as the experts' matrices' is.

Under a mesh of more than one device the table is cut over `vocab`
(parallel/sharding.py) and the gradient needs a sum over the batch's
shards, which GSPMD places and a Mosaic call cannot be partitioned for:
there the lookup is the expression above, op for op. Which of the two runs
is read off the mesh, nothing else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import attention, moe

# Vocabulary rows of one group: two lane tiles. The one-hot's width is the
# product's work and the groups' number its padding; 512 read within 0.05 ms
# of 256 at every cell's shape (PERF.md, PR 51).
_GROUP_ROWS = 256
# The MXU's height: a taller tile only pads (gpt2s, 0.4 ms more at 256).
_MAX_TILE_ROWS = 128


def tile_rows(n_ids: int, n_groups: int, dtype) -> int:
    """Rows of one tile, from the shape alone: the largest power of two up
    to the mean group, between the type's sublane packing and 128, so that
    the call's grid (n_ids / rows + n_groups steps of ~0.35 us) stays in
    the hundreds and the padding, half a tile a group, under half the ids."""
    rows = moe._sublanes(dtype)
    while rows * 2 <= min(_MAX_TILE_ROWS, n_ids // n_groups):
        rows *= 2
    return rows


def table_gradient(ids, g, vocab: int, *, rows=None):
    """ids [T] int, g [T, d] -> [vocab, d] in g's type: row v is the
    float32 sum of the g rows whose id is v, rounded once. An id outside
    0 .. vocab - 1 gets no row and adds nothing."""
    t, d = g.shape
    group = _GROUP_ROWS
    n_groups = -(-vocab // group)
    rows = rows or tile_rows(t, n_groups, g.dtype)
    if t + 1 + t * group >= 2 ** 31:
        raise ValueError(f"{t} ids of one batch: a slot and its place in "
                         "its group no longer pack into one int32")
    ids = ids.reshape(t).astype(jnp.int32)
    ids = jnp.where((ids >= 0) & (ids < vocab), ids, n_groups * group)
    order = moe.order_slots((ids // group)[:, None], n_groups, rows,
                            partial=True)
    # The row space takes ONE element gather (6.6 ns an element on the
    # chip, as much as a whole row of g): the slots are sorted by id, which
    # is by group, and the order carries, packed beside each slot, its id's
    # place within the group; past t, so that lay_out's mark of a padding
    # row, t, stays apart. (order_slots' own sorts are then dead code: its
    # sizes and offsets are what is read.)
    by_id, slot = lax.sort((ids, jnp.arange(t, dtype=jnp.int32)), num_keys=1)
    packed = t + 1 + slot * group + by_id % group
    plan = moe.lay_out(order._replace(order=packed), rows,
                       moe._every_slot(t, n_groups, rows))
    held = plan.row_slot > t
    carried = plan.row_slot - (t + 1)
    plan = plan._replace(row_slot=jnp.where(held, carried // group, t))
    # a row's id within its group; -1, which no column matches, on padding
    within = jnp.where(held, carried % group, -1)
    onehot = (within[:, None] == jnp.arange(group, dtype=jnp.int32)[None, :]
              ).astype(g.dtype)
    return moe._tgmm(onehot, moe.dispatch(g, plan), plan, n_groups,
                     attention._default_interpret(), table_rows=vocab)


@functools.lru_cache(maxsize=None)
def _make_lookup(vocab: int, table_dtype, dtype):
    @jax.custom_vjp
    def lookup(table, tokens):
        if tokens.size <= vocab:
            return table[tokens].astype(dtype)
        return table.astype(dtype)[tokens]

    def fwd(table, tokens):
        return lookup(table, tokens), tokens

    def bwd(tokens, g):
        # in the model's type, widened where the table is a master: XLA
        # fuses that into whatever reads it (the optimizer's update)
        dtable = table_gradient(tokens, g.reshape(tokens.size, -1), vocab)
        return dtable.astype(table_dtype), None

    lookup.defvjp(fwd, bwd)
    return lookup


def embed_lookup(table, tokens, dtype, mesh=None):
    """table [V, d] (float32 masters or the model's type), tokens [B, S]
    int -> [B, S, d] in `dtype`: `table.astype(dtype)[tokens]`, bit for
    bit. mesh: as `models/gpt.py:_per_shard` reads it; None or one device
    takes the lookup above, more than one today's expression."""
    if mesh is not None and mesh.size > 1:
        return table.astype(dtype)[tokens]
    return _make_lookup(table.shape[0], jnp.dtype(table.dtype),
                        jnp.dtype(dtype))(table, tokens)
