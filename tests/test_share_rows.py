"""One chip's share of the experts (ops/moe.py): the plan with tokens that
have no slot here, the token-ordered view and `moe_run_sum`, the row space
sized for the rows expected and exact past it, against the masked dense
computation on the CPU (the kernels in interpret mode). The family that
first held a share: tests/test_latent_moe_model.py."""

import numpy as np
import pytest

from helpers.sparse_block import experts


# ---------------------------------------------------------------------------
# (a) the plan of a share, read by the rows and by the slots
# ---------------------------------------------------------------------------


def test_plan_with_tokens_that_have_no_slot_here(jax_cpu):
    """plan_dispatch(partial=True): a slot whose expert is not among the
    groups gets no row; dispatch, the grouped matmul and combine, forward
    and gradients, equal the masked dense computation over the groups."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    t, k, d, f, groups = 40, 3, 16, 8, 4
    rng = np.random.default_rng(0)
    idx = rng.integers(-4, 12, (t, k)).astype(np.int32)   # 0..3 are here
    idx[:5] = 9                                           # no slot here
    idx[5:8] = [0, 1, 2]                                  # every slot here
    here = (idx >= 0) & (idx < groups)
    assert not here[:5].any() and here[5:8].all()
    plan = moe.plan_dispatch(jnp.asarray(idx), groups, 8, partial=True)
    np.testing.assert_array_equal(plan.token_held, here)
    slots = np.asarray(plan.row_slot)
    real = slots[slots < t * k]
    assert sorted(real) == sorted(np.flatnonzero(here.reshape(-1)))
    rows_of = np.asarray(plan.token_rows)
    np.testing.assert_array_equal(slots[rows_of[here]],
                                  np.flatnonzero(here.reshape(-1)))
    assert (rows_of[~here] == 0).all()
    tile_group = np.asarray(plan.tile_group)
    for row, slot in enumerate(slots):
        if slot < t * k:
            assert idx.reshape(-1)[slot] == tile_group[row // 8]

    x = jax.random.normal(jax.random.PRNGKey(0), (t, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (groups, d, f), jnp.float32)
    weights = jax.random.uniform(jax.random.PRNGKey(2), (t, k), jnp.float32)

    def sparse(x, w, weights):
        out = moe.grouped_matmul(moe.dispatch(x, plan), w, plan)
        return (moe.combine(out, weights, plan) ** 2).sum()

    def dense(x, w, weights):
        every = jnp.einsum("td,gdf->tgf", x, w)
        mask = (jnp.asarray(idx)[..., None] == jnp.arange(groups)) \
            * weights[..., None]                            # [t, k, g]
        return (jnp.einsum("tkg,tgf->tf", mask, every) ** 2).sum()
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(sparse, (0, 1, 2))(x, w, weights)
        want = jax.value_and_grad(dense, (0, 1, 2))(x, w, weights)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=1e-4)
    assert not np.any(np.asarray(got[1][2])[~here])


def _share_plans(jnp, moe, rows=8):
    """A share's plan over a row space smaller than the slots, which
    `rows_to_tokens` reads by the rows, and the same plan without the
    token-ordered view, which it reads by the slots. 64 tokens x 4 choices,
    4 groups of 16 experts held, tiles of 8 rows (16 for bfloat16): tokens
    with none, one, two and all four of their slots here, group 2 chosen by
    nobody, and 160 rows of which the routing fills fewer."""
    t, k, groups, tiles = 64, 4, 4, 160 // rows
    rng = np.random.default_rng(11)
    idx = np.full((t, k), 9, np.int32)                     # not here
    idx[8:24, 0] = rng.choice([0, 1, 3], 16)               # one slot here
    idx[24:40, 1:3] = [[0, 3]] * 8 + [[1, 0]] * 8          # two
    idx[40:44] = [3, 1, 0, 1]                              # all four
    idx[44:, 3] = rng.choice([0, 1, 3, 9, 12], 20)         # one or none
    order = moe.order_slots(jnp.asarray(idx), groups, rows, partial=True)
    plan = moe.lay_out(order, rows, tiles)
    held = np.asarray(plan.token_held).sum(1)
    assert set(held) == {0, 1, 2, 4} and int(order.sizes[2]) == 0
    assert int(plan.tiles_used[0]) < tiles - 1             # padding tiles
    assert plan.by_token is not None
    assert tiles * rows + t < t * k
    return plan, plan._replace(by_token=None), idx


def test_the_token_ordered_view_lists_every_held_row_once(jax_cpu):
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    plan, _by_slots, idx = _share_plans(jnp, moe)
    t, k = idx.shape
    view = plan.by_token
    slots, row_slot = np.asarray(view.slots), np.asarray(plan.row_slot)
    np.testing.assert_array_equal(slots, row_slot[np.asarray(view.rows)])
    assert (np.diff(slots) >= 0).all()
    here = np.asarray(plan.token_held)
    np.testing.assert_array_equal(slots[:here.sum()],
                                  np.flatnonzero(here.reshape(-1)))
    assert (slots[here.sum():] == t * k).all()             # padding, last
    heads = np.asarray(view.heads)
    for token in range(t):
        if here[token].any():
            run = slots[heads[token]:heads[token] + here[token].sum()]
            assert (run // k == token).all()
        else:
            assert heads[token] == len(slots)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("what", ["combine", "dispatch_vjp", "combine_vjp"])
def test_by_the_rows_equals_by_the_slots(jax_cpu, what, dtype):
    """rows_to_tokens over the token-ordered view against the gather of
    every slot: combine's forward, dispatch's backward (the same sum with
    no weights) and, through them, combine's own VJP. The rows past
    tiles_used are never computed on the chip: they hold NaN here and must
    not reach a token."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    d, dt = 256, jnp.dtype(dtype)
    tile = 32 // dt.itemsize
    plan, by_slots, idx = _share_plans(jnp, moe, tile)
    t, k = idx.shape
    r = plan.row_slot.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    z = jax.random.normal(keys[0], (r, d), jnp.float32).astype(dt)
    never = int(plan.tiles_used[0]) * tile
    z = jnp.where(jnp.arange(r)[:, None] < never, z, jnp.nan)
    weights = jax.random.uniform(keys[1], (t, k), jnp.float32)
    g = jax.random.normal(keys[2], (t, d), jnp.float32).astype(dt)
    # a sum of at most four terms in another order: a rounding of the result
    tol = dict(rtol=2e-6, atol=2e-6) if dtype == "float32" \
        else dict(rtol=1e-2, atol=1e-2)

    def both(fn):
        return [np.asarray(x, np.float32) for x in fn(plan)], \
            [np.asarray(x, np.float32) for x in fn(by_slots)]
    if what == "combine":
        got, want = both(lambda p: [moe.rows_to_tokens(z, p, weights),
                                    moe.combine(z, weights, p)])
    elif what == "dispatch_vjp":
        got, want = both(lambda p: jax.vjp(
            lambda x: moe.dispatch(x, p), g)[1](z))
    else:
        z = jnp.nan_to_num(z)        # dz is taken at every row
        got, want = both(lambda p: jax.vjp(
            lambda z, w: moe.combine(z, w, p), z, weights)[1](g))
    for a, b in zip(got, want):
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, **tol)
    if what != "combine_vjp":
        # a token with nothing here gets zeros, one with one row that row
        here = np.asarray(plan.token_held)
        assert not got[0][here.sum(1) == 0].any()


def _long_run_plan(jax, moe, k, tile):
    """A share's plan at k experts a token over tiles of `tile` rows, read
    by the rows wherever `moe._run_halo` divides the tile: 128 tokens, k of
    512 experts held, and `tile` - 1 tokens with one slot here ahead of a
    token with all k, so that its run starts on a block's last row and
    takes k - 1 rows of the next block (the deepest a run can reach past
    one: 16 rows at k = 17, 32 at 33); then tokens with none, with
    several (2 .. k - 1) and with one or none."""
    t, groups = 128, k
    rng = np.random.default_rng(k)
    idx = np.full((t, k), 400, np.int32)                   # not here
    for token in range(tile - 1):                          # one slot here
        idx[token, token % k] = token % groups
    idx[tile - 1] = rng.permutation(groups)                # all k
    for token in range(tile + 8, tile + 24):               # several
        some = 2 + (token - tile - 8) % (k - 2)
        idx[token, rng.permutation(k)[:some]] = rng.permutation(groups)[:some]
    for token in range(tile + 24, t):                      # one or none
        if rng.random() < 0.5:
            idx[token, rng.integers(k)] = rng.integers(groups)
    plan = jax.jit(lambda idx: moe.lay_out(                # one compile
        moe.order_slots(idx, groups, tile, partial=True), tile,
        groups + 8))(idx)
    held = np.asarray(plan.token_held).sum(1)
    assert {0, 1, 2, k} <= set(held) and held[tile - 1] == k
    assert int(plan.tiles_used[0]) < groups + 7            # padding tiles
    assert plan.by_token is not None
    assert int(plan.by_token.heads[tile - 1]) == tile - 1
    return plan, idx


@pytest.mark.parametrize("k,dtype,tile", [
    (17, "bfloat16", 32), (17, "float32", 32),     # halo 16 / 16
    (18, "bfloat16", 64), (18, "float32", 48),     # 32 / 24
    (22, "bfloat16", 64), (22, "float32", 48),     # 32 / 24
    (33, "bfloat16", 64), (33, "float32", 64),     # 32 / 32
    (22, "bfloat16", 16),                          # 32 divides no 16: slots
], ids=str)
def test_runs_past_one_sublane_tile_go_by_the_rows(jax_cpu, k, dtype, tile):
    """`moe_run_sum`'s halo follows k: at 17, 18, 22 and 33 experts a token
    the token side goes by the rows where the halo divides the tile, and is
    the same plan's sum by the slots; `dispatch` and `combine` around a
    grouped matmul give the masked dense computation's values and
    gradients. The rows past tiles_used hold NaN and must reach no token. A
    16-row tile under k = 22 keeps the slots' form."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    d, f, dt = 128, 128, jnp.dtype(dtype)
    plan, idx = _long_run_plan(jax, moe, k, tile)
    by_slots = plan._replace(by_token=None)
    t, groups, r = idx.shape[0], k, plan.row_slot.shape[0]
    by_the_rows = tile % moe._run_halo(k, dt) == 0
    assert by_the_rows == (tile != 16)
    keys = jax.random.split(jax.random.PRNGKey(k), 4)
    z = jax.random.normal(keys[0], (r, d), jnp.float32).astype(dt)
    never = int(plan.tiles_used[0]) * tile
    z = jnp.where(jnp.arange(r)[:, None] < never, z, jnp.nan)
    weights = jax.random.uniform(keys[1], (t, k), jnp.float32)
    assert ("moe_run_sum" in str(jax.make_jaxpr(
        lambda z: moe.rows_to_tokens(z, plan, weights))(z))) == by_the_rows
    # up to 33 terms in another order, rounded once
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" \
        else dict(rtol=1e-2, atol=2e-2)
    here = np.asarray(plan.token_held)
    # combine forward (weighted) and dispatch backward (not), both ways; one
    # jit: eagerly the followers' small ops take longer than the sums
    sums = jax.jit(lambda z: [[moe.rows_to_tokens(z, p, w)
                               for p in (plan, by_slots)]
                              for w in (weights, None)])(z)
    for got, want in sums:
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, **tol)
        assert not got[here.sum(1) == 0].any()
    # unweighted, a token with one row here gets that very row
    one = np.flatnonzero(here.sum(1) == 1)[0]
    np.testing.assert_array_equal(
        got[one], np.asarray(z, np.float32)[
            np.asarray(plan.token_rows)[one][here[one]][0]])

    x = jax.random.normal(keys[2], (t, d), jnp.float32).astype(dt)
    w = (jax.random.normal(keys[3], (groups, d, f), jnp.float32)
         / np.sqrt(d)).astype(dt)

    def sparse(x, w, weights):
        y = moe.combine(moe.grouped_matmul(moe.dispatch(x, plan), w, plan),
                        weights, plan).astype(jnp.float32)
        return (y ** 2).sum(), y

    def dense(x, w, weights):
        every = jnp.einsum("td,gdf->tgf", x, w)
        mask = (jnp.asarray(idx)[..., None] == jnp.arange(groups)) \
            * weights[..., None]                            # [t, k, g]
        y = jnp.einsum("tkg,tgf->tf", mask, every)
        return (y ** 2).sum(), y
    with jax.default_matmul_precision("highest"):
        (_, y), grads = jax.jit(jax.value_and_grad(
            sparse, (0, 1, 2), has_aux=True))(x, w, weights)
        (_, y_want), grads_want = jax.jit(jax.value_and_grad(
            dense, (0, 1, 2), has_aux=True))(
                x.astype(jnp.float32), w.astype(jnp.float32), weights)
    # the rows are rounded to their type after the experts and again as
    # tokens; the gradients carry both roundings
    rel = 1e-5 if dtype == "float32" else 3e-2
    for a, b in zip((y, *grads), (y_want, *grads_want)):
        a, b = np.asarray(a, np.float32), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=rel, atol=rel * np.abs(b).max())
    assert not np.any(np.asarray(grads[2])[~here])


# ---------------------------------------------------------------------------
# (b) the share's row space: sized for the rows expected, exact past it
# ---------------------------------------------------------------------------


def _masked_dense(x, weights, idx, w_gate, w_up, w_down, first):
    """_experts by the book: every token through every held expert, the
    slots that chose it weighted in, float32."""
    import jax
    import jax.numpy as jnp
    e = w_gate.shape[0]
    mask = jnp.sum((idx[..., None] - first == jnp.arange(e))
                   * weights[..., None], axis=-2)             # [b, s, e]
    act = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, w_gate)) \
        * jnp.einsum("bsd,edf->bsef", x, w_up)
    return jnp.einsum("bsef,efd,bse->bsd", act, w_down, mask)


def _share_operands(jax, held, seed=0, b=2, s=64, k=4, d=16, f=8):
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(keys[0], (b, s, d), jnp.float32),
            jax.random.uniform(keys[1], (b, s, k), jnp.float32),
            *(0.3 * jax.random.normal(key, shape, jnp.float32)
              for key, shape in zip(keys[2:], [(held, d, f), (held, d, f),
                                               (held, f, d)])))


def _distinct_choices(rng, tokens, k, of):
    return np.stack([rng.permutation(of)[:k] for _ in range(tokens)]
                    ).astype(np.int32)


def _conditionals(jaxpr):
    """`cond` equations of a jaxpr at any depth, the kernels' bodies apart
    (a `pl.when` is one too)."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        found += eqn.primitive.name == "cond"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _conditionals(sub)
    return found


def _loss_and_grads(jax, fn, x, weights, idx, *matrices):
    """sum(y^2) and its gradients by x, the weights and the matrices, with
    whatever else fn returns."""
    def loss(x, weights, *matrices):
        y, *rest = fn(x, weights, idx, *matrices)
        return (y ** 2).sum(), (y, rest)
    with jax.default_matmul_precision("highest"):
        (_, (y, rest)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(x, weights, *matrices)
    return y, grads, rest


@pytest.mark.parametrize("held,of", [(4, 32), (8, 32)],
                         ids=["an_eighth", "a_quarter"])
def test_bounded_row_space_equals_the_one_for_every_slot(jax_cpu, monkeypatch,
                                                         held, of):
    """Random routing lands near held / of of the slots here, the bounded
    row space holds them, and _experts gives what it gives over room for
    every slot (the factor out of reach: no check, the parent's code):
    forward and all five gradients to float32 round-off."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    first = 8
    x, weights, *matrices = _share_operands(jax, held)
    idx = jnp.asarray(_distinct_choices(np.random.default_rng(of), 128, 4, of)
                      ).reshape(2, 64, 4)

    def share():       # a new function a call: no trace is found again
        return lambda *operands: experts(*operands, held=(first, of))
    y, grads, (fitted,) = _loss_and_grads(jax, share(), x, weights, idx,
                                          *matrices)
    assert fitted.shape == (1,) and float(fitted[0]) == 1.0
    assert _conditionals(
        jax.make_jaxpr(share())(x, weights, idx, *matrices).jaxpr) == 1

    monkeypatch.setattr(moe, "_ROW_SPACE_FACTOR", 1 << 20)
    assert _conditionals(
        jax.make_jaxpr(share())(x, weights, idx, *matrices).jaxpr) == 0
    y_every, grads_every, (always,) = _loss_and_grads(
        jax, share(), x, weights, idx, *matrices)
    assert float(always[0]) == 1.0          # nothing to bound: the constant
    # (the bounded row space adds a token's rows in choice order, from its
    # first held one: an ulp or two of float32 from the einsum's order)
    np.testing.assert_allclose(y, y_every, rtol=5e-6, atol=1e-6)
    for g, g_every in zip(grads, grads_every):
        np.testing.assert_allclose(g, g_every, rtol=5e-6, atol=1e-6)
    # and both are the masked dense computation
    np.testing.assert_allclose(
        y, _masked_dense(x, weights, idx, *matrices, first), atol=1e-5)


# held 4 of 32, 512 slots, 8-row tiles: 64 slots expected = 8 tiles, so the
# bounded row space is 2 x 8 + 4 = 20 tiles where every slot needs 68
@pytest.mark.parametrize("here,fits", [
    (512, 0.0),      # every token chose held experts alone
    (17 * 8, 1.0),   # one group of 17 full tiles + 3 empty groups' = 20
    (17 * 8 + 1, 0.0),                       # one row over: 21 tiles
    # the token side goes by the rows where the bounded row space runs and
    # by the slots past it: two and three slots a token here, in runs
    ("two_a_token", 1.0),     # 32 tokens x 2: two groups of 8 tiles + 2
    ("three_a_token", 0.0),   # 64 tokens x 3: three groups of 8 + 1 = 25
], ids=["every_slot_here", "exactly_at_the_bound", "one_row_over",
        "two_slots_a_token_fit", "three_slots_a_token_do_not"])
def test_past_the_bound_the_plan_for_every_slot_runs(jax_cpu, here, fits):
    """No capacity: what does not fit the bounded row space runs over room
    for every slot, and the result and its gradients are the masked dense
    computation's either way; the flag says which ran."""
    jax = jax_cpu
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    held, of, first = 4, 32, 8
    assert moe.tile_rows(512 * held // of, held, jnp.float32) == 8
    x, weights, *matrices = _share_operands(jax, held, seed=1)
    flat = np.full(512, first + held + 3, np.int32)           # not here
    if here == 512:
        flat = first + np.random.default_rng(3).integers(0, held, 512)
    elif here == "two_a_token":
        flat.reshape(128, 4)[16:80:2, 1:3] = [first + 2, first]
    elif here == "three_a_token":
        flat.reshape(128, 4)[:64, :3] = [first + 1, first + 3, first]
    else:
        flat[:here] = first                                    # one group
    idx = jnp.asarray(flat.astype(np.int32)).reshape(2, 64, 4)
    y, grads, (fitted,) = _loss_and_grads(
        jax, lambda *a: experts(*a, held=(first, of)), x, weights, idx,
        *matrices)
    assert float(fitted[0]) == fits

    def dense(x, weights, idx, *matrices):
        return (_masked_dense(x, weights, idx, *matrices, first),)
    want, want_grads, _ = _loss_and_grads(jax, dense, x, weights, idx,
                                          *matrices)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fixed,fitted_want", [
    ((0, 1), [0.0] + [1.0] * 7),
    ((0, 1, 14, 15), [0.0] + [1.0] * 6 + [0.0]),
], ids=["two_on_share_0", "a_collapsed_router"])
def test_eight_shares_one_of_them_past_its_bound_add_up_to_the_whole(
        jax_cpu, fixed, fitted_want):
    """Sixteen experts on eight chips, two each, and a router that sends
    two of every token's four choices to experts 0 and 1: share 0 gets four
    times its expectation and runs the plan for every slot, the other seven
    run bounded (their token side by the rows), and the eight partial sums
    are the uncut layer's. Or all four to experts 0, 1, 14 and 15, a
    collapsed router: shares 0 and 7 are past their bound and no token has
    a row on the other six, whose bounded row spaces are all padding."""
    jax = jax_cpu
    import jax.numpy as jnp
    of, held, k = 16, 2, 4
    x, weights, *matrices = _share_operands(jax, of, seed=2, k=k)
    rng = np.random.default_rng(5)
    free = np.setdiff1d(np.arange(of), fixed)
    idx = np.stack([np.concatenate([fixed,
                                    rng.permutation(free)[:k - len(fixed)]])
                    for _ in range(128)]).astype(np.int32).reshape(2, 64, k)
    idx = jnp.asarray(idx)
    parts, fitted = [], []
    with jax.default_matmul_precision("highest"):
        for rank in range(of // held):
            mine = [m[held * rank:held * (rank + 1)] for m in matrices]
            y, flag = experts(x, weights, idx, *mine,
                               held=(held * rank, of))
            parts.append(y)
            fitted.append(float(flag[0]))
        whole = experts(x, weights, idx, *matrices)
        want = _masked_dense(x, weights, idx, *matrices, 0)
    assert fitted == fitted_want
    np.testing.assert_allclose(sum(parts), want, atol=2e-5)
    np.testing.assert_allclose(whole, want, atol=2e-5)


def _plan_by_hand(idx, n_groups, rows):
    """The layout in numpy: slots in expert order (stable), each group
    padded to whole tiles, an empty group one tile, room for every slot."""
    n = idx.size
    flat = idx.reshape(-1)
    tiles = -(-n // rows) + n_groups
    row_slot = np.full(tiles * rows, n, np.int32)
    token_rows = np.zeros(n, np.int32)
    tile_group = np.full(tiles, n_groups - 1, np.int32)
    tile = 0
    for group in range(n_groups):
        members = np.flatnonzero(flat == group)
        row_slot[tile * rows:tile * rows + len(members)] = members
        token_rows[members] = tile * rows + np.arange(len(members))
        took = max(-(-len(members) // rows), 1)
        tile_group[tile:tile + took] = group
        tile += took
    return row_slot, token_rows.reshape(idx.shape), tile_group, tile


@pytest.mark.parametrize("tokens,k,groups,rows", [
    (37, 2, 8, 8), (128, 8, 64, 16), (40, 3, 4, 8)])
def test_the_plan_of_all_the_experts_is_what_it_was(jax_cpu, tokens, k,
                                                    groups, rows):
    """plan_dispatch(partial=False): shapes and values as the layout says,
    room for every slot, no token_held; nothing of the bound reaches it."""
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    idx = np.random.default_rng(tokens).integers(0, groups, (tokens, k)
                                                 ).astype(np.int32)
    idx[: tokens // 4] = 0                                     # skewed
    plan = moe.plan_dispatch(jnp.asarray(idx), groups, rows)
    row_slot, token_rows, tile_group, used = _plan_by_hand(idx, groups, rows)
    assert plan.token_held is None
    # every slot is a row: no token-ordered view, the two sorts of
    # order_slots and no third; nor over a share's room for every slot
    assert plan.by_token is None
    assert str(jax_cpu.make_jaxpr(
        lambda i: moe.plan_dispatch(i, groups, rows))(idx)).count(
            " sort[") == 2
    assert moe.plan_dispatch(jnp.asarray(idx), groups // 2, rows,
                             partial=True).by_token is None
    assert plan.row_slot.shape == row_slot.shape
    np.testing.assert_array_equal(plan.row_slot, row_slot)
    np.testing.assert_array_equal(plan.token_rows, token_rows)
    np.testing.assert_array_equal(plan.tile_group, tile_group)
    np.testing.assert_array_equal(plan.tiles_used, [used])


def test_tile_rows_follow_from_the_held_count():
    import jax.numpy as jnp
    from ray_tpu.ops import moe
    # the cell: 2 x 8192 tokens x 6 a token, 16 of 128 experts held
    slots = 2 * 8192 * 6
    assert moe.tile_rows(slots * 16 // 128, 16, jnp.bfloat16) == 128
    # all of them held: what olmoe's call passes is the slots themselves
    assert slots * 128 // 128 == slots
