"""The operator between the two projections of a gated short-convolution
layer: (B, C, X, w) -> C * filter(B * X).

B, C and X are [batch, seq, channels], the layout the projections either
side of it use; w is [channels, taps], one causal filter a channel:

    u = B * X;   m_t = sum_j w[:, j] * u_{t - (taps - 1) + j};   out = C * m

with u zero before the sequence's start (a cross-correlation, as a depthwise
Conv1d with left padding taps - 1 cut to seq). The shift runs along seq, the
second-minor dimension of the layout, so nothing is transposed. Products and
sums are float32, rounded once to the inputs' dtype. No activation: both
gates are products.

Two formulations. `short_conv_reference` is jnp (the oracle, differentiated
by autodiff; XLA fuses it into elementwise passes). `short_conv_fwd` and
`short_conv_bwd` (names in util/profiling.KERNELS) are one Pallas pass each
under a custom_vjp: the forward reads B, C, X and writes out; the backward
reads them and the cotangent, recomputes u and m, and writes dB, dC, dX and
the filter's gradient summed over its block's rows (the blocks' sums are
added outside). A block is [rows, cols] of one sequence; the taps - 1 rows a
shift needs from the next block along the sequence come through a second
BlockSpec on the same array, one sublane tile of rows (`halo`), so the grid
has no order and no carry. Blocks follow from the shape (`_conv_blocks`); a
shape that does not tile takes the jnp formulation.

The plain form beside it, for a layer that filters a projection and gates
nothing (`silu_conv`: x, w -> silu(filter(x)), the convolution a gated
delta-rule layer puts on q, k and v): the same blocks, halo and shifts under
names of their own, `conv_silu_fwd` (reads x, writes the result) and
`conv_silu_bwd` (reads x and the cotangent, computes the filtered x again,
in the block and in the rows after it whose cotangents reach back into it,
and writes dx and the filter's gradient); `silu_conv_reference` is its jnp.
The plain form takes a bias a channel (added before the SiLU): one more row
of the taps' operand, whose gradient the backward kernel sums beside theirs.
"""

from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.ops.attention import LANES, lane_divisor

# (rows, cols, halo) of the [seq, channels] plane one grid step moves, and
# the rows of the neighbouring block it reads beside them.
_ConvBlocks = collections.namedtuple("_ConvBlocks", "rows cols halo")

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"),
    vmem_limit_bytes=48 << 20)


def short_conv_reference(gate_in, gate_out, value, taps):
    """gate_in (B), gate_out (C), value (X): [batch, seq, channels];
    taps: [channels, L]. The filter as L shifted products."""
    f32 = jnp.float32
    u = gate_in.astype(f32) * value.astype(f32)
    seq, n = u.shape[1], taps.shape[1]
    # padded[t] = u[t - (n - 1)]: zeros before the sequence's start
    padded = jnp.pad(u, ((0, 0), (n - 1, 0), (0, 0)))
    mixed = sum(taps[:, j].astype(f32) * padded[:, j:j + seq]
                for j in range(n))
    return (gate_out.astype(f32) * mixed).astype(gate_in.dtype)


def _conv_blocks(seq: int, channels: int, n_taps: int,
                 itemsize: int) -> Optional[_ConvBlocks]:
    """Blocks of both kernels, from the shape alone; None for a shape the
    kernels do not tile. 512 x 512: seven two-byte blocks in flight, two
    buffers each, beside the float32 intermediates, in 48 MB of VMEM."""
    if itemsize not in (2, 4):
        return None
    halo = 32 // itemsize              # rows of one register: 8 fp32, 16 bf16
    if (channels % LANES or seq % halo or seq < 2 * halo
            or n_taps - 1 > halo):
        return None
    rows = max(r for r in range(halo, min(seq, 512) + 1, halo)
               if seq % r == 0)
    return _ConvBlocks(rows, lane_divisor(channels, 512), halo)


def _shifted(u, beside, k: int, down: bool):
    """u [rows, cols] moved k rows along the sequence: down, row t holds
    u[t - k] and the first k rows come from the end of `beside` (the halo
    before the block); up, row t holds u[t + k] and the last k come from
    the start of `beside` (the halo after it)."""
    if k == 0:
        return u
    rows, halo = u.shape[0], beside.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, beside.shape, 0)
    if down:
        moved = pltpu.roll(u, k, 0)
        edge = jnp.where(row < k, pltpu.roll(beside, k, 0), moved[:halo])
        return (edge if rows == halo
                else jnp.concatenate([edge, moved[halo:]], axis=0))
    moved = pltpu.roll(u, rows - k, 0)
    edge = jnp.where(row >= halo - k, pltpu.roll(beside, halo - k, 0),
                     moved[rows - halo:])
    return (edge if rows == halo
            else jnp.concatenate([moved[:rows - halo], edge], axis=0))


def _fwd_kernel(b_ref, x_ref, c_ref, b_prev_ref, x_prev_ref, w_ref, o_ref):
    """Grid (batch, seq block, channel block)."""
    f32 = jnp.float32
    n = w_ref.shape[0]
    u = b_ref[0].astype(f32) * x_ref[0].astype(f32)
    # the halo's rows where the block has a neighbour, zeros at the start
    before = jnp.where(pl.program_id(1) > 0, b_prev_ref[0].astype(f32)
                       * x_prev_ref[0].astype(f32), 0.0)
    mixed = sum(w_ref[n - 1 - k:n - k, :] * _shifted(u, before, k, True)
                for k in range(n))
    o_ref[0] = (c_ref[0].astype(f32) * mixed).astype(o_ref.dtype)


def _bwd_kernel(b_ref, x_ref, c_ref, g_ref, b_prev_ref, x_prev_ref,
                c_next_ref, g_next_ref, w_ref,
                db_ref, dx_ref, dc_ref, dw_ref):
    """Grid (batch, seq block, channel block). m is computed again; dm = g
    * C is shifted the other way, so it needs the rows after the block."""
    f32 = jnp.float32
    n = w_ref.shape[0]
    i = pl.program_id(1)
    b, x = b_ref[0].astype(f32), x_ref[0].astype(f32)
    g = g_ref[0].astype(f32)
    u = b * x
    before = jnp.where(i > 0, b_prev_ref[0].astype(f32)
                       * x_prev_ref[0].astype(f32), 0.0)
    dm = g * c_ref[0].astype(f32)
    after = jnp.where(i < pl.num_programs(1) - 1, g_next_ref[0].astype(f32)
                      * c_next_ref[0].astype(f32), 0.0)
    mixed, du = jnp.zeros_like(u), jnp.zeros_like(u)
    for k in range(n):
        tap = w_ref[n - 1 - k:n - k, :]
        back = _shifted(u, before, k, True)
        mixed = mixed + tap * back
        du = du + tap * _shifted(dm, after, k, False)
        dw_ref[0, 0, n - 1 - k:n - k, :] = jnp.sum(dm * back, axis=0,
                                                   keepdims=True)
    dc_ref[0] = (g * mixed).astype(dc_ref.dtype)
    db_ref[0] = (du * x).astype(db_ref.dtype)
    dx_ref[0] = (du * b).astype(dx_ref.dtype)


def _specs(blocks: _ConvBlocks, seq: int):
    """(a block of [B, S, d], the halo before it, the halo after it)."""
    rows, cols, halo = blocks
    per, last = rows // halo, seq // halo - 1
    main = pl.BlockSpec((1, rows, cols), lambda b, i, j: (b, i, j))
    prev = pl.BlockSpec((1, halo, cols),
                        lambda b, i, j: (b, jnp.maximum(i * per - 1, 0), j))
    nxt = pl.BlockSpec((1, halo, cols),
                       lambda b, i, j: (b, jnp.minimum((i + 1) * per, last),
                                        j))
    return main, prev, nxt


def _taps_spec(n: int, cols: int):
    """The taps [n, channels] of a channel block, whatever the row block."""
    return pl.BlockSpec((n, cols), lambda b, i, j: (0, j))


@functools.lru_cache(maxsize=None)
def _make_conv_fn(blocks: _ConvBlocks, interpret: bool):
    """short_conv_fwd with short_conv_bwd as its backward; the residuals
    are the four inputs."""
    rows, cols, _halo = blocks

    def forward(gate_in, gate_out, value, taps):
        batch, seq, d = gate_in.shape
        main, prev, _nxt = _specs(blocks, seq)
        return pl.pallas_call(
            _fwd_kernel,
            grid=(batch, seq // rows, d // cols),
            in_specs=[main, main, main, prev, prev,
                      _taps_spec(taps.shape[1], cols)],
            out_specs=main,
            out_shape=jax.ShapeDtypeStruct(gate_in.shape, gate_in.dtype),
            compiler_params=_PARAMS,
            interpret=interpret,
            name="short_conv_fwd",
        )(gate_in, value, gate_out, gate_in, value,
          taps.astype(jnp.float32).T)

    @jax.custom_vjp
    def f(gate_in, gate_out, value, taps):
        return forward(gate_in, gate_out, value, taps)

    def fwd(gate_in, gate_out, value, taps):
        return forward(gate_in, gate_out, value, taps), (
            gate_in, gate_out, value, taps)

    def bwd(residuals, g):
        gate_in, gate_out, value, taps = residuals
        batch, seq, d = gate_in.shape
        n = taps.shape[1]
        main, prev, nxt = _specs(blocks, seq)
        like = jax.ShapeDtypeStruct(gate_in.shape, gate_in.dtype)
        d_in, d_value, d_out, d_taps = pl.pallas_call(
            _bwd_kernel,
            grid=(batch, seq // rows, d // cols),
            in_specs=[main, main, main, main, prev, prev, nxt, nxt,
                      _taps_spec(n, cols)],
            out_specs=[main, main, main,
                       pl.BlockSpec((1, 1, n, cols),
                                    lambda b, i, j: (b, i, 0, j))],
            out_shape=[like, like, like,
                       jax.ShapeDtypeStruct((batch, seq // rows, n, d),
                                            jnp.float32)],
            compiler_params=_PARAMS,
            interpret=interpret,
            name="short_conv_bwd",
        )(gate_in, value, gate_out, g, gate_in, value, gate_out, g,
          taps.astype(jnp.float32).T)
        return (d_in, d_out, d_value,
                jnp.sum(d_taps, axis=(0, 1)).T.astype(taps.dtype))

    f.defvjp(fwd, bwd)
    return f


def short_conv(gate_in, gate_out, value, taps, *,
               interpret: Optional[bool] = None):
    """C * filter(B * X): see the module's docstring. gate_in (B), gate_out
    (C), value (X): [batch, seq, channels]; taps: [channels, L]."""
    _, seq, channels = gate_in.shape
    blocks = _conv_blocks(seq, channels, taps.shape[1],
                          gate_in.dtype.itemsize)
    if blocks is None:
        return short_conv_reference(gate_in, gate_out, value, taps)
    if interpret is None:
        interpret = attention._default_interpret()
    return _make_conv_fn(blocks, interpret)(gate_in, gate_out, value, taps)


# ---------------------------------------------------------------------------
# The plain form: silu(filter(x))
# ---------------------------------------------------------------------------

def silu_conv_reference(x, taps, bias=None):
    """x: [batch, seq, channels]; taps: [channels, L]; bias: [channels] or
    None. silu of the filter as L shifted products (plus the bias), zeros
    before the sequence's start."""
    f32 = jnp.float32
    seq, n = x.shape[1], taps.shape[1]
    padded = jnp.pad(x.astype(f32), ((0, 0), (n - 1, 0), (0, 0)))
    mixed = sum(taps[:, j].astype(f32) * padded[:, j:j + seq]
                for j in range(n))
    if bias is not None:
        mixed = mixed + bias.astype(f32)
    return jax.nn.silu(mixed).astype(x.dtype)


def _filtered(u, before, w_ref, n: int):
    """(the filter's output on u [rows, cols] with `before` the halo ahead
    of it, u moved down by each tap's rows). w_ref: the n taps' rows and,
    where the filter has a bias, one more row that holds it."""
    moved = [_shifted(u, before, k, True) for k in range(n)]
    mixed = sum(w_ref[n - 1 - k:n - k, :] * moved[k] for k in range(n))
    if w_ref.shape[0] > n:
        mixed = mixed + w_ref[n:n + 1, :]
    return mixed, moved


def _silu_fwd_kernel(x_ref, x_prev_ref, w_ref, o_ref, *, n: int):
    """Grid (batch, seq block, channel block)."""
    f32 = jnp.float32
    before = jnp.where(pl.program_id(1) > 0, x_prev_ref[0].astype(f32), 0.0)
    mixed, _ = _filtered(x_ref[0].astype(f32), before, w_ref, n)
    o_ref[0] = (mixed * jax.nn.sigmoid(mixed)).astype(o_ref.dtype)


def _silu_slope(mixed):
    """d silu(m) / d m."""
    s = jax.nn.sigmoid(mixed)
    return s * (1.0 + mixed * (1.0 - s))


def _silu_bwd_kernel(x_ref, g_ref, x_prev_ref, x_next_ref, g_next_ref, w_ref,
                     dx_ref, dw_ref, *, n: int):
    """Grid (batch, seq block, channel block). dm = g * silu'(m) is shifted
    the other way, so it needs m in the rows after the block: the filter
    over the halo after it, whose own rows before are the block's last. A
    bias's gradient is dm summed, one more row of dw."""
    f32 = jnp.float32
    i = pl.program_id(1)
    x = x_ref[0].astype(f32)
    halo = x_prev_ref.shape[1]
    before = jnp.where(i > 0, x_prev_ref[0].astype(f32), 0.0)
    mixed, moved = _filtered(x, before, w_ref, n)
    dm = g_ref[0].astype(f32) * _silu_slope(mixed)
    mixed_after, _ = _filtered(x_next_ref[0].astype(f32),
                               x[x.shape[0] - halo:], w_ref, n)
    after = jnp.where(i < pl.num_programs(1) - 1, g_next_ref[0].astype(f32)
                      * _silu_slope(mixed_after), 0.0)
    dx = jnp.zeros_like(x)
    for k in range(n):
        dx = dx + w_ref[n - 1 - k:n - k, :] * _shifted(dm, after, k, False)
        dw_ref[0, 0, n - 1 - k:n - k, :] = jnp.sum(dm * moved[k], axis=0,
                                                   keepdims=True)
    if w_ref.shape[0] > n:
        dw_ref[0, 0, n:n + 1, :] = jnp.sum(dm, axis=0, keepdims=True)
    dx_ref[0] = dx.astype(dx_ref.dtype)


@functools.lru_cache(maxsize=None)
def _make_silu_conv_fn(blocks: _ConvBlocks, interpret: bool):
    """conv_silu_fwd with conv_silu_bwd as its backward; the residuals are
    the inputs. f(x, w): w [channels, L] the taps, or [channels, L + 1] the
    taps and a bias a channel in the last column (`silu_conv` joins them and
    parts the gradient: the kernels see one more row of the same operand)."""
    rows, cols, _halo = blocks

    def forward(x, w, n):
        batch, seq, d = x.shape
        main, prev, _nxt = _specs(blocks, seq)
        return pl.pallas_call(
            functools.partial(_silu_fwd_kernel, n=n),
            grid=(batch, seq // rows, d // cols),
            in_specs=[main, prev, _taps_spec(w.shape[1], cols)],
            out_specs=main,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=_PARAMS,
            interpret=interpret,
            name="conv_silu_fwd",
        )(x, x, w.astype(jnp.float32).T)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def f(x, w, n):
        return forward(x, w, n)

    def fwd(x, w, n):
        return forward(x, w, n), (x, w)

    def bwd(n, residuals, g):
        x, w = residuals
        batch, seq, d = x.shape
        held = w.shape[1]
        main, prev, nxt = _specs(blocks, seq)
        dx, d_w = pl.pallas_call(
            functools.partial(_silu_bwd_kernel, n=n),
            grid=(batch, seq // rows, d // cols),
            in_specs=[main, main, prev, nxt, nxt, _taps_spec(held, cols)],
            out_specs=[main, pl.BlockSpec((1, 1, held, cols),
                                          lambda b, i, j: (b, i, 0, j))],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((batch, seq // rows, held, d),
                                            jnp.float32)],
            compiler_params=_PARAMS,
            interpret=interpret,
            name="conv_silu_bwd",
        )(x, g, x, x, g, w.astype(jnp.float32).T)
        return dx, jnp.sum(d_w, axis=(0, 1)).T.astype(w.dtype)

    f.defvjp(fwd, bwd)
    return f


def silu_conv(x, taps, bias=None, *, interpret: Optional[bool] = None):
    """silu(filter(x) + bias): the plain form of the module's docstring. x:
    [batch, seq, channels]; taps: [channels, L]; bias: [channels] or None
    (the forward adds it before the SiLU; its gradient is summed in the
    kernel that sums the filter's)."""
    _, seq, channels = x.shape
    blocks = _conv_blocks(seq, channels, taps.shape[1], x.dtype.itemsize)
    if blocks is None:
        return silu_conv_reference(x, taps, bias)
    if interpret is None:
        interpret = attention._default_interpret()
    w = taps if bias is None else jnp.concatenate(
        [taps, bias.astype(taps.dtype)[:, None]], axis=1)
    return _make_silu_conv_fn(blocks, interpret)(x, w, taps.shape[1])
