"""Attention kernels: Pallas flash attention + ring attention (context
parallelism over the ICI ring).

Net-new relative to the reference, which has no sequence-parallel support
(SURVEY.md §5 "Long-context"): ring attention moves K/V shards around the
'sequence' mesh axis with lax.ppermute while each device accumulates
blockwise-softmax partials for its local Q shard — compute overlaps the
ICI transfer, HBM never holds the full sequence.

Layouts: q, k, v are [batch, num_heads, seq, head_dim].
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Reference implementation (small seqs, correctness baseline)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, *, causal: bool = True,
                  sm_scale: Optional[float] = None):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        qlen, klen = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((qlen, klen), dtype=bool), klen - qlen)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Pallas flash attention (single device)
# ---------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                  block_k, seq_k, causal_offset):
    """One (batch*head, q_block) program: loop K blocks w/ online softmax.

    causal_offset = seq_k - seq_q: masking is bottom-right aligned, matching
    mha_reference (query i attends keys <= i + offset). Also emits the
    per-row logsumexp (lse) residual consumed by the backward kernels.
    """
    # Dots run in the INPUT dtype (bf16 on the model path) with fp32
    # accumulation: an fp32 x fp32 MXU matmul is several times slower
    # than bf16 x bf16 -> fp32 on v5e, and upcasting q/k/v before the
    # dot was this kernel's original whole-step slowdown. Softmax math
    # stays fp32.
    q = q_ref[0]                                         # [bq, d] (in dt)
    bq = q.shape[0]
    d = q.shape[1]
    q_idx = pl.program_id(1)
    q_start = q_idx * bq

    num_k_blocks = pl.cdiv(seq_k, block_k)

    def body(i, carry):
        acc, m_prev, l_prev = carry
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk] f32
        if causal:
            q_pos = q_start + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    init = (jnp.zeros((bq, d), jnp.float32),
            jnp.full((bq,), NEG_INF, jnp.float32),
            jnp.zeros((bq,), jnp.float32))
    if causal:
        # Skip fully-masked K blocks past the (offset) diagonal.
        num_blocks = jnp.minimum(
            num_k_blocks,
            pl.cdiv((q_idx + 1) * bq + causal_offset, block_k)).astype(jnp.int32)
    else:
        num_blocks = num_k_blocks
    acc, m, l = jax.lax.fori_loop(0, num_blocks, body, init)
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def _flash_forward(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    batch, heads, seq_q, d = q.shape
    seq_k = k.shape[2]
    bh = batch * heads
    qr = q.reshape(bh, seq_q, d)
    kr = k.reshape(bh, seq_k, d)
    vr = v.reshape(bh, seq_k, d)
    kernel = functools.partial(_flash_kernel, sm_scale=sm_scale,
                               causal=causal, block_k=block_k, seq_k=seq_k,
                               causal_offset=seq_k - seq_q)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, seq_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_k, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_k, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            # lse rides as [bh, 1, seq_q]: TPU Pallas needs the last two
            # block dims divisible by (8, 128) or equal to the array dims.
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qr, kr, vr)
    return out.reshape(batch, heads, seq_q, d), lse


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, sm_scale, causal, block_k, seq_k,
                         causal_offset):
    """dQ for one (batch*head, q_block): loop K blocks.

    p = exp(s - lse); dS = p * (dO·Vᵀ - delta); dQ = scale · dS·K
    (standard flash-attention backward, FlashAttention-2 form).
    """
    # bf16 dot inputs + fp32 accumulation (see _flash_kernel dtype note).
    q = q_ref[0]                                          # [bq, d]
    do = do_ref[0]                                        # [bq, d]
    lse = lse_ref[0, 0]                                   # [bq]
    delta = delta_ref[0, 0]                               # [bq]
    bq, d = q.shape
    q_idx = pl.program_id(1)
    q_start = q_idx * bq
    num_k_blocks = pl.cdiv(seq_k, block_k)

    def body(i, dq):
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = q_start + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        # Explicit zero for masked entries: a fully-masked row has
        # lse = NEG_INF, and exp(NEG_INF - NEG_INF) would be 1, not 0.
        p = jnp.where(s > NEG_INF / 2,
                      jnp.exp(s - lse[:, None]), 0.0)     # [bq, bk]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk]
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        num_blocks = jnp.minimum(
            num_k_blocks,
            pl.cdiv((q_idx + 1) * bq + causal_offset, block_k)).astype(jnp.int32)
    else:
        num_blocks = num_k_blocks
    dq = jax.lax.fori_loop(0, num_blocks, body,
                           jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, sm_scale, causal, block_q,
                          seq_q, causal_offset):
    """dK/dV for one (batch*head, k_block): loop Q blocks.

    dV = Pᵀ·dO; dK = scale · dSᵀ·Q. Causal skip: k block starting at ks
    only sees q rows with q_pos >= k_pos, i.e. q >= ks - causal_offset.
    """
    # bf16 dot inputs + fp32 accumulation (see _flash_kernel dtype note).
    k_blk = k_ref[0]                                      # [bk, d]
    v_blk = v_ref[0]                                      # [bk, d]
    bk, d = k_blk.shape
    k_idx = pl.program_id(1)
    k_start = k_idx * bk
    num_q_blocks = pl.cdiv(seq_q, block_q)

    def body(j, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(j * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(j * block_q, block_q), :]
        lse_blk = lse_ref[0, 0, pl.ds(j * block_q, block_q)]
        delta_blk = delta_ref[0, 0, pl.ds(j * block_q, block_q)]
        s = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        if causal:
            q_pos = j * block_q + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        # See dq kernel: masked rows have lse = NEG_INF; force p to 0.
        p = jnp.where(s > NEG_INF / 2,
                      jnp.exp(s - lse_blk[:, None]), 0.0)  # [bq, bk]
        dv = dv + jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bk, d]
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk]
        ds = p * (dp - delta_blk[:, None])
        dk = dk + jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bk, d]
        return dk, dv

    if causal:
        start = jnp.maximum(
            0, (k_start - causal_offset) // block_q).astype(jnp.int32)
    else:
        start = 0
    dk, dv = jax.lax.fori_loop(
        start, num_q_blocks, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
                    interpret):
    batch, heads, seq_q, d = q.shape
    seq_k = k.shape[2]
    bh = batch * heads
    qr = q.reshape(bh, seq_q, d)
    kr = k.reshape(bh, seq_k, d)
    vr = v.reshape(bh, seq_k, d)
    gr = g.reshape(bh, seq_q, d)
    # delta_i = rowsum(dO_i * O_i): cheap elementwise, fused by XLA.
    delta = jnp.sum(gr.astype(jnp.float32)
                    * out.reshape(bh, seq_q, d).astype(jnp.float32),
                    axis=-1).reshape(bh, 1, seq_q)
    offset = seq_k - seq_q

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
        block_k=block_k, seq_k=seq_k, causal_offset=offset)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, seq_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # q
            pl.BlockSpec((1, seq_k, d), lambda b, i: (b, 0, 0)),     # k
            pl.BlockSpec((1, seq_k, d), lambda b, i: (b, 0, 0)),     # v
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # do
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),   # lse
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),   # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qr, kr, vr, gr, lse, delta)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, seq_q=seq_q, causal_offset=offset)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((1, seq_q, d), lambda b, i: (b, 0, 0)),     # q
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),   # k
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),   # v
            pl.BlockSpec((1, seq_q, d), lambda b, i: (b, 0, 0)),     # do
            pl.BlockSpec((1, 1, seq_q), lambda b, i: (b, 0, 0)),     # lse
            pl.BlockSpec((1, 1, seq_q), lambda b, i: (b, 0, 0)),     # delta
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_k, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qr, kr, vr, gr, lse, delta)
    return (dq.reshape(batch, heads, seq_q, d),
            dk.reshape(batch, heads, seq_k, d),
            dv.reshape(batch, heads, seq_k, d))


@functools.lru_cache(maxsize=None)
def _make_flash_fn(causal, sm_scale, block_q, block_k, interpret):
    """Pallas forward + Pallas backward under jax.custom_vjp.

    The backward is the flash-attention recompute form (dQ kernel + dK/dV
    kernel over saved lse/delta) — O(seq) memory, no S² logits tensor in
    HBM.
    """

    @jax.custom_vjp
    def f(q, k, v):
        out, _ = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                                interpret)
        return out

    def fwd(q, k, v):
        out, lse = _flash_forward(q, k, v, causal, sm_scale, block_q,
                                  block_k, interpret)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        q, k, v, out, lse = res
        return _flash_backward(q, k, v, out, lse, g, causal, sm_scale,
                               block_q, block_k, interpret)

    f.defvjp(fwd, bwd)
    return f


def _default_interpret() -> bool:
    """Mosaic-compiled on a TPU backend, interpreted (the Pallas software
    emulator, what the CPU tests run) on every other."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Fused attention on the MXU; O(seq) memory via online softmax.

    Sequence lengths must be multiples of the (clamped) block sizes: pad
    upstream. A ragged shape raises instead of silently running another
    kernel.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    seq_q, seq_k = q.shape[2], k.shape[2]
    if interpret is None:
        interpret = _default_interpret()
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    if seq_q % block_q or seq_k % block_k:
        raise ValueError(
            f"flash_attention needs seq_q={seq_q} and seq_k={seq_k} to be "
            f"multiples of block_q={block_q} and block_k={block_k}; pad "
            "the sequence or call mha_reference")
    fn = _make_flash_fn(causal, float(sm_scale), block_q, block_k, interpret)
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

        b, h, s, d = q_loc.shape
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
