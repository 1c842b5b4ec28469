"""Blocked (flash) attention as a Pallas TPU kernel — streamed K/V and
a custom flash backward.

Softmax(QK^T)V without materialising the [Tq, Tk] score matrix in HBM.
Forward: grid (batch*heads, q_blocks, k_blocks); each step stages one
[block_q, D] query block and one [block_k, D] key/value block into VMEM
through BlockSpec index maps (K/V live in HBM and STREAM block by block
— nothing holds the full sequence in VMEM, so sequence length is bounded
by HBM, not VMEM). The online-softmax accumulator (o, m, l) lives in
VMEM scratch and is carried across the k axis, which is the innermost,
sequential ("arbitrary") grid dimension.

Backward: the standard flash decomposition with recompute —
  delta = rowsum(dO * O)                      (jnp, fused by XLA)
  dQ kernel: grid (bh, q_blocks, k_blocks), accumulates over k
  dK/dV kernel: grid (bh, k_blocks, q_blocks), accumulates over q
using the saved per-row logsumexp instead of the (m, l) pair, so only
per-row statistics are saved — activation memory is O(T * _STAT_LANES)
(the lane-padded stat layout below), not O(T^2).

This is the dense per-device block compute under parallel/ring.py's
sequence-parallel ring; reference counterpart: the fused attention in
src/operator/contrib/transformer.cu (MXNet's interleaved_matmul_*
ops), re-thought for the MXU/VMEM hierarchy instead of warp shuffles.
"""

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# masking value, stat-lane layout, block clamp rule, and the per-shape
# block_k choice cache are shared with kernels/paged_decode.py — one
# source of truth for both kernel families (kernels/common.py)
from .common import (NEG_INF as _NEG_INF, STAT_LANES as _STAT_LANES,
                     causal_mask as _causal_mask, choose_block_k)
from .common import adjust_block as _adjust_block_common


# ------------------------------------------------------------- forward --
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc,
                *, causal, scale, num_kb):
    block_q, head_dim = q_ref.shape
    block_k = k_ref.shape[0]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    q_start = qi * block_q
    k_start = ki * block_k
    # blocks strictly above the causal diagonal contribute nothing
    live = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, q_start, k_start, block_q, block_k)
        m_prev = m_sc[...]                       # [bq, LANES], lanes equal
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_sc[...] = alpha[:, :1] * acc_sc[...] + pv
        m_sc[...] = m_new

    @pl.when(ki == num_kb - 1)
    def _flush():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[...] = (acc_sc[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = m_sc[...] + jnp.log(l)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret"))
def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    """q: [BH, Tq, D], k/v: [BH, Tk, D] ->
    (o [BH, Tq, D], lse [BH, Tq, _STAT_LANES] — lanes all equal)."""
    bh, seq_q, head_dim = q.shape
    seq_k = k.shape[1]
    scale = 1.0 / (head_dim ** 0.5)
    num_kb = seq_k // block_k
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               num_kb=num_kb)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, seq_q // block_q, num_kb),
        in_specs=[
            pl.BlockSpec((None, block_q, head_dim),
                         lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_k, head_dim),
                         lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((None, block_k, head_dim),
                         lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, head_dim),
                         lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_q, _STAT_LANES),
                         lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, _STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            # (o, m, l) online-softmax carry, persistent across the
            # sequential k axis
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ----------------------------------------------- ring-carry variant -----
def _carry_kernel(off_ref, q_ref, k_ref, v_ref, oi_ref, mi_ref, li_ref,
                  o_ref, m_ref, l_ref, acc_sc, m_sc, l_sc,
                  *, causal, scale, num_kb):
    """One ring step: fold this device's current K/V shard into the
    (o, m, l) online-softmax carry. Offsets of the q and kv shards in
    the GLOBAL sequence arrive as scalars (SMEM) because they depend on
    the traced ring position."""
    block_q, head_dim = q_ref.shape
    block_k = k_ref.shape[0]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_off = off_ref[0]
    kv_off = off_ref[1]

    @pl.when(ki == 0)
    def _load_carry():
        acc_sc[...] = oi_ref[...].astype(jnp.float32)
        m_sc[...] = mi_ref[...].astype(jnp.float32)
        l_sc[...] = li_ref[...].astype(jnp.float32)

    q_start = q_off + qi * block_q
    k_start = kv_off + ki * block_k
    live = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, q_start, k_start, block_q, block_k)
        m_prev = m_sc[...]                       # [bq, LANES], lanes equal
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_sc[...] = alpha[:, :1] * acc_sc[...] + pv
        m_sc[...] = m_new

    @pl.when(ki == num_kb - 1)
    def _flush():
        o_ref[...] = acc_sc[...]
        m_ref[...] = m_sc[...]
        l_ref[...] = l_sc[...]


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "vma"))
def flash_carry_block(q, k, v, o, m, l, q_offset, kv_offset, causal,
                      block_q=128, block_k=128, interpret=None,
                      vma=None):
    """UNNORMALIZED flash update for ring attention: q [BH, Tq, D],
    k/v [BH, Tk, D], carry o [BH, Tq, D] (f32), m/l [BH, Tq] (f32);
    offsets are traced int32 scalars (global positions of element 0).
    Returns the updated (o, m, l). The caller normalizes o / l at the
    end of the ring (parallel/ring.py)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bh, seq_q, head_dim = q.shape
    seq_k = k.shape[1]
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    if seq_q % block_q or seq_k % block_k:
        raise ValueError(
            "ring shard lengths (%d, %d) must divide by blocks (%d, %d)"
            % (seq_q, seq_k, block_q, block_k))
    scale = 1.0 / (head_dim ** 0.5)
    num_kb = seq_k // block_k
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(kv_offset, jnp.int32)])

    def _struct(shape):
        # under a partially-manual shard_map the checker needs to know
        # which mesh axes the kernel outputs vary over (vma)
        if vma:
            try:
                return jax.ShapeDtypeStruct(shape, jnp.float32,
                                            vma=frozenset(vma))
            except TypeError:
                pass
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    kernel = functools.partial(_carry_kernel, causal=causal, scale=scale,
                               num_kb=num_kb)
    grid = (bh, seq_q // block_q, num_kb)
    qspec = pl.BlockSpec((None, block_q, head_dim),
                         lambda b, qi, ki: (b, qi, 0))
    kspec = pl.BlockSpec((None, block_k, head_dim),
                         lambda b, qi, ki: (b, ki, 0))
    rspec = pl.BlockSpec((None, block_q, _STAT_LANES),
                         lambda b, qi, ki: (b, qi, 0))
    stat3 = (bh, seq_q, _STAT_LANES)
    o, m3, l3 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # offsets, whole
            qspec, kspec, kspec, qspec, rspec, rspec,
        ],
        out_specs=[qspec, rspec, rspec],
        out_shape=[_struct(o.shape), _struct(stat3), _struct(stat3)],
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(offsets, q, k, v, o,
      jnp.broadcast_to(m[:, :, None], stat3),
      jnp.broadcast_to(l[:, :, None], stat3))
    return o, m3[..., 0], l3[..., 0]


# ------------------------------------------------------------ backward --
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_sc, *, causal, scale, num_kb):
    block_q, head_dim = q_ref.shape
    block_k = k_ref.shape[0]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    q_start = qi * block_q
    k_start = ki * block_k
    live = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, q_start, k_start, block_q, block_k)
        p = jnp.exp(s - lse_ref[...][:, :1])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[...][:, :1])
        dq_sc[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_kb - 1)
    def _flush():
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, causal, scale, num_qb):
    block_k, head_dim = k_ref.shape
    block_q = q_ref.shape[0]
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    q_start = qi * block_q
    k_start = ki * block_k
    # for this k block, q blocks that end before the diagonal are dead
    live = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, q_start, k_start, block_q, block_k)
        p = jnp.exp(s - lse_ref[...][:, :1])           # [bq, bk]
        dv_sc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[...][:, :1])          # [bq, bk]
        # q is already scaled by 1/sqrt(D) above, which supplies the
        # single scale factor of dK = scale * dS^T Q
        dk_sc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_qb - 1)
    def _flush():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret"))
def _flash_bwd(q, k, v, o, lse, do, causal, block_q, block_k, interpret):
    bh, seq_q, head_dim = q.shape
    seq_k = k.shape[1]
    scale = 1.0 / (head_dim ** 0.5)
    num_qb = seq_q // block_q
    num_kb = seq_k // block_k
    # delta_i = sum_d dO_i O_i — tiny elementwise+reduce, XLA fuses it;
    # broadcast into the stat-lane layout the kernels stream (lse
    # already arrives in it from the forward)
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1, keepdims=True), lse.shape)

    sspec_q = pl.BlockSpec((None, block_q, _STAT_LANES),
                           lambda b, qi, ki: (b, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale,
                          num_kb=num_kb),
        grid=(bh, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((None, block_q, head_dim),
                         lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_k, head_dim),
                         lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((None, block_k, head_dim),
                         lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((None, block_q, head_dim),
                         lambda b, qi, ki: (b, qi, 0)),
            sspec_q, sspec_q,
        ],
        out_specs=pl.BlockSpec((None, block_q, head_dim),
                               lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale,
                          num_qb=num_qb),
        grid=(bh, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec((None, block_q, head_dim),
                         lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((None, block_k, head_dim),
                         lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((None, block_k, head_dim),
                         lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((None, block_q, head_dim),
                         lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((None, block_q, _STAT_LANES),
                         lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((None, block_q, _STAT_LANES),
                         lambda b, ki, qi: (b, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, head_dim),
                         lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((None, block_k, head_dim),
                         lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------- custom vjp ---
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bh(q, k, v, causal, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return o


def _flash_bh_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bh_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, causal, block_q,
                            block_k, interpret)
    return dq, dk, dv


_flash_bh.defvjp(_flash_bh_fwd, _flash_bh_bwd)


# ------------------------------------------------------------- decode ---
def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc,
                   m_sc, l_sc, *, scale, block_k, num_kb):
    """T_q=1 step: the query rows of one KV head (1 for MHA, the G
    grouped heads for GQA) attend to that head's cache, streamed block
    by block. The valid cache length arrives per row through SMEM; key
    positions at or past it are masked out of the online softmax, so
    one compiled kernel serves every decode position. With GQA the
    cache block is read ONCE for all G query rows — the HBM saving is
    the point of grouping."""
    b = pl.program_id(0)
    ki = pl.program_id(1)
    length = len_ref[b]
    g = q_ref.shape[0]

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    k_start = ki * block_k

    @pl.when(k_start < length)
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale       # (G, D)
        k = k_ref[...].astype(jnp.float32)               # (block_k, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (g, block_k), 1)
        s = jnp.where(k_pos < length, s, _NEG_INF)
        m_prev = m_sc[...]                       # [g, LANES], lanes equal
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_sc[...] = alpha[:, :1] * acc_sc[...] + pv
        m_sc[...] = m_new

    @pl.when(ki == num_kb - 1)
    def _flush():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[...] = (acc_sc[...] / l[:, :1]).astype(o_ref.dtype)
        # lse = m + log(l): log of the true sum of exp(scores) over this
        # cache — the sufficient statistic for cross-shard combination
        # (sequence-parallel flash decoding); rows with no valid keys
        # flush to ~-inf and drop out of the combine
        lse_ref[...] = m_sc[...] + jnp.log(l)


@functools.partial(jax.jit,
                   static_argnames=("block_k", "interpret"))
def _flash_decode_bh(q, k, v, lengths, block_k, interpret):
    """q [BKV, G, D] (G query rows share each KV row — 1 for MHA, the
    group size for GQA), k/v [BKV, Tmax, D], lengths [BKV] ->
    (o [BKV, G, D], lse [BKV, G, _STAT_LANES] — lanes all equal)."""
    bkv, t_max, head_dim = k.shape
    g = q.shape[1]
    scale = 1.0 / (head_dim ** 0.5)
    num_kb = t_max // block_k
    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_k=block_k, num_kb=num_kb)
    return pl.pallas_call(
        kernel,
        grid=(bkv, num_kb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, g, head_dim), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((None, block_k, head_dim),
                         lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((None, block_k, head_dim),
                         lambda b, ki: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, g, head_dim), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((None, g, _STAT_LANES), lambda b, ki: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, g, head_dim), q.dtype),
            jax.ShapeDtypeStruct((bkv, g, _STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, head_dim), jnp.float32),
            pltpu.VMEM((g, _STAT_LANES), jnp.float32),
            pltpu.VMEM((g, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(lengths, q, k, v)


def flash_decode(q, k_cache, v_cache, lengths, block_k=None,
                 interpret=None):
    """Single-step (T_q=1) attention against a KV cache.

    q: [B, H, D] — the current token's queries.
    k_cache/v_cache: [B, Tmax, KVH, D] — preallocated cache (KVH = H
    for MHA; any divisor of H for GQA, where each cache block is read
    once per query GROUP); only the first `lengths` positions of each
    row are attended.
    lengths: int32 [B] (or scalar, broadcast) valid cache lengths.

    Decode attention is HBM-bandwidth-bound (the whole cache is read
    once per token); this kernel streams K/V blocks through VMEM with
    the query row resident and masks by the dynamic length, so the same
    compiled program serves every position. Inference-only (no vjp).
    """
    o, _ = flash_decode_with_lse(q, k_cache, v_cache, lengths,
                                 block_k=block_k, interpret=interpret)
    return o


def dense_decode_with_lse(q, k_cache, v_cache, lengths):
    """(o [B, H, D] fp32, lse [B, H] fp32) by plain XLA ops — the same
    contract as flash_decode_with_lse, without Pallas.

    On a single v5e chip this BEATS the Pallas decode kernel at serving
    shapes (chip: 4075 tok/s dense vs 841 flash at bs8/d512/8L/4096 —
    PERF.md "Chip numbers of 2026-08-01", a claim until re-measured):
    decode attention reads
    [1, T] scores, so there is no T x T materialization for a flash
    schedule to avoid, and XLA runs the whole cache read as one fused
    batched contraction while the kernel pays per-grid-step overhead
    on thousands of tiny (rows<=G, D) blocks. GQA reads the cache once
    per GROUP via the grouped einsum — no materialized repeat. Rows
    with zero valid keys return o=0, lse~-1e30 and drop out of the
    cross-shard combine.

    models.transformer._decode_attention carries the same grouped
    contraction with a deliberately different numeric profile (PV at
    cache dtype, no lse — the single-chip serving hot loop); a
    masking/scaling fix in either likely applies to both."""
    b, h, d = q.shape
    t, kvh = k_cache.shape[1], k_cache.shape[2]
    if h % kvh:
        raise ValueError("query heads %d must be a multiple of KV "
                         "heads %d" % (h, kvh))
    g = h // kvh
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    qg = q.reshape(b, kvh, g, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg,
                   k_cache.astype(jnp.float32)) / (d ** 0.5)
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    vmask = valid[:, None, None, :]
    s = jnp.where(vmask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(vmask, jnp.exp(s - m[..., None]), 0.0)
    l = p.sum(-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v_cache.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-30)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return o.reshape(b, h, d), lse.reshape(b, h)


def flash_decode_with_lse(q, k_cache, v_cache, lengths, block_k=None,
                          interpret=None):
    """flash_decode returning (o [B, H, D], lse [B, H]) — the partial
    result + its log-sum-exp, combinable across cache shards:

        m = max_i(lse_i); w_i = exp(lse_i - m)
        o = sum_i(w_i * o_i) / sum_i(w_i)

    This is the flash-decoding decomposition for sequence-parallel
    caches (each device holds a slice of the sequence).

    GQA: when the caches carry KVH < H heads (H divisible by KVH),
    query heads [j*G:(j+1)*G] share cache head j (G = H // KVH) and
    each cache block is read once per GROUP, not per query head — the
    KV-cache bandwidth saving grouped-query attention exists for.

    block_k=None picks the largest of (512, 256, 128) dividing the
    cache length (falling back to the full length): the grid runs
    (B*KVH) x (Tmax/block_k) sequential steps, so small blocks pay
    per-step overhead on tiny (G, D) tiles — the chip A/B that
    retired this kernel as the sp default measured it at 128.
    dense_decode_with_lse is the plain-XLA form that usually wins."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, heads, head_dim = q.shape
    t_max, kv_heads = k_cache.shape[1], k_cache.shape[2]
    if heads % kv_heads:
        raise ValueError("query heads %d must be a multiple of KV "
                         "heads %d" % (heads, kv_heads))
    g = heads // kv_heads
    if block_k is None:
        # memoized per shape (the choice is pure shape math, but it
        # used to sit on the per-call path): largest of (512, 256, 128)
        # dividing the cache length, else one full-length block — the
        # same cache the paged decode kernel keys its block_k on
        block_k = choose_block_k(
            t_max, shape_key=("flash_decode", b, kv_heads, g, head_dim))
    block_k = min(block_k, t_max)
    if t_max % block_k:
        raise ValueError("block_k %d must divide the cache length %d"
                         % (block_k, t_max))
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(
        b * kv_heads, x.shape[1], head_dim)
    o, lse = _flash_decode_bh(
        q.reshape(b, kv_heads, g, head_dim).reshape(
            b * kv_heads, g, head_dim),
        to_bh(k_cache), to_bh(v_cache),
        jnp.repeat(lengths, kv_heads), block_k, interpret)
    return (o.reshape(b, heads, head_dim),
            lse[..., 0].reshape(b, heads))


def _adjust_block(block, seq, name):
    """kernels.common.adjust_block with this family's name in the
    warning (kept as a module symbol — tests and callers import it)."""
    return _adjust_block_common(block, seq, name,
                                family="flash_attention")


def flash_attention(q, k, v, causal=False, block_q=None, block_k=None,
                    interpret=None):
    """Multi-head attention over [B, T, H, D] tensors.

    Equivalent to softmax(q k^T / sqrt(D)) v computed blockwise in
    VMEM with K/V streamed from HBM (sequence length is HBM-bounded).
    Differentiable via the flash backward (recompute + saved logsumexp).
    Block sizes clamp to the sequence lengths; sequences must be
    divisible by the (clamped) blocks. `interpret` defaults to True off
    TPU so the same code runs everywhere.

    block_q/block_k default to 128 (overridable per-process via
    MXNET_FLASH_BLOCK_Q / MXNET_FLASH_BLOCK_K): the grid runs
    (B*H) x (Tq/block_q) x (Tk/block_k) sequential steps, so small
    batch*heads with long T pays per-step overhead that bigger tiles
    amortize — a measurable A/B knob, same class as the decode
    kernel's block_k finding."""
    if block_q is None:
        block_q = int(os.environ.get("MXNET_FLASH_BLOCK_Q", "128"))
    if block_k is None:
        block_k = int(os.environ.get("MXNET_FLASH_BLOCK_K", "128"))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, seq_q, heads, head_dim = q.shape
    seq_k = k.shape[1]
    # clamp to the sequence, then gcd-adjust a non-dividing block —
    # one deterministic rule for explicit args, env overrides, and
    # short/odd smoke shapes alike (callers need no block math of
    # their own). A collapsing gcd (e.g. prime T) would silently build
    # a pathologically fine (B*H) x T x T grid, so blocks that fall
    # below _MIN_BLOCK fall back to ONE full-sequence block with a
    # warning instead (ADVICE r5).
    block_q = _adjust_block(block_q, seq_q, "block_q")
    block_k = _adjust_block(block_k, seq_k, "block_k")
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(
        b * heads, x.shape[1], head_dim)
    out = _flash_bh(to_bh(q), to_bh(k), to_bh(v), causal,
                    block_q, block_k, interpret)
    return out.reshape(b, heads, seq_q, head_dim).transpose(0, 2, 1, 3)
