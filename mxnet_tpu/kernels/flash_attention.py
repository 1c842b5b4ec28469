"""Blocked (flash) attention as Pallas TPU kernels: a training forward
and its backward, the ring's carry update, and a T_q = 1 decode.

softmax(q k^T / sqrt(D)) v without a [Tq, Tk] plane in HBM. XLA writes
causal self-attention as a float32 [B, H, T, T] score plane that six
kinds of fusion write, save and read, forward and backward: forty times
the bytes of q, k, v, o and their gradients at the LM training cell's
[4, 2048, 16, 128] (PERF.md section 6, PR 51). Here the plane exists a
block at a time, in VMEM:

  * the kernels work on [B, H, T, D], the order a head's block of rows
    is a plain block in. flash_attention takes and returns [B, T, H, D]
    and transposes; behind a projection's matmul XLA assigns the
    kernel's order to the matmul's result, and the output projection
    and the backward's matmuls read the results as they lie: compiled
    for a v5e, the LM training step holds no copy or transpose of an
    array of q's size before or after a call
    (tests/test_tpu_compile.py);
  * the grid's last axis walks the PAIRS of a query block and a key
    block that hold a live score, listed from the shapes before the
    call and scalar-prefetched (kernels/grouped_matmul.py lists its
    pairs the same way): a pair above the causal diagonal is no step
    and no fetch, a pair wholly under it skips the mask;
  * the dots take the operands in the dtype they arrive in and
    accumulate in float32; the probabilities and dS go to the second
    dot in the operands' dtype; the scale multiplies the float32
    scores; the row maximum, the row sum, the logsumexp, delta and
    every accumulator are float32;
  * forward: grid (B, H, pairs by query block), the online softmax's
    (o, m, l) in scratch across a query block's pairs; it saves one
    logsumexp a row, as a ROW vector [B, H, 1, T] (the plain lane
    order, 4 bytes a row where a lane-padded column is 512);
  * backward: ONE kernel, grid (B, H, pairs by key block), working on
    the TRANSPOSED block s^T = k q^T [keys, queries], so that the saved
    logsumexp and delta = rowsum(dO * O) (XLA, one fusion) broadcast
    down the sublanes as they lie and dV += p^T dO, dK += dS^T q are
    plain matmuls; only dQ += dS k contracts a leading axis. dK and dV
    accumulate in scratch across a key block's pairs, dQ across ALL of
    a head's pairs in a [Tq, D] float32 scratch, so the scores and dP
    are computed once a pair (five matmuls a pair where separate dQ and
    dK/dV kernels make seven, and half the exponentials).

flash_blocks derives the blocks from the shapes, or says that a call
keeps the XLA text (models/transformer.py causal_attention_blocks is the
route's rule). flash_carry_block is the dense per-device block compute
under parallel/ring.py's sequence-parallel ring; reference counterpart:
the fused attention in src/operator/contrib/transformer.cu (MXNet's
interleaved_matmul_* ops), re-thought for the MXU/VMEM hierarchy instead
of warp shuffles.
"""

import functools

import numpy as np
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


# ------------------------------------------- training forward/backward --
LANES = 128
# what a call may use of a core's 128 MiB of VMEM: a pair of 1,024-row
# blocks holds ~20 MB of float32 score temporaries in the backward
VMEM_LIMIT = 64 * 2 ** 20
# a head's dQ [T, D]: the float32 accumulator and the result's two
# buffers stay under this (T <= 16,384 at D = 128, bfloat16)
DQ_BYTES = 16 * 2 ** 20
# the most rows of a query and of a key block, and the shortest sequence
# the route hands the kernels. What a v5e read at [4, 2048, 16, 128]
# bfloat16, forward + backward, ms a layer (PERF.md section 6, PR 51):
# 1,024 x 1,024 2.53, 512 x 1,024 2.69, 512 x 512 2.93, 1,024 x 512 3.34,
# 256 x 256 5.20; the XLA text 15.23. At 8,192 tokens a step the kernels
# stayed ahead of the XLA text down to T = 256 (1.39 against 2.25; the
# forward alone 0.65 against 0.61 there); MIN_SEQ keeps the lengths
# under 1,024 on the XLA text until a cell runs one
MIN_SEQ = 1024
BLOCK_Q, BLOCK_K = 1024, 1024

_FIRST, _LAST, _MASKED = 1, 2, 4


def flash_blocks(t, d, itemsize):
    """(block_q, block_k) for causal self-attention over `t` positions of
    heads `d` wide where these kernels serve it: of each, the largest
    multiple of 128 up to BLOCK_Q / BLOCK_K that divides `t`. None, and
    the caller keeps the XLA text, where `d` is no multiple of 128 (toy
    widths), where `t` is under MIN_SEQ (a short plane costs XLA little)
    or 128 does not divide it, and where a head's dQ accumulator would
    not fit (DQ_BYTES)."""
    if d % LANES or t % LANES or t < MIN_SEQ \
            or t * d * (4 + 2 * itemsize) > DQ_BYTES:
        return None
    return tuple(next(rows for rows in range(most, 0, -LANES)
                      if t % rows == 0) for most in (BLOCK_Q, BLOCK_K))


def _pairs(num_qb, num_kb, block_q, block_k, causal, by_key):
    """The (query block, key block) pairs a call steps through, as three
    int32 arrays (qi, ki, flags): the pairs that hold a live score (all
    of them without `causal`), grouped by query block with the keys
    ascending (forward) or `by_key` with the queries ascending
    (backward: there every key block also keeps its pair with the last
    query block, live or not, so that its dK and dV are written). flags:
    _FIRST / _LAST of its group, _MASKED where the diagonal crosses the
    pair."""
    live = [(qi, ki) for qi in range(num_qb) for ki in range(num_kb)
            if not causal or ki * block_k < (qi + 1) * block_q
            or (by_key and qi == num_qb - 1)]
    group = int(by_key)
    live.sort(key=lambda pair: (pair[group], pair[1 - group]))
    flags = []
    for i, (qi, ki) in enumerate(live):
        first = i == 0 or live[i - 1][group] != live[i][group]
        last = i == len(live) - 1 or live[i + 1][group] != live[i][group]
        masked = causal and (ki + 1) * block_k - 1 > qi * block_q
        flags.append(_FIRST * first + _LAST * last + _MASKED * masked)
    qi, ki = zip(*live)
    return tuple(np.asarray(x, np.int32) for x in (qi, ki, flags))


def _on(flags, bit):
    return (flags & bit) != 0


def _fwd_kernel(qi_ref, ki_ref, flag_ref, q_ref, k_ref, v_ref, o_ref,
                lse_ref, acc_sc, m_sc, l_sc, *, scale):
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]
    pair = pl.program_id(2)
    flags = flag_ref[pair]
    q_start = qi_ref[pair] * block_q
    k_start = ki_ref[pair] * block_k

    @pl.when(_on(flags, _FIRST))
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    def step(masked):
        v = v_ref[...]
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal_mask(s, q_start, k_start, block_q, block_k)
        m_prev = m_sc[...]                       # [bq, LANES], lanes equal
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row's first pair holds key 0, which every row sees: m_new is
        # a real score and a masked one underflows to 0
        p = jnp.exp(s - m_new[:, :1])
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        acc_sc[...] = alpha[:, :1] * acc_sc[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    pl.when(_on(flags, _MASKED))(lambda: step(True))
    pl.when(jnp.logical_not(_on(flags, _MASKED)))(lambda: step(False))

    @pl.when(_on(flags, _LAST))
    def _flush():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[...] = (acc_sc[...] / l[:, :1]).astype(o_ref.dtype)
        # the rows' statistic leaves as a row: [bq, LANES] -> [1, bq]
        lse_ref[...] = jnp.transpose(m_sc[...] + jnp.log(l))[:1]


def _specs(head_dim, block_q, block_k):
    """Block specs over [B, H, T, D] arrays and the [B, H, 1, T] row
    statistics, by a pair's query block (rows, stat) or key block
    (keys)."""
    def rows(b, h, pair, qi, ki, flags):
        return (b, h, qi[pair], 0)

    def keys(b, h, pair, qi, ki, flags):
        return (b, h, ki[pair], 0)

    def stat(b, h, pair, qi, ki, flags):
        return (b, h, 0, qi[pair])

    return (pl.BlockSpec((None, None, block_q, head_dim), rows),
            pl.BlockSpec((None, None, block_k, head_dim), keys),
            pl.BlockSpec((None, None, 1, block_q), stat))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT)


@functools.partial(jax.jit,
                   static_argnames=("causal", "scale", "block_q", "block_k",
                                    "interpret"))
def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    """q [B, H, Tq, D], k, v [B, H, Tk, D] ->
    (o [B, H, Tq, D], lse [B, H, 1, Tq] float32)."""
    b, heads, seq_q, head_dim = q.shape
    pairs = _pairs(seq_q // block_q, k.shape[2] // block_k, block_q,
                   block_k, causal, by_key=False)
    rows, keys, stat = _specs(head_dim, block_q, block_k)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, heads, len(pairs[0])),
            in_specs=[rows, keys, keys],
            out_specs=[rows, stat],
            scratch_shapes=[
                # the online softmax's (o, m, l) across a row's pairs
                pltpu.VMEM((block_q, head_dim), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, heads, 1, seq_q), jnp.float32)],
        compiler_params=_PARAMS,
        name="flash_fwd",
        interpret=interpret,
    )(*pairs, q, k, v)


def _bwd_kernel(qi_ref, ki_ref, flag_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_sc, dk_sc,
                dv_sc, *, scale):
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]
    pair = pl.program_id(2)
    flags = flag_ref[pair]
    q_start = qi_ref[pair] * block_q
    k_start = ki_ref[pair] * block_k

    @pl.when(pair == 0)
    def _init_dq():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(_on(flags, _FIRST))
    def _init_dkv():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(masked):
        """Everything [keys, queries]: the statistics are rows."""
        q, k, do = q_ref[...], k_ref[...], do_ref[...]
        st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        if masked:
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                       st.shape, 0)
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                       st.shape, 1)
            st = jnp.where(q_pos >= k_pos, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[...])
        dpt = jax.lax.dot_general(v_ref[...], do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[...])).astype(q.dtype)
        dv_sc[...] += jnp.dot(pt.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dk_sc[...] += jnp.dot(dst, q, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(q_start, block_q), block_q)
        dq_sc[rows, :] += jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    pl.when(_on(flags, _MASKED))(lambda: step(True))
    pl.when(jnp.logical_not(_on(flags, _MASKED)))(lambda: step(False))

    @pl.when(_on(flags, _LAST))
    def _flush_dkv():
        # s = scale * q k^T: the factor dS^T q still owes
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when(pair == pl.num_programs(2) - 1)
    def _flush_dq():
        dq_ref[...] = (dq_sc[...] * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "scale", "block_q", "block_k",
                                    "interpret"))
def _flash_bwd(q, k, v, o, lse, do, causal, scale, block_q, block_k,
               interpret):
    b, heads, seq_q, head_dim = q.shape
    pairs = _pairs(seq_q // block_q, k.shape[2] // block_k, block_q,
                   block_k, causal, by_key=True)
    # delta_i = sum_d dO_i O_i, one XLA fusion, as a row like lse
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    rows, keys, stat = _specs(head_dim, block_q, block_k)
    whole = pl.BlockSpec((None, None, seq_q, head_dim),
                         lambda b_, h, pair, qi, ki, flags: (b_, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, heads, len(pairs[0])),
            in_specs=[rows, keys, keys, rows, stat, stat],
            # a head's dQ is one block, written when its pairs are done
            out_specs=[whole, keys, keys],
            scratch_shapes=[
                pltpu.VMEM((seq_q, head_dim), jnp.float32),
                pltpu.VMEM((block_k, head_dim), jnp.float32),
                pltpu.VMEM((block_k, head_dim), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_PARAMS,
        name="flash_bwd",
        interpret=interpret,
    )(*pairs, q, k, v, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                      interpret)[0]


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        interpret)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, res, do):
    return tuple(_flash_bwd(*res, do, causal, scale, block_q, block_k,
                            interpret))


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


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
                    interpret=None, scale=None):
    """Multi-head attention over [B, T, H, D] tensors (one dtype).

    Equivalent to softmax(scale q k^T) v (`scale` None: 1 / sqrt(D))
    computed blockwise in VMEM with K/V streamed from HBM.
    Differentiable via the flash backward (recompute + saved logsumexp).
    Block sizes (default: BLOCK_Q, BLOCK_K) clamp to the sequence
    lengths and are gcd-adjusted to divide them. `interpret` defaults to
    True off TPU so the same code runs everywhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    seq_q, head_dim = q.shape[1], q.shape[3]
    # clamp to the sequence, then gcd-adjust a non-dividing block — one
    # deterministic rule for explicit args and short/odd smoke shapes
    # alike (callers need no block math of their own). A collapsing gcd
    # (e.g. prime T) would silently build a pathologically fine grid,
    # so blocks that fall below _MIN_BLOCK fall back to ONE
    # full-sequence block with a warning instead (ADVICE r5).
    block_q = _adjust_block(block_q or BLOCK_Q, seq_q, "block_q")
    block_k = _adjust_block(block_k or BLOCK_K, k.shape[1], "block_k")
    heads_first = lambda x: x.transpose(0, 2, 1, 3)
    out = _flash(heads_first(q), heads_first(k), heads_first(v),
                 bool(causal),
                 float(head_dim ** -0.5 if scale is None else scale),
                 block_q, block_k, bool(interpret))
    return heads_first(out)
