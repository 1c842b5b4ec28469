"""Serving-native paged decode/verify attention — ONE batched-lane
Pallas kernel family for the paged x int8 x GQA x spec-verify layout.

The serving stack's hot read (`models/transformer.py
decode_step_paged` / `verify_chunk_paged`) lowers today as a fused XLA
gather that MATERIALIZES the dense [B, T, KVH, D] cache view and feeds
it to a dense contraction: every decode step moves ~3x the live cache
bytes (read pool + write copy + read copy), pays the full table
capacity T for every lane regardless of its live length, and under
int8-KV dequantizes nothing early only because the contraction is
int8 — the copy itself is still the tax. The per-sequence flash-decode
kernel is not the answer either: a [1, T] score read gives flash
scheduling nothing to skip, and the chip A/B retired it at 841 tok/s
vs 4075 dense (PERF.md round 5).

This kernel serves the real layout directly, one grid for the whole
batch:

  * block-table gathers INSIDE the grid — BlockSpec index maps read
    the scalar-prefetched tables, so pool blocks stream HBM->VMEM
    exactly once per (lane, KV head) with no dense copy in between;
  * dead steps skipped — a lane whose live length ends before a grid
    step redirects that step's DMA to the null block and skips the
    compute, so a short lane costs its LIVE length, not the table
    capacity (the adaptivity "keyed on max live length" is dynamic,
    per lane, inside one compiled program);
  * GQA head-packing — the G query heads sharing a KV head ride one
    [C*G, D] MXU contraction, reading each cache block once per group;
  * int8-KV fused dequant — codes stay int8 into the MXU (int8 x int8
    -> int32), per-block k-scales multiply scores AFTER the
    contraction and v-scales fold into the re-quantized probabilities,
    replicating `_int8_cache_attention`'s op order exactly;
  * the ragged [B, k+1] spec-verify window is the span>1 case of the
    SAME kernel: packed row r = c*G + g masks key positions
    <= pos[b] + c, which at span=1 is plain decode.

Numerics contract: pass-for-pass the score/scale/mask op order of the
dense reference paths, so greedy token streams are identical (tested
in tests/test_paged_kernel.py; residual diffs are reduction-order
ulps — int32 score/PV accumulation is exactly associative, the fp
softmax statistics carry ~1e-7 sum-order noise). To hold the int8 and
bf16 prob-quantization order (the references quantize NORMALIZED
probabilities), the kernel is TWO-PASS over the same grid — a stats
trip (m, l, amax) then a PV trip re-streaming K/V — rather than
single-pass online softmax; the second K read is the price of
bit-faithful code emission.

block_k (pool blocks staged per grid step) adapts per shape through
kernels/common.choose_block_k's process-wide cache, override
MXNET_PAGED_BLOCK_K. Wired behind MXNET_PAGED_DECODE_PALLAS=1 in
models/transformer.py; the batcher's membudget preflight covers the
jit boundary it rides in, and the attribution scopes
`paged_decode_kernel` / `paged_verify_kernel` carry its bytes.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import NEG_INF, STAT_LANES, choose_block_k

__all__ = ["paged_attention"]


def _paged_kernel(tables_ref, pos_ref, q_ref, *refs, kb, bs, num_kb,
                  span, g, int8):
    """Grid (B, KVH, 2, num_kb); trip p=0 accumulates the softmax
    statistics, trip p=1 re-reads K/V and accumulates PV. Scratch
    persists across the sequential (p, ki) axes of one (b, h)."""
    if int8:
        k_refs = refs[0:kb]
        v_refs = refs[kb:2 * kb]
        ks_refs = refs[2 * kb:3 * kb]
        vs_refs = refs[3 * kb:4 * kb]
        o_ref, acc_sc, m_sc, l_sc, amax_sc = refs[4 * kb:]
    else:
        k_refs = refs[0:kb]
        v_refs = refs[kb:2 * kb]
        o_ref, acc_sc, m_sc, l_sc = refs[2 * kb:]
        amax_sc = None
    b = pl.program_id(0)
    h = pl.program_id(1)
    p = pl.program_id(2)
    ki = pl.program_id(3)
    rows, d = q_ref.shape
    block_k = kb * bs
    pos = pos_ref[b]
    k_start = ki * block_k
    # the last key position any row of this lane may attend; steps
    # past it are dead (their DMAs were redirected to the null block
    # by the index maps — see _pool_index in paged_attention)
    live = k_start <= pos + span - 1

    @pl.when(jnp.logical_and(p == 0, ki == 0))
    def _init_stats():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        if int8:
            amax_sc[...] = jnp.zeros_like(amax_sc)

    @pl.when(jnp.logical_and(p == 1, ki == 0))
    def _init_acc():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _head_plane(scale_refs):
        """Stage this step's per-(position, head) scale planes and
        select head h's column: [block_k]. Rank-1 dynamic indexing is
        not Mosaic-lowerable, so the selection is a one-hot
        multiply-sum (exact: one nonzero term)."""
        cat = jnp.concatenate([r[...] for r in scale_refs], axis=0)
        kvh = cat.shape[1]
        sel = jax.lax.broadcasted_iota(jnp.int32,
                                       (block_k, kvh), 1) == h
        return jnp.sum(jnp.where(sel, cat, 0.0), axis=1)

    def _scores():
        """[rows, block_k] masked scores, replicating the dense
        reference op order exactly (scores are recomputed identically
        on both trips — int32 dots make them bit-stable)."""
        k = jnp.concatenate([r[...] for r in k_refs], axis=0)
        if int8:
            # _kv_quant(q) per call, like _int8_cache_attention
            qf = q_ref[...].astype(jnp.float32)
            qs = jnp.maximum(jnp.max(jnp.abs(qf), axis=-1),
                             1e-8) / 127.0
            q8 = jnp.clip(jnp.round(qf / qs[:, None]),
                          -127, 127).astype(jnp.int8)
            s = jax.lax.dot_general(
                q8, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32).astype(jnp.float32)
            s = s * qs[:, None] * _head_plane(ks_refs)[None, :] \
                / np.sqrt(d)
        else:
            s = jax.lax.dot_general(
                q_ref[...], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) / np.sqrt(d)
        # packed row r = c*G + g attends key positions <= pos + c
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        c_row = jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 0) // g
        return jnp.where(k_pos <= pos + c_row, s, NEG_INF)

    @pl.when(jnp.logical_and(live, p == 0))
    def _stats_step():
        s = _scores()
        m_prev = m_sc[...]                   # [rows, LANES], lanes equal
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new[:, :1])     # masked entries underflow to 0
        l_sc[...] = alpha * l_sc[...] + pexp.sum(axis=1, keepdims=True)
        if int8:
            av = pexp * _head_plane(vs_refs)[None, :]
            amax_sc[...] = jnp.maximum(amax_sc[...] * alpha,
                                       av.max(axis=1, keepdims=True))
        m_sc[...] = m_new

    @pl.when(jnp.logical_and(live, p == 1))
    def _pv_step():
        s = _scores()
        m = m_sc[...][:, :1]
        l = l_sc[...][:, :1]
        a = jnp.exp(s - m) / l               # normalized, like the refs
        v = jnp.concatenate([r[...] for r in v_refs], axis=0)
        if int8:
            # _kv_quant(a * vs) with the row-global scale from pass 0
            as_ = jnp.maximum(amax_sc[...][:, :1] / l, 1e-8) / 127.0
            a8 = jnp.clip(jnp.round(a * _head_plane(vs_refs)[None, :]
                                    / as_), -127, 127).astype(jnp.int8)
            acc_sc[...] += jax.lax.dot_general(
                a8, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        else:
            acc_sc[...] += jax.lax.dot_general(
                a.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(p == 1, ki == num_kb - 1))
    def _flush():
        if int8:
            l = l_sc[...][:, :1]
            as_ = jnp.maximum(amax_sc[...][:, :1] / l, 1e-8) / 127.0
            o_ref[...] = (acc_sc[...].astype(jnp.float32)
                          * as_).astype(o_ref.dtype)
        else:
            o_ref[...] = acc_sc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kb", "bs", "num_kb",
                                             "span", "g", "interpret"))
def _paged_call(q, kpool, vpool, ks, vs, tables, pos, kb, bs, num_kb,
                span, g, interpret):
    """q packed [B, KVH, span*G, D]; pools [NB, bs, KVH, D] (+ scale
    planes [NB, bs, KVH]); tables [B, num_kb*kb]; pos [B]. Returns
    o [B, KVH, span*G, D] in q.dtype."""
    int8 = ks is not None
    b, kvh, rows, d = q.shape
    block_k = kb * bs

    def _scalar_args(idx):
        return idx[:4], idx[4], idx[5]       # grid ids, tables, pos

    def _pool_index(i):
        # table entry for pool block i of grid step ki; dead steps
        # (whole step past the lane's deepest attendable position)
        # redirect to the reserved null block 0 — the DMA is cheap,
        # repeated, and never read (compute is pl.when-skipped)
        def idx(b_, h_, p_, ki_, tables_ref, pos_ref):
            blk = tables_ref[b_, ki_ * kb + i]
            live = ki_ * block_k <= pos_ref[b_] + span - 1
            return (jnp.where(live, blk, 0), 0, h_)
        return idx

    def _scale_index(i):
        def idx(b_, h_, p_, ki_, tables_ref, pos_ref):
            blk = tables_ref[b_, ki_ * kb + i]
            live = ki_ * block_k <= pos_ref[b_] + span - 1
            return (jnp.where(live, blk, 0), 0, 0)
        return idx

    def _q_index(b_, h_, p_, ki_, tables_ref, pos_ref):
        return (b_, h_, 0, 0)

    qspec = pl.BlockSpec((None, None, rows, d), _q_index)
    # Mosaic tiles the LAST TWO block dims, so a (bs, <squeezed KV
    # head>, d) block of the [NB, bs, KVH, D] pool does not lower.
    # View the pool as [NB, bs, KVH*D] (a free reshape: same bytes)
    # and take head h as lane-block h of width d — (bs, d) is then the
    # tiled pair, bs the full second-minor dim and d lane-aligned
    # (d % 128 == 0 on a TPU; interpret mode takes any d).
    kflat = kpool.reshape(-1, bs, kvh * d)
    vflat = vpool.reshape(-1, bs, kvh * d)
    kvspec = [pl.BlockSpec((None, bs, d), _pool_index(i))
              for i in range(kb)]
    in_specs = [qspec] + kvspec + kvspec
    inputs = [q] + [kflat] * kb + [vflat] * kb
    scratch = [
        pltpu.VMEM((rows, d), jnp.int32 if int8 else jnp.float32),
        pltpu.VMEM((rows, STAT_LANES), jnp.float32),
        pltpu.VMEM((rows, STAT_LANES), jnp.float32),
    ]
    if int8:
        sspec = [pl.BlockSpec((None, bs, kvh), _scale_index(i))
                 for i in range(kb)]
        in_specs += sspec + sspec
        inputs += [ks] * kb + [vs] * kb
        scratch.append(pltpu.VMEM((rows, STAT_LANES), jnp.float32))

    kernel = functools.partial(_paged_kernel, kb=kb, bs=bs,
                               num_kb=num_kb, span=span, g=g, int8=int8)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, 2, num_kb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, rows, d), _q_index),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows, d), q.dtype),
        interpret=interpret,
    )(tables, pos, *inputs)


def paged_attention(q, layer_pool, tables, pos, block_k=None,
                    interpret=None):
    """Batched-lane attention straight against one layer's block pool.

    q: [B, C, H, D] — C=1 is plain decode, C=k+1 the spec-verify
    window (row (b, c) holds the query at stream position pos[b]+c).
    layer_pool: {"k", "v": [NB, bs, KVH, D]} plus {"ks", "vs":
    [NB, bs, KVH] fp32} under int8-KV (detected by key presence, like
    every pool consumer).
    tables: [B, max_len // bs] int32 block tables (entry j covers
    positions [j*bs, (j+1)*bs); unallocated entries = null block 0).
    pos: [B] int32 — row (b, c) attends pool positions <= pos[b] + c,
    exactly the dense reference masks. The window's own K/V must
    already be written to the pool (the transformer wiring writes
    before it reads, so causal-within-window is implied by position).

    Returns [B, C, H, D] in q.dtype, matching `_decode_attention` /
    `verify_chunk_paged`'s contraction up to reduction-order ulps
    (greedy-stream identical; see the module docstring).

    block_k=None resolves through kernels/common.choose_block_k
    (largest of 512/256/128 that both divides the table capacity and
    is a multiple of the pool block size, else one full-capacity
    step; MXNET_PAGED_BLOCK_K overrides) — memoized per shape.
    `interpret` defaults to True off TPU so the same code runs
    everywhere (tier-1 parity tests run it on CPU).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    int8 = "ks" in layer_pool
    kpool, vpool = layer_pool["k"], layer_pool["v"]
    nblocks, bs, kvh, d = kpool.shape
    b, span, h, dq = q.shape
    if h % kvh:
        raise ValueError("query heads %d must be a multiple of KV "
                         "heads %d" % (h, kvh))
    g = h // kvh
    rows = span * g
    nb = int(tables.shape[1])
    t_max = nb * bs
    if block_k is None:
        block_k = choose_block_k(
            t_max, shape_key=("paged", b, kvh, rows, d,
                              str(jnp.dtype(kpool.dtype)), bs),
            multiple=bs, env="MXNET_PAGED_BLOCK_K")
    block_k = min(block_k, t_max)
    if block_k % bs or t_max % block_k:
        raise ValueError(
            "block_k %d must be a multiple of the pool block size %d "
            "and divide the table capacity %d" % (block_k, bs, t_max))
    kb = block_k // bs
    num_kb = nb // kb
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    tables = jnp.asarray(tables, jnp.int32)
    # GQA head-packing: [B, C, H, D] -> [B, KVH, C*G, D]; packed row
    # r = c*G + g_idx, so r // G recovers the window offset c
    qp = q.reshape(b, span, kvh, g, d).transpose(0, 2, 1, 3, 4) \
         .reshape(b, kvh, rows, d)
    o = _paged_call(qp, kpool, vpool,
                    layer_pool.get("ks"), layer_pool.get("vs"),
                    tables, pos, kb, bs, num_kb, span, g, interpret)
    return o.reshape(b, kvh, span, g, d).transpose(0, 2, 1, 3, 4) \
            .reshape(b, span, h, d)
