"""Decode's contraction over dense K/V rows: one pass over each lane's
live rows.

models/transformer.py _decode_contraction contracts one query row a head
with the cached rows `k`, `v` [B, T, KVH, D]. As two XLA einsums with a
softmax between them that reads all T rows of every lane twice, whatever
its position, and masks what lies past it after it was fetched
(kv_decode_reference below: the text it was, the fallback and the oracle
of the kernel's tests). XLA runs those reads near the HBM peak, so the
lever is the bytes: a lane at position p needs p + 1 rows.

The kernel streams a lane's rows block by block through VMEM with its
lengths scalar-prefetched, as kernels/latent_decode.py does for latents:

  * the index maps of `k` and `v` clamp the block index to the lane's
    last live block, so a block past a lane's position is never fetched
    (an unchanged block index issues no DMA) and its step is skipped;
  * the leaves are read where they lie: a block is `rows` positions of
    all KVH heads, (None, rows, KVH, D) of the 4-D leaf, and inside the
    kernel it is the [rows x KVH, D] matrix the same bytes spell;
  * both dots are plain 2-D matmuls at every grouping: all H query rows
    against the flattened block, the score columns of another K/V head
    than the query's masked away (KVH times the flops the mathematics
    needs, on an MXU that waits for the rows anyway), so a block is
    read once for its G query heads;
  * the softmax runs online (running maximum, sum and a float32 [H, D]
    accumulator in scratch), so no [B, KVH, G, T] plane exists.

Same mathematics and precisions as the XLA text: the rows' dtype into
the MXU, float32 scores, statistics and accumulation, the weights cast
to the rows' dtype for the second dot.

kv_block derives the block from the rows' shape, or says that the call
keeps the XLA text; rows_fetched says what a dispatch's lengths make
the contraction fetch, and serving.py's counter kv.rows_read reads it
from here so the two cannot drift.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import NEG_INF, STAT_LANES

__all__ = ["kv_decode", "kv_decode_reference", "kv_block", "rows_fetched"]

# a K block of about 1 MB: ~1.3 us at a v5e's HBM peak against ~0.35 us a
# grid step (PERF.md, PR 42), and two leaves double-buffered stay well
# inside the scoped VMEM
BLOCK_BYTES = 1 << 20
LANES = 128     # a block's rows come in multiples of this, as D does


def kv_block(t, kvh, d, itemsize):
    """Rows a grid step of a cache [.., t, kvh, d]: the largest divisor of
    `t` that is a multiple of 128 and holds no more than BLOCK_BYTES of
    one leaf (128 rows where a row is wider than 8 KB). None, and the
    caller keeps the XLA text (kv_decode_reference), where `d` is no
    multiple of 128 (toy widths), where 128 does not divide `t`, or
    where BLOCK_BYTES of rows are the whole cache, so that nothing could
    be skipped."""
    most = max(BLOCK_BYTES // (kvh * d * itemsize), LANES)
    if d % LANES or t % LANES or most >= t:
        return None
    return next(rows for rows in range(most // LANES * LANES, 0, -LANES)
                if t % rows == 0)


def rows_fetched(lengths, t, block):
    """Rows the contraction fetches a K/V layer a step for lanes of
    `lengths` rows (int array, any shape; as the kernel clamps them, to
    1..t): whole blocks of `block` up to each lane's last live one, or
    all `t` a lane where the XLA text runs (`block` None)."""
    block = block or t
    lengths = np.clip(np.asarray(lengths, np.int64), 1, t)
    return int(np.sum(-(-lengths // block) * block))


def kv_decode_reference(q, k, v, pos, window=None):
    """The contraction as plain XLA ops over all T rows, grouped (the
    KVH-head cache is read once a GROUP of query heads, no materialized
    repeat): q [B, H, D], k, v [B, T, KVH, D], attending the rows at
    positions <= pos (a scalar, or [B]: ragged decode); with `window`,
    k and v a window layer's ring [B, R, KVH, D] (transformer.py
    _ring_rows), attending the positions in (pos - window, pos] that
    its slots hold. [B, H, D] float32. The PV dot runs at the rows'
    dtype (a bf16 MXU pass), as the kernel's."""
    b, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, d)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k,
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    t_pos = jnp.arange(k.shape[1])
    # [1] broadcasts the scalar case
    if window is None:
        mask = t_pos[None, :] <= jnp.atleast_1d(pos)[:, None]
    else:
        # slot j holds the newest position <= pos congruent to j
        at = jnp.atleast_1d(pos)[:, None]
        held = at - (at - t_pos[None, :]) % k.shape[1]
        mask = (held >= 0) & (at - held < window)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", a.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, d)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_sc, m_sc, l_sc, *,
            scale, block, group):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    length = len_ref[b]
    start = ki * block
    kvh = k_ref.shape[1]
    flat = (block * kvh, k_ref.shape[2])

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    def step(ragged):
        """One block into the running sums; `ragged`: the lane's last
        live block, whose rows at or past the length are masked out of
        the scores and, whatever they hold (0 x NaN is NaN), zeroed out
        of the second dot's operand."""
        keys = k_ref[...].reshape(flat)          # row = position x head
        vals = v_ref[...].reshape(flat)
        s = jax.lax.dot_general(q_ref[...], keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ) * scale                 # [H, block * KVH]
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        own = col % kvh == jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) // group
        if ragged:
            live = (length - start) * kvh
            own = jnp.logical_and(own, col < live)
            row = jax.lax.broadcasted_iota(jnp.int32, flat, 0)
            vals = jnp.where(row < live, vals, jnp.zeros_like(vals))
        s = jnp.where(own, s, NEG_INF)
        m_prev = m_sc[...]                     # [H, LANES], lanes equal
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a masked score underflows to 0: a live block's first position
        # is live for every head, so m_new is a real score
        p = jnp.exp(s - m_new[:, :1])
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        acc_sc[...] = alpha[:, :1] * acc_sc[...] + jnp.dot(
            p.astype(vals.dtype), vals, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    pl.when(start + block <= length)(lambda: step(False))
    pl.when(jnp.logical_and(start < length, length < start + block))(
        lambda: step(True))

    @pl.when(ki == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_sc[...] / l_sc[...][:, :1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(q, k, v, lengths, interpret):
    b, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    block = kv_block(t, kvh, d, k.dtype.itemsize)

    def lane(b_, ki, len_ref):
        return (b_, 0, 0)

    def rows(b_, ki, len_ref):
        # past the lane's last live block the index stays put
        return (b_, jnp.minimum(ki, (len_ref[b_] - 1) // block), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, t // block),
        in_specs=[pl.BlockSpec((None, h, d), lane),
                  pl.BlockSpec((None, block, kvh, d), rows),
                  pl.BlockSpec((None, block, kvh, d), rows)],
        out_specs=pl.BlockSpec((None, h, d), lane),
        scratch_shapes=[pltpu.VMEM((h, d), jnp.float32),
                        pltpu.VMEM((h, STAT_LANES), jnp.float32),
                        pltpu.VMEM((h, STAT_LANES), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / np.sqrt(d), block=block,
                          group=h // kvh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="kv_decode",
        interpret=interpret,
    )(lengths, q, k, v)


def kv_decode(q, k, v, lengths, interpret=None):
    """One decode row a lane against its cached K/V rows.

    q [B, H, D], k, v [B, T, KVH, D] (the rows, one dtype with q; H a
    multiple of KVH, query head h reading K/V head h // (H / KVH)),
    lengths int32 [B] or scalar (lane b attends its first lengths[b]
    rows; clamped to 1..T). Returns softmax(q . k^T / sqrt(D)) . v,
    [B, H, D] float32. Rows at or past a lane's length are never read
    into a sum, whatever they hold.

    `interpret` defaults to True off a TPU, so the same code runs
    everywhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, kvh, d = k.shape
    if kv_block(t, kvh, d, k.dtype.itemsize) is None:
        raise ValueError(
            "kv_decode cannot tile rows [%d, %d, %d] of %d bytes an entry "
            "(kv_block; kv_decode_reference is the same contraction as XLA "
            "ops)" % (t, kvh, d, k.dtype.itemsize))
    lengths = jnp.clip(jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32), (b,)), 1, t)
    return _call(q, k, v, lengths, bool(interpret))
