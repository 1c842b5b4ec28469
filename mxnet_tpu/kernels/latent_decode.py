"""Latent attention's decode contraction: one pass over each lane's live
rows.

models/transformer.py _latent_decode_attention absorbs W_kvb into the
query and then contracts one query row a head with the cached latents
`c` [B, T, R] and the shared rotated key part `kr` [B, T, E]. As two XLA
einsums with a softmax between them that is two passes over all T rows
of every lane, whatever its context, and a [B, H, T] float32 score
plane written and read again (latent_decode_reference below: the text
it was, kept as the oracle of the kernel's tests).

The kernel streams a lane's rows block by block through VMEM with its
lengths scalar-prefetched:

  * the index maps of `c` and `kr` clamp the block index to the lane's
    last live block, so a block past a lane's context is never fetched
    (an unchanged block index issues no DMA) and its step is skipped;
  * a block of `c` is loaded once and feeds both dots: the scores
    (q_lat . c^T + q_r . kr^T) / norm and the weighted sum p . c;
  * the softmax runs online (running maximum, sum and a float32
    [H, R] accumulator in scratch), so no score plane exists.

Same mathematics and precisions as the XLA text: the rows' dtype into
the MXU, float32 scores, statistics and accumulation, the weights cast
to the rows' dtype for the second dot.

The block is derived from T (latent_block: 1,024 down to 128 rows, or
one block of a short cache; a long cache that 128 does not divide has
none, and the caller keeps the XLA text for it); rows_fetched says what
a dispatch's lengths make the contraction fetch, and serving.py's
counter mla.rows_read reads it from here so the two cannot drift.

latent_row_store is decode's store of a round's fresh `kr` rows, one a
lane, in that same [B, E, T] order and in place: as a scatter
(`kr.at[lanes, pos].set(rows)`) XLA wants E minor, and copies the whole
leaf into that order and back around it, every layer of every round.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import NEG_INF, STAT_LANES, choose_block_k, length_mask

__all__ = ["latent_decode", "latent_decode_reference", "latent_block",
           "latent_row_store", "rows_fetched"]

# a 1,024-row block of 512 + 64 bf16 latents is 1.15 MB, ~1.4 us at a
# v5e's HBM peak against ~0.35 us a grid step (on the chip 1,024 read
# 0.78 ms a Kimi-K2.6 layer where 512 read 0.98: PERF.md, PR 42). None
# is under 128: `kr`'s block has its rows on the lanes, and Mosaic takes
# a last dimension that is a multiple of 128 or the whole array's
BLOCKS = (1024, 512, 256, 128)


def latent_block(t):
    """Rows a grid step of a cache of `t` positions: the largest of
    1,024, 512, 256, 128 that divides it; where none does, the whole
    cache as one block if that is no more than the largest (a toy
    cache), else None: the kernel cannot tile such a cache, and
    models/transformer.py keeps the XLA text for it."""
    block = choose_block_k(t, shape_key=("latent_decode",),
                           candidates=BLOCKS)
    return block if block <= BLOCKS[0] else None


def rows_fetched(lengths, t):
    """Rows the decode contraction fetches a latent layer a step for
    lanes of `lengths` rows (int array, any shape; as the kernel clamps
    them, to 1..t): whole blocks up to each lane's last live one, or
    all `t` a lane where the XLA text runs (latent_block: None)."""
    block = latent_block(t) or t
    lengths = np.clip(np.asarray(lengths, np.int64), 1, t)
    return int(np.sum(-(-lengths // block) * block))


def latent_decode_reference(q_lat, q_r, c, kr, lengths, norm):
    """The contraction as plain XLA ops, two passes over all T rows:
    [B, H, R] float32."""
    s = (jnp.einsum("bhr,btr->bht", q_lat, c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhe,bte->bht", q_r, kr,
                      preferred_element_type=jnp.float32)) / norm
    seen = jnp.arange(c.shape[1])[None, :] < lengths[:, None]
    a = jax.nn.softmax(jnp.where(seen[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("bht,btr->bhr", a.astype(c.dtype), c,
                      preferred_element_type=jnp.float32)


def _kernel(len_ref, ql_ref, qr_ref, c_ref, krt_ref, o_ref, acc_sc, m_sc,
            l_sc, *, scale, block):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    length = len_ref[b]
    start = ki * block

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
        rows = c_ref[...]                                # [block, R]
        s = (jax.lax.dot_general(ql_ref[...], rows, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jnp.dot(qr_ref[...], krt_ref[...],
                       preferred_element_type=jnp.float32)
             ) * scale                                   # [H, block]
        if ragged:
            s = length_mask(s, start, length)
            at = start + jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
            rows = jnp.where(at < length, rows, jnp.zeros_like(rows))
        m_prev = m_sc[...]                     # [H, LANES], lanes equal
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a masked score underflows to 0: a live block's first row is
        # live, so m_new is a real score
        p = jnp.exp(s - m_new[:, :1])
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        acc_sc[...] = alpha[:, :1] * acc_sc[...] + jax.lax.dot_general(
            p.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    pl.when(start + block <= length)(lambda: step(False))
    pl.when(jnp.logical_and(start < length, length < start + block))(
        lambda: step(True))

    @pl.when(ki == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_sc[...] / l_sc[...][:, :1]


@functools.partial(jax.jit, static_argnames=("norm", "interpret"))
def _call(q_lat, q_r, c, kr, lengths, norm, interpret):
    b, h, r = q_lat.shape
    t, e = kr.shape[1], kr.shape[2]
    block = latent_block(t)
    # [B, E, T]: the order a TPU keeps an array with so narrow a last
    # dimension in anyway (T minor, nothing padded to 128 lanes), so the
    # swap is a bitcast there and the kernel's block has T on the lanes
    kr_t = jnp.swapaxes(kr, 1, 2)

    def lane(b_, ki, len_ref):
        return (b_, 0, 0)

    def live(b_, ki, len_ref):
        # past the lane's last live block the index stays put
        return jnp.minimum(ki, (len_ref[b_] - 1) // block)

    def c_block(b_, ki, len_ref):
        return (b_, live(b_, ki, len_ref), 0)

    def kr_block(b_, ki, len_ref):
        return (b_, 0, live(b_, ki, len_ref))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, t // block),
        in_specs=[pl.BlockSpec((None, h, r), lane),
                  pl.BlockSpec((None, h, e), lane),
                  pl.BlockSpec((None, block, r), c_block),
                  pl.BlockSpec((None, e, block), kr_block)],
        out_specs=pl.BlockSpec((None, h, r), lane),
        scratch_shapes=[pltpu.VMEM((h, r), jnp.float32),
                        pltpu.VMEM((h, STAT_LANES), jnp.float32),
                        pltpu.VMEM((h, STAT_LANES), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / norm, block=block),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="mla_decode",
        interpret=interpret,
    )(lengths, q_lat, q_r, c, kr_t)


def latent_decode(q_lat, q_r, c, kr, lengths, norm, interpret=None):
    """One decode row a lane against its cached latents.

    q_lat [B, H, R] (the query with W_kvb's key half absorbed), q_r
    [B, H, E] (its decoupled part), c [B, T, R], kr [B, T, E] (the
    rows), lengths int32 [B] or scalar (lane b attends its first
    lengths[b] rows; clamped to 1..T), norm (what the scores are
    divided by). Returns softmax(scores) . c, [B, H, R] float32: the
    weighted sum of latents the caller up-projects. Rows at or past a
    lane's length are never read into a sum, whatever they hold.

    `interpret` defaults to True off a TPU, so the same code runs
    everywhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t = c.shape[0], c.shape[1]
    if latent_block(t) is None:
        raise ValueError(
            "latent_decode cannot tile a cache of %d rows: more than one "
            "block of %d and no multiple of %d (latent_decode_reference "
            "is the same contraction as XLA ops)"
            % (t, BLOCKS[0], BLOCKS[-1]))
    lengths = jnp.clip(jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32), (b,)), 1, t)
    return _call(q_lat, q_r, c, kr, lengths, float(norm), bool(interpret))


def _store_kernel(pos_ref, row_ref, krt_ref, o_ref, *, block, last):
    at = pos_ref[pl.program_id(0)]
    # the tile's columns by their position, the tile being the one the
    # index map chose; a position outside the cache is no column of it,
    # and the tile goes back as it came
    start = jnp.clip(at // block, 0, last) * block
    column = start + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    o_ref[...] = jnp.where(column == at, row_ref[...], krt_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _store_call(kr, rows, pos, interpret):
    b, t, e = kr.shape
    # a tile of 128 positions is the least Mosaic takes on the lanes
    # (E x 128 bf16: 16 KB in and out a lane), or the whole of a short
    # cache that 128 does not divide
    block = BLOCKS[-1] if t % BLOCKS[-1] == 0 else t
    last = t // block - 1

    def tile(b_, pos_ref):
        return (b_, 0, jnp.clip(pos_ref[b_] // block, 0, last))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[pl.BlockSpec((None, e, 1), lambda b_, pos_ref: (b_, 0, 0)),
                  pl.BlockSpec((None, e, block), tile)],
        out_specs=pl.BlockSpec((None, e, block), tile))
    kr_t = pl.pallas_call(
        functools.partial(_store_kernel, block=block, last=last),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, e, t), kr.dtype),
        # the leaf itself (operand 2, after the positions and the rows)
        # is the output: only the tiles the grid visits move
        input_output_aliases={2: 0},
        name="mla_row_store",
        interpret=interpret,
    )(pos, rows[:, :, None], jnp.swapaxes(kr, 1, 2))
    return jnp.swapaxes(kr_t, 1, 2)


def latent_row_store(kr, rows, pos, interpret=None):
    """`kr` [B, T, E] with row pos[b] of lane b replaced by rows[b]
    ([B, E], cast to the leaf's dtype; pos int32 [B]) and everything
    else as it was: `kr.at[arange(B), pos].set(rows)` bit for bit, a
    negative position counted from the end and one outside the cache
    dropped. Written through the [B, E, T] view latent_decode reads
    (_call), with the leaf aliased to the result: a lane fetches and
    writes back the one tile of positions that holds its row, where the
    scatter copies the whole leaf twice. Takes the caches latent_decode
    takes (latent_block)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t = kr.shape[1]
    if latent_block(t) is None:
        raise ValueError(
            "latent_row_store cannot tile a cache of %d rows (latent_block; "
            "kr.at[lanes, pos].set(rows) is the same store as an XLA "
            "scatter)" % t)
    pos = jnp.asarray(pos, jnp.int32)
    return _store_call(kr, rows.astype(kr.dtype),
                       jnp.where(pos < 0, pos + t, pos), bool(interpret))
