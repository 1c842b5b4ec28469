"""A chunk's attention against a layer's cached K/V rows, without a score
plane.

models/transformer.py _cached_attention contracts the C queries of a
prefill chunk (an admission's bucket, a _prefill_rows chunk) with the
rows `k`, `v` [B, T, KVH, D] of a layer's cache, query i at position
start + i seeing the rows at positions <= start + i. As XLA ops
(_cached_plane) that is a float32 score plane [B, C, KVH, G, T] over ALL
T rows of the lane, written by one fusion and read by two more, whatever
the prompt's length: 15 ms of an admission of 1,024 at the Cerebras
cell's 24 layers x [1, 1024, 16, 1, 2048], where the live scores are
4.3 GFLOP a layer over 8 MB of rows (PERF.md section 6, PR 53).

Here the plane exists a block at a time, in VMEM:

  * the grid is (B, query blocks, key blocks) with `start` scalar-
    prefetched; the index maps of `k` and `v` clamp the key block to the
    last one a query block's last position sees, so the rows behind it
    are never fetched (an unchanged block index issues no DMA) and their
    steps are skipped, as kernels/kv_decode.py does with a lane's
    length; a key block wholly at or under a query block's first
    position skips the mask;
  * the rows are read where they lie: a block is `block_k` positions of
    all KVH heads, (1, block_k, KVH, D) of the 4-D leaf, and one K/V
    head's [block_k, D] matrix is a strided load of the flattened block
    (bfloat16 rows are packed two to a 32-bit word along the sublanes, so
    a PAIR of heads is one strided load of words and two shifts). A
    strided load needs a static head, so a step first parts the block
    into scratch head by head, a short copy each, and the long body
    below is traced ONCE and run a `fori_loop` iteration a K/V head
    (unrolled 16 times it compiled in 10-25 s a kernel and ran no
    faster);
  * the queries arrive heads-first, [B, H, C, D] (XLA assigns that order
    to the projection's result), and the G = H / KVH query heads of a
    K/V head are folded into the query block's rows: [G x block_q, D]
    against [block_k, D], one matmul a K/V head whatever the grouping;
  * the softmax runs online: running maximum, sum and a float32
    accumulator in scratch for every head of the query block, so a key
    block is fetched once for all H heads.

Same mathematics and precisions as the XLA text: the operands' dtype into
the MXU, float32 scores, statistics and accumulation, the probabilities
cast to the rows' dtype for the second dot. Forward only: an admission
is never differentiated.

chunk_blocks derives the blocks from the shapes, or says that the call
keeps the XLA text (models/transformer.py chunk_attention_blocks is the
route's rule).
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import NEG_INF, STAT_LANES

__all__ = ["chunk_attention", "chunk_blocks"]

LANES = 128
# the fewest queries the route hands the kernel. Under it a plane is
# small, the speculative verifiers' few rows keep decode's bits, and the
# chip says the kernel is not worth its place in a program (PERF.md
# section 6, PR 53): a plane of 256 or 512 queries against 2,048 rows of
# 16 heads (34, 67 MB) never leaves VMEM and XLA runs it in 0.068 /
# 0.099 ms a layer where the kernel takes 0.057 / 0.069 (0.145 behind a
# prefix of 1,024), while every admission program that carries the
# kernel pays ~1.5 s of tracing and lowering at start-up on the chip's
# host; from 1,024 on the planes pay HBM, 0.626 against 0.130
MIN_QUERIES = 1024
# the most positions of a query and of a key block; the most rows (heads
# x positions) of a query block, whose float32 accumulator and two
# lane-padded statistics are 1.5 KB a row of the scratch; and the most
# rows (query heads of a group x positions) of one K/V head's score
# tile. What a v5e read, ms a layer, the kernel alone beside the XLA
# text it replaces (PERF.md section 6, PR 53): [1, 1024, 16, 128]
# against 2,048 rows of 16 heads 512 x 1,024 0.130, 512 x 512 0.143,
# 256 x 1,024 0.152, 128 x 1,024 0.205, XLA's plane 0.626; 8,192 queries
# of 28 heads against 16,384 rows of 4 (seven query heads a K/V head
# folded into a block's rows) 256 x 1,024 4.23, 128 x 1,024 4.47,
# 256 x 512 6.51, XLA's blocks 5.03; 32 heads against 2, a tile of 2,048
# rows 4.79 and of 4,096 4.62 at twice the compile. A key block no
# longer than the chunk: a fresh prompt's first query block sees no
# further (256 queries: 256 x 256 0.057, 256 x 1,024 0.072)
BLOCK_Q, BLOCK_K = 512, 1024
QUERY_ROWS = 8192
TILE_ROWS = 2048
VMEM_LIMIT = 64 * 2 ** 20


def chunk_blocks(c, t, heads, kvh, d, itemsize, floor=MIN_QUERIES):
    """(block_q, block_k) for `c` queries of `heads` heads `d` wide
    against `t` cached rows of `kvh` heads, `itemsize` bytes an entry,
    where this kernel serves the call: the largest multiples of 128 that
    divide `c` and `t`, up to BLOCK_Q (and QUERY_ROWS / heads, and
    TILE_ROWS / the query heads of a K/V head) for the queries and up to
    BLOCK_K (and `c`) for the rows. None, and the caller keeps the XLA
    text, where `d` is no multiple of 128 (toy widths), where the chunk
    is under `floor`, where 128 does not divide `c` or `t`, and for
    rows one head of which is no strided load (_head_rows): entries of
    neither 4 nor 2 bytes, or of 2 with an odd number of heads above
    one."""
    if d % LANES or c % LANES or t % LANES or c < floor \
            or itemsize not in (2, 4) \
            or (itemsize == 2 and kvh > 1 and kvh % 2):
        return None
    most_q = max(min(BLOCK_Q, QUERY_ROWS // heads,
                     TILE_ROWS * kvh // heads) // LANES * LANES, LANES)
    return tuple(next(rows for rows in range(most, 0, -LANES)
                      if n % rows == 0)
                 for most, n in ((most_q, c), (min(BLOCK_K, c), t)))


def _head_rows(ref, head):
    """K/V head `head`'s rows [block_k, D] of a block ref
    [1, block_k, KVH, D], and, where two heads share a 32-bit word
    (2-byte entries, KVH even), its neighbour's: a list of one or two."""
    if len(ref.shape) == 3:         # one K/V head: [1, block_k, D]
        return [ref[0]]
    _, block, kvh, d = ref.shape
    flat = ref.reshape(block * kvh, d)
    if ref.dtype.itemsize == 4:
        return [flat[pl.ds(head, block, stride=kvh), :]]
    # row r of the flattened block lies in word r // 2, the even row in
    # its low half; a bfloat16 is the high half of a float32
    words = flat.bitcast(jnp.uint32)[pl.ds(head // 2, block,
                                           stride=kvh // 2), :]
    return [pltpu.bitcast(half, jnp.float32).astype(ref.dtype)
            for half in (words << 16, words & jnp.uint32(0xFFFF0000))]


def _kernel(start_ref, q_ref, k_ref, v_ref, o_ref, acc_sc, m_sc, l_sc,
            *head_sc, scale, group):
    heads, block_q, d = q_ref.shape
    block_k, kvh = k_ref.shape[1], heads // group
    qi, ki = pl.program_id(1), pl.program_id(2)
    q_start = start_ref[0] + qi * block_q
    k_start = ki * block_k
    rows = group * block_q
    k_sc, v_sc = head_sc or (None, None)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    def contract(masked):
        """The block into every head's running sums."""
        if masked:
            # a row of the folded block is (query head of the group,
            # position): the position is the row modulo block_q
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (group, block_q, block_k), 1).reshape(
                    rows, block_k)
            seen = q_pos >= k_start + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 1)

        def one(head, carry):
            q = q_ref[pl.ds(head * group, group)].reshape(rows, d)
            keys, vals = (k_sc[head], v_sc[head]) if head_sc \
                else (_head_rows(k_ref, 0)[0], _head_rows(v_ref, 0)[0])
            s = jax.lax.dot_general(
                q, keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(seen, s, NEG_INF)
            m_prev = m_sc[head]                  # [rows, LANES], lanes equal
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a query's first block holds row 0, which every position
            # sees: m_new is a real score and a masked one underflows to 0
            p = jnp.exp(s - m_new[:, :1])
            l_sc[head] = alpha * l_sc[head] + p.sum(axis=1, keepdims=True)
            acc_sc[head] = alpha[:, :1] * acc_sc[head] + jnp.dot(
                p.astype(vals.dtype), vals,
                preferred_element_type=jnp.float32)
            m_sc[head] = m_new
            return carry

        jax.lax.fori_loop(0, kvh, one, 0)

    # the last key a query of the block sees is its own position
    @pl.when(k_start <= q_start + block_q - 1)
    def _live():
        if head_sc:
            # the strides are static, so the heads are parted here, one
            # short copy each, ONCE a step (this is most of the kernel's
            # text, and a program pays for it at every start-up), and
            # the long body is traced once a mask
            for sc, ref in zip(head_sc, (k_ref, v_ref)):
                head = 0
                while head < kvh:
                    for part in _head_rows(ref, head):
                        sc[head] = part
                        head += 1
        clear = k_start + block_k - 1 <= q_start
        pl.when(clear)(lambda: contract(False))
        pl.when(jnp.logical_not(clear))(lambda: contract(True))

    @pl.when(ki == pl.num_programs(2) - 1)
    def _flush():
        def one(head, carry):
            o = acc_sc[head] / l_sc[head][:, :1]
            o_ref[pl.ds(head * group, group)] = o.reshape(
                group, block_q, d).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, kvh, one, 0)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def _call(q, k, v, start, block_q, block_k, interpret):
    """q [B, H, C, D], k, v [B, T, KVH, D], start int32 [1] ->
    [B, H, C, D]."""
    b, heads, c, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    group = heads // kvh
    last = t // block_k - 1

    def queries(b_, qi, ki, start_ref):
        return (b_, 0, qi, 0)

    def rows(b_, qi, ki, start_ref):
        # past the last block the query block sees the index stays put
        sees = (start_ref[0] + (qi + 1) * block_q - 1) // block_k
        return (b_, jnp.minimum(ki, jnp.clip(sees, 0, last))) \
            + (0,) * (k.ndim - 2)

    if kvh == 1:
        # one K/V head: the chip keeps [B, T, 1, D] as the [B, T, D] the
        # same bytes spell (its head axis is no tile's), which a block
        # of the 4-D leaf's last two axes, (1, D), is not
        k, v = (x.reshape(b, t, d) for x in (k, v))
    # the leading 1 is kept: a ref reshapes whole
    leaf = pl.BlockSpec((1, block_k) + k.shape[2:], rows)

    return pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / np.sqrt(d), group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, c // block_q, t // block_k),
            in_specs=[pl.BlockSpec((None, heads, block_q, d), queries),
                      leaf, leaf],
            out_specs=pl.BlockSpec((None, heads, block_q, d), queries),
            scratch_shapes=[
                # the online softmax's (o, m, l) of every K/V head's
                # folded rows across a query block's key blocks
                pltpu.VMEM((kvh, group * block_q, d), jnp.float32),
                pltpu.VMEM((kvh, group * block_q, STAT_LANES), jnp.float32),
                pltpu.VMEM((kvh, group * block_q, STAT_LANES), jnp.float32)]
            # a key block's rows, head by head
            + [pltpu.VMEM((kvh, block_k, d), k.dtype)] * 2 * (kvh > 1)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="chunk_attn",
        interpret=interpret,
    )(start, q, k, v)


def chunk_attention(q, k, v, start, block_q=None, block_k=None,
                    interpret=None):
    """A chunk's queries against a layer's cached rows.

    q [B, C, H, D], k, v [B, T, KVH, D] (the cache's leaves as they lie,
    one dtype with q; H a multiple of KVH, query head h reading K/V head
    h // (H / KVH)), `start` an int32 scalar, traced or not: query i sits
    at position start + i and attends the rows at positions <= start + i
    (0 <= start, start + C <= T). Returns softmax(q . k^T / sqrt(D)) . v,
    [B, C, H, D] in q's dtype. Rows behind start + C - 1 are never read.

    `interpret` defaults to True off a TPU, so the same code runs
    everywhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, c, heads, d = q.shape
    # the floor is the route's; the kernel itself tiles any multiple of 128
    blocks = chunk_blocks(c, k.shape[1], heads, k.shape[2], d,
                          k.dtype.itemsize, floor=LANES)
    if blocks is None:
        raise ValueError(
            "chunk_attention cannot tile %d queries of %d heads %d wide "
            "against %d rows of %d bytes an entry (chunk_blocks; "
            "transformer._cached_plane is the same contraction as XLA ops)"
            % (c, heads, d, k.shape[1], k.dtype.itemsize))
    out = _call(q.transpose(0, 2, 1, 3), k, v,
                jnp.asarray(start, jnp.int32).reshape(1),
                block_q or blocks[0], block_k or blocks[1], bool(interpret))
    return out.transpose(0, 2, 1, 3)
