"""The routed experts' grouped matmul: only the row tiles and experts
that hold a pick.

models/transformer.py _expert_ffn sorts a call's picks by expert and
multiplies each expert's rows with that expert's weight: rows [m, k]
sorted by group, w [G, k, n], sizes int32 [G] (rows a group, in order;
rows at or past sum(sizes) belong to no group). As jax.lax.ragged_dot
XLA's own grouped matmul also skips a group without a row, but streams
a touched group's weight at 37-59% of a v5e's HBM peak at decode's few
rows and runs a prefill chunk's 128 rows a group at a sixth of the
MXU's peak (PERF.md, PR 44; grouped_matmul_reference below: the text it
was, kept as the oracle of the kernel's tests, as the form a shape
without a legal block keeps, as what GSPMD partitions over a mesh, and
as the differentiated form).

The kernel walks (row tile, group) PAIRS that hold at least one row,
listed from `sizes` outside it and scalar-prefetched:

  * the grid's second dimension is the number of such pairs, a value
    of the data: a group with no row costs no step and no DMA of its
    weight, a row tile past the last group is never visited;
  * a pair's weight block is [k, column block] of its group, the
    contraction whole, so the block's index changes only with the
    group (or the column block, outermost): a group spanning many row
    tiles has its weight fetched once a column block;
  * a tile two groups share is visited once for each, consecutively,
    its store masked to the group's rows; the tile's first visit
    writes zeros to the rest, so within a visited tile the rows in no
    group read 0. A tile never visited is never written: what it holds
    is unspecified, and _expert_ffn selects those rows away
    (`jnp.where(here, ...)`).

Same mathematics and precisions as the XLA text: the operands' dtype
into the MXU, float32 accumulation, the result in the operands' dtype.
Tiles come from the static shapes alone (grouped_tiles); serving.py's
counters moe.grouped_kernel / moe.grouped_reference read the same
function, so what is counted is what ran.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul", "grouped_matmul_reference", "grouped_tiles"]

# a row tile is a whole number of bfloat16 sublane tiles (16 rows) and
# no more than the MXU's 128: a wider one only multiplies rows of other
# groups that the store masks away
ROW_TILES = (128, 64, 32, 16)
# a weight block [k, column block], two of them in flight: on the chip 8
# MiB read up to 8% faster than 4 at the three expert cells' shapes
# (PERF.md, PR 44), for fewer steps and fewer passes over the rows
WEIGHT_BLOCK_BYTES = 8 << 20
VMEM_LIMIT_BYTES = 64 << 20


def grouped_tiles(m, k, n, itemsize=2):
    """(row tile, column block) of the kernel for rows [m, k] against
    weights [G, k, n] of `itemsize` bytes an element, from the shapes
    alone; None where no legal block exists and the call keeps
    jax.lax.ragged_dot: a contraction or a width that is no multiple
    of the 128 lanes (the toy widths of CPU tests), more than one tile
    of rows that 16 does not divide, or a contraction so long that 128
    columns of it overflow WEIGHT_BLOCK_BYTES."""
    if k % 128 or n % 128 or m < 1:
        return None
    tile = next((t for t in ROW_TILES if m % t == 0),
                m if m <= ROW_TILES[0] else None)
    cols = [c for c in range(n, 0, -128)
            if n % c == 0 and k * c * itemsize <= WEIGHT_BLOCK_BYTES]
    if tile is None or not cols:
        return None
    return tile, cols[0]


def grouped_matmul_reference(rows, w, sizes):
    """The grouped matmul as XLA's own: [m, n], zeros in the rows of no
    group."""
    return jax.lax.ragged_dot(rows, w, sizes)


def _pairs(sizes, m, tile):
    """The (row tile, group) pairs that hold a row, in the rows' order:
    (group [P], row tile [P], first row [G], end row [G], pairs) with P
    the static bound (every tile once and one more for each group but
    the first) and `pairs` how many of them are real, at least 1 (with
    no row in any group one pair of an empty group stands in, whose
    store writes the first tile's zeros)."""
    held = sizes.shape[0]
    tiles = -(-m // tile)
    end = jnp.cumsum(sizes)
    start = end - sizes
    first = start // tile
    span = jnp.where(sizes > 0, (end - 1) // tile - first + 1, 0)
    upto = jnp.cumsum(span)
    at = jnp.arange(tiles + held - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(upto, at, side="right"),
                        held - 1).astype(jnp.int32)
    row_tile = jnp.clip(first[group] + at - (upto[group] - span[group]),
                        0, tiles - 1).astype(jnp.int32)
    return group, row_tile, start, end, jnp.maximum(upto[-1], 1)


def _kernel(group_ref, tile_ref, start_ref, end_ref, rows_ref, w_ref, o_ref,
            *, tile):
    at = pl.program_id(1)
    group = group_ref[at]
    here = tile_ref[at]
    y = jnp.dot(rows_ref[...], w_ref[...],
                preferred_element_type=jnp.float32).astype(o_ref.dtype)
    row = here * tile + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    mine = jnp.logical_and(row >= start_ref[group], row < end_ref[group])
    fresh = jnp.logical_or(at == 0,
                           tile_ref[jnp.maximum(at - 1, 0)] != here)

    @pl.when(fresh)
    def _first_visit():
        o_ref[...] = jnp.where(mine, y, jnp.zeros_like(y))

    @pl.when(jnp.logical_not(fresh))
    def _shared_tile():
        o_ref[...] = jnp.where(mine, y, o_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(rows, w, sizes, interpret):
    m, k = rows.shape
    n = w.shape[2]
    tile, cols = grouped_tiles(m, k, n, w.dtype.itemsize)
    group, row_tile, start, end, pairs = _pairs(sizes, m, tile)

    def rows_block(j, at, group_ref, tile_ref, start_ref, end_ref):
        return (tile_ref[at], 0)

    def w_block(j, at, group_ref, tile_ref, start_ref, end_ref):
        return (group_ref[at], 0, j)

    def out_block(j, at, group_ref, tile_ref, start_ref, end_ref):
        return (tile_ref[at], j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // cols, pairs),
        in_specs=[pl.BlockSpec((tile, k), rows_block),
                  pl.BlockSpec((None, k, cols), w_block)],
        out_specs=pl.BlockSpec((tile, cols), out_block))
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="moe_gmm",
        interpret=interpret,
    )(group, row_tile, start, end, rows, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_form(rows, w, sizes, interpret):
    return _call(rows, w, sizes, interpret)


def _forward(rows, w, sizes, interpret):
    # differentiated, the call is XLA's form from end to end: the rows
    # of no group are zeros there, which the next operation's own
    # derivative reads
    return grouped_matmul_reference(rows, w, sizes), (rows, w, sizes)


def _backward(interpret, saved, g):
    rows, w, sizes = saved
    _, pull = jax.vjp(
        lambda r, x: grouped_matmul_reference(r, x, sizes), rows, w)
    return pull(g) + (None,)


_kernel_form.defvjp(_forward, _backward)


def grouped_matmul(rows, w, sizes, partitioned=False, interpret=None):
    """rows [m, k] sorted by group, w [G, k, n] of the same dtype, sizes
    int32 [G] -> [m, n] in that dtype: row i of group g is rows[i] @
    w[g], accumulated in float32. A row at or past sum(sizes) belongs
    to no group and what comes back for it is unspecified (zeros from
    the XLA form, zeros or nothing written from the kernel): the
    caller selects such rows away.

    The kernel runs where grouped_tiles finds blocks; a shape without,
    operands of two dtypes, and `partitioned` (the call stands in a
    program GSPMD partitions over a mesh, which it can do to
    jax.lax.ragged_dot and not to a Pallas call) keep the XLA form.
    Differentiated, forward and backward are the XLA form's own.

    `interpret` defaults to True off a TPU, so the same code runs
    everywhere."""
    if partitioned or rows.dtype != w.dtype or grouped_tiles(
            rows.shape[0], rows.shape[1], w.shape[2],
            w.dtype.itemsize) is None:
        return grouped_matmul_reference(rows, w, sizes)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _kernel_form(rows, w, sizes.astype(jnp.int32), bool(interpret))
