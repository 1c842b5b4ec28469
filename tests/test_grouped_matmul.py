"""The routed experts' grouped matmul (kernels/grouped_matmul.py),
interpreted on the CPU, against jax.lax.ragged_dot: the rows of every
group, whatever the groups' sizes and wherever they fall in a row tile;
the differentiated call; the shapes that keep the XLA form and the
counters that say which ran; and _expert_ffn through either.

Operands are small whole numbers, so every product and sum is exact in
bfloat16 and float32 alike and the comparison is equality. tests/
test_tpu_compile.py lowers the same kernel for a described v5e (green
here says nothing about lowering)."""

import dataclasses
import importlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import observability as obs
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import attribution

# the module: the package exports the function under the same name
gm = importlib.import_module("mxnet_tpu.kernels.grouped_matmul")


def _operands(m, k, n, groups, dtype=jnp.bfloat16, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randint(-3, 4, (m, k)), dtype),
            jnp.asarray(rng.randint(-2, 3, (groups, k, n)), dtype))


def _same_rows(rows, w, sizes):
    """The kernel's rows equal ragged_dot's in every group; in a tile it
    visited the rows of no group read 0."""
    sizes = jnp.asarray(sizes, jnp.int32)
    got = gm.grouped_matmul(rows, w, sizes)
    want = gm.grouped_matmul_reference(rows, w, sizes)
    assert got.shape == want.shape and got.dtype == want.dtype == rows.dtype
    total = int(sizes.sum())
    np.testing.assert_array_equal(np.asarray(got[:total], np.float32),
                                  np.asarray(want[:total], np.float32))
    tile = gm.grouped_tiles(rows.shape[0], rows.shape[1], w.shape[2],
                            w.dtype.itemsize)[0]
    visited = max(-(-total // tile), 1) * tile
    assert not np.asarray(got[total:visited], np.float32).any()


# 256 rows in tiles of 128; groups given as rows a group
SIZES = {
    "empty-groups-first": [0, 0, 100, 28, 128],
    "empty-groups-in-the-middle": [60, 0, 0, 130, 0, 66],
    "empty-groups-last": [128, 128, 0, 0],
    "fewer-rows-than-m": [5, 0, 130, 0, 7, 0],
    "fewer-rows-than-a-tile": [3, 0, 9],
    "no-row-in-any-group": [0, 0, 0],
    "one-group-holds-every-row": [0, 256, 0],
    "one-group-alone": [256],
    "groups-start-and-end-inside-a-tile": [100, 56, 1, 70, 29],
    "a-group-spans-three-tiles": [1, 254, 1],
    "one-row-a-group": [1] * 40,
    "tile-aligned": [128, 128],
}


@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_every_group_gets_ragged_dots_rows(sizes):
    rows, w = _operands(256, 128, 256, len(sizes))
    _same_rows(rows, w, sizes)


@pytest.mark.parametrize("m,k,n,sizes", [
    (256, 128, 384, [90, 0, 90, 40]),        # wide: [k, n] with n > k
    (256, 384, 128, [90, 0, 90, 40]),        # narrow
    (384, 256, 256, [200, 100, 84]),         # three row tiles
    (192, 128, 128, [50, 100, 30]),          # tiles of 64: 128 does not divide m
    (48, 128, 128, [10, 0, 30]),             # tiles of 16
    (24, 128, 256, [3, 0, 17]),              # one tile of all 24 rows
    (8, 256, 128, [2, 2, 0, 4]),             # decode's row of a small batch
], ids=["wide", "narrow", "three-tiles", "tiles-of-64", "tiles-of-16",
        "one-odd-tile", "eight-rows"])
def test_both_orientations_and_every_row_tile(m, k, n, sizes):
    rows, w = _operands(m, k, n, len(sizes), seed=1)
    _same_rows(rows, w, sizes)


@pytest.mark.parametrize("budget,cols", [(128 * 128 * 2, 128),
                                         (128 * 256 * 2, 256),
                                         (8 << 20, 512)])
def test_column_blocks_are_walked_outermost(monkeypatch, budget, cols):
    """A weight block is [k, column block]; with more than one column
    block the pairs are walked once for each."""
    monkeypatch.setattr(gm, "WEIGHT_BLOCK_BYTES", budget)
    assert gm.grouped_tiles(256, 128, 512) == (128, cols)
    rows, w = _operands(256, 128, 512, 4, seed=2)
    # jit caches by shape, not by the budget: call the text itself
    got = gm._call.__wrapped__(rows, w, jnp.asarray([60, 0, 130, 40]), True)
    want = gm.grouped_matmul_reference(rows, w, jnp.asarray([60, 0, 130, 40]))
    np.testing.assert_array_equal(np.asarray(got[:230], np.float32),
                                  np.asarray(want[:230], np.float32))


def test_float32_operands_take_the_kernel_too():
    rows, w = _operands(256, 128, 128, 3, jnp.float32)
    _same_rows(rows, w, [100, 0, 120])


def test_sizes_are_data_not_shape():
    """One program for every routing: the pairs come from `sizes` at run
    time (the grid's second dimension is a value)."""
    rows, w = _operands(256, 128, 128, 4, seed=3)
    fn = jax.jit(gm.grouped_matmul)
    for sizes in ([64, 64, 64, 64], [0, 0, 0, 256], [0, 1, 0, 0], [0] * 4):
        s = jnp.asarray(sizes, jnp.int32)
        total = sum(sizes)
        np.testing.assert_array_equal(
            np.asarray(fn(rows, w, s)[:total], np.float32),
            np.asarray(gm.grouped_matmul_reference(rows, w, s)[:total],
                       np.float32))
    assert fn._cache_size() == 1


PAIRS = {
    # sizes, tile -> (group, row tile) of every real pair
    "a-shared-tile": ([100, 56, 100], 128,
                      [(0, 0), (1, 0), (1, 1), (2, 1)]),
    "empty-groups-cost-no-pair": ([0, 128, 0, 0, 128, 0], 128,
                                  [(1, 0), (4, 1)]),
    "rows-past-the-last-group-no-tile": ([10, 0, 0], 128, [(0, 0)]),
    "nothing-routed-one-stand-in": ([0, 0, 0], 128, [(2, 0)]),
    "a-group-over-three-tiles": ([300, 84], 128,
                                 [(0, 0), (0, 1), (0, 2), (1, 2)]),
}


@pytest.mark.parametrize("sizes,tile,want", PAIRS.values(), ids=PAIRS.keys())
def test_the_grid_walks_only_pairs_that_hold_a_row(sizes, tile, want):
    group, row_tile, start, end, pairs = gm._pairs(
        jnp.asarray(sizes, jnp.int32), 384, tile)
    assert group.shape == row_tile.shape == (3 + len(sizes) - 1,)
    pairs = int(pairs)
    assert list(zip(np.asarray(group)[:pairs].tolist(),
                    np.asarray(row_tile)[:pairs].tolist())) == want
    assert np.asarray(end - start).tolist() == sizes


@pytest.mark.parametrize("sizes", [[100, 0, 60, 40], [0, 256, 0, 0],
                                   [0, 0, 0, 0], [30, 30, 30, 30]],
                         ids=["ragged", "one-group", "none", "short"])
def test_differentiated_it_is_ragged_dot_forward_and_backward(sizes):
    rows, w = _operands(256, 128, 128, 4, jnp.float32, seed=4)
    s = jnp.asarray(sizes, jnp.int32)
    seed = jnp.asarray(np.random.RandomState(5).randn(256, 128), jnp.float32)

    def through(matmul):
        # a nonlinearity behind the call, as _expert_ffn has: its own
        # derivative reads the rows of no group
        return jax.value_and_grad(
            lambda r, x: jnp.sum(jax.nn.silu(matmul(r, x, s)) * seed),
            argnums=(0, 1))(rows, w)

    got, (got_rows, got_w) = through(gm.grouped_matmul)
    want, (want_rows, want_w) = through(gm.grouped_matmul_reference)
    assert float(got) == float(want)
    np.testing.assert_array_equal(got_rows, want_rows)
    np.testing.assert_array_equal(got_w, want_w)
    assert np.isfinite(np.asarray(got_rows)).all()


TILES = {
    # m, k, n -> (row tile, column block): the first chip call's shapes
    "kimi-linear-decode-in": ((256, 2304, 1024), (128, 1024)),
    "kimi-linear-decode-out": ((256, 1024, 2304), (128, 2304)),
    "xing4-chunk-in": ((8192, 3584, 1024), (128, 1024)),
    "xing4-chunk-out": ((8192, 1024, 3584), (128, 3584)),
    "xing4-decode-in": ((128, 3584, 1024), (128, 1024)),
    "kimi-k2-decode-in": ((256, 7168, 2048), (128, 512)),
    "kimi-k2-chunk-out": ((32768, 2048, 7168), (128, 1792)),
    "24-lanes-of-8-picks": ((192, 2304, 1024), (64, 1024)),
    "a-toy-width": ((256, 32, 64), None),
    "a-contraction-off-the-lanes": ((256, 96, 128), None),
    "a-width-off-the-lanes": ((256, 128, 192), None),
    "rows-no-tile-divides": ((200, 128, 128), None),
    "a-contraction-too-long-for-a-block": ((256, 1 << 16, 128), None),
}


@pytest.mark.parametrize("shape,want", TILES.values(), ids=TILES.keys())
def test_tiles_come_from_the_shapes_alone(shape, want):
    assert gm.grouped_tiles(*shape) == want


@pytest.mark.parametrize("why,m,k,n,dtype", [
    ("toy widths", 64, 32, 64, jnp.float32),
    ("rows no tile divides", 200, 128, 128, jnp.float32),
    ("operands of two dtypes", 256, 128, 128, jnp.bfloat16),
])
def test_a_call_without_a_kernel_is_ragged_dot(monkeypatch, why, m, k, n,
                                               dtype):
    monkeypatch.setattr(gm, "_call", None)       # the kernel would raise
    rows, w = _operands(m, k, n, 3, jnp.float32)
    rows = rows.astype(dtype)
    s = jnp.asarray([m // 4, 0, m // 2], jnp.int32)
    np.testing.assert_array_equal(gm.grouped_matmul(rows, w, s),
                                  jax.lax.ragged_dot(rows, w, s))


def test_partitioned_keeps_the_form_gspmd_can_split(monkeypatch):
    monkeypatch.setattr(gm, "_call", None)
    rows, w = _operands(256, 128, 128, 3)
    s = jnp.asarray([100, 0, 120], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(gm.grouped_matmul(rows, w, s, partitioned=True),
                   np.float32),
        np.asarray(jax.lax.ragged_dot(rows, w, s), np.float32))


# ------------------------------------------------- through _expert_ffn ---

def _experts_model(d, width, **kw):
    cfg = tf.TransformerConfig(
        vocab_size=64, d_model=d, n_heads=2, n_layers=2, d_ff=width,
        ffn="gated_silu", n_experts=8, experts_per_token=2,
        expert_scoring="sigmoid", expert_scale=2.0, n_shared_experts=1,
        first_dense_layers=1, max_len=32, **kw)
    return tf.init_params(cfg, 0), cfg


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["all-held", "4-of-8"])
def test_the_expert_layer_is_the_same_through_either_form(held):
    """At a width the kernel takes, _expert_ffn through the kernel and
    through the mesh arm's ragged_dot: float32, sums in another order."""
    params, cfg = _experts_model(128, 128, experts_held=held)
    p = params["layers"][1]
    assert p["w1"].shape[0] == (held[1] if held else 8)
    x = jnp.asarray(np.random.RandomState(6).randn(2, 8, 128), jnp.float32)
    loads = []
    got = tf._ffn(x, p, cfg, loads)
    want = tf._ffn(x, p, cfg, loads, mesh=object())
    assert tf.expert_matmuls(params, cfg, 16) == (3, 0)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(loads[0], loads[1])
    assert np.isfinite(np.asarray(got)).all()


@pytest.fixture
def telemetry(monkeypatch):
    """MXNET_OBS on from a clean registry, and nothing left behind (see
    tests/test_kimi_linear.py)."""
    monkeypatch.setenv("MXNET_OBS", "1")
    obs.reset()
    yield monkeypatch
    attribution.reset()
    obs.reset()


@pytest.mark.parametrize("d,width,counted,silent", [
    (128, 128, "moe.grouped_kernel", "moe.grouped_reference"),
    (32, 64, "moe.grouped_reference", "moe.grouped_kernel"),
], ids=["a-width-with-blocks", "a-toy-width"])
def test_dispatches_and_admissions_count_which_form_ran(
        telemetry, d, width, counted, silent):
    """One expert layer of three grouped matmuls: an admission's call
    counts 3, a decode round 3 a step, under the name of the form the
    shapes chose; both names are in health_snapshot()."""
    params, cfg = _experts_model(d, width)
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    srv.admit([1, 2, 3, 4, 5], 4)
    assert obs.counter(counted).value == 3
    srv.step()
    steps = obs.counter("serving.dispatches").value * srv.chunk_size
    assert steps >= 1
    assert obs.counter(counted).value == 3 + 3 * steps
    assert obs.counter(silent).value == 0
    snap = srv.health_snapshot()
    assert snap[counted] == 3 + 3 * steps and snap[silent] == 0
    # nothing is counted while nothing records
    telemetry.setenv("MXNET_OBS", "0")
    srv.step()
    assert obs.counter(counted).value == 3 + 3 * steps


def test_a_model_without_a_router_counts_no_grouped_matmul(telemetry):
    cfg = tf.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                               n_layers=1, d_ff=64, max_len=32)
    srv = ContinuousBatcher(tf.init_params(cfg, 0), cfg, max_batch=2)
    srv.admit([1, 2, 3], 4)
    srv.step()
    assert not [name for name in obs.counters() if "grouped" in name]
    assert "moe.grouped_kernel" not in srv.health_snapshot()


def test_served_tokens_are_the_same_through_either_form(monkeypatch):
    """Greedy streams of a model whose experts take the kernel, against
    the same model with every grouped matmul held to ragged_dot (under
    another name for an axis no mesh is there to carry: programs are
    cached by configuration)."""
    params, cfg = _experts_model(128, 128)

    def serve(cfg):
        srv = ContinuousBatcher(params, cfg, max_batch=2)
        rids = [srv.admit([3, 1, 4, 1, 5, 9, 2, 6], 6),
                srv.admit([2, 7, 1, 8], 6)]
        done = {}
        while len(done) < 2:
            done.update(srv.step())
        return [done[r] for r in rids]

    kernel = serve(cfg)
    assert tf.expert_matmuls(params, cfg, 2) == (3, 0)
    monkeypatch.setattr(gm, "grouped_tiles", lambda *a: None)
    assert tf.expert_matmuls(params, cfg, 2) == (0, 3)
    assert serve(dataclasses.replace(cfg, ep_axis="experts")) == kernel


def test_the_mesh_sharded_forward_keeps_ragged_dot_at_a_kernel_width(
        monkeypatch):
    """Experts partitioned over ep at a width the kernel takes: the
    unsharded forward's program holds the kernel (here the interpreter's
    loop over its grid), the mesh-sharded one never reaches it (GSPMD
    partitions jax.lax.ragged_dot and cannot partition a Pallas call),
    and the two agree; so do the loss's gradients, which are
    ragged_dot's on both sides."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh
    cfg = tf.TransformerConfig(vocab_size=32, d_model=128, n_heads=4,
                               n_layers=1, d_ff=128, n_experts=4,
                               experts_per_token=2, max_len=16)
    params = tf.init_params(cfg, seed=0)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 32, (8, 16)), jnp.int32)
    assert tf.expert_matmuls(params, cfg, 8 * 16) == (2, 0)
    alone = jax.jit(lambda p, t: tf.forward(p, t, cfg))
    assert "stablehlo.while" in alone.lower(params, tokens).as_text()
    want = alone(params, tokens)
    ref_loss, ref_grads = jax.value_and_grad(tf.loss_fn)(params, tokens, cfg)

    mesh = make_mesh({"ep": 2, "dp": 4, "tp": 1, "sp": 1})
    sharded = tf.shard_params(params, cfg, mesh)
    assert sharded["layers"][0]["w1"].sharding.spec[0] == "ep"
    tok = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    monkeypatch.setattr(gm, "_call", None)       # the kernel would raise
    over = jax.jit(lambda p, t: tf.forward(p, t, cfg, mesh))
    assert "stablehlo.while" not in over.lower(sharded, tok).as_text()
    np.testing.assert_allclose(over(sharded, tok), want, rtol=2e-3,
                               atol=2e-3)
    loss, grads = jax.value_and_grad(tf.loss_fn)(sharded, tok, cfg, mesh)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
