"""KVStore tests — mirrors tests/python/unittest/test_kvstore.py and the
nightly dist_sync_kvstore.py exact-sum checks (SURVEY §4: multi-process
collective tests runnable on one host → here, multi-device mesh on the
virtual 8-device CPU backend)."""

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import kvstore as kvs
from mxnet_tpu import parallel


SHAPE = (4, 4)
KEYS = [5, 7, 11]


def init_kv(name="local"):
    kv = kvs.create(name)
    kv.init(3, mx.nd.zeros(SHAPE))
    kv.init(KEYS, [mx.nd.zeros(SHAPE)] * len(KEYS))
    return kv


@pytest.mark.parametrize("name", ["local", "device", "dist_tpu_sync"])
def test_single_kv_pair(name):
    kv = init_kv(name)
    kv.push(3, mx.nd.ones(SHAPE))
    out = mx.nd.empty(SHAPE)
    kv.pull(3, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.ones(SHAPE))


def test_list_kv_pair():
    kv = init_kv()
    kv.push(KEYS, [mx.nd.ones(SHAPE) * 4] * len(KEYS))
    out = [mx.nd.empty(SHAPE)] * len(KEYS)
    kv.pull(KEYS, out=out)
    for o in out:
        np.testing.assert_allclose(o.asnumpy(), np.full(SHAPE, 4.0))


def test_aggregator():
    """Multi-device push aggregates by sum (test_kvstore.py
    test_aggregator): push a list of 'device' values for one key."""
    kv = init_kv()
    num_devs = 4
    devs_vals = [mx.nd.ones(SHAPE) for _ in range(num_devs)]
    kv.push(3, devs_vals)
    out = mx.nd.empty(SHAPE)
    kv.pull(3, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full(SHAPE, num_devs))


def test_updater_runs_on_store():
    """update_on_kvstore: optimizer applied inside the store
    (dist_sync_kvstore.py check_diff semantics)."""
    kv = init_kv()
    opt = mx.optimizer.create("test", rescale_grad=1.0)
    kv.set_optimizer(opt)
    kv.push(3, [mx.nd.ones(SHAPE)] * 4)
    out = mx.nd.empty(SHAPE)
    kv.pull(3, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full(SHAPE, 4.0))
    kv.push(3, [mx.nd.ones(SHAPE)] * 4)
    kv.pull(3, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full(SHAPE, 8.0))


def test_row_sparse_pull():
    kv = init_kv()
    kv.push(3, mx.nd.array(np.arange(16).reshape(4, 4).astype(np.float32)))
    out = mx.nd.zeros(SHAPE)
    row_ids = mx.nd.array([1, 3])
    kv.row_sparse_pull(3, out=out, row_ids=row_ids)
    expect = np.zeros(SHAPE, dtype=np.float32)
    src = np.arange(16).reshape(4, 4)
    expect[1] = src[1]
    expect[3] = src[3]
    np.testing.assert_allclose(out.asnumpy(), expect)


def test_dist_async_rejected():
    with pytest.raises(ValueError):
        kvs.create("dist_async")


def test_mesh_collectives_exact_sum():
    """shard_map psum over the 8-device CPU mesh — the all-reduce that
    backs dist_tpu_sync (exact-sum check as in dist_sync_kvstore.py:28)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    import jax.numpy as jnp

    mesh = parallel.make_mesh({"dp": 8})
    x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)

    def f(xs):
        return parallel.all_reduce(xs, "dp")

    g = shard_map(f, mesh=mesh, in_specs=P("dp", None),
                  out_specs=P("dp", None))
    out = np.asarray(jax.jit(g)(x))
    expect = x.reshape(8, 1, 4).sum(axis=0)
    for d in range(8):
        np.testing.assert_allclose(out[d:d + 1], expect, rtol=1e-6)


def test_kvstore_type_and_rank():
    kv = kvs.create("dist_tpu_sync")
    assert kv.type == "dist_tpu_sync"
    assert kv.rank == 0
    assert kv.num_workers == 1
    kv.barrier()


def test_optimizer_states_save_load(tmp_path):
    kv = init_kv()
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1,
                                         momentum=0.9))
    kv.push(3, mx.nd.ones(SHAPE))
    p = str(tmp_path / "states")
    kv.save_optimizer_states(p)
    kv.load_optimizer_states(p)


def test_dist_tpu_sync_exact_sum_through_kvstore():
    """Exact-sum across 8 'workers' THROUGH the KVStore API (reference
    tests/nightly/dist_sync_kvstore.py:28-60 check_diff): each worker
    pushes rank+1; the pulled aggregate must equal n(n+1)/2 exactly, and
    the reduction must run as one sharded XLA computation over the
    8-device mesh (one shard per device along the worker axis)."""
    n = jax.device_count()
    assert n == 8, "suite runs on the virtual 8-device mesh"
    kv = kvs.create("dist_tpu_sync")
    kv.init(9, mx.nd.zeros(SHAPE))
    vals = [mx.nd.ones(SHAPE) * (i + 1) for i in range(n)]
    kv.push(9, vals)
    out = mx.nd.empty(SHAPE)
    kv.pull(9, out=out)
    expect = np.full(SHAPE, n * (n + 1) / 2.0, np.float32)
    np.testing.assert_array_equal(out.asnumpy(), expect)
    # the stored aggregate must actually live replicated over all 8
    # devices (i.e. the collective path ran, not a host loop)
    stored = kv._store["9"]._data
    assert len(stored.sharding.device_set) == n
    # repeated rounds stay exact
    kv.push(9, vals)
    kv.pull(9, out=out)
    np.testing.assert_array_equal(out.asnumpy(), expect)


def test_dist_tpu_sync_update_on_kvstore_mesh():
    """update_on_kvstore over the mesh: optimizer applies to the stored
    weight with the collective-aggregated gradient."""
    n = jax.device_count()
    kv = kvs.create("dist_tpu_sync")
    kv.init(2, mx.nd.zeros(SHAPE))
    kv.set_optimizer(mx.optimizer.create("test", rescale_grad=1.0))
    kv.push(2, [mx.nd.ones(SHAPE)] * n)
    out = mx.nd.empty(SHAPE)
    kv.pull(2, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full(SHAPE, float(n)))


def test_gradient_compression_reconstruction():
    """2-bit compression semantics (gradient_compression.h:38-132):
    values >= threshold -> +threshold, <= -threshold -> -threshold, else
    0, with the quantization error accumulated in a residual that feeds
    back into the next round (dist_sync_kvstore.py compression checks)."""
    from mxnet_tpu.gradient_compression import GradientCompression
    gc = GradientCompression(type="2bit", threshold=0.5)
    grad = np.array([0.7, -0.6, 0.3, -0.2, 1.3, 0.0], np.float32)
    res = gc.init_residual(grad.shape)
    recon, res = gc.compress_decompress(jax.numpy.asarray(grad), res)
    np.testing.assert_allclose(
        np.asarray(recon), [0.5, -0.5, 0.0, 0.0, 0.5, 0.0])
    np.testing.assert_allclose(
        np.asarray(res), [0.2, -0.1, 0.3, -0.2, 0.8, 0.0], atol=1e-6)
    # error feedback: pushing zero gradients flushes accumulated residual
    recon2, res = gc.compress_decompress(
        jax.numpy.zeros_like(jax.numpy.asarray(grad)), res)
    np.testing.assert_allclose(
        np.asarray(recon2), [0.0, 0.0, 0.0, 0.0, 0.5, 0.0])
    np.testing.assert_allclose(
        np.asarray(res), [0.2, -0.1, 0.3, -0.2, 0.3, 0.0], atol=1e-6)


def test_gradient_compression_packing_factor():
    """The wire format really is 2 bits/value: 16 fp32 -> one uint32."""
    from mxnet_tpu.gradient_compression import GradientCompression
    gc = GradientCompression(type="2bit", threshold=1.0)
    grad = jax.numpy.asarray(np.linspace(-2, 2, 64, dtype=np.float32))
    packed, _ = gc.quantize(grad, gc.init_residual(grad.shape))
    assert packed.shape == (4,) and packed.dtype == np.uint32
    assert gc.get_compression_factor() == 16
    assert gc.compressed_size(100) == 7
    out = gc.dequantize(packed, grad.shape)
    expect = np.where(np.asarray(grad) >= 1.0, 1.0,
                      np.where(np.asarray(grad) <= -1.0, -1.0, 0.0))
    np.testing.assert_allclose(np.asarray(out), expect)


def test_kvstore_compression_through_push():
    """set_gradient_compression wires into push: small gradients are
    suppressed until residual crosses the threshold."""
    kv = kvs.create("dist_tpu_sync")
    kv.init(4, mx.nd.zeros(SHAPE))
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    assert kv.gradient_compression.active
    small = mx.nd.ones(SHAPE) * 0.3
    out = mx.nd.empty(SHAPE)
    kv.push(4, small)          # residual 0.3 — below threshold
    kv.pull(4, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.zeros(SHAPE))
    kv.push(4, small)          # residual 0.6 — emits +0.5
    kv.pull(4, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full(SHAPE, 0.5))
