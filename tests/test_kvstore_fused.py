"""Bucketed gradient fusion tests (parallel/fusion.py + kvstore
pushpull_fused): bit-exactness vs the per-key path across the virtual
8-device mesh (dist_sync_kvstore.py check_diff style), bucket planning,
mixed-dtype lanes straddling a bucket boundary, the sharded weight
update (reduce-scatter -> 1/N optimizer update -> all-gather) and its
optimizer-state round-trip, and the dispatch-count contract the
benchmark relies on."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import kvstore as kvs
from mxnet_tpu.parallel import fusion


SHAPES = [(64, 32), (3,), (17, 5, 2), (128,), (1024,), (9, 9)]


def _grads(shapes, n_workers, seed=0, dtypes=None):
    rng = np.random.RandomState(seed)
    out = []
    for i, s in enumerate(shapes):
        dt = np.float32 if dtypes is None else dtypes[i]
        out.append([rng.uniform(-1, 1, s).astype(dt)
                    for _ in range(n_workers)])
    return out


# ------------------------------------------------------------ planning --

def test_plan_buckets_fixed_byte_budget():
    entries = [(str(i), (1000,), "float32") for i in range(10)]  # 4 kB each
    plan = fusion.plan_buckets(entries, max_bytes=12000)         # 3 per bucket
    assert [len(b.lanes[0].segments) for b in plan] == [3, 3, 3, 1]
    # segments keep caller order and tile back to back
    lane = plan[0].lanes[0]
    assert [s.key for s in lane.segments] == ["0", "1", "2"]
    assert [s.offset for s in lane.segments] == [0, 1000, 2000]


def test_plan_buckets_oversized_entry_travels_alone():
    entries = [("small", (10,), "float32"),
               ("big", (10_000_000,), "float32"),
               ("tail", (10,), "float32")]
    plan = fusion.plan_buckets(entries, max_bytes=1 << 20)
    assert len(plan) == 3
    assert [b.lanes[0].segments[0].key for b in plan] \
        == ["small", "big", "tail"]


def test_plan_buckets_mixed_dtypes_get_separate_lanes():
    entries = [("a", (8,), "float32"), ("b", (8,), "bfloat16"),
               ("c", (8,), "float32")]
    plan = fusion.plan_buckets(entries, max_bytes=1 << 20)
    assert len(plan) == 1
    lanes = {l.dtype: [s.key for s in l.segments] for l in plan[0].lanes}
    assert lanes == {"float32": ["a", "c"], "bfloat16": ["b"]}


def test_pack_unpack_roundtrip():
    entries = [("x", (4, 3), "float32"), ("y", (7,), "float32")]
    plan = fusion.plan_buckets(entries)
    lane = plan[0].lanes[0]
    vals = {"x": jnp.arange(12.0).reshape(4, 3), "y": jnp.ones(7)}
    flat = fusion.pack_lane(lane, vals, pad_to=24)
    assert flat.shape == (24,)
    back = fusion.unpack_lane(flat, lane)
    np.testing.assert_array_equal(np.asarray(back["x"]),
                                  np.asarray(vals["x"]))
    np.testing.assert_array_equal(np.asarray(back["y"]),
                                  np.asarray(vals["y"]))


# ------------------------------------------------------- bit-exactness --

@pytest.mark.parametrize("kv_type", ["local", "device", "dist_tpu_sync"])
def test_fused_bit_exact_vs_per_key(kv_type):
    """The fused aggregate must equal the per-key aggregate BIT FOR BIT
    on a multi-device mesh (acceptance: >= 4 devices)."""
    n = jax.device_count()
    assert n >= 4
    raw = _grads(SHAPES, n, seed=3)

    kv_a = kvs.create(kv_type)
    kv_b = kvs.create(kv_type)
    keys = list(range(len(SHAPES)))
    for kv in (kv_a, kv_b):
        for k, s in zip(keys, SHAPES):
            kv.init(k, mx.nd.zeros(s))

    grads_a = [[mx.nd.array(a) for a in row] for row in raw]
    outs_a = [mx.nd.empty(s) for s in SHAPES]
    kv_a.push(keys, grads_a)
    kv_a.pull(keys, out=outs_a)

    grads_b = [[mx.nd.array(a) for a in row] for row in raw]
    outs_b = [mx.nd.empty(s) for s in SHAPES]
    kv_b.pushpull_fused(keys, grads_b, out=outs_b)

    for oa, ob in zip(outs_a, outs_b):
        np.testing.assert_array_equal(oa.asnumpy(), ob.asnumpy())


def test_fused_exact_sum_check_diff():
    """dist_sync_kvstore.py:28 check_diff through the fused path: every
    worker pushes rank+1, the aggregate must be exactly n(n+1)/2."""
    n = jax.device_count()
    kv = kvs.create("dist_tpu_sync")
    keys = list(range(len(SHAPES)))
    for k, s in zip(keys, SHAPES):
        kv.init(k, mx.nd.zeros(s))
    grads = [[mx.nd.ones(s) * (r + 1) for r in range(n)] for s in SHAPES]
    outs = [mx.nd.empty(s) for s in SHAPES]
    kv.pushpull_fused(keys, grads, out=outs)
    for o, s in zip(outs, SHAPES):
        np.testing.assert_array_equal(
            o.asnumpy(), np.full(s, n * (n + 1) / 2.0, np.float32))


def test_fused_mixed_dtype_straddles_bucket_boundary():
    """A tiny bucket budget forces a boundary INSIDE an interleaved
    fp32/bf16 key sequence; each dtype lane must still aggregate
    bit-exactly (no cross-dtype concat, no cast)."""
    n = jax.device_count()
    shapes = [(300,), (300,), (300,), (300,), (300,), (300,)]
    dtypes = [np.float32, "bfloat16", np.float32,
              "bfloat16", np.float32, np.float32]
    keys = list(range(len(shapes)))
    rng = np.random.RandomState(11)
    raw = [[(rng.uniform(-1, 1, s) * 4).astype(np.float32)
            for _ in range(n)] for s in shapes]

    def build(kv):
        grads = []
        for k, (row, dt) in enumerate(zip(raw, dtypes)):
            kv.init(k, mx.nd.zeros(shapes[k], dtype=np.dtype(dt).name))
            grads.append([mx.nd.array(a, dtype=np.dtype(dt).name)
                          for a in row])
        return grads

    os.environ["MXNET_KVSTORE_BUCKET_BYTES"] = "2500"  # ~2 keys/bucket
    try:
        kv_a, kv_b = kvs.create("dist_tpu_sync"), kvs.create("dist_tpu_sync")
        ga, gb = build(kv_a), build(kv_b)
        outs_a = [mx.nd.zeros(s, dtype=np.dtype(dt).name)
                  for s, dt in zip(shapes, dtypes)]
        outs_b = [mx.nd.zeros(s, dtype=np.dtype(dt).name)
                  for s, dt in zip(shapes, dtypes)]
        kv_a.push(keys, ga)
        kv_a.pull(keys, out=outs_a)
        kv_b.pushpull_fused(keys, gb, out=outs_b)
        # the plan really straddled: > 1 bucket and both dtypes present
        plan = list(kv_b._fusion_plans.values())[0]
        assert len(plan) >= 3
        assert {l.dtype for b in plan for l in b.lanes} \
            == {"float32", "bfloat16"}
        for oa, ob in zip(outs_a, outs_b):
            assert oa.dtype == ob.dtype
            np.testing.assert_array_equal(oa.asnumpy(), ob.asnumpy())
    finally:
        del os.environ["MXNET_KVSTORE_BUCKET_BYTES"]


def test_fused_update_on_kvstore_matches_per_key():
    """updater set, no sharding: the fused path unpacks the aggregate
    and applies the same per-key updater — trajectories identical."""
    n = jax.device_count()
    shapes = [(32, 16), (16,), (64,)]
    keys = list(range(len(shapes)))
    raw = _grads(shapes, n, seed=5)
    stores = []
    for fused in (False, True):
        kv = kvs.create("dist_tpu_sync")
        kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1,
                                             momentum=0.9))
        for k, s in zip(keys, shapes):
            kv.init(k, mx.nd.ones(s))
        for _ in range(3):
            grads = [[mx.nd.array(a) for a in row] for row in raw]
            if fused:
                kv.pushpull_fused(keys, grads)
            else:
                kv.push(keys, grads)
        stores.append([kv._store[str(k)].asnumpy() for k in keys])
    for a, b in zip(*stores):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


# ------------------------------------------------------ sharded update --

def _shard_env(on=True):
    if on:
        os.environ["MXNET_KVSTORE_SHARD_UPDATE"] = "1"
    else:
        os.environ.pop("MXNET_KVSTORE_SHARD_UPDATE", None)


@pytest.mark.parametrize("optimizer,hyper", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("sgd", {"learning_rate": 0.05}),
    ("adam", {"learning_rate": 0.01}),
])
def test_shard_update_matches_replicated(optimizer, hyper):
    """reduce-scatter -> sharded update -> all-gather must produce the
    same weights as the replicated per-key update. Integer-valued
    gradients make the collective sum order-independent, so the
    comparison is exact for sgd; adam's rsqrt tolerates 1e-6."""
    n = jax.device_count()
    shapes = [(40, 12), (30,), (333,), (8, 8, 2)]
    keys = list(range(len(shapes)))
    rng = np.random.RandomState(7)
    raw = [[rng.randint(-4, 5, s).astype(np.float32) for _ in range(n)]
           for s in shapes]
    weights = {}
    for shard in (False, True):
        _shard_env(shard)
        try:
            kv = kvs.create("dist_tpu_sync")
            kv.set_optimizer(mx.optimizer.create(optimizer, **hyper))
            for k, s in zip(keys, shapes):
                kv.init(k, mx.nd.ones(s))
            for _ in range(4):
                grads = [[mx.nd.array(a) for a in row] for row in raw]
                kv.pushpull_fused(keys, grads)
            weights[shard] = [kv._store[str(k)].asnumpy() for k in keys]
            if shard:
                assert kv._shard_slots, "shard path did not engage"
        finally:
            _shard_env(False)
    for a, b in zip(weights[False], weights[True]):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)


def test_shard_update_state_bytes_cut():
    """Acceptance: per-replica optimizer-state bytes drop ~(N-1)/N —
    the state arrays are genuinely sharded 1/N per device."""
    n = jax.device_count()
    _shard_env(True)
    try:
        kv = kvs.create("dist_tpu_sync")
        kv.set_optimizer(mx.optimizer.create("adam", learning_rate=0.01))
        shapes = [(256, 32), (1000,), (128, 7)]
        keys = list(range(len(shapes)))
        for k, s in zip(keys, shapes):
            kv.init(k, mx.nd.ones(s))
        grads = [[mx.nd.ones(s) for _ in range(n)] for s in shapes]
        kv.pushpull_fused(keys, grads)
        assert kv._shard_slots
        for slot in kv._shard_slots.values():
            assert slot.state_bytes_per_replica * n \
                == slot.state_bytes_total
            for st in slot.states:
                assert len(st.sharding.device_set) == n
                # each device holds exactly 1/N of the flat state
                shard0 = st.addressable_shards[0]
                assert shard0.data.size * n == st.size
    finally:
        _shard_env(False)


def test_shard_update_optimizer_state_roundtrip(tmp_path):
    """save -> keep training -> reload -> retrain must replay the same
    trajectory (momentum state round-trips through the flat shards)."""
    n = jax.device_count()
    shapes = [(24, 8), (50,)]
    keys = [0, 1]
    rng = np.random.RandomState(13)
    raw = [[rng.randint(-3, 4, s).astype(np.float32) for _ in range(n)]
           for s in shapes]

    def push(kv):
        kv.pushpull_fused(keys, [[mx.nd.array(a) for a in row]
                                 for row in raw])

    _shard_env(True)
    try:
        kv = kvs.create("dist_tpu_sync")
        kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1,
                                             momentum=0.9))
        for k, s in zip(keys, shapes):
            kv.init(k, mx.nd.ones(s))
        push(kv)
        push(kv)
        fname = str(tmp_path / "states")
        kv.save_optimizer_states(fname)
        snap_w = [kv._store[str(k)].asnumpy().copy() for k in keys]
        push(kv)
        after1 = [kv._store[str(k)].asnumpy() for k in keys]

        # rebuild a store at the snapshot point and reload the states
        kv2 = kvs.create("dist_tpu_sync")
        kv2.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1,
                                              momentum=0.9))
        for k, s, w in zip(keys, shapes, snap_w):
            kv2.init(k, mx.nd.array(w))
        kv2.load_optimizer_states(fname)     # hydrates lazily
        push(kv2)
        after2 = [kv2._store[str(k)].asnumpy() for k in keys]
        for a, b in zip(after1, after2):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    finally:
        _shard_env(False)


def test_shard_update_multi_precision_master_is_sharded():
    """bf16 weights + multi_precision: the fp32 master lives SHARDED
    (the PAPERS.md fp32-master-state cut) and weights stay bf16."""
    n = jax.device_count()
    _shard_env(True)
    try:
        kv = kvs.create("dist_tpu_sync")
        kv.set_optimizer(mx.optimizer.create(
            "sgd", learning_rate=0.1, momentum=0.9, multi_precision=True))
        kv.init(0, mx.nd.ones((128, 16), dtype="bfloat16"))
        grads = [[mx.nd.ones((128, 16), dtype="bfloat16")
                  for _ in range(n)]]
        kv.pushpull_fused([0], grads)
        slot = list(kv._shard_slots.values())[0]
        assert slot.master_fp32
        assert slot.flat_w.dtype == jnp.float32
        assert slot.flat_w.addressable_shards[0].data.size * n \
            == slot.flat_w.size
        assert kv._store["0"]._data.dtype == jnp.bfloat16
    finally:
        _shard_env(False)


# ------------------------------------------------------ dispatch count --

def test_fused_dispatch_count_contract():
    """The benchmark's acceptance lever: >= 5x fewer collective
    dispatches for a many-small-keys model."""
    n = jax.device_count()
    shapes = [(64,)] * 30
    keys = list(range(30))
    kv = kvs.create("dist_tpu_sync")
    for k, s in zip(keys, shapes):
        kv.init(k, mx.nd.zeros(s))
    grads = [[mx.nd.ones(s) for _ in range(n)] for s in shapes]
    kv.reset_dispatch_stats()
    kv.push(keys, grads)
    per_key = kv.dispatch_stats["collectives"]
    kv.reset_dispatch_stats()
    kv.pushpull_fused(keys, grads)
    fused = kv.dispatch_stats["collectives"]
    assert per_key == 30
    assert fused == 1
    assert per_key >= 5 * fused


# ------------------------------------------------------ in-jit fusion --

def test_bucketed_all_reduce_in_jit():
    """The in-jit form: one psum per bucket inside shard_map, results
    equal per-array psums."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from mxnet_tpu import parallel

    n = jax.device_count()
    mesh = parallel.make_mesh({"dp": n})
    shapes = [(n, 16), (n, 3), (n, 40)]
    rng = np.random.RandomState(2)
    xs = [rng.randint(-5, 6, s).astype(np.float32) for s in shapes]

    def fused(*args):
        return tuple(parallel.bucketed_all_reduce(list(args),
                                                  axis_name="dp"))

    def per_key(*args):
        return tuple(jax.lax.psum(a, "dp") for a in args)

    specs = tuple(P("dp") for _ in shapes)
    out_f = jax.jit(shard_map(fused, mesh=mesh, in_specs=specs,
                              out_specs=specs))(*xs)
    out_p = jax.jit(shard_map(per_key, mesh=mesh, in_specs=specs,
                              out_specs=specs))(*xs)
    for a, b in zip(out_f, out_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------- trainer wiring --

def test_trainer_fused_matches_per_key_path():
    """Trainer.step through the bucketed path == per-key path."""
    from mxnet_tpu import gluon, autograd

    def run(fused):
        os.environ["MXNET_KVSTORE_FUSION"] = "1" if fused else "0"
        try:
            net = gluon.nn.Dense(7, in_units=5)
            net.initialize(mx.init.Constant(0.5))
            tr = gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1, "momentum": 0.9},
                               kvstore="dist_tpu_sync")
            x = mx.nd.array(np.arange(15, dtype=np.float32).reshape(3, 5))
            for _ in range(3):
                with autograd.record():
                    y = net(x)
                    loss = (y * y).sum()
                loss.backward()
                tr.step(batch_size=3)
            return [p.data().asnumpy()
                    for p in net.collect_params().values()]
        finally:
            del os.environ["MXNET_KVSTORE_FUSION"]

    for a, b in zip(run(False), run(True)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_trainer_shard_update_end_to_end():
    """MXNET_KVSTORE_SHARD_UPDATE=1 flips the Trainer onto the
    store-side sharded update; trajectory matches the local update."""
    from mxnet_tpu import gluon, autograd

    def run(shard):
        _shard_env(shard)
        try:
            net = gluon.nn.Dense(6, in_units=4)
            net.initialize(mx.init.Constant(0.25))
            tr = gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.05, "momentum": 0.9},
                               kvstore="dist_tpu_sync")
            x = mx.nd.array(np.arange(8, dtype=np.float32).reshape(2, 4))
            for _ in range(3):
                with autograd.record():
                    loss = (net(x) ** 2).sum()
                loss.backward()
                tr.step(batch_size=2)
            if shard:
                assert tr._update_on_kvstore
                assert tr._kvstore._shard_slots
            return [p.data().asnumpy()
                    for p in net.collect_params().values()]
        finally:
            _shard_env(False)

    for a, b in zip(run(False), run(True)):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)
