"""Parallelism tests on the virtual 8-device CPU mesh: ring attention
(sequence parallel), SPMD transformer train step (dp/tp/sp/ep), and the
driver contract in __graft_entry__.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.ring import ring_attention_sharded
from mxnet_tpu.models import transformer as T


def _ref_attention(q, k, v, causal):
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        Tq = q.shape[1]
        mask = np.tril(np.ones((Tq, Tq), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    B, Tq, H, D = 2, 32, 4, 16
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, Tq, H, D).astype("float32"))
               for _ in range(3))
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("sp", "dp"))
    out = ring_attention_sharded(q, k, v, mesh, axis_name="sp",
                                 causal=causal)
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_ring_attention_grads_flow():
    B, Tq, H, D = 1, 16, 2, 8
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(B, Tq, H, D).astype("float32"))
               for _ in range(3))
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("sp",))
    f = jax.jit(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh, axis_name="sp", causal=True).sum())
    gq, gk = jax.grad(f, argnums=(0, 1))(q, k, v)
    assert float(jnp.abs(gq).sum()) > 0
    assert float(jnp.abs(gk).sum()) > 0


def test_transformer_train_step_dp_tp_sp_ep_loss_drops():
    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2, "ep": 1})
    cfg = T.TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                              n_layers=2, d_ff=64, n_experts=2, max_len=16)
    params = T.shard_params(T.init_params(cfg, seed=0), cfg, mesh)
    mom = T.init_momentum(params)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 32, (8, 16)), jnp.int32)
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    step = T.make_train_step(cfg, mesh, lr=0.1)
    losses = []
    for _ in range(5):
        params, mom, loss = step(params, mom, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_transformer_sharded_matches_single_device():
    """The dp/tp/sp/ep-sharded forward must equal the unsharded one."""
    cfg = T.TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                              n_layers=1, d_ff=64, n_experts=2, max_len=16)
    params = T.init_params(cfg, seed=0)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 32, (4, 16)), jnp.int32)
    ref = T.forward(params, tokens, cfg, mesh=None)

    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2, "ep": 1})
    sharded = T.shard_params(params, cfg, mesh)
    out = T.forward(sharded,
                    jax.device_put(tokens,
                                   NamedSharding(mesh, P("dp", None))),
                    cfg, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_moe_expert_sharded_matches_unsharded():
    """ep>=2 for real: the expert dimension is PARTITIONED (2 experts
    per device at ep=2, n_experts=4), not merely carried under an
    ep-axis of width 1, and the sharded MoE forward/loss/grads must
    equal the unsharded ones. Guards the PARITY EP row — every other
    mesh in this file pins ep=1."""
    cfg = T.TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                              n_layers=2, d_ff=64, n_experts=4,
                              max_len=16)
    params = T.init_params(cfg, seed=0)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 32, (8, 16)), jnp.int32)
    ref_out = T.forward(params, tokens, cfg, mesh=None)
    ref_loss, ref_grads = jax.value_and_grad(T.loss_fn)(
        params, tokens, cfg, None)

    mesh = make_mesh({"ep": 2, "dp": 4, "tp": 1, "sp": 1})
    sharded = T.shard_params(params, cfg, mesh)
    # the expert weights really are split over ep: each device holds
    # half the experts (and all of d_model/d_ff at tp=1)
    w1 = sharded["layers"][0]["w1"]
    assert w1.sharding.spec[0] == "ep"
    assert w1.addressable_shards[0].data.shape == (2, 32, 64)

    tok = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    out = T.forward(sharded, tok, cfg, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-3, atol=2e-3)
    loss, grads = jax.value_and_grad(T.loss_fn)(sharded, tok, cfg, mesh)
    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)
    for g_ref, g_sh in zip(jax.tree.leaves(ref_grads),
                           jax.tree.leaves(grads)):
        np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_ref),
                                   rtol=2e-3, atol=2e-3)


def test_moe_ep_times_tp_train_step_loss_drops():
    """ep and tp sharded together ({'ep':2,'tp':2,'dp':2}): the w1/w2
    expert weights split over BOTH axes (experts over ep, d_ff over tp)
    and training still converges."""
    mesh = make_mesh({"ep": 2, "tp": 2, "dp": 2, "sp": 1})
    cfg = T.TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                              n_layers=2, d_ff=64, n_experts=2,
                              max_len=16)
    params = T.shard_params(T.init_params(cfg, seed=0), cfg, mesh)
    w1 = params["layers"][0]["w1"]
    assert w1.sharding.spec[0] == "ep" and w1.sharding.spec[2] == "tp"
    assert w1.addressable_shards[0].data.shape == (1, 32, 32)
    mom = T.init_momentum(params)
    tokens = jax.device_put(
        jnp.asarray(np.random.RandomState(1).randint(0, 32, (8, 16)),
                    jnp.int32),
        NamedSharding(mesh, P("dp", None)))
    step = T.make_train_step(cfg, mesh, lr=0.1)
    losses = []
    for _ in range(5):
        params, mom, loss = step(params, mom, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_resnet_dp_mesh_matches_single_device():
    """Flagship-model data parallelism through the user-facing gluon
    Trainer/kvstore path: the SAME train loop run (a) single-device and
    (b) with the batch sharded P('dp') over the 8-device mesh must give
    the same losses and parameters (reference DP semantics:
    module/executor_group.py:282-311 — here the batch is one global
    array and XLA inserts the cross-device reductions)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, autograd, gluon
    from mxnet_tpu.gluon.model_zoo import vision

    def run(sharded, steps=2):
        mx.random.seed(77)
        net = vision.resnet18_v1(classes=10)
        net.initialize(mx.init.Xavier(), force_reinit=True)
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05, "momentum": 0.9},
                                kvstore=mx.kvstore.create("device"))
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        rs = np.random.RandomState(0)
        X = rs.rand(8, 3, 32, 32).astype(np.float32)
        Y = rs.randint(0, 10, (8,)).astype(np.float32)
        losses = []
        for _ in range(steps):
            if sharded:
                mesh = make_mesh({"dp": 8})
                x = nd.NDArray(
                    jax.device_put(jnp.asarray(X),
                                   NamedSharding(mesh, P("dp"))), mx.cpu())
                y = nd.NDArray(
                    jax.device_put(jnp.asarray(Y),
                                   NamedSharding(mesh, P("dp"))), mx.cpu())
            else:
                x, y = nd.array(X), nd.array(Y)
            with autograd.record():
                l = loss_fn(net(x), y).mean()
            l.backward()
            trainer.step(1)
            losses.append(float(l.asnumpy()))
        params = {k: v.data().asnumpy()
                  for k, v in net.collect_params().items()}
        return losses, params

    l_ref, p_ref = run(False)
    l_dp, p_dp = run(True)
    # step-1 losses agree to fp32 dispatch noise; later steps accumulate
    # reduction-order drift (psum tree vs single-device sum)
    np.testing.assert_allclose(l_dp[0], l_ref[0], rtol=1e-4)
    np.testing.assert_allclose(l_dp, l_ref, rtol=5e-3)
    # name prefixes differ per instantiation (gluon global name scopes);
    # layer order is deterministic, so align by sorted key
    # tolerance sized to 2 steps of fp32 reduction-order drift through
    # momentum: observed max |delta| ~3e-2 on <0.0003% of elements
    # (CPU psum tree vs single-device sum)
    for kr, kd in zip(sorted(p_ref), sorted(p_dp)):
        np.testing.assert_allclose(p_dp[kd], p_ref[kr], rtol=5e-3,
                                   atol=4e-2, err_msg=kr)


@pytest.mark.slow
def test_graft_entry_dryrun_multichip():
    import __graft_entry__ as ge
    ge.dryrun_multichip(8)


def test_graft_entry_forward_jits():
    import __graft_entry__ as ge
    fn, ex = ge.entry()
    out = jax.jit(fn)(*ex)
    assert out.shape == (8, 1000)
    assert np.isfinite(np.asarray(out)).all()


def test_spmd_pipeline_matches_sequential():
    """parallel/pipeline.py: pp=2 pipeline over a 4-layer MLP stack
    equals sequential layer application, forward and backward."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.pipeline import (stack_stage_params,
                                             spmd_pipeline)
    mesh = make_mesh({"pp": 2, "dp": 4})
    rng = np.random.RandomState(0)
    L, D, B = 4, 8, 8
    layers = [{"w": jnp.asarray(rng.randn(D, D).astype(np.float32) * 0.3),
               "b": jnp.asarray(rng.randn(D).astype(np.float32) * 0.1)}
              for _ in range(L)]
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))

    def layer_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    stacked = stack_stage_params(layers, 2)
    y = jax.jit(lambda s, x_: spmd_pipeline(layer_fn, s, x_, mesh))(
        stacked, x)
    ref = x
    for p in layers:
        ref = jnp.tanh(ref @ p["w"] + p["b"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-6)

    def loss(s, x_):
        return jnp.sum(spmd_pipeline(layer_fn, s, x_, mesh) ** 2)

    def loss_ref(ls, x_):
        h = x_
        for p in ls:
            h = jnp.tanh(h @ p["w"] + p["b"])
        return jnp.sum(h ** 2)

    g = jax.jit(jax.grad(loss))(stacked, x)
    gref = jax.grad(loss_ref)(layers, x)
    # stage 0 layer 0 == layers[0]; stage 1 layer 1 == layers[3]
    np.testing.assert_allclose(np.asarray(g["w"][0, 0]),
                               np.asarray(gref[0]["w"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(g["w"][1, 1]),
                               np.asarray(gref[3]["w"]), atol=1e-5)


def test_transformer_pp_matches_unsharded():
    """Full transformer train-step parity: pp=2 (+sp ring attention +tp)
    loss equals the single-device unsharded loss (VERDICT r1 item 6)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.models import transformer as T
    mesh = make_mesh({"pp": 2, "sp": 2, "tp": 2, "dp": 1, "ep": 1})
    cfg = T.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                              n_layers=4, d_ff=64, max_len=32,
                              pp_axis="pp", use_ring_attention=True)
    cfg_ref = T.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                  n_layers=4, d_ff=64, max_len=32,
                                  use_ring_attention=False)
    params = T.init_params(cfg, seed=0)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (4, 32)), jnp.int32)
    loss_ref = float(T.loss_fn(params, tokens, cfg_ref, mesh=None))
    sharded = T.shard_params(params, cfg, mesh)
    tok = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    loss_pp = float(jax.jit(
        lambda p, t: T.loss_fn(p, t, cfg, mesh))(sharded, tok))
    # tolerance: the pipeline decomposition's reduction order differs
    # from the unsharded step; observed
    # drift is ~1e-3 relative, a REAL divergence would be O(1)
    assert abs(loss_ref - loss_pp) < 5e-3 * abs(loss_ref), \
        (loss_ref, loss_pp)
    # and the full train step executes with finite loss
    step = T.make_train_step(cfg, mesh, lr=1e-2)
    _, _, l = step(sharded, T.init_momentum(sharded), tok)
    assert np.isfinite(float(l))


def test_expert_parallel_ep2_matches_dense():
    """MoE layers sharded over a REAL ep axis (dp2 x sp2 x ep2) equal
    the unsharded forward — expert weights split across the expert
    axis, tokens routed by the gate regardless of placement."""
    import jax
    import jax.numpy as jnp
    cfg = T.TransformerConfig(vocab_size=16, d_model=32, n_heads=2,
                              n_layers=1, d_ff=64, n_experts=2,
                              max_len=16, tp_axis=None)
    params = T.init_params(cfg, seed=0)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 16, (4, 16)), jnp.int32)
    ref = T.forward(params, tokens, cfg, mesh=None)
    mesh = make_mesh({"dp": 2, "sp": 2, "ep": 2})
    with mesh:
        sp = T.shard_params(T.init_params(cfg, seed=0), cfg, mesh)
        out = jax.jit(lambda p, t: T.forward(p, t, cfg, mesh))(sp, tokens)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_decode_step_sharded_matches_single_device():
    """Serving under the mesh: TP-sharded weights + a dp/tp-sharded KV
    cache decode to the same logits as the unsharded step (GSPMD
    inserts the wo all-reduce; attention stays device-local per head
    shard)."""
    cfg = T.TransformerConfig(vocab_size=31, d_model=32, n_heads=4,
                              n_layers=2, d_ff=48, max_len=16)
    params = T.init_params(cfg, seed=7)
    rs = np.random.RandomState(8)
    toks = jnp.asarray(rs.randint(0, 31, (4, 10)), jnp.int32)

    # single-device reference
    cache = T.init_cache(cfg, 4)
    ref = []
    for pos in range(10):
        logits, cache = T.decode_step(params, cache, toks[:, pos], pos,
                                      cfg)
        ref.append(np.asarray(logits))

    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2, "ep": 1})
    sp = T.shard_params(params, cfg, mesh)
    scache = T.shard_cache(T.init_cache(cfg, 4), cfg, mesh)
    stoks = jax.device_put(
        toks, NamedSharding(mesh, P("dp", None)))
    step = T.make_decode_step(cfg)
    for pos in range(10):
        logits, scache = step(sp, scache, stoks[:, pos], pos)
        np.testing.assert_allclose(np.asarray(logits), ref[pos],
                                   rtol=2e-4, atol=2e-4)


def test_sp_flash_decode_matches_dense():
    """Sequence-parallel flash decoding: the KV cache sharded over sp,
    per-shard partial softmax + lse combine == dense attention over
    the full cache, including lengths that end inside a shard (and
    shards that hold no valid keys)."""
    from mxnet_tpu.parallel.ring import sp_flash_decode

    B, T, H, D = 3, 64, 2, 16
    rng = np.random.RandomState(21)
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
    kc = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    vc = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    mesh = make_mesh({"sp": 8})
    lengths = np.array([5, 64, 17], np.int32)   # shard 0 only / all / mid

    out = sp_flash_decode(q, kc, vc, jnp.asarray(lengths), mesh)
    for i in range(B):
        L = int(lengths[i])
        s = np.einsum("hd,thd->ht", np.asarray(q[i], np.float64),
                      np.asarray(kc[i, :L], np.float64)) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("ht,thd->hd", p, np.asarray(vc[i, :L],
                                                    np.float64))
        np.testing.assert_allclose(np.asarray(out[i]), ref,
                                   rtol=2e-4, atol=2e-4)


def test_sp_flash_decode_warns_when_explicit_pallas_overridden():
    """An EXPLICIT use_pallas=True dropped by interpret mode (non-TPU
    backend) must be audible — deliberate fallback vs misconfiguration
    (ADVICE r5). The env-driven and default paths stay silent."""
    import warnings
    from mxnet_tpu.parallel.ring import sp_flash_decode

    B, T, H, D = 2, 32, 2, 8
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
    kc = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    vc = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    mesh = make_mesh({"sp": 8})
    lengths = jnp.asarray(np.array([7, 32], np.int32))

    with pytest.warns(UserWarning, match="use_pallas=True ignored"):
        noisy = sp_flash_decode(q, kc, vc, lengths, mesh,
                                use_pallas=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        quiet = sp_flash_decode(q, kc, vc, lengths, mesh)
    # the override still computes the right thing, just audibly
    np.testing.assert_allclose(np.asarray(noisy), np.asarray(quiet),
                               rtol=1e-6, atol=1e-6)


def test_sp_flash_decode_zero_length_row():
    """A batch row with global length 0 (fresh sequence in a mixed
    batch) returns zeros, not the mean of V."""
    from mxnet_tpu.parallel.ring import sp_flash_decode

    B, T, H, D = 2, 32, 1, 8
    rng = np.random.RandomState(23)
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
    kc = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    vc = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    mesh = make_mesh({"sp": 8})
    out = sp_flash_decode(q, kc, vc, jnp.asarray([0, 10], np.int32),
                          mesh)
    np.testing.assert_allclose(np.asarray(out[0]), 0.0, atol=1e-6)
    assert np.abs(np.asarray(out[1])).max() > 1e-3


def test_rope_ring_matches_single_device():
    """RoPE under sp-sharded ring attention: per-shard global position
    offsets make the sharded forward equal the single-device one."""
    cfg = T.TransformerConfig(vocab_size=31, d_model=32, n_heads=4,
                              n_layers=2, d_ff=48, max_len=32,
                              rope=True)
    params = T.init_params(cfg, seed=25)
    toks = jnp.asarray(np.random.RandomState(26).randint(0, 31, (2, 32)),
                       jnp.int32)
    single = T.forward(params, toks, cfg)

    mesh = make_mesh({"dp": 1, "tp": 1, "sp": 8, "ep": 1})
    sp = T.shard_params(params, cfg, mesh)
    stoks = jax.device_put(toks, NamedSharding(mesh, P(None, None)))
    sharded = T.forward(sp, stoks, cfg, mesh)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(single),
                               rtol=2e-4, atol=2e-4)


def test_rope_pipeline_matches_unsharded():
    """RoPE inside the pipeline stage body (manual sp shard_map):
    axis-offset rotation makes pp/sp/tp loss equal single-device."""
    mesh = make_mesh({"pp": 2, "sp": 2, "tp": 2, "dp": 1, "ep": 1})
    cfg = T.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                              n_layers=4, d_ff=64, max_len=32,
                              pp_axis="pp", use_ring_attention=True,
                              rope=True)
    cfg_ref = T.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                  n_layers=4, d_ff=64, max_len=32,
                                  use_ring_attention=False, rope=True)
    params = T.init_params(cfg, seed=27)
    tokens = jnp.asarray(
        np.random.RandomState(28).randint(0, 64, (4, 32)), jnp.int32)
    loss_ref = float(T.loss_fn(params, tokens, cfg_ref, mesh=None))
    sharded = T.shard_params(params, cfg, mesh)
    tok = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    loss_pp = float(jax.jit(
        lambda p, t: T.loss_fn(p, t, cfg, mesh))(sharded, tok))
    # relative tolerance for decomposition drift (see
    # test_transformer_pp_matches_unsharded)
    assert abs(loss_ref - loss_pp) < 5e-3 * abs(loss_ref), \
        (loss_ref, loss_pp)


def test_sp_flash_decode_gqa_matches_repeated_kv():
    """GQA through the sequence-parallel decode: a KVH-head cache
    sharded over sp equals the same computation with the cache
    repeated to MHA width (group mapping is per-shard, combine is
    head-wise — both paths must agree including mid-shard lengths)."""
    from mxnet_tpu.parallel.ring import sp_flash_decode

    B, T, H, KVH, D = 2, 64, 4, 2, 16
    rng = np.random.RandomState(29)
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
    kc = jnp.asarray(rng.randn(B, T, KVH, D).astype(np.float32))
    vc = jnp.asarray(rng.randn(B, T, KVH, D).astype(np.float32))
    lengths = np.asarray([64, 23], np.int32)
    mesh = make_mesh({"sp": 8})
    gqa = sp_flash_decode(q, kc, vc, jnp.asarray(lengths), mesh)
    # independent fp64 dense reference (NOT the repeated-KV call —
    # off-TPU the interpret fallback repeats KV itself, and comparing
    # it with a hand-repeated call would be a self-comparison)
    g = H // KVH
    for i in range(2):
        L = int(lengths[i])
        kr = np.repeat(np.asarray(kc[i, :L], np.float64), g, axis=1)
        vr = np.repeat(np.asarray(vc[i, :L], np.float64), g, axis=1)
        s = np.einsum("hd,thd->ht", np.asarray(q[i], np.float64),
                      kr) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("ht,thd->hd", p, vr)
        np.testing.assert_allclose(np.asarray(gqa[i]), ref,
                                   rtol=2e-4, atol=2e-4)
