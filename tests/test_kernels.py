"""Pallas kernels (interpret mode on CPU, compiled on TPU).

Reference counterpart: the hand-written CUDA kernels / cuDNN call-outs
the reference keeps where codegen fell short; here the set is small and
Pallas-based (kernels/).
"""

import importlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.kernels import flash_attention

# the package re-exports the function under the module's own name
_fa = importlib.import_module("mxnet_tpu.kernels.flash_attention")


def _dense_attention(q, k, v, causal):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = np.einsum("bqhd,bkhd->bhqk", q, k).astype(np.float64) * scale
    if causal:
        tq, tk = s.shape[2], s.shape[3]
        mask = np.arange(tq)[:, None] >= np.arange(tk)[None, :]
        s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v.astype(np.float64))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_dense(causal):
    rng = np.random.RandomState(0)
    b, t, h, d = 2, 64, 3, 16
    q = rng.randn(b, t, h, d).astype(np.float32)
    k = rng.randn(b, t, h, d).astype(np.float32)
    v = rng.randn(b, t, h, d).astype(np.float32)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, block_q=16, block_k=16)
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_flash_attention_cross_lengths():
    rng = np.random.RandomState(1)
    q = rng.randn(1, 32, 2, 8).astype(np.float32)
    k = rng.randn(1, 96, 2, 8).astype(np.float32)
    v = rng.randn(1, 96, 2, 8).astype(np.float32)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          block_q=16, block_k=32)
    ref = _dense_attention(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_flash_attention_gcd_adjusts_ragged_blocks():
    """A block that does not divide the sequence is gcd-adjusted (one
    deterministic rule shared by explicit args, env overrides, and
    the transformer call site) — same numerics as a dividing block.
    When the gcd COLLAPSES (30 % 16 -> gcd 2, a degenerate 15-step
    grid) the kernel warns and falls back to one full-sequence block
    instead of silently building the fine grid (ADVICE r5)."""
    import warnings
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 30, 1, 8), jnp.float32)
    with pytest.warns(UserWarning, match="degenerate"):
        ragged = flash_attention(x, x, x, causal=True, block_q=16,
                                 block_k=16)  # 30 % 16 -> gcd 2 -> T
    clean = flash_attention(x, x, x, causal=True, block_q=15,
                            block_k=15)
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(clean),
                               rtol=1e-5, atol=1e-5)
    # a benign gcd adjustment (48 % 32 -> 16, a real tile) stays silent
    y = jnp.asarray(rng.randn(1, 48, 1, 8), jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        benign = flash_attention(y, y, y, causal=True, block_q=32,
                                 block_k=32)
    np.testing.assert_allclose(
        np.asarray(benign),
        np.asarray(flash_attention(y, y, y, causal=True, block_q=16,
                                   block_k=16)),
        rtol=1e-5, atol=1e-5)
    # prime T: gcd collapses all the way to 1 -> same fallback
    z = jnp.asarray(rng.randn(1, 29, 1, 8), jnp.float32)
    with pytest.warns(UserWarning, match="degenerate"):
        flash_attention(z, z, z, block_q=16, block_k=16)


def _wide_lm(**kw):
    """A model whose heads are 128 wide, so that the route's rule
    (transformer.causal_attention_blocks) has blocks for its attention
    from kernels.flash_attention.MIN_SEQ positions on."""
    from mxnet_tpu.models import transformer as T
    cfg = T.TransformerConfig(
        vocab_size=50, d_model=256, n_heads=2, n_layers=1, d_ff=64,
        max_len=2 * _fa.MIN_SEQ, dp_axis=None, tp_axis=None, sp_axis=None,
        ep_axis=None, use_ring_attention=False, **kw)
    return cfg, T.init_params(cfg, seed=3)


def _tokens(t, batch=1):
    return jnp.asarray(np.random.RandomState(0).randint(0, 50, (batch, t)))


def test_transformer_flash_kernel_matches_dense_path(monkeypatch):
    """The training forward at the crossover length takes the kernels
    and reads what the XLA text reads (the rule switched off)."""
    from mxnet_tpu.models import transformer as T
    cfg, params = _wide_lm()
    toks = _tokens(_fa.MIN_SEQ)
    assert T.causal_attention_blocks(
        *[jax.ShapeDtypeStruct((1, _fa.MIN_SEQ, 2, 128), jnp.float32)] * 3)
    flash = T.forward(params, toks, cfg)
    monkeypatch.setattr(T, "causal_attention_blocks",
                        lambda *a, **kw: None)
    dense = T.forward(params, toks, cfg)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


def test_flash_crossover_dispatch(monkeypatch):
    """The route follows the shapes alone: under the crossover
    (kernels.flash_attention.MIN_SEQ) the dense softmax, from it on the
    kernels, a T the block does not divide the dense softmax again, and
    a toy width the dense softmax whatever use_flash_kernel says (the
    field stopped deciding this route)."""
    import mxnet_tpu.kernels as kernels
    from mxnet_tpu.models import transformer as T
    calls = []
    real = kernels.flash_attention

    def spy(*a, **kw):
        calls.append((a[0].shape, kw))
        return real(*a, **kw)

    monkeypatch.setattr(kernels, "flash_attention", spy)
    cfg, params = _wide_lm()
    shapes = lambda t: jax.eval_shape(
        lambda p, x: T.forward(p, x, cfg), params, _tokens(t))

    shapes(_fa.MIN_SEQ // 2)
    assert not calls
    shapes(_fa.MIN_SEQ)
    assert len(calls) == 1 and calls[0][0] == (1, _fa.MIN_SEQ, 2, 128)
    assert (calls[0][1]["block_q"], calls[0][1]["block_k"]) \
        == _fa.flash_blocks(_fa.MIN_SEQ, 128, 4)
    shapes(_fa.MIN_SEQ + 64)           # 128 does not divide it
    assert len(calls) == 1

    # toy heads keep the XLA text with the field set, and its numbers
    kw = dict(vocab_size=50, d_model=32, n_heads=2, n_layers=1,
              d_ff=64, max_len=32, dp_axis=None, tp_axis=None,
              sp_axis=None, ep_axis=None, use_ring_attention=False)
    cfg_dense = T.TransformerConfig(use_flash_kernel=False, **kw)
    cfg_flash = T.TransformerConfig(use_flash_kernel=True, **kw)
    toy = T.init_params(cfg_dense, seed=3)
    toks = _tokens(32, batch=2)
    np.testing.assert_array_equal(
        np.asarray(T.forward(toy, toks, cfg_flash)),
        np.asarray(T.forward(toy, toks, cfg_dense)))
    assert len(calls) == 1


def _causal_oracle(q, k, v):
    """float32 causal attention over [B, T, H, D] as plain jax."""
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("blocks", [1, 2, 4])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_flash_kernels_match_the_dense_oracle(dtype, tol, blocks):
    """The forward and all three gradients of the fused backward, heads
    of 128, at 1, 2 and 4 blocks a side (1 + 3 + 10 live pairs; with 2
    and 4 a pair under the diagonal skips the mask), against the float32
    oracle on the same rounded inputs: bfloat16 operands into every dot,
    the probabilities and dS cast to them, float32 accumulators. The
    widest gap as a share of the largest entry: a few float32 roundings,
    or a few of bfloat16's (2^-8 each)."""
    rng = np.random.RandomState(5)
    b, t, h, d = 1, 256, 2, 128
    q, k, v = [jnp.asarray(rng.randn(b, t, h, d) * 0.5, dtype)
               for _ in range(3)]
    w = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)

    def loss(attend, cast):
        def f(q, k, v):
            o = attend(cast(q), cast(k), cast(v))
            return jnp.sum(o.astype(jnp.float32) * w), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    block = t // blocks
    (_, o), grads = loss(
        lambda *a: flash_attention(*a, causal=True, block_q=block,
                                   block_k=block), lambda x: x)(q, k, v)
    assert o.dtype == dtype and all(g.dtype == dtype for g in grads)
    (_, want), want_grads = loss(
        _causal_oracle, lambda x: x.astype(jnp.float32))(q, k, v)
    for got, ref in zip((o,) + grads, (want,) + want_grads):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        gap = np.abs(got - ref).max() / np.abs(ref).max()
        print("gap %.2e of the largest entry" % gap)
        assert gap < tol


def test_flash_kernels_take_unequal_blocks():
    """1,024 x 512-style blocks: a live pair whose upper rows see no key
    of the block, and a key block whose first query block is not the
    one above it."""
    rng = np.random.RandomState(6)
    q, k, v = [jnp.asarray(rng.randn(1, 128, 1, 16), jnp.float32)
               for _ in range(3)]

    def grads(bq, bk):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(flash_attention(
            *a, causal=True, block_q=bq, block_k=bk))), (0, 1, 2))(q, k, v)

    for bq, bk in ((64, 32), (32, 64), (128, 16)):
        for got, ref in zip(grads(bq, bk), grads(32, 32)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-4, atol=2e-5)


CELL = (4, 2048, 16, 128)        # cerebras-gpt-1.3b-train-8k's q, k, v


@pytest.mark.parametrize("q,k,v,kw,kernel", [
    (CELL, CELL, CELL, {}, True),
    ((4, 256, 16, 128),) * 3 + ({}, False),
    ((4, 2048, 32, 64),) * 3 + ({}, False),
    ((4, 2048 + 64, 16, 128),) * 3 + ({}, False),
    (CELL, CELL, CELL, {"window": 512}, False),
    ((4, 2048, 16, 192), (4, 2048, 16, 192), CELL, {}, False),
    (CELL, CELL, (jnp.float32,) + CELL, {}, False),
    (CELL, CELL, CELL, {"mesh": "a mesh"}, False),
], ids=["cell", "T256", "D64", "T-undivided", "window", "latent-widths",
        "mixed-dtypes", "mesh"])
def test_the_causal_route_follows_the_shapes(q, k, v, kw, kernel):
    """transformer.causal_attention_blocks, the route's rule, and the
    counters _causal_attention adds to by it as a program is traced."""
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.observability import core as obs

    def sds(shape):
        dtype = jnp.bfloat16
        if not isinstance(shape[0], int):
            dtype, shape = shape[0], shape[1:]
        return jax.ShapeDtypeStruct(shape, dtype)

    q, k, v = sds(q), sds(k), sds(v)
    blocks = T.causal_attention_blocks(q, k, v, **kw)
    assert (blocks is not None) == kernel
    if kernel:
        assert blocks == (_fa.BLOCK_Q, _fa.BLOCK_K) \
            and q.shape[1] % blocks[0] == q.shape[1] % blocks[1] == 0
    names = ("attn.causal_kernel", "attn.causal_reference")
    before = [obs.counter(name).value for name in names]
    out = jax.eval_shape(
        lambda *a: T._causal_attention(*a, jnp.bfloat16, **kw),
        q, k, v)
    assert out.shape == q.shape[:3] + v.shape[3:]
    after = [obs.counter(name).value for name in names]
    assert [b - a for a, b in zip(before, after)] \
        == [int(kernel), int(not kernel)]


def test_prefill_takes_the_kernels_for_a_long_prompt():
    """generate()'s whole-prompt prefill of a 128-wide model at a prompt
    of MIN_SEQ tokens runs the kernel's forward (counted), and its last
    logits are the training forward's."""
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.observability import core as obs
    cfg, params = _wide_lm()
    toks = _tokens(_fa.MIN_SEQ)
    before = obs.counter("attn.causal_kernel").value
    logits, _ = T.prefill(params, T.init_cache(cfg, 1), toks, cfg)
    assert obs.counter("attn.causal_kernel").value == before + 1
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(T.forward(params, toks, cfg))[:, -1],
        rtol=2e-3, atol=2e-3)


def test_pallas_module_consumer():
    """rtc.PallasModule launching a real (scaled-add) Pallas kernel."""
    from mxnet_tpu import nd, rtc

    def saxpy_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = 2.0 * x_ref[...] + y_ref[...]

    mod = rtc.PallasModule(saxpy=(
        saxpy_kernel,
        lambda x, y: jax.ShapeDtypeStruct(x.shape, x.dtype)))
    kernel = mod.get_kernel("saxpy")
    x = nd.array(np.arange(8.0, dtype=np.float32))
    y = nd.array(np.ones(8, dtype=np.float32))
    out = kernel.launch([x, y])
    np.testing.assert_allclose(np.asarray(out),
                               2.0 * np.arange(8.0) + 1.0)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_backward_matches_dense(causal):
    """The custom flash backward (recompute + saved logsumexp) must
    reproduce autodiff-through-dense-attention gradients."""
    rng = np.random.RandomState(3)
    B, T, H, D = 2, 256, 2, 32
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        if causal:
            mask = jnp.tril(jnp.ones((T, T), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", a, v)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=64,
                              block_k=64)
        return jnp.sum(out * jnp.cos(out))

    def loss_dense(q, k, v):
        out = dense(q, k, v)
        return jnp.sum(out * jnp.cos(out))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_long_sequence_streams():
    """8k sequence with 128-blocks: K/V stream per block (whole-sequence
    VMEM residency would be impossible on real hardware at this size
    times batch*heads; here we check numerics at length)."""
    rng = np.random.RandomState(4)
    B, T, H, D = 1, 8192, 1, 16
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    out = flash_attention(q, k, v, causal=True, block_q=256, block_k=256)
    # spot-check rows against the dense computation (full dense at 8k is
    # 64M scores — compute only selected query rows)
    rows = [0, 1, 511, 4096, 8191]
    qs = np.asarray(q)[0, rows, 0]        # [R, D]
    s = qs @ np.asarray(k)[0, :, 0].T / np.sqrt(D)
    for ri, r in enumerate(rows):
        srow = s[ri, :r + 1]
        p = np.exp(srow - srow.max())
        p /= p.sum()
        expect = p @ np.asarray(v)[0, :r + 1, 0]
        np.testing.assert_allclose(np.asarray(out)[0, r, 0], expect,
                                   rtol=2e-4, atol=2e-5)


def test_ring_attention_flash_kernel_matches_jnp_path():
    """ring_attention(use_flash_kernel=True) — the Pallas carry kernel
    under shard_map over the 8-device sp ring — must match the jnp
    blockwise path and dense attention."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import ring as R

    devs = np.array(jax.devices()[:8]).reshape(8)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.RandomState(7)
    B, T, H, D = 2, 256, 2, 16      # 32 per shard
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    out_jnp = R.ring_attention_sharded(q, k, v, mesh, causal=True)
    out_flash = R.ring_attention_sharded(q, k, v, mesh, causal=True,
                                         use_flash_kernel=True)
    np.testing.assert_allclose(np.asarray(out_flash),
                               np.asarray(out_jnp), rtol=2e-4,
                               atol=2e-5)


def test_transformer_ring_plus_flash_kernel():
    """cfg.use_flash_kernel under ring attention: model forward matches
    the jnp ring path on the 8-device mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.models import transformer as T

    mesh = make_mesh({"dp": 1, "tp": 1, "sp": 8, "ep": 1})
    kw = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
              d_ff=64, max_len=64)
    cfg_jnp = T.TransformerConfig(use_ring_attention=True, **kw)
    cfg_flash = T.TransformerConfig(use_ring_attention=True,
                                    use_flash_kernel=True, **kw)
    params = T.shard_params(T.init_params(cfg_jnp, seed=0), cfg_jnp, mesh)
    tokens = jax.device_put(
        jnp.asarray(np.random.RandomState(0).randint(0, 64, (4, 64)),
                    jnp.int32), NamedSharding(mesh, P(None, None)))
    l0 = float(T.loss_fn(params, tokens, cfg_jnp, mesh))
    l1 = float(T.loss_fn(params, tokens, cfg_flash, mesh))
    assert abs(l0 - l1) < 2e-4, (l0, l1)


def test_flash_decode_matches_dense_per_batch_lengths():
    """T_q=1 cache attention: per-row dynamic lengths mask the streamed
    K/V blocks exactly like a dense masked softmax."""
    from mxnet_tpu.kernels import flash_decode
    rng = np.random.RandomState(1)
    b, t_max, h, d = 3, 64, 2, 16
    q = rng.randn(b, h, d).astype(np.float32)
    kc = rng.randn(b, t_max, h, d).astype(np.float32)
    vc = rng.randn(b, t_max, h, d).astype(np.float32)
    lengths = np.array([5, 64, 17], np.int32)
    out = flash_decode(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                       jnp.asarray(lengths), block_k=16)
    for i in range(b):
        L = lengths[i]
        ref = _dense_attention(q[i:i + 1, None], kc[i:i + 1, :L],
                               vc[i:i + 1, :L], causal=False)[0, 0]
        np.testing.assert_allclose(np.asarray(out[i]), ref,
                                   rtol=2e-4, atol=2e-4)


def test_flash_decode_scalar_length_broadcasts():
    from mxnet_tpu.kernels import flash_decode
    rng = np.random.RandomState(2)
    b, t_max, h, d = 2, 32, 2, 8
    q = rng.randn(b, h, d).astype(np.float32)
    kc = rng.randn(b, t_max, h, d).astype(np.float32)
    vc = rng.randn(b, t_max, h, d).astype(np.float32)
    out = flash_decode(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                       9, block_k=8)
    ref = _dense_attention(q[:, None], kc[:, :9], vc[:, :9],
                           causal=False)[:, 0]
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("use_flash", [False, True])
def test_transformer_decode_matches_forward(use_flash):
    """Token-by-token decode_step reproduces the full-sequence forward
    logits at every position (KV cache correctness end to end)."""
    from mxnet_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=31, d_model=32, n_heads=2,
                               n_layers=2, d_ff=48, max_len=16,
                               use_flash_kernel=use_flash)
    params = tf.init_params(cfg, seed=3)
    rng = np.random.RandomState(4)
    toks = jnp.asarray(rng.randint(0, 31, (2, 12)), jnp.int32)
    full = tf.forward(params, toks, cfg)          # [B, T, V]

    cache = tf.init_cache(cfg, 2)
    step = tf.make_decode_step(cfg)
    for pos in range(12):
        logits, cache = step(params, cache, toks[:, pos], pos)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, pos]),
            rtol=2e-4, atol=2e-4)


def test_transformer_generate_greedy_consistent():
    """generate() continues a prompt; regenerating with a longer prompt
    that includes the first continuation reproduces it (greedy
    determinism through the scanned cache)."""
    from mxnet_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=17, d_model=24, n_heads=2,
                               n_layers=1, d_ff=32, max_len=16)
    params = tf.init_params(cfg, seed=5)
    rng = np.random.RandomState(6)
    prompt = jnp.asarray(rng.randint(0, 17, (2, 4)), jnp.int32)
    out = tf.generate(params, prompt, 6, cfg)
    assert out.shape == (2, 10)
    assert np.array_equal(np.asarray(out[:, :4]), np.asarray(prompt))
    out2 = tf.generate(params, out[:, :7], 3, cfg)
    assert np.array_equal(np.asarray(out2), np.asarray(out))


def test_generate_sampling_controls():
    """temperature/top_k/top_p sampling: top_k=1 equals greedy; a
    near-zero temperature concentrates on the argmax; top_p masking
    keeps valid distributions (no NaN, tokens in range)."""
    from mxnet_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=13, d_model=24, n_heads=2,
                               n_layers=1, d_ff=32, max_len=16)
    params = tf.init_params(cfg, seed=9)
    rng = np.random.RandomState(10)
    prompt = jnp.asarray(rng.randint(0, 13, (2, 4)), jnp.int32)

    greedy = np.asarray(tf.generate(params, prompt, 6, cfg))
    top1 = np.asarray(tf.generate(params, prompt, 6, cfg, greedy=False,
                                  top_k=1, seed=3))
    assert np.array_equal(top1, greedy)

    cold = np.asarray(tf.generate(params, prompt, 6, cfg, greedy=False,
                                  temperature=1e-4, seed=4))
    assert np.array_equal(cold, greedy)

    nucleus = np.asarray(tf.generate(params, prompt, 6, cfg,
                                     greedy=False, top_p=0.7, seed=5))
    assert nucleus.shape == (2, 10)
    assert ((nucleus >= 0) & (nucleus < 13)).all()
    # sampling with a generous nucleus at T=1 differs from greedy with
    # overwhelming probability on an untrained model
    warm = np.asarray(tf.generate(params, prompt, 6, cfg, greedy=False,
                                  temperature=1.5, top_p=0.95, seed=6))
    assert not np.array_equal(warm, greedy)


@pytest.mark.parametrize("use_flash", [False, True])
def test_prefill_matches_token_by_token(use_flash):
    """Batched prompt prefill fills the same cache and produces the
    same last-token logits as stepping decode_step through the prompt."""
    from mxnet_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=19, d_model=32, n_heads=2,
                               n_layers=2, d_ff=48, max_len=16,
                               use_flash_kernel=use_flash)
    params = tf.init_params(cfg, seed=11)
    rng = np.random.RandomState(12)
    toks = jnp.asarray(rng.randint(0, 19, (2, 7)), jnp.int32)

    step_cache = tf.init_cache(cfg, 2)
    for pos in range(7):
        step_logits, step_cache = tf.decode_step(
            params, step_cache, toks[:, pos], pos, cfg)

    pre_logits, pre_cache = tf.prefill(params, tf.init_cache(cfg, 2),
                                       toks, cfg)
    np.testing.assert_allclose(np.asarray(pre_logits),
                               np.asarray(step_logits),
                               rtol=2e-4, atol=2e-4)
    for lc_step, lc_pre in zip(step_cache, pre_cache):
        np.testing.assert_allclose(
            np.asarray(lc_pre["k"][:, :7]),
            np.asarray(lc_step["k"][:, :7]), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(lc_pre["v"][:, :7]),
            np.asarray(lc_step["v"][:, :7]), rtol=2e-4, atol=2e-4)


def test_int8_weight_only_decode_close_to_fp():
    """quantize_weights_int8: decode with int8 weights tracks the fp
    path (weight-only quantization error), and generate accepts the
    quantized tree end-to-end."""
    from mxnet_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=23, d_model=32, n_heads=2,
                               n_layers=2, d_ff=48, max_len=16)
    params = tf.init_params(cfg, seed=13)
    q_params = tf.quantize_weights_int8(params)
    # at least the dense weights became int8 pairs
    import jax
    n_q8 = sum(1 for l in jax.tree.leaves(
        q_params, is_leaf=tf._is_q8) if tf._is_q8(l))
    assert n_q8 >= 2 + 6 * cfg.n_layers   # embed+pos + per-layer dense

    rng = np.random.RandomState(14)
    toks = jnp.asarray(rng.randint(0, 23, (2, 6)), jnp.int32)
    cache_f = tf.init_cache(cfg, 2)
    cache_q = tf.init_cache(cfg, 2)
    for pos in range(6):
        lf, cache_f = tf.decode_step(params, cache_f, toks[:, pos],
                                     pos, cfg)
        lq, cache_q = tf.decode_step(q_params, cache_q, toks[:, pos],
                                     pos, cfg)
    # weight-only int8: logits agree to quantization tolerance
    denom = np.abs(np.asarray(lf)).max()
    assert np.abs(np.asarray(lq) - np.asarray(lf)).max() / denom < 0.05

    out = tf.generate(q_params, toks[:, :3], 4, cfg)
    assert out.shape == (2, 7)


def test_beam_search_beam1_equals_greedy_and_scores_sorted():
    """beam=1 reduces to greedy generate(); wider beams return
    descending scores whose best is >= the greedy path's logprob."""
    from mxnet_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=17, d_model=24, n_heads=2,
                               n_layers=1, d_ff=32, max_len=14)
    params = tf.init_params(cfg, seed=15)
    rng = np.random.RandomState(16)
    prompt = jnp.asarray(rng.randint(0, 17, (2, 4)), jnp.int32)

    greedy = np.asarray(tf.generate(params, prompt, 6, cfg))
    seqs1, scores1 = tf.beam_search(params, prompt, 6, cfg, beam=1)
    assert np.array_equal(np.asarray(seqs1)[:, 0], greedy)

    seqs4, scores4 = tf.beam_search(params, prompt, 6, cfg, beam=4)
    s4 = np.asarray(scores4)
    assert (np.diff(s4, axis=1) <= 1e-6).all()      # sorted best-first
    assert seqs4.shape == (2, 4, 10)
    # the prompt is preserved on every beam
    assert np.array_equal(
        np.asarray(seqs4)[:, :, :4],
        np.repeat(np.asarray(prompt)[:, None], 4, axis=1))

    # real invariant: each returned score IS the sequence's total
    # logprob under the model (recomputed with the full forward)
    for bi in range(2):
        for ki in range(4):
            seq = np.asarray(seqs4)[bi, ki]
            logits = np.asarray(tf.forward(
                params, jnp.asarray(seq[None]), cfg))[0]
            logp = logits - np.log(
                np.exp(logits - logits.max(-1, keepdims=True)).sum(
                    -1, keepdims=True)) - logits.max(-1, keepdims=True)
            tot = sum(logp[t, seq[t + 1]] for t in range(3, 9))
            np.testing.assert_allclose(s4[bi, ki], tot, rtol=1e-4,
                                       atol=1e-4)

    import pytest as _pytest
    with _pytest.raises(ValueError):
        tf.beam_search(params, prompt, 6, cfg, beam=18)  # > vocab


def test_flash_decode_lse_chunks_combine():
    """flash_decode_with_lse: splitting the cache in two and combining
    the partials with their lse weights reproduces the full-cache
    result (the flash-decoding decomposition, kernel path)."""
    from mxnet_tpu.kernels.flash_attention import (flash_decode,
                                                   flash_decode_with_lse)
    rng = np.random.RandomState(22)
    b, t, h, d = 2, 64, 2, 16
    q = jnp.asarray(rng.randn(b, h, d).astype(np.float32))
    kc = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    vc = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    L = 50                                   # ends inside chunk 2

    full = flash_decode(q, kc, vc, L, block_k=16)

    o1, lse1 = flash_decode_with_lse(q, kc[:, :32], vc[:, :32],
                                     min(L, 32), block_k=16)
    o2, lse2 = flash_decode_with_lse(q, kc[:, 32:], vc[:, 32:],
                                     max(L - 32, 0), block_k=16)
    m = np.maximum(np.asarray(lse1), np.asarray(lse2))
    w1 = np.exp(np.asarray(lse1) - m)
    w2 = np.exp(np.asarray(lse2) - m)
    o = (w1[..., None] * np.asarray(o1, np.float64)
         + w2[..., None] * np.asarray(o2, np.float64)) / \
        (w1 + w2)[..., None]
    np.testing.assert_allclose(o, np.asarray(full), rtol=2e-4,
                               atol=2e-4)


def test_flash_decode_gqa_matches_repeated_kv():
    """GQA decode: a cache with KVH < H heads gives the same result as
    MHA decode over the cache with each KV head repeated G times."""
    from mxnet_tpu.kernels.flash_attention import flash_decode
    rng = np.random.RandomState(24)
    b, t, h, kvh, d = 2, 32, 8, 2, 16
    g = h // kvh
    q = jnp.asarray(rng.randn(b, h, d).astype(np.float32))
    kc = jnp.asarray(rng.randn(b, t, kvh, d).astype(np.float32))
    vc = jnp.asarray(rng.randn(b, t, kvh, d).astype(np.float32))
    lengths = jnp.asarray([20, 32], jnp.int32)

    gqa = flash_decode(q, kc, vc, lengths, block_k=8)
    mha = flash_decode(q, jnp.repeat(kc, g, axis=2),
                       jnp.repeat(vc, g, axis=2), lengths, block_k=8)
    np.testing.assert_allclose(np.asarray(gqa), np.asarray(mha),
                               rtol=2e-4, atol=2e-4)

    bad_kc = jnp.asarray(rng.randn(b, t, 3, d).astype(np.float32))
    with pytest.raises(ValueError):
        flash_decode(q, bad_kc, bad_kc, lengths)


@pytest.mark.parametrize("use_flash", [False, True])
def test_transformer_gqa_decode_matches_forward(use_flash):
    """GQA config (n_kv_heads < n_heads): the KV cache carries only the
    KV heads, and token-by-token decode reproduces full-sequence
    forward logits on both attention paths."""
    from mxnet_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=29, d_model=32, n_heads=4,
                               n_kv_heads=2, n_layers=2, d_ff=48,
                               max_len=16, use_flash_kernel=use_flash)
    params = tf.init_params(cfg, seed=17)
    # cache really is smaller: KVH=2 of 4 heads
    cache = tf.init_cache(cfg, 2)
    assert cache[0]["k"].shape == (2, 16, 2, 8)

    rng = np.random.RandomState(18)
    toks = jnp.asarray(rng.randint(0, 29, (2, 10)), jnp.int32)
    full = tf.forward(params, toks, cfg)
    step = tf.make_decode_step(cfg)
    for pos in range(10):
        logits, cache = step(params, cache, toks[:, pos], pos)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, pos]),
            rtol=2e-4, atol=2e-4)

    out = tf.generate(params, toks[:, :3], 4, cfg)
    assert out.shape == (2, 7)


def test_gqa_config_validation():
    from mxnet_tpu.models import transformer as tf
    bad = tf.TransformerConfig(vocab_size=11, d_model=24, n_heads=4,
                               n_kv_heads=3, n_layers=1, d_ff=32,
                               max_len=8)
    with pytest.raises(ValueError):
        tf.init_params(bad, seed=0)

    from mxnet_tpu.parallel import make_mesh
    cfg = tf.TransformerConfig(vocab_size=11, d_model=32, n_heads=4,
                               n_kv_heads=2, n_layers=1, d_ff=32,
                               max_len=8)
    params = tf.init_params(cfg, seed=0)
    mesh = make_mesh({"dp": 2, "tp": 4})
    with pytest.raises(ValueError):
        tf.shard_params(params, cfg, mesh)   # tp=4 > 2 KV heads


@pytest.mark.parametrize("use_flash", [False, True])
def test_rope_decode_matches_forward(use_flash):
    """RoPE config: rotated keys live in the cache, and token-by-token
    decode reproduces the full-sequence forward logits."""
    from mxnet_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=27, d_model=32, n_heads=4,
                               n_layers=2, d_ff=48, max_len=16,
                               rope=True, use_flash_kernel=use_flash)
    params = tf.init_params(cfg, seed=19)
    rng = np.random.RandomState(20)
    toks = jnp.asarray(rng.randint(0, 27, (2, 9)), jnp.int32)
    full = tf.forward(params, toks, cfg)
    cache = tf.init_cache(cfg, 2)
    step = tf.make_decode_step(cfg)
    for pos in range(9):
        logits, cache = step(params, cache, toks[:, pos], pos)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, pos]),
            rtol=2e-4, atol=2e-4)
    # rope models carry no learned position table at all
    assert "pos" not in params
    # and the rotation really enters the computation: shifting the
    # prompt one position changes the logits of identical tokens
    toks2 = jnp.concatenate([toks[:, :1], toks], axis=1)[:, :9]
    shifted = tf.forward(params, toks2, cfg)
    assert np.abs(np.asarray(shifted[:, 2]) -
                  np.asarray(full[:, 1])).max() > 1e-4


@pytest.mark.parametrize("rope", [False, True])
def test_speculative_generate_exact_vs_greedy(rope):
    """Speculative decoding returns EXACTLY the big model's greedy
    continuation — with a trained-ish draft, an untrained draft, and
    the degenerate draft == target (all drafts accepted)."""
    from mxnet_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=21, d_model=32, n_heads=4,
                               n_layers=2, d_ff=48, max_len=24,
                               rope=rope)
    dcfg = tf.TransformerConfig(vocab_size=21, d_model=16, n_heads=2,
                                n_layers=1, d_ff=24, max_len=24,
                                rope=rope)
    params = tf.init_params(cfg, seed=31)
    draft = tf.init_params(dcfg, seed=32)
    prompt = jnp.asarray(
        np.random.RandomState(33).randint(0, 21, (1, 5)), jnp.int32)

    ref = np.asarray(tf.generate(params, prompt, 9, cfg))
    spec = np.asarray(tf.speculative_generate(
        params, draft, prompt, 9, cfg, dcfg, k_draft=3))
    assert np.array_equal(spec, ref)

    # draft == target: every draft accepted in EVERY round (this is
    # the regression check for the draft-cache hole after a fully
    # accepted round — a zeroed K/V slot collapses later acceptances),
    # and far fewer big-model launches than tokens
    spec2, stats = tf.speculative_generate(
        params, params, prompt, 9, cfg, cfg, k_draft=4,
        return_stats=True)
    assert np.array_equal(np.asarray(spec2), ref)
    full_rounds = [a for a in stats["acceptances"][:-1]]
    assert all(a == 4 for a in full_rounds), stats
    assert stats["big_model_launches"] < 9


def test_prefill_chunk_matches_decode_steps():
    """Chunked prefill at an offset writes the same cache and logits as
    stepping decode_step token by token."""
    from mxnet_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=19, d_model=32, n_heads=4,
                               n_kv_heads=2, n_layers=2, d_ff=48,
                               max_len=16)
    params = tf.init_params(cfg, seed=34)
    toks = jnp.asarray(np.random.RandomState(35).randint(0, 19, (2, 9)),
                       jnp.int32)

    cache_a = tf.init_cache(cfg, 2)
    logits_a = []
    for pos in range(9):
        la, cache_a = tf.decode_step(params, cache_a, toks[:, pos],
                                     pos, cfg)
        logits_a.append(np.asarray(la))

    # prefill first 4 as a chunk at 0, the rest as a chunk at 4
    cache_b = tf.init_cache(cfg, 2)
    lb1, cache_b = tf.prefill_chunk(params, cache_b, toks[:, :4], 0,
                                    cfg)
    lb2, cache_b = tf.prefill_chunk(params, cache_b, toks[:, 4:], 4,
                                    cfg)
    chunked = np.concatenate([np.asarray(lb1), np.asarray(lb2)], axis=1)
    np.testing.assert_allclose(chunked, np.stack(logits_a, axis=1),
                               rtol=2e-4, atol=2e-4)
    for la, lb in zip(cache_a, cache_b):
        for key in ("k", "v"):
            np.testing.assert_allclose(np.asarray(lb[key][:, :9]),
                                       np.asarray(la[key][:, :9]),
                                       rtol=2e-4, atol=2e-4)


def test_prefill_chunk_consistent_with_prefill():
    """prefill and prefill_chunk(start=0) write compatible caches and
    agree on the last-row logits — the contract speculative decoding's
    cache handoff relies on (the two keep separate attention layouts
    on purpose: prefill attends within the chunk, prefill_chunk over
    the cache)."""
    from mxnet_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=23, d_model=32, n_heads=4,
                               n_layers=2, d_ff=48, max_len=12,
                               rope=True)
    params = tf.init_params(cfg, seed=36)
    toks = jnp.asarray(np.random.RandomState(37).randint(0, 23, (2, 7)),
                       jnp.int32)
    la, ca = tf.prefill(params, tf.init_cache(cfg, 2), toks, cfg)
    lb, cb = tf.prefill_chunk(params, tf.init_cache(cfg, 2), toks, 0,
                              cfg)
    np.testing.assert_allclose(np.asarray(lb[:, -1]), np.asarray(la),
                               rtol=2e-4, atol=2e-4)
    for xa, xb in zip(ca, cb):
        for key in ("k", "v"):
            np.testing.assert_allclose(np.asarray(xb[key][:, :7]),
                                       np.asarray(xa[key][:, :7]),
                                       rtol=2e-4, atol=2e-4)


def test_speculative_generate_budget_does_not_retrace():
    """n_new is data in the one-dispatch speculative program: varying
    the budget at a fixed prompt length reuses the compiled program
    (tracing counted via a side-effecting probe), and every budget
    still matches greedy generate() exactly."""
    from mxnet_tpu.models import transformer as tf
    cfg = tf.TransformerConfig(vocab_size=17, d_model=24, n_heads=4,
                               n_layers=1, d_ff=32, max_len=32)
    dcfg = tf.TransformerConfig(vocab_size=17, d_model=16, n_heads=2,
                                n_layers=1, d_ff=16, max_len=32)
    params = tf.init_params(cfg, seed=41)
    draft = tf.init_params(dcfg, seed=42)
    prompt = jnp.asarray(
        np.random.RandomState(43).randint(0, 17, (1, 4)), jnp.int32)
    traces = []
    orig = tf._spec_core

    def probed(*a, **kw):
        traces.append(1)
        return orig(*a, **kw)

    tf._spec_core = probed
    try:
        for n_new in (5, 9, 12):
            spec = np.asarray(tf.speculative_generate(
                params, draft, prompt, n_new, cfg, dcfg, k_draft=3))
            ref = np.asarray(tf.generate(params, prompt, n_new, cfg))
            assert np.array_equal(spec, ref), n_new
    finally:
        tf._spec_core = orig
    assert sum(traces) == 1, "expected one trace, got %d" % sum(traces)


def test_flash_stat_lanes_env_value_equivalence():
    """MXNET_FLASH_STAT_LANES=1 (the low-traffic stat layout queued
    for the on-chip A/B) computes the same flash forward and backward
    as the default 128-lane layout — checked on CPU so a value-level
    layout bug never burns chip time."""
    import subprocess, sys, os
    script = (
        "import numpy as np, jax, jax.numpy as jnp\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from mxnet_tpu.kernels.flash_attention import flash_attention\n"
        "rng = np.random.RandomState(0)\n"
        "q, k, v = (jnp.asarray(rng.randn(2, 64, 2, 32), jnp.float32)\n"
        "           for _ in range(3))\n"
        "g = jax.grad(lambda q, k, v: jnp.sum(\n"
        "    flash_attention(q, k, v, causal=True, block_q=32,\n"
        "                    block_k=32) ** 2), argnums=(0, 1, 2))\n"
        "outs = [flash_attention(q, k, v, causal=True, block_q=32,\n"
        "                        block_k=32)] + list(g(q, k, v))\n"
        "print('SUM', [float(jnp.sum(o)) for o in outs])\n")
    sums = {}
    for lanes in ("128", "1"):
        env = dict(os.environ, MXNET_FLASH_STAT_LANES=lanes,
                   JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-1500:]
        line = [l for l in r.stdout.splitlines()
                if l.startswith("SUM")][0]
        sums[lanes] = eval(line[4:])
    np.testing.assert_allclose(sums["1"], sums["128"], rtol=1e-6)


def test_dense_decode_with_lse_matches_flash_contract():
    """dense_decode_with_lse (the sp-decode default since the chip A/B
    retired the Pallas kernel there) honors the exact
    flash_decode_with_lse contract: same (o, lse) for MHA and GQA,
    per-row lengths, and the zero-valid-keys sentinel that drops a
    shard out of the cross-shard combine."""
    from mxnet_tpu.kernels.flash_attention import (
        dense_decode_with_lse, flash_decode_with_lse)

    rng = np.random.RandomState(7)
    b, h, d, t = 3, 8, 16, 64
    q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
    for kvh in (h, 2):                       # MHA and GQA
        kc = jnp.asarray(rng.randn(b, t, kvh, d), jnp.float32)
        vc = jnp.asarray(rng.randn(b, t, kvh, d), jnp.float32)
        lengths = jnp.asarray([t, 17, 0], jnp.int32)
        o_d, lse_d = dense_decode_with_lse(q, kc, vc, lengths)
        o_f, lse_f = flash_decode_with_lse(q, kc, vc, lengths,
                                           block_k=32, interpret=True)
        # rows with valid keys agree in value and in the combine
        # statistic
        np.testing.assert_allclose(np.asarray(o_d[:2]),
                                   np.asarray(o_f[:2]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse_d[:2]),
                                   np.asarray(lse_f[:2]),
                                   rtol=2e-5, atol=2e-5)
        # the empty row is the drop-out sentinel in both
        assert np.abs(np.asarray(o_d[2])).max() == 0.0
        assert (np.asarray(lse_d[2]) < -1e29).all()
        assert (np.asarray(lse_f[2]) < -1e29).all()


# ------------------------------------------- latent attention's decode ---

def _latent_operands(seed, b, h, r, e, t, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, h, r), dtype),
            jax.random.normal(ks[1], (b, h, e), dtype),
            jax.random.normal(ks[2], (b, t, r), dtype),
            jax.random.normal(ks[3], (b, t, e), dtype))


def _nan_past(rows, lengths):
    """rows [B, T, F] with every position at or past its lane's length
    set to NaN: what must never reach a sum."""
    dead = jnp.arange(rows.shape[1])[None, :, None] \
        >= jnp.asarray(lengths)[:, None, None]
    return jnp.where(dead, jnp.nan, rows)


@pytest.mark.parametrize("t", [64, 192, 384, 2048],
                         ids=["one-block", "one-odd-block", "three-of-128",
                              "two-of-1024"])
@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 2e-5)],
                         ids=["bf16", "float32"])
@pytest.mark.parametrize("h", [32, 64])
def test_latent_decode_matches_the_two_xla_passes(h, dtype, tol, t):
    """The kernel (interpreted here) against the XLA text it replaced, at
    ragged lengths: one row, a block's edge, the edge plus one, the whole
    cache, a parked lane (position 0 with another request's rows behind
    it) and a length past the cache, which is clamped. Every row at or
    past a lane's length is NaN on the kernel's side: a dead block that
    was read into a sum, or a dead row of the last live block that
    reached the second dot, would show."""
    from mxnet_tpu.kernels.latent_decode import (
        latent_block, latent_decode, latent_decode_reference)
    block = latent_block(t)
    assert t // block == {64: 1, 192: 1, 384: 3, 2048: 2}[t]
    lengths = [1, block, min(block + 1, t), t, 1, t + 5]
    q_lat, q_r, c, kr = _latent_operands(h + t, len(lengths), h, 128, 64,
                                         t, dtype)
    norm = float(np.sqrt(192.0))
    want = latent_decode_reference(
        q_lat, q_r, c, kr, jnp.minimum(jnp.asarray(lengths), t), norm)
    got = latent_decode(q_lat, q_r, _nan_past(c, lengths),
                        _nan_past(kr, lengths), jnp.asarray(lengths), norm)
    assert got.shape == (len(lengths), h, 128) and got.dtype == jnp.float32
    assert not bool(jnp.isnan(got).any())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("pos", [7, [0, 127, 128, 383]],
                         ids=["scalar", "per-lane"])
@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "yarn"])
def test_the_latent_decode_contraction_attends_up_to_pos(pos, scaled):
    """transformer._latent_decode_attention (absorb, kernel, up-project)
    with a scalar position and one a lane, with and without a scaling
    record's softmax scale, against the absorbed contraction as plain
    einsums."""
    from mxnet_tpu.kernels.latent_decode import latent_decode_reference
    from mxnet_tpu.models import transformer as tf
    b, h, t = 4, 4, 384
    cfg = tf.TransformerConfig(
        n_heads=h, max_len=t, rope=scaled,
        positions="rope" if scaled else "none",
        layer_kinds=("mla",) * 2, mla_rank=32, mla_nope_dim=16,
        mla_rope_dim=8, mla_v_dim=12,
        rope_scaling=tf.YarnScaling(8.0, 32, 4.0, 1.0, 1.0, 0.707)
        if scaled else None)
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (b, h, 24))
    rows = {"c": jax.random.normal(ks[1], (b, t, 32)),
            "kr": jax.random.normal(ks[2], (b, t, 8))}
    p = {"wkvb": jax.random.normal(ks[3], (32, h, 28)) / 6}
    pos = jnp.asarray(pos, jnp.int32)
    got = tf._latent_decode_attention(q, rows, pos, p, cfg)
    o = latent_decode_reference(
        jnp.einsum("bhn,rhn->bhr", q[..., :16], p["wkvb"][..., :16]),
        q[..., 16:], rows["c"], rows["kr"],
        jnp.broadcast_to(pos + 1, (b,)), tf._latent_score_norm(cfg, 24))
    want = jnp.einsum("bhr,rhv->bhv", o, p["wkvb"][..., 16:])
    assert got.shape == (b, h, 12)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,block", [(19456, 1024), (11264, 1024),
                                     (4096, 1024), (1536, 512), (384, 128),
                                     (20096, 128), (192, 192), (64, 64),
                                     (100, 100), (1000, 1000), (1032, None),
                                     (20000, None)])
def test_a_latent_block_is_the_largest_power_of_two_dividing_the_cache(
        t, block):
    """Down to 128 (the rows lie on `kr`'s lanes); a cache nothing
    divides is one block while it is no more than the largest, and past
    that has none: the kernel refuses it by name."""
    from mxnet_tpu.kernels.latent_decode import latent_block, latent_decode
    assert latent_block(t) == block
    if block is None:
        q_lat, q_r, c, kr = _latent_operands(0, 1, 4, 32, 8, t, jnp.float32)
        with pytest.raises(ValueError, match="cannot tile a cache of %d" % t):
            latent_decode(q_lat, q_r, c, kr, 5, 1.0)


@pytest.mark.parametrize("t,kernel", [(384, True), (1000, True),
                                      (1032, False)])
def test_the_latent_contraction_is_the_kernel_wherever_it_tiles(t, kernel):
    """_latent_decode_attention chooses by the rows' shape: the kernel,
    or for a cache of more than one block that 128 does not divide the
    two XLA passes, with the same answer either way."""
    from mxnet_tpu.kernels.latent_decode import latent_decode_reference
    from mxnet_tpu.models import transformer as tf
    b, h = 3, 4
    cfg = tf.TransformerConfig(
        n_heads=h, max_len=t, rope=False, positions="none",
        layer_kinds=("mla",) * 2, mla_rank=32, mla_nope_dim=16,
        mla_rope_dim=8, mla_v_dim=12)
    ks = jax.random.split(jax.random.PRNGKey(t), 4)
    q = jax.random.normal(ks[0], (b, h, 24))
    rows = {"c": jax.random.normal(ks[1], (b, t, 32)),
            "kr": jax.random.normal(ks[2], (b, t, 8))}
    p = {"wkvb": jax.random.normal(ks[3], (32, h, 28)) / 6}
    pos = jnp.asarray([0, 200, t - 1], jnp.int32)
    fn = lambda q, rows, pos: tf._latent_decode_attention(q, rows, pos, p,
                                                          cfg)
    assert ("pallas_call" in str(jax.make_jaxpr(fn)(q, rows, pos))) == kernel
    o = latent_decode_reference(
        jnp.einsum("bhn,rhn->bhr", q[..., :16], p["wkvb"][..., :16]),
        q[..., 16:], rows["c"], rows["kr"], pos + 1,
        tf._latent_score_norm(cfg, 24))
    np.testing.assert_allclose(
        np.asarray(fn(q, rows, pos)),
        np.asarray(jnp.einsum("bhr,rhv->bhv", o, p["wkvb"][..., 16:])),
        rtol=2e-5, atol=2e-5)


def test_rows_fetched_are_whole_blocks_up_to_each_lanes_length():
    """What serving.py's mla.rows_read adds: beside the kernel, from the
    same block and the same clamp."""
    from mxnet_tpu.kernels.latent_decode import rows_fetched
    assert rows_fetched([1, 1024, 1025, 19456, 0, 20000], 19456) \
        == 1024 + 1024 + 2048 + 19456 + 1024 + 19456
    assert rows_fetched([[5, 128], [6, 129]], 384) == 128 + 128 + 128 + 256
    # one block, and a cache the kernel cannot tile: all of it a lane
    assert rows_fetched([5, 192], 192) == 2 * 192
    assert rows_fetched([1, 500, 0], 20000) == 3 * 20000


# -------------------------------------- latent attention's decode store ---

def _bits(x):
    """An array's bytes: equal where NaN is too."""
    return np.asarray(x).tobytes()


@pytest.mark.parametrize("t", [384, 96, 192],
                         ids=["three-of-128", "one-block-96",
                              "one-block-192"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
def test_latent_row_store_equals_the_scatter(dtype, t):
    """The writer (interpreted here) against the scatter it replaces,
    bit for bit: a block's first and last column and the next block's
    first, the cache's last row, a position at T and past it (dropped:
    the lane's rows stay), negative positions as the scatter counts
    them; float32 rows cast to the leaf's dtype; the leaf NaN wherever
    its lane index and position are both odd, which must come back."""
    from mxnet_tpu.kernels.latent_decode import latent_row_store
    pos = [0, min(127, t - 2), min(128, t - 1), t - 1, t, t + 300, -1,
           -t - 1]
    b, e = len(pos), 64
    ks = jax.random.split(jax.random.PRNGKey(t), 2)
    kr = jax.random.normal(ks[0], (b, t, e), dtype)
    odd = (jnp.arange(b)[:, None, None] % 2 == 1) \
        & (jnp.arange(t)[None, :, None] % 2 == 1)
    kr = jnp.where(odd, jnp.nan, kr)
    rows = jax.random.normal(ks[1], (b, e), jnp.float32)
    pos = jnp.asarray(pos, jnp.int32)
    want = kr.at[jnp.arange(b), pos].set(rows.astype(dtype))
    got = latent_row_store(kr, rows, pos)
    assert got.shape == kr.shape and got.dtype == kr.dtype
    assert _bits(got) == _bits(want)
    # the dropped lanes came back as they went in, NaN and all
    assert _bits(got[4:6]) == _bits(kr[4:6]) and _bits(got[7]) == _bits(kr[7])
    assert _bits(got[:4]) != _bits(kr[:4])


def test_latent_row_store_refuses_a_cache_it_cannot_tile():
    from mxnet_tpu.kernels.latent_decode import latent_row_store
    with pytest.raises(ValueError, match="cannot tile a cache of 1032"):
        latent_row_store(jnp.zeros((2, 1032, 8)), jnp.zeros((2, 8)),
                         jnp.zeros((2,), jnp.int32))


@pytest.mark.parametrize("t,kernel", [(384, True), (1000, True),
                                      (1032, False)])
def test_decode_stores_a_latent_rows_kr_in_place_wherever_it_tiles(
        t, kernel, monkeypatch):
    """decode_step with a position a lane over two latent layers: the
    `kr` rows go in through the writer wherever latent_decode runs
    (_dense_rows chooses by the leaf's shape), through the scatter at a
    cache of more than one block that 128 does not divide; either way
    the cache and the logits are those of the parent's text, the scatter
    for every leaf, bit for bit. One lane stands at the cache's end."""
    import importlib
    from mxnet_tpu.models import transformer as tf
    # the package re-exports the function under the module's own name
    ld = importlib.import_module("mxnet_tpu.kernels.latent_decode")
    cfg = tf.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=t, rope=False, positions="none",
        layer_kinds=("mla",) * 2, mla_rank=32, mla_nope_dim=16,
        mla_rope_dim=8, mla_v_dim=12)
    params = tf.init_params(cfg, 3)
    b = 3
    cache = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(t), x.shape, x.dtype),
        tf.init_cache(cfg, b))
    tokens = jnp.asarray([5, 6, 7], jnp.int32)
    pos = jnp.asarray([0, 200, t - 1], jnp.int32)
    step = lambda: tf.decode_step(params, cache, tokens, pos, cfg)
    # a fresh function a trace: make_jaxpr keeps what it traced
    text = lambda: str(jax.make_jaxpr(lambda: step())())
    assert ("mla_row_store" in text()) == kernel
    logits, new = step()
    monkeypatch.setattr(
        ld, "latent_row_store", lambda kr, rows, where: kr.at[
            jnp.arange(kr.shape[0]), where].set(rows.astype(kr.dtype)))
    assert "mla_row_store" not in text()
    want_logits, want = step()
    assert _bits(logits) == _bits(want_logits)
    for layer, want_layer, old in zip(new, want, cache):
        for name in ("c", "kr"):
            assert _bits(layer[name]) == _bits(want_layer[name]) \
                != _bits(old[name])
