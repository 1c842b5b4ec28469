#!/usr/bin/env python3
"""Quickest proof that the system starts and computes on the attached chip.

One process, one chip, the entry points a user calls, published widths:

  A(a) resnet50_gluon   gluon.model_zoo resnet50_v1, hybridize, autograd,
                        gluon.Trainer on mx.tpu(0); bf16 compute, fp32
                        master weights (net.cast + multi_precision)
  A(b) resnet50_fused   the one-program train step bench.py times
  B(a) lm_train         models.transformer.make_train_step at d2048 x 8L
  B(b) lm_serve         models.serving.ContinuousBatcher vs solo generate()
  K    kernels          every Pallas kernel COMPILED (never interpreted) at
                        a real shape against its dense reference

Each phase prints one JSON line as it ends; the last line of stdout is
{"ok": ..., "device": {"platform", "kind", "count"}} as JAX reports the
device. Any failed phase makes that "ok" false and the exit code non-zero.
Without a TPU the script refuses to start (non-zero, nothing printed to
stdout). Nothing here is a benchmark: a rate that appears is a by-product,
labelled with the device.

  python chip_smoke.py             one chip: A, B, K
  python chip_smoke.py --chips 4   ONLY the multi-chip path: ResNet-50 over
                                   dp=4 through Trainer(kvstore=
                                   "dist_tpu_sync") and the LM step over
                                   dp=2 x tp=2, each against one device of
                                   the same process
  python chip_smoke.py --rehearse  tiny sizes on whatever device JAX has
                                   (kernels interpreted off-TPU); the last
                                   line still reports the device truthfully

Kernels on paths A/B with default settings: the LM train step's causal
attention is kernels/flash_attention.py's forward and fused backward
wherever its shapes give them blocks (heads a multiple of 128 wide, T
from 1,024 on and a multiple of 128: transformer.py
causal_attention_blocks), and the LM's decode contraction over dense K/V
rows is kernels/kv_decode.py by the same kind of rule (kv_decode_block).
Not on them: the paged kernel (MXNET_PAGED_DECODE_PALLAS=1), flash_decode
(cfg.use_flash_kernel), and latent_decode, latent_row_store and
grouped_matmul, which sit on every "mla" and every expert layer's path
(neither model here has one). Phase K runs every kernel alone.
"""

import argparse
import json
import sys
import time
import traceback

REAL = {
    "resnet": dict(batch=128, size=224, classes=1000, steps=6),
    # benchmark/train_lm_bench.py with the train_lm_d2048 leg's values
    "lm": dict(d_model=2048, n_layers=8, n_heads=16, d_ff=8192,
               vocab=32000, seq=1024, batch=8, steps=3),
    "serve": dict(requests=8, prompt=512, n_new=64),
    "flash": dict(b=2, t=8192, h=16, d=128),
    "decode": dict(b=8, t=4096, h=16, d=128, kvh=2),
    "paged": dict(nblocks=2048, bs=16, kvh=4),
    # the Kimi-K2.6 cell's decode contraction: 32 lanes x 19,456 rows
    "latent": dict(b=32, h=64, t=19456, r=512, e=64),
}
TINY = {
    "resnet": dict(batch=8, size=32, classes=10, steps=3),
    "lm": dict(d_model=64, n_layers=2, n_heads=4, d_ff=128,
               vocab=256, seq=64, batch=4, steps=3),
    "serve": dict(requests=3, prompt=16, n_new=8),
    "flash": dict(b=1, t=256, h=2, d=128),
    "decode": dict(b=2, t=256, h=4, d=128, kvh=2),
    "paged": dict(nblocks=33, bs=16, kvh=2),
    "latent": dict(b=3, h=4, t=256, r=128, e=64),
}

# one fixed batch, no warm-up schedule: small enough that the first
# momentum steps of a freshly initialised ResNet-50 do not overshoot
RESNET_LR = 0.01

# sharded losses against one device, bf16, as (first step, every step):
# the psum and the one-device sum round differently; step 1 is one
# forward pass apart, later steps carry that through momentum
SHARDED_RTOL = (1e-2, 5e-2)

# steps in each window of the block_until_ready-vs-fetch check
BARRIER_STEPS = 5

# bf16 kernels against an fp32-accumulated dense reference: the bound
# tests/test_paged_kernel.py holds bf16 to, scaled by the reference's size
BF16_TOL = 3e-2
# a served stream may leave solo generate() only where the top-2 logits
# of the solo program sit within this many bf16 ulps of each other
NEAR_TIE_ULPS = 4


def _compile_ledger(since):
    """What the program's compile ledger holds since `since` (ns on the
    telemetry epoch): seconds compiling, loading cached executables
    (jax times compile-or-load together, so they are taken out of
    compile_s), tracing and lowering, and the cache's hits and misses."""
    from mxnet_tpu.observability import recompile
    led = recompile.summary(since=since)
    return {"compile_s": round(led["compile_s"], 2),
            "cache_load_s": round(led["cache_load_s"], 2),
            "trace_lower_s": round(led["trace_s"] + led["lower_s"], 2),
            "programs": led["programs"],
            "cache_hits": led["hits"], "cache_misses": led["misses"]}


def _device_bytes(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return (max(s.get("peak_bytes_in_use", 0) for s in stats),
            max(s.get("bytes_in_use", 0) for s in stats))


def _losses_ok(losses):
    import numpy as np
    return bool(np.all(np.isfinite(losses)) and losses[-1] < losses[0])


def _step_fields(losses, step_s):
    """What every training phase prints of its steps (host clock around
    a loss fetch; the first step carries the compile)."""
    return {"losses": [round(l, 4) for l in losses],
            "first_step_s": round(step_s[0], 2),
            "steady_step_s": round(min(step_s[1:]), 4)}


def _on_tpu(tree):
    import jax
    return all(d.platform == "tpu"
               for leaf in jax.tree.leaves(tree) for d in leaf.devices())


def _resnet_batch(sz, seed=0):
    import numpy as np
    rng = np.random.RandomState(seed)
    x = rng.rand(sz["batch"], 3, sz["size"], sz["size"]).astype("float32")
    y = rng.randint(0, sz["classes"], (sz["batch"],))
    return x, y


# ------------------------------------------------------------ phase A ---

def _gluon_resnet_losses(sz, ctx, mesh=None, kvstore="device", seed=7):
    """Train steps through the Gluon entry points; with `mesh` the one
    global batch is sharded P('dp') over it (data parallelism)."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(seed)
    net = vision.resnet50_v1(classes=sz["classes"])
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.cast("bfloat16")
    net.hybridize()
    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": RESNET_LR, "momentum": 0.9,
         "multi_precision": True},
        kvstore=kvstore)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x_np, y_np = _resnet_batch(sz)
    if mesh is None:
        x = nd.array(x_np, ctx=ctx, dtype="bfloat16")
        y = nd.array(y_np.astype("float32"), ctx=ctx)
    else:
        dp = NamedSharding(mesh, P("dp"))
        x = nd.NDArray(jax.device_put(
            jnp.asarray(x_np, jnp.bfloat16), dp), ctx)
        y = nd.NDArray(jax.device_put(
            jnp.asarray(y_np, jnp.float32), dp), ctx)
    losses, step_s = [], []
    for _ in range(sz["steps"]):
        t0 = time.time()
        with autograd.record():
            loss = loss_fn(net(x).astype("float32"), y).mean()
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
        step_s.append(time.time() - t0)
    params = [p.data()._data for p in net.collect_params().values()]
    return losses, step_s, params, x._data


def phase_resnet50_gluon(sizes, rehearse, carry):
    import mxnet_tpu as mx
    # under the rehearsal's JAX_PLATFORMS=cpu pin tpu(0) is the host
    losses, step_s, params, _ = _gluon_resnet_losses(sizes["resnet"],
                                                     mx.tpu(0))
    return dict(_step_fields(losses, step_s),
                ok=_losses_ok(losses) and (rehearse or _on_tpu(params)),
                params_on=sorted({d.platform for p in params
                                  for d in p.devices()}),
                param_dtypes=sorted({str(p.dtype) for p in params}))


def phase_resnet50_fused(sizes, rehearse, carry):
    import jax.numpy as jnp
    import bench
    sz = sizes["resnet"]
    step, state, mom, aux = bench.build_train_step(
        sz["batch"], sz["size"], classes=sz["classes"], lr=RESNET_LR)
    x_np, y_np = _resnet_batch(sz)
    x, y = jnp.asarray(x_np), jnp.asarray(y_np, jnp.int32)
    losses, step_s = [], []
    for _ in range(sz["steps"]):
        t0 = time.time()
        state, mom, aux, loss = step(state, mom, aux, x, y)
        losses.append(float(loss))
        step_s.append(time.time() - t0)
    # Is block_until_ready a sound barrier on this runtime? Time the same
    # n steps ended by it and ended by a host fetch of the loss
    # (benchmark/common.fetch_barrier): one that returned early would
    # show a window much shorter than the fetch's.
    import jax
    from benchmark.common import fetch_barrier
    windows = {}
    for name, barrier in (("fetch", fetch_barrier),
                          ("block_until_ready", jax.block_until_ready),
                          ("fetch_again", fetch_barrier)):
        t0 = time.time()
        for _ in range(BARRIER_STEPS):
            state, mom, aux, loss = step(state, mom, aux, x, y)
        barrier(loss)
        windows[name] = time.time() - t0
    fetch = min(windows["fetch"], windows["fetch_again"])
    sound = windows["block_until_ready"] >= 0.9 * fetch
    return dict(_step_fields(losses, step_s),
                ok=_losses_ok(losses) and sound
                and (rehearse or _on_tpu(state)),
                barrier=dict({k: round(v, 4) for k, v in windows.items()},
                             steps=BARRIER_STEPS,
                             block_until_ready_sound=bool(sound)))


# ------------------------------------------------------------ phase B ---

def _lm_cfg(sz):
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tf
    return tf.TransformerConfig(
        vocab_size=sz["vocab"], d_model=sz["d_model"],
        n_heads=sz["n_heads"], n_layers=sz["n_layers"], d_ff=sz["d_ff"],
        max_len=sz["seq"], dtype=jnp.bfloat16, rope=True)


def _lm_tokens(sz, seed=0):
    import numpy as np
    return np.random.RandomState(seed).randint(
        1, sz["vocab"], (sz["batch"], sz["seq"])).astype("int32")


def phase_lm_train(sizes, rehearse, carry):
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tf
    sz = sizes["lm"]
    cfg = _lm_cfg(sz)
    params = tf.init_params(cfg, seed=0)
    mom = tf.init_momentum(params)
    step = tf.make_train_step(cfg)
    tokens = jnp.asarray(_lm_tokens(sz))
    losses, step_s = [], []
    for _ in range(sz["steps"]):
        t0 = time.time()
        params, mom, loss = step(params, mom, tokens)
        losses.append(float(loss))
        step_s.append(time.time() - t0)
    carry["lm_params"] = params       # B(b) serves the trained weights
    return dict(_step_fields(losses, step_s),
                ok=_losses_ok(losses) and (rehearse or _on_tpu(params)))


def _bf16_ulp(x):
    import math
    return 2.0 ** (math.floor(math.log2(max(abs(x), 1e-30))) - 7)


def phase_lm_serve(sizes, rehearse, carry):
    """8 requests through ContinuousBatcher; each stream must equal solo
    generate() on the same prompt (tests/test_continuous_batching.py's
    contract). The batch-8 and batch-1 programs may round differently in
    bf16, so a stream may leave the solo one ONLY at a near-tie of the
    solo program's own logits, which is measured and printed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.models.serving import ContinuousBatcher
    sz, sv = sizes["lm"], sizes["serve"]
    cfg = _lm_cfg(sz)
    params = carry.pop("lm_params", None)
    weights = "after lm_train's steps"
    if params is None:
        params, weights = tf.init_params(cfg, seed=0), "init_params(seed=0)"
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, sz["vocab"], (sv["prompt"],)).tolist()
               for _ in range(sv["requests"])]
    t0 = time.time()
    solo = [np.asarray(tf.generate(
        params, jnp.asarray([p], jnp.int32), sv["n_new"], cfg)[0])
        for p in prompts]
    solo_s = time.time() - t0
    t0 = time.time()
    srv = ContinuousBatcher(params, cfg, max_batch=sv["requests"])
    results, order = srv.run([(p, sv["n_new"]) for p in prompts])
    serve_s = time.time() - t0

    total = sv["prompt"] + sv["n_new"]
    score = jax.jit(lambda p, t: tf.forward(p, t, cfg))
    diffs, ok = [], len(order) == len(prompts)
    for i, rid in enumerate(order):
        got = np.asarray(results[rid])
        if got.shape != solo[i].shape:
            diffs.append({"request": i, "shape": list(got.shape)})
            ok = False
            continue
        bad = np.nonzero(got != solo[i])[0]
        if not bad.size:
            continue
        at = int(bad[0])
        # solo's own logits for the token at `at`, given its prefix
        # (causal: padding the tail with solo's later tokens is inert)
        ctx = np.zeros((1, cfg.max_len), np.int32)
        ctx[0, :total] = solo[i]
        logits = np.asarray(score(params, jnp.asarray(ctx))[0, at - 1]
                            .astype(jnp.float32))
        top2 = np.sort(logits)[-2:]
        margin = float(top2[1] - top2[0])
        bound = NEAR_TIE_ULPS * _bf16_ulp(float(top2[1]))
        near = margin <= bound
        ok = ok and near and at >= sv["prompt"]
        diffs.append({"request": i, "first_diff": at,
                      "solo_token": int(solo[i][at]),
                      "served_token": int(got[at]),
                      "top2_margin": margin, "bound": bound,
                      "near_tie": bool(near)})
    return {"ok": bool(ok), "weights": weights,
            "streams": len(order), "equal": len(order) - len(diffs),
            "differing": diffs,
            "near_tie_bound": "%d bf16 ulps of the top logit"
                              % NEAR_TIE_ULPS,
            "solo_s": round(solo_s, 2), "serve_s": round(serve_s, 2),
            "dispatches": srv.dispatch_count}


# ------------------------------------------------------------ phase K ---

def _compiled(fn, *args):
    """jit(fn)(*args), refusing to run unless the Mosaic kernel is in
    the lowered program — a silent interpret run cannot pass phase K."""
    import jax
    lowered = jax.jit(fn).lower(*args)
    return (lowered.compile()(*args),
            "tpu_custom_call" in lowered.as_text())


def _err(a, b):
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return (float(jnp.max(jnp.abs(a - b))),
            max(1.0, float(jnp.max(jnp.abs(b)))))


def _dense_attention(q, k, v):
    """Plain causal softmax attention, fp32 accumulation: the reference
    the flash kernel is held to."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", a.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _k_flash(sz, interpret):
    """flash_attention forward and backward against the dense reference,
    one (batch, 2-head) slice of the reference at a time (the dense
    [T, T] scores of all heads at once would not fit beside it)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import flash_attention
    b, t, h, d = sz["b"], sz["t"], sz["h"], sz["d"]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
                  for kk in ks)

    def flash_loss(q_, k_, v_, w_):
        o = flash_attention(q_, k_, v_, causal=True, interpret=interpret)
        return (o.astype(jnp.float32) * w_.astype(jnp.float32)).sum(), o

    # w rides as an argument: closed over it would be a 67 MB constant
    # in the executable, too large for the persistent cache to keep
    (got, kernel) = _compiled(
        jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True),
        q, k, v, w)
    (_, o), grads = got

    @jax.jit
    def dense_slice(q_, k_, v_, w_):
        def loss(q__, k__, v__):
            o_ = _dense_attention(q__, k__, v__)
            return (o_.astype(jnp.float32)
                    * w_.astype(jnp.float32)).sum(), o_
        (_, o_), g_ = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q_, k_, v_)
        return o_, g_

    errs = {"o": 0.0, "dq": 0.0, "dk": 0.0, "dv": 0.0}
    ok = True
    for bi in range(b):
        for h0 in range(0, h, 2):
            sl = (slice(bi, bi + 1), slice(None), slice(h0, h0 + 2))
            o_ref, g_ref = dense_slice(q[sl], k[sl], v[sl], w[sl])
            pairs = [("o", o[sl], o_ref)] + [
                (n, g[sl], r) for n, g, r in zip(
                    ("dq", "dk", "dv"), grads, g_ref)]
            for name, a, r in pairs:
                e, scale = _err(a, r)
                errs[name] = max(errs[name], e / scale)
                ok = ok and e <= BF16_TOL * scale
    return {"ok": ok and (kernel or interpret), "kernel_in_hlo": kernel,
            "shape": [b, t, h, d],
            "max_err_over_scale": {n: round(e, 5) for n, e in errs.items()}}


def _k_decode(sz, interpret):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels import dense_decode_with_lse, flash_decode
    b, t, h, d = sz["b"], sz["t"], sz["h"], sz["d"]
    out, ok = {}, True
    for name, kvh in (("mha", h), ("gqa", sz["kvh"])):
        ks = jax.random.split(jax.random.PRNGKey(kvh), 3)
        q = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
        kc = jax.random.normal(ks[1], (b, t, kvh, d), jnp.bfloat16)
        vc = jax.random.normal(ks[2], (b, t, kvh, d), jnp.bfloat16)
        lengths = jnp.asarray([t - 7 * i for i in range(b)], jnp.int32)
        got, kernel = _compiled(
            lambda *a: flash_decode(*a, interpret=interpret),
            q, kc, vc, lengths)
        ref, _ = dense_decode_with_lse(q, kc, vc, lengths)
        e, scale = _err(got, ref)
        ok = ok and e <= BF16_TOL * scale and (kernel or interpret)
        out[name] = {"kernel_in_hlo": kernel, "max_abs_err": round(e, 5)}
    out.update(ok=ok, shape=[b, t, h, d])
    return out


def _k_carry(sz, interpret):
    """flash_carry_block the way ring attention drives it: an own-block
    round from an empty carry, then a rotated round onto that carry —
    parallel.ring.local_attention_block, kernel against its jnp path."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import ring
    b, t, h, d = sz["b"], sz["t"] // 4, sz["h"], sz["d"]
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q, k0, v0, k1, v1 = (jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
                         for kk in ks)

    def two_rounds(use_kernel):
        def fn(q_, k0_, v0_, k1_, v1_):
            scale = 1.0 / (d ** 0.5)
            carry = ring.local_attention_block(
                q_, k0_, v0_, 1 * t, 1 * t, True, scale, carry=None,
                use_flash_kernel=use_kernel)
            return ring.local_attention_block(
                q_, k1_, v1_, 1 * t, 0 * t, True, scale, carry=carry,
                use_flash_kernel=use_kernel)
        return fn

    if interpret:
        got, kernel = jax.jit(two_rounds(True))(q, k0, v0, k1, v1), False
    else:
        got, kernel = _compiled(two_rounds(True), q, k0, v0, k1, v1)
    ref = jax.jit(two_rounds(False))(q, k0, v0, k1, v1)
    (o, m, l), (o_r, m_r, l_r) = got, ref
    # compare what the ring returns: the normalised output
    norm = lambda o_, l_: o_ / jnp.maximum(l_, 1e-30).transpose(
        0, 2, 1)[..., None]
    e, scale = _err(norm(o, l), norm(o_r, l_r))
    return {"ok": e <= BF16_TOL * scale and (kernel or interpret),
            "kernel_in_hlo": kernel, "shard_shape": [b, t, h, d],
            "max_abs_err": round(e, 5)}


def _paged_dense_ref(q, pool, tables, pos):
    """What decode_step_paged / verify_chunk_paged compute without the
    kernel: gather through the tables, mask `<= pos + c`, dense softmax
    (tests/test_paged_kernel.py's reference)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.models import transformer as tf
    b, span, h, d = q.shape
    kvh = pool["k"].shape[2]
    att = tf._paged_gather(pool, tables)
    t_pos = jnp.arange(att["k"].shape[1])
    positions = pos[:, None] + jnp.arange(span)[None, :]
    mask = t_pos[None, None, :] <= positions[:, :, None]
    qg = q.reshape(b, span, kvh, h // kvh, d)
    if "ks" in pool:
        o = tf._int8_cache_attention(qg, att, mask, q.dtype)
    else:
        s = jnp.einsum("bckgd,btkd->bckgt", qg, att["k"],
                       preferred_element_type=jnp.float32) / np.sqrt(d)
        s = jnp.where(mask[:, :, None, None, :], s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bckgt,btkd->bckgd", a.astype(att["v"].dtype),
                       att["v"], preferred_element_type=jnp.float32
                       ).astype(q.dtype)
    return o.reshape(b, span, h, d)


def _k_paged(sz, dec, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.kernels import paged_attention
    from mxnet_tpu.models import transformer as tf
    nblocks, bs, kvh = sz["nblocks"], sz["bs"], sz["kvh"]
    b, h, d = dec["b"], dec["h"], dec["d"]
    nb = (nblocks - 1) // b                  # table entries per lane
    rng = np.random.RandomState(5)
    out, ok = {}, True
    for int8 in (False, True):
        kk, kv = jax.random.split(jax.random.PRNGKey(int8), 2)
        kf = jax.random.normal(kk, (nblocks, bs, kvh, d), jnp.float32)
        vf = jax.random.normal(kv, (nblocks, bs, kvh, d), jnp.float32)
        if int8:
            k8, ks = tf._kv_quant(kf)
            v8, vs = tf._kv_quant(vf)
            pool = {"k": k8, "v": v8, "ks": ks, "vs": vs}
        else:
            pool = {"k": kf.astype(jnp.bfloat16),
                    "v": vf.astype(jnp.bfloat16)}
        for span in (1, 5):
            # ragged lanes over permuted tables, null-block tails
            pos = np.array([(nb * bs - span) * (i + 1) // b
                            for i in range(b)], np.int32)
            tables = np.zeros((b, nb), np.int32)
            for i in range(b):
                perm = rng.permutation(nb)
                need = -(-(int(pos[i]) + span) // bs)
                tables[i, :need] = 1 + i * nb + perm[:need]
            q = jax.random.normal(jax.random.PRNGKey(span),
                                  (b, span, h, d), jnp.bfloat16)
            args = (q, pool, jnp.asarray(tables), jnp.asarray(pos))
            got, kernel = _compiled(
                lambda *a: paged_attention(*a, interpret=interpret), *args)
            ref = jax.jit(_paged_dense_ref)(*args)
            e, scale = _err(got, ref)
            ok = ok and e <= BF16_TOL * scale and (kernel or interpret)
            out["%s_span%d" % ("int8" if int8 else "bf16", span)] = {
                "kernel_in_hlo": kernel, "max_abs_err": round(e, 6),
                "ref_max_abs": round(float(jnp.max(jnp.abs(
                    ref.astype(jnp.float32)))), 4)}
    out.update(ok=ok, pool=[nblocks, bs, kvh, d], table_len=nb * bs)
    return out


def _k_latent(sz, interpret):
    """latent_decode (every "mla" layer's decode contraction) against
    the two XLA passes it replaced, at ragged lengths, and beside it
    latent_row_store (the decode store of the round's fresh `kr` rows,
    in place) against the scatter it replaced, bit for bit, a lane at
    the row its length ends on and one past the cache (dropped): the
    cell's cache, then one block that is no multiple of 128 and three
    blocks of 128, the smallest the kernels tile with."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.kernels import (latent_decode, latent_decode_reference,
                                   latent_row_store)
    b, h, r, e = (sz[k] for k in "bhre")
    norm = float(np.sqrt(r + e))
    out = {"ok": True, "shape": [b, h, sz["t"], r + e]}
    for t in (sz["t"], 192, 384):
        ks = jax.random.split(jax.random.PRNGKey(t), 4)
        q_lat = jax.random.normal(ks[0], (b, h, r), jnp.bfloat16)
        q_r = jax.random.normal(ks[1], (b, h, e), jnp.bfloat16)
        c = jax.random.normal(ks[2], (b, t, r), jnp.bfloat16)
        kr = jax.random.normal(ks[3], (b, t, e), jnp.bfloat16)
        lengths = jnp.asarray([1 + (t - 1) * i // max(b - 1, 1)
                               for i in range(b)], jnp.int32)
        got, kernel = _compiled(
            lambda *a: latent_decode(*a, norm, interpret=interpret),
            q_lat, q_r, c, kr, lengths)
        ref = jax.jit(lambda *a: latent_decode_reference(*a, norm))(
            q_lat, q_r, c, kr, lengths)
        err, scale = _err(got, ref)
        fresh = jax.random.normal(ks[0], (b, e), jnp.bfloat16)
        pos = (lengths - 1).at[1].set(t)
        stored, writer = _compiled(
            lambda *a: latent_row_store(*a, interpret=interpret),
            kr, fresh, pos)
        same = bool(jnp.array_equal(stored, jax.jit(
            lambda kr_, rows, at: kr_.at[jnp.arange(b), at].set(rows))(
            kr, fresh, pos)))
        out["ok"] = out["ok"] and err <= BF16_TOL * scale \
            and (kernel or interpret) and same and (writer or interpret)
        out["t%d" % t] = {"kernel_in_hlo": kernel,
                          "max_abs_err": round(err, 5),
                          "row_store_in_hlo": writer,
                          "row_store_equals_scatter": same}
    return out


def phase_kernels(sizes, rehearse, carry):
    import jax
    interpret = jax.default_backend() != "tpu"     # only under --rehearse
    parts = {
        "flash_attention": _k_flash(sizes["flash"], interpret),
        "flash_decode": _k_decode(sizes["decode"], interpret),
        "flash_carry_block": _k_carry(sizes["flash"], interpret),
        "paged_attention": _k_paged(sizes["paged"], sizes["decode"],
                                    interpret),
        "latent_decode": _k_latent(sizes["latent"], interpret),
    }
    rec = {"ok": all(p.pop("ok") for p in parts.values()),
           "interpreted": interpret, "tolerance": BF16_TOL}
    rec.update(parts)
    return rec


# ------------------------------------------------- --chips 4 (section 7) ---

def _sharded_record(name, sharded, one, tree, rehearse):
    """Sharded losses against one device's, and a census of `tree`: how
    many of the four devices hold an addressable shard of some leaf, and
    the bytes in use on each — code that never met four chips may have
    put everything on the first."""
    import jax
    import numpy as np
    devices = jax.devices()[:4]
    holding = set()
    for leaf in jax.tree.leaves(tree):
        holding.update(s.device for s in leaf.addressable_shards)
    n_hold = len(holding & set(devices))
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices]
    close = bool(np.allclose(sharded[0], one[0], rtol=SHARDED_RTOL[0])
                 and np.allclose(sharded, one, rtol=SHARDED_RTOL[1]))
    return {"ok": close and _losses_ok(sharded) and n_hold == 4
            and (rehearse or all(in_use)),
            "losses_" + name: [round(l, 4) for l in sharded],
            "losses_one_device": [round(l, 4) for l in one],
            "rtol_first_step_and_all": list(SHARDED_RTOL),
            "devices_holding_shards": n_hold,
            "bytes_in_use_per_device": in_use}


def phase_resnet50_dp4(sizes, rehearse, carry):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    sz = dict(sizes["resnet"], steps=3)
    one, _, _, _ = _gluon_resnet_losses(sz, mx.tpu(0))
    mesh = parallel.make_mesh({"dp": 4})
    with parallel.use_mesh(mesh):
        dp4, _, params, x = _gluon_resnet_losses(
            sz, mx.tpu(0), mesh=mesh, kvstore="dist_tpu_sync")
    return _sharded_record("dp4", dp4, one, [params, x], rehearse)


def phase_lm_dp2_tp2(sizes, rehearse, carry):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu import parallel
    from mxnet_tpu.models import transformer as tf
    sz = sizes["lm"]
    cfg = _lm_cfg(sz)
    cfg.use_ring_attention = False          # sp=1: nothing to ring over
    tokens_np = _lm_tokens(sz)

    def run(mesh):
        params = tf.init_params(cfg, seed=0)
        tokens = jnp.asarray(tokens_np)
        if mesh is not None:
            params = tf.shard_params(params, cfg, mesh)
            tokens = jax.device_put(
                tokens, NamedSharding(mesh, P("dp", None)))
        mom = tf.init_momentum(params)
        step = tf.make_train_step(cfg, mesh)
        losses = []
        for _ in range(sz["steps"]):
            params, mom, loss = step(params, mom, tokens)
            losses.append(float(loss))
        return losses, params, tokens

    one, _, _ = run(None)
    mesh = parallel.make_mesh({"dp": 2, "tp": 2, "sp": 1, "ep": 1})
    sharded, params, tokens = run(mesh)
    return _sharded_record("dp2_tp2", sharded, one, [params, tokens],
                           rehearse)


# ------------------------------------------------------------- driver ---

ONE_CHIP = [("resnet50_gluon", phase_resnet50_gluon),
            ("resnet50_fused", phase_resnet50_fused),
            ("lm_train", phase_lm_train),
            ("lm_serve", phase_lm_serve),
            ("kernels", phase_kernels)]
FOUR_CHIPS = [("resnet50_dp4", phase_resnet50_dp4),
              ("lm_dp2_tp2", phase_lm_dp2_tp2)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever device JAX has")
    ap.add_argument("--only", action="append", metavar="PHASE",
                    help="run only the named phase(s)")
    ap.add_argument("--break-phase", metavar="PHASE",
                    help="poison PHASE's losses with a NaN (the script "
                         "must then fail)")
    args = ap.parse_args(argv)

    import jax
    from mxnet_tpu import _native, chip
    from mxnet_tpu.observability import core as obs
    dev = chip.describe()
    if not args.rehearse and dev["platform"] != "tpu":
        print("chip_smoke: jax found platform %r, not a TPU; nothing "
              "was run (--rehearse runs a tiny CPU rehearsal)"
              % dev["platform"], file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print("chip_smoke: --chips %d but jax has %d device(s)"
              % (args.chips, dev["count"]), file=sys.stderr)
        return 2
    cache_dir = chip.use_compile_cache()
    sizes = TINY if args.rehearse else REAL
    phases = FOUR_CHIPS if args.chips == 4 else ONE_CHIP
    if args.only:
        phases = [p for p in phases if p[0] in args.only]
    print(json.dumps({
        "phase": "start", "jax": jax.__version__,
        "jaxlib": __import__("jaxlib").__version__,
        "python": sys.version.split()[0], "device": dev,
        "peaks_known": dev["kind"] in chip.PEAKS,
        "compile_cache": cache_dir,
        "native_recordio": _native.recordio_lib() is not None,
        "rehearse": args.rehearse,
        "kernels_on_paths_A_B": "none with default settings; phase K "
                                "alone covers the Pallas kernels"}),
          flush=True)

    t_all, ok, carry, totals = time.time(), True, {}, {}
    for name, fn in phases:
        t0, since = time.time(), obs.now_ns()
        try:
            rec = fn(sizes, args.rehearse, carry)
            if name == args.break_phase:
                rec["losses"] = [float("nan")]
                rec["ok"] = _losses_ok(rec["losses"])
        except Exception as exc:  # noqa: BLE001 — reported, and fatal below
            traceback.print_exc()
            rec = {"ok": False, "error": "%s: %s" % (
                type(exc).__name__, str(exc)[:2000])}
        line = {"phase": name, "ok": bool(rec.pop("ok")),
                "seconds": round(time.time() - t0, 2)}
        built = _compile_ledger(since)
        # summed here: the ledger is bounded, and a long run outlives it
        totals = {k: round(totals.get(k, 0) + v, 2)
                  for k, v in built.items()}
        line.update(built)
        line.update(rec)
        # the peak is the process's so far (PJRT keeps no per-phase
        # peak); bytes_in_use is what this phase left behind
        line["peak_bytes_in_use"], line["bytes_in_use"] = _device_bytes(
            jax.devices()[:args.chips])
        print(json.dumps(line), flush=True)
        ok = ok and line["ok"]
    print(json.dumps(dict(phase="total", ok=ok,
                          seconds=round(time.time() - t_all, 2),
                          **totals)), flush=True)
    print(json.dumps({"ok": ok, "device": dev}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
