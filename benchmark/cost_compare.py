"""Framework-vs-hand-built cost analysis at the REAL benchmark shapes.

PERF.md "Framework step vs hand-built": on-chip round 2 measured the
shipped framework ResNet-50 train step at 97.1 GB/step vs a hand-built
jax step's 74.5 GB at identical FLOPs; the 22 GB gap was attributed to
fp32 BN residuals and the bf16-residual fix shipped round 3 — but the
verifying cost-analysis only ever ran at bs=8/64px where fusion noise
swamps the signal. This script lowers BOTH steps at bs=128/224x224 and
prints XLA cost analysis (FLOPs, bytes accessed) for each, so the fix
is auditable without a timed run.

    python - < benchmark/cost_compare.py            # both legs
    python - framework < benchmark/cost_compare.py  # framework only
    python - handbuilt < benchmark/cost_compare.py  # hand-built only
    python - timed < benchmark/cost_compare.py      # + timed img/s legs

Run from the repo root via stdin so it stays on sys.path.
"""

import os
import sys
import time

import numpy as np

BATCH = int(os.environ.get("MXNET_COST_BATCH", "128"))
SIZE = int(os.environ.get("MXNET_COST_SIZE", "224"))
LAYERS = (3, 4, 6, 3)
CHANNELS = (64, 256, 512, 1024, 2048)


# ------------------------------------------------------------------
# Hand-built leg: ResNet-50 v1 train step written directly in jax —
# same architecture/ordering as gluon.model_zoo resnet50_v1, same AMP
# recipe as bench.py (bf16 compute / fp32 master weights + momentum
# SGD), single-pass shift-centered BN with bf16 residuals.
# ------------------------------------------------------------------

def _hb_conv(x, w, stride=1, pad=0):
    from jax import lax
    return lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _hb_bn(x, p, train=True):
    import jax.numpy as jnp
    from jax import lax
    gamma, beta, mmean, mvar = p
    c = x.shape[1]
    shape = (1, c, 1, 1)
    if train:
        shift = lax.stop_gradient(mmean).astype(x.dtype).reshape(shape)
        centered = x - shift
        red = (0, 2, 3)
        mean_c = jnp.mean(centered, axis=red, dtype=jnp.float32)
        var = jnp.maximum(
            jnp.mean(centered * centered, axis=red, dtype=jnp.float32)
            - mean_c * mean_c, 0.0)
        mean = mean_c + mmean
    else:
        mean, var = mmean, mvar
    inv = lax.rsqrt(var + 1e-3)
    scale = (gamma * inv).astype(x.dtype)
    bias = (beta - gamma * mean * inv).astype(x.dtype)
    return x * scale.reshape(shape) + bias.reshape(shape), mean, var


def _hb_init_bn(c):
    return [np.ones(c, np.float32), np.zeros(c, np.float32),
            np.zeros(c, np.float32), np.ones(c, np.float32)]


def hb_init(rng):
    """Parameter pytree mirroring resnet50_v1 (BottleneckV1: 1x1 ->
    3x3(stride) -> 1x1, downsample 1x1 on the shortcut)."""

    def conv_w(o, i, k):
        fan = i * k * k
        return (rng.randn(o, i, k, k) * np.sqrt(2.0 / fan)).astype(
            np.float32)

    params = {"stem_w": conv_w(64, 3, 7), "stem_bn": _hb_init_bn(64)}
    in_c = CHANNELS[0]
    for si, n in enumerate(LAYERS):
        out_c = CHANNELS[si + 1]
        mid = out_c // 4
        stride = 1 if si == 0 else 2
        blocks = []
        for b in range(n):
            s = stride if b == 0 else 1
            blk = {
                "w1": conv_w(mid, in_c, 1), "bn1": _hb_init_bn(mid),
                "w2": conv_w(mid, mid, 3), "bn2": _hb_init_bn(mid),
                "w3": conv_w(out_c, mid, 1), "bn3": _hb_init_bn(out_c),
            }
            if b == 0:
                blk["wd"] = conv_w(out_c, in_c, 1)
                blk["bnd"] = _hb_init_bn(out_c)
            blocks.append(blk)
            in_c = out_c
        params["stage%d" % si] = blocks
    params["fc_w"] = (rng.randn(CHANNELS[-1], 1000)
                      * np.sqrt(1.0 / CHANNELS[-1])).astype(np.float32)
    params["fc_b"] = np.zeros(1000, np.float32)
    return params


def hb_forward(params, x):
    import jax
    import jax.numpy as jnp
    from jax import lax
    x = x.astype(jnp.bfloat16)
    x = _hb_conv(x, params["stem_w"], 2, 3)
    x, _, _ = _hb_bn(x, params["stem_bn"])
    x = jax.nn.relu(x)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                          (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    for si, n in enumerate(LAYERS):
        stride = 1 if si == 0 else 2
        for b in range(n):
            blk = params["stage%d" % si][b]
            s = stride if b == 0 else 1
            sc = x
            y = _hb_conv(x, blk["w1"], 1, 0)
            y, _, _ = _hb_bn(y, blk["bn1"])
            y = jax.nn.relu(y)
            y = _hb_conv(y, blk["w2"], s, 1)
            y, _, _ = _hb_bn(y, blk["bn2"])
            y = jax.nn.relu(y)
            y = _hb_conv(y, blk["w3"], 1, 0)
            y, _, _ = _hb_bn(y, blk["bn3"])
            if "wd" in blk:
                sc = _hb_conv(sc, blk["wd"], s, 0)
                sc, _, _ = _hb_bn(sc, blk["bnd"])
            x = jax.nn.relu(y + sc)
    x = jnp.mean(x, axis=(2, 3), dtype=jnp.float32)
    return x @ params["fc_w"] + params["fc_b"]


def hb_build(batch, size):
    import jax
    import jax.numpy as jnp
    params = hb_init(np.random.RandomState(0))

    def loss_of(p, x, y):
        logits = hb_forward(p, x).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0].mean()

    def step(p, mom, x, y):
        loss, grads = jax.value_and_grad(loss_of)(p, x, y)
        mom = jax.tree.map(lambda m, g: 0.9 * m + g, mom, grads)
        p = jax.tree.map(lambda w, m: w - 0.1 * m, p, mom)
        return p, mom, loss

    mom = jax.tree.map(lambda w: np.zeros(w.shape, np.float32), params)
    return jax.jit(step, donate_argnums=(0, 1)), params, mom


def report(tag, compiled):
    from mxnet_tpu.observability.hlo import compiled_cost
    ca = compiled_cost(compiled)
    flops = ca.get("flops", 0.0)
    gb = ca.get("bytes accessed", 0.0) / 1e9
    print("%-10s  %.2f TFLOP  %.1f GB/step  (%.1f FLOP/byte)"
          % (tag, flops / 1e12, gb, flops / max(ca.get(
              "bytes accessed", 1.0), 1.0)))
    return gb


def main():
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(BATCH, 3, SIZE, SIZE).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, (BATCH,)), jnp.int32)
    which = [a for a in sys.argv[1:] if a in ("framework", "handbuilt")]
    timed = "timed" in sys.argv
    from benchmark.common import obs_ops_requested, print_ops_table
    obs_ops = obs_ops_requested()

    if not which or "framework" in which:
        import bench
        step, args, mom, aux = bench.build_train_step(BATCH, SIZE)
        c = step.lower(args, mom, aux, x, y).compile()
        report("framework", c)
        if obs_ops:
            print_ops_table(c)
        if timed:
            args, mom, aux, loss = c(args, mom, aux, x, y)
            float(loss)
            t0 = time.time()
            for _ in range(20):
                args, mom, aux, loss = c(args, mom, aux, x, y)
            float(loss)
            print("framework img/s: %.1f" % (BATCH * 20 / (time.time() - t0)))

    if not which or "handbuilt" in which:
        step, params, mom = hb_build(BATCH, SIZE)
        c = step.lower(params, mom, x, y).compile()
        report("handbuilt", c)
        if obs_ops:
            print_ops_table(c)
        if timed:
            params, mom, loss = c(params, mom, x, y)
            float(loss)
            t0 = time.time()
            for _ in range(20):
                params, mom, loss = c(params, mom, x, y)
            float(loss)
            print("handbuilt img/s: %.1f" % (BATCH * 20 / (time.time() - t0)))


if __name__ == "__main__":
    main()
