"""Structural residual diff: framework step vs hand-built jax step.

The on-chip gap (PERF.md: framework ~101 GB/step vs hand-built 74.5 GB
at identical FLOPs) must come from bytes the framework step moves that
the hand-built one does not. The saved-activation (vjp residual) tree
is the structural, backend-independent half of that story: this script
builds BOTH steps at the same shapes, takes `jax.vjp` eagerly, and
prints each side's residual histogram grouped by (dtype, shape) plus
the asymmetric entries — what one side saves that the other doesn't.

    JAX_PLATFORMS=cpu python - < benchmark/residual_compare.py

Run from the repo root via stdin so cwd lands on sys.path.
bs/size default 8/64 (structure is shape-proportional); override with
MXNET_AB_BATCH / MXNET_AB_SIZE.
"""

import collections
import os
import sys

BATCH = int(os.environ.get("MXNET_AB_BATCH", "8"))
SIZE = int(os.environ.get("MXNET_AB_SIZE", "64"))


def _framework_residuals(batch, size):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.utils import functionalize_block

    net = vision.resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    x0 = mx.nd.zeros((batch, 3, size, size))
    graph_fn, data_names, args, aux = functionalize_block(
        net, x0, is_train=True)
    key = jax.random.PRNGKey(0)

    def loss_of(args_f32, x, y):
        args_bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                                 args_f32)
        inputs = dict(args_bf16)
        inputs[data_names[0]] = x.astype(jnp.bfloat16)
        aux_bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), aux)
        outs, _ = graph_fn(inputs, aux_bf16, key)
        logits = outs[0].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 3, size, size).astype("float32"))
    y = jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32)
    _, vjp = jax.vjp(lambda a: loss_of(a, x, y), args)
    return jax.tree.leaves(vjp)


def _handbuilt_residuals(batch, size):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import cost_compare as cc

    params = cc.hb_init(np.random.RandomState(0))

    def loss_of(p, x, y):
        logits = cc.hb_forward(p, x).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[:, None],
                                    axis=-1)[:, 0].mean()

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 3, size, size).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32)
    _, vjp = jax.vjp(lambda p: loss_of(p, x, y), params)
    return jax.tree.leaves(vjp)


def _histogram(leaves):
    h = collections.Counter()
    for leaf in leaves:
        if hasattr(leaf, "nbytes"):
            h[(str(leaf.dtype), tuple(leaf.shape))] += 1
    return h


def _mb(key, n):
    import numpy as np
    dtype, shape = key
    return n * int(np.prod(shape or (1,))) * np.dtype(
        dtype if dtype != "bfloat16" else "uint16").itemsize / 1e6


def main():
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()

    fw = _framework_residuals(BATCH, SIZE)
    hb = _handbuilt_residuals(BATCH, SIZE)
    hf, hh = _histogram(fw), _histogram(hb)

    def total(h):
        return sum(_mb(k, n) for k, n in h.items())

    print("residuals @ bs=%d %dpx: framework %.1f MB (%d arrays) vs "
          "hand-built %.1f MB (%d arrays)"
          % (BATCH, SIZE, total(hf), sum(hf.values()),
             total(hh), sum(hh.values())))

    rows = []
    for key in set(hf) | set(hh):
        nf, nh = hf.get(key, 0), hh.get(key, 0)
        delta = _mb(key, nf) - _mb(key, nh)
        rows.append((abs(delta), delta, key, nf, nh))
    rows.sort(reverse=True)
    print("%-10s %-22s %6s %6s %10s" % ("dtype", "shape", "fw#", "hb#",
                                        "delta MB"))
    for _, delta, (dtype, shape), nf, nh in rows[:25]:
        if abs(delta) < 0.05:
            continue
        print("%-10s %-22s %6d %6d %+10.1f"
              % (dtype, str(shape), nf, nh, delta))


if __name__ == "__main__":
    main()
