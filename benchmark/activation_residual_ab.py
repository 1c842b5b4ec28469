"""Saved-activation (residual) memory A/B for the framework ResNet-50
step — the arithmetic-intensity lever behind the MFU north star.

PERF.md's roofline pins the step at ~77 FLOP/byte vs the chip's ~240
balance point; the only way toward 45% MFU is fewer bytes per step, and
the backward pass's saved activations are the biggest slice. This
script measures those bytes DIRECTLY and backend-independently: the
eager `jax.vjp` residual closure is a pytree of concrete arrays, so
summing leaf bytes gives the saved-activation footprint of each
variant. Variants:

  base        shipped step (bf16 compute, fp32 master weights)
  relu_mask   MXNET_RELU_MASK_RESIDUAL=1 — relu saves a 1-byte sign
              mask instead of the bf16 activation (exact compression)
  mirror      MXNET_BACKWARD_DO_MIRROR=1 (dots policy) — recompute
              everything but MXU results

Prints one JSON line per variant (residual MB + delta vs base). The
img/s leg runs on chip (same flags through bench.py); this gives the
bytes side of the intensity argument anywhere.
"""

import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def residual_bytes(batch=None, size=None):
    batch = int(os.environ.get("MXNET_AB_BATCH", batch or 8))
    size = int(os.environ.get("MXNET_AB_SIZE", size or 64))
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.utils import functionalize_block
    from mxnet_tpu.executor import apply_mirror, mirror_enabled

    net = vision.resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    x0 = mx.nd.zeros((batch, 3, size, size))
    graph_fn, data_names, args, aux = functionalize_block(
        net, x0, is_train=True)
    key = jax.random.PRNGKey(0)

    def loss_of(args_f32, x, y):
        args_bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), args_f32)
        inputs = dict(args_bf16)
        inputs[data_names[0]] = x.astype(jnp.bfloat16)
        aux_bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), aux)
        outs, _ = graph_fn(inputs, aux_bf16, key)
        logits = outs[0].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    loss_of = apply_mirror(loss_of, mirror_enabled())

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 3, size, size).astype("float32"))
    y = jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32)
    _, vjp = jax.vjp(lambda a: loss_of(a, x, y), args)
    return sum(l.nbytes for l in jax.tree.leaves(vjp)
               if hasattr(l, "nbytes"))


def run_variant(name, env):
    """Fresh interpreter per variant: the flags are read at op/trace
    time and module state (op registry closures) must not leak."""
    import subprocess
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from benchmark.activation_residual_ab import residual_bytes\n"
            "print('RB', residual_bytes())" % os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))
    e = dict(os.environ)
    e.update(env)
    e["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], env=e,
                       capture_output=True, text=True, timeout=1200)
    for line in r.stdout.splitlines():
        if line.startswith("RB "):
            return int(line.split()[1])
    raise RuntimeError("%s failed:\n%s" % (name, r.stderr[-2000:]))


def main():
    print("[activation_residual_ab] CPU structure check by design: JAX_PLATFORMS=%s "
          "(pinned by this script when unset); counts, bytes and "
          "orderings only — no time or rate below is a device number"
          % os.environ["JAX_PLATFORMS"], flush=True)
    variants = [
        ("base", {}),
        ("bn_bf16", {"MXNET_BN_BF16_RESIDUAL": "1"}),
        ("relu_mask", {"MXNET_RELU_MASK_RESIDUAL": "1"}),
        ("mirror_dots", {"MXNET_BACKWARD_DO_MIRROR": "1"}),
        ("bn_bf16_relu_mask", {"MXNET_BN_BF16_RESIDUAL": "1",
                               "MXNET_RELU_MASK_RESIDUAL": "1"}),
        ("all_three", {"MXNET_BN_BF16_RESIDUAL": "1",
                       "MXNET_RELU_MASK_RESIDUAL": "1",
                       "MXNET_BACKWARD_DO_MIRROR": "1"}),
        ("int8_conv", {"MXNET_INT8_RESIDUAL": "1"}),
    ]
    base = None
    for name, env in variants:
        b = run_variant(name, env)
        if base is None:
            base = b
        print(json.dumps({
            "metric": "resnet50_residual_bytes_%s" % name,
            "value": round(b / 1e6, 2), "unit": "MB",
            "vs_base": round(b / base, 3)}))


if __name__ == "__main__":
    main()
