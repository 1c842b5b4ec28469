"""Plain reference of ResNet-50 v1 (He et al. 2015, Table 1, 50-layer) as
`gluon.model_zoo.vision.resnet50_v1` lays it out: 7x7/2 stem, 3x3/2 max
pool, bottleneck stages [3, 4, 6, 3] with the stride on the first 1x1
convolution, batch norm (eps 1e-5, batch statistics) after every
convolution, global average pool, dense classifier with bias. float32,
highest convolution precision, NCHW. It imports nothing of the program.

Weights are a flat dict keyed by the Gluon parameter's name without the
network prefix (`conv0_weight`, `stage1_batchnorm0_gamma`, ...), made from
the seed by `init_weights` in one jitted call.
"""

from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from .common import HIGHEST, exact, leaf_norms

STAGES = (3, 4, 6, 3)
CHANNELS = (64, 256, 512, 1024, 2048)
# gain of the batch norm that closes each residual branch. With 1.0 on all
# sixteen, the seeded network doubles its variance block after block and is
# chaotic: two float32 runs already differ by 2% in a gradient's norm, and
# bfloat16 by 20-50% (PERF.md, PR 24), so nothing can be compared. A small
# closing gain (the "zero gamma" habit of large-batch ResNet training,
# Goyal et al. 2017, kept above zero so that every leaf has a gradient)
# makes the comparison well conditioned and costs the timed path nothing.
LAST_GAMMA = 0.2


def _plan(cfg):
    """[(kind, name, shape, fan_in)] in the order Gluon creates them."""
    out = [("conv", "conv0", (64, 3, 7, 7), 3 * 49),
           ("bn", "batchnorm0", 64, 1.0)]
    for s, blocks in enumerate(STAGES):
        cin, cout = CHANNELS[s], CHANNELS[s + 1]
        mid, n = cout // 4, 0
        pre = "stage%d_" % (s + 1)
        for b in range(blocks):
            bin_ = cin if b == 0 else cout
            convs = [(mid, bin_, 1), (mid, mid, 3), (cout, mid, 1)]
            if b == 0:
                convs.append((cout, cin, 1))          # downsample
            for j, (o, i, k) in enumerate(convs):
                out.append(("conv", "%sconv%d" % (pre, n), (o, i, k, k),
                            i * k * k))
                out.append(("bn", "%sbatchnorm%d" % (pre, n), o,
                            LAST_GAMMA if j == 2 else 1.0))
                n += 1
    out.append(("dense", "dense0", (cfg["classes"], CHANNELS[-1]),
                CHANNELS[-1]))
    return out


def leaf_specs(cfg):
    """[(name, shape, init, trained)]; init is the std of a seeded normal,
    or ("const", value)."""
    out = []
    for item in _plan(cfg):
        if item[0] == "conv":
            out.append((item[1] + "_weight", item[2],
                        (2.0 / item[3]) ** 0.5, True))
        elif item[0] == "bn":
            for leaf, value, trained in (("gamma", item[3], True),
                                         ("beta", 0.0, True),
                                         ("running_mean", 0.0, False),
                                         ("running_var", 1.0, False)):
                out.append(("%s_%s" % (item[1], leaf), (item[2],),
                            ("const", value), trained))
        else:
            out.append((item[1] + "_weight", item[2], item[3] ** -0.5, True))
            out.append((item[1] + "_bias", (item[2][0],), ("const", 0.0),
                        True))
    return out


def init_weights(cfg, seed):
    """All parameters on the device in one jitted call from the seed:
    convolution and dense weights in bfloat16 (the dtype `net.cast` serves
    them in), batch-norm parameters in float32."""
    specs = leaf_specs(cfg)

    @jax.jit
    def make(seed_u32):
        key = jax.random.key(seed_u32, impl="rbg")
        out = {}
        for i, (name, shape, init, _) in enumerate(specs):
            if isinstance(init, tuple):
                out[name] = jnp.full(
                    shape, init[1],
                    jnp.bfloat16 if name.endswith("bias") else jnp.float32)
            else:
                out[name] = (jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                    * init).astype(jnp.bfloat16)
        return out

    return make(jnp.uint32(int(seed) % (2 ** 32)))


def make_batch(cfg, batch, seed):
    """One seeded batch: images uniform in [0, 1) as bfloat16, labels."""
    @jax.jit
    def make(seed_u32):
        key = jax.random.key(seed_u32, impl="rbg")
        x = jax.random.uniform(
            jax.random.fold_in(key, 1_000_001),
            (batch, 3, cfg["image_size"], cfg["image_size"]), jnp.float32)
        y = jax.random.randint(jax.random.fold_in(key, 1_000_002),
                               (batch,), 0, cfg["classes"])
        return x.astype(jnp.bfloat16), y

    return make(jnp.uint32(int(seed) % (2 ** 32)))


def _conv(x, w, stride, pad, q):
    return jax.lax.conv_general_dilated(
        q(x), q(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)


def _bn(x, w, name):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    g = w[name + "_gamma"].reshape(1, -1, 1, 1)
    b = w[name + "_beta"].reshape(1, -1, 1, 1)
    return (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b


def _block(x, w, pre, n, stride, downsample, q):
    def cb(x, i, s, p):
        y = _conv(x, w["%sconv%d_weight" % (pre, n + i)], s, p, q)
        return _bn(y, w, "%sbatchnorm%d" % (pre, n + i))
    y = jax.nn.relu(cb(x, 0, stride, 0))
    y = jax.nn.relu(cb(y, 1, 1, 1))
    y = cb(y, 2, 1, 0)
    if downsample:
        x = cb(x, 3, stride, 0)
    return jax.nn.relu(y + x)


def forward(weights, x, cfg, q=exact):
    """x [N, 3, S, S] -> logits [N, classes], batch-norm in training mode."""
    w = {k: v.astype(jnp.float32) for k, v in weights.items()}
    x = x.astype(jnp.float32)
    x = jax.nn.relu(_bn(_conv(x, w["conv0_weight"], 2, 3, q), w,
                        "batchnorm0"))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    for s, blocks in enumerate(STAGES):
        pre, n = "stage%d_" % (s + 1), 0
        for b in range(blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            blk = jax.checkpoint(partial(
                _block, pre=pre, n=n, stride=stride, downsample=(b == 0),
                q=q))
            x = blk(x, {k: v for k, v in w.items() if k.startswith(pre)})
            n += 4 if b == 0 else 3
    x = jnp.mean(x, axis=(2, 3))
    return jnp.einsum("nc,kc->nk", q(x), q(w["dense0_weight"]),
                      precision=HIGHEST) + w["dense0_bias"]


def sample_losses(weights, x, y, cfg, q=exact):
    logp = jax.nn.log_softmax(forward(weights, x, cfg, q), axis=-1)
    return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]


@lru_cache(maxsize=None)
def _make_step(classes, trained, lr, momentum, q):
    """One jitted reference step per (sizes, optimizer, operand treatment):
    a process that follows many seeds compiles it once."""
    cfg = {"classes": classes}

    @jax.jit
    def step(w, m, x, y):
        def mean_loss(t):
            each = sample_losses({**w, **t}, x, y, cfg, q)
            return jnp.mean(each), each
        (lval, each), g = jax.value_and_grad(mean_loss, has_aux=True)(
            {k: w[k] for k in trained})
        m = {k: momentum * m[k] - lr * g[k] for k in trained}
        w = {**w, **{k: w[k] + m[k] for k in trained}}
        return w, m, lval, each, leaf_norms(g)

    return step


def train_reference(cfg, seed, batch, steps, lr, momentum=0.9, q=exact):
    """Follow the first `steps` steps of SGD with momentum on float32
    master weights (`multi_precision`): mom = momentum*mom - lr*grad,
    w += mom, on the one seeded batch. Same return as the LM reference's.
    The batch rides as an argument: closed over, it would be a 38 MB
    constant in an executable that differs with every seed."""
    w0 = init_weights(cfg, seed)
    x, y = make_batch(cfg, batch, seed)
    trained = [s[0] for s in leaf_specs(cfg) if s[3]]
    step = _make_step(cfg["classes"], tuple(trained), lr, momentum, q)
    w = {k: v.astype(jnp.float32) for k, v in w0.items()}
    start = {k: w[k] for k in trained}
    m = {k: jnp.zeros_like(w[k]) for k in trained}
    out = {"losses": []}
    for i in range(steps):
        w, m, lval, each, gn = step(w, m, x, y)
        out["losses"].append(float(lval))
        if i == 0:
            out["grad_norms"] = {k: float(v) for k, v in gn.items()}
            out["sample_losses"] = [float(v) for v in jax.device_get(each)]
    delta = leaf_norms({k: w[k] - start[k] for k in trained})
    out["delta_norms"] = {k: float(v) for k, v in delta.items()}
    return out
