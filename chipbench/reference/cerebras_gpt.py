"""Plain reference of the GPT-2-style decoder the `cerebras-gpt-1.3b*`
configurations run: float32 `jax.numpy`, highest matmul precision, no
cache, no batching tricks. It imports nothing of the program.

Block (as the program computes it; departures from the published
Cerebras-GPT block are listed in the configuration file): learned absolute
positions, pre-norm sequential residual, RMSNorm (eps 1e-6, gain, no bias),
multi-head causal attention scaled by 1/sqrt(head size), tanh-approximated
GELU feed-forward, no linear biases, output head tied to the embedding.

Weights are a flat dict name -> array, made from the seed by
`init_weights` in ONE jitted call, in the dtype they are served in; the
runners arrange the same arrays into the program's tree, so program and
reference start from the same numbers without either taking the other's.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp

from .common import einsum, exact, leaf_norms

LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w1", "w2")


def leaf_specs(cfg):
    """[(name, shape, std)] in a fixed order; std None = ones."""
    d, h, f = cfg["n_embd"], cfg["n_head"], cfg["n_inner"]
    hd = d // h
    out = [("embed", (cfg["vocab_size"], d), 0.02),
           ("pos", (cfg["n_positions"], d), 0.02),
           ("ln_f", (d,), None)]
    layer = {"ln1": ((d,), None), "ln2": ((d,), None),
             "wq": ((d, h, hd), d ** -0.5), "wk": ((d, h, hd), d ** -0.5),
             "wv": ((d, h, hd), d ** -0.5), "wo": ((h, hd, d), d ** -0.5),
             "w1": ((d, f), d ** -0.5), "w2": ((f, d), f ** -0.5)}
    for i in range(cfg["n_layer"]):
        for k in LAYER_LEAVES:
            out.append(("layers.%d.%s" % (i, k),) + layer[k])
    return out


def init_weights(cfg, seed, dtype=jnp.bfloat16):
    """All weights on the device in one jitted call from the seed."""
    specs = leaf_specs(cfg)

    @jax.jit
    def make(seed_u32):
        key = jax.random.key(seed_u32, impl="rbg")
        out = {}
        for i, (name, shape, std) in enumerate(specs):
            if std is None:
                out[name] = jnp.ones(shape, dtype)
            else:
                out[name] = (jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                    * std).astype(dtype)
        return out

    return make(jnp.uint32(int(seed) % (2 ** 32)))


def as_tree(weights, cfg):
    """The flat dict arranged as {"embed", "pos", "ln_f", "layers": [..]}."""
    return {"embed": weights["embed"], "pos": weights["pos"],
            "ln_f": weights["ln_f"],
            "layers": [{k: weights["layers.%d.%s" % (i, k)]
                        for k in LAYER_LEAVES}
                       for i in range(cfg["n_layer"])]}


def flatten_tree(tree):
    out = {k: tree[k] for k in ("embed", "pos", "ln_f")}
    for i, layer in enumerate(tree["layers"]):
        for k in LAYER_LEAVES:
            out["layers.%d.%s" % (i, k)] = layer[k]
    return out


def _rms_norm(x, g):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * g


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _layer(x, p, q):
    t = x.shape[0]
    h = _rms_norm(x, p["ln1"])
    qh = einsum("td,dhk->thk", h, p["wq"], q)
    kh = einsum("td,dhk->thk", h, p["wk"], q)
    vh = einsum("td,dhk->thk", h, p["wv"], q)
    s = einsum("qhd,khd->hqk", qh, kh, q) / math.sqrt(qh.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    o = einsum("hqk,khd->qhd", a, vh, q)
    x = x + einsum("thk,hkd->td", o, p["wo"], q)
    h = _rms_norm(x, p["ln2"])
    f = _gelu_tanh(einsum("td,df->tf", h, p["w1"], q))
    return x + einsum("tf,fd->td", f, p["w2"], q)


def forward_row(weights, tokens, cfg, q=exact, remat=False):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    w = {k: v.astype(jnp.float32) for k, v in weights.items()}
    x = jnp.take(w["embed"], tokens, axis=0) + w["pos"][: tokens.shape[0]]
    layer = jax.checkpoint(_layer, static_argnums=(2,)) if remat else _layer
    for i in range(cfg["n_layer"]):
        p = {k: w["layers.%d.%s" % (i, k)] for k in LAYER_LEAVES}
        x = layer(x, p, q)
    x = _rms_norm(x, w["ln_f"])
    return einsum("td,vd->tv", x, w["embed"], q)


def loss_row(weights, tokens, cfg, q=exact):
    """Mean next-token cross entropy of one sequence."""
    logits = forward_row(weights, tokens, cfg, q, remat=True)[:-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


# ------------------------------------------------------------ serving ---

def served_gaps(cfg, seed, streams, q_control=None):
    """For each served stream (prompt_len, tokens[prompt + generated]):
    by how much each served token's reference logit lies below the
    reference's best at its position. With `q_control`, also the same gap
    for the token the lower-precision forward puts first there.

    Returns [{"gaps": [...], "control_gaps": [...] | None}] per stream.
    One compiled shape: every stream is padded to n_positions (causal, so
    the padding is inert)."""
    weights = init_weights(cfg, seed)
    width = cfg["n_positions"]

    @jax.jit
    def gaps(weights, tokens):
        logits = forward_row(weights, tokens, cfg)
        best = jnp.max(logits, axis=-1)
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
        served = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        out = [best - served]
        if q_control is not None:
            low = jnp.argmax(forward_row(weights, tokens, cfg, q_control),
                             axis=-1)
            out.append(best - jnp.take_along_axis(
                logits, low[:, None], axis=-1)[:, 0])
        return out

    import numpy as np
    results = []
    for t_p, toks in streams:
        padded = np.zeros((width,), np.int32)
        padded[: len(toks)] = toks
        out = [np.asarray(o) for o in gaps(weights, jnp.asarray(padded))]
        # logits at position i choose token i+1: generated tokens sit at
        # [t_p, len) so their choosing positions are [t_p-1, len-1)
        sl = slice(t_p - 1, len(toks) - 1)
        results.append({"gaps": out[0][sl].tolist(),
                        "control_gaps": out[1][sl].tolist()
                        if q_control is not None else None})
    return results


# ----------------------------------------------------------- training ---

def train_reference(cfg, seed, tokens, steps, lr, momentum=0.9, q=exact):
    """Follow the first `steps` SGD-momentum steps on `tokens` [B, T].
    Parameters are held in bfloat16 as the configuration states (no master
    copy): the update is computed in float32 and rounded once. Gradients
    are accumulated a sequence at a time, so the reference fits beside
    nothing.

    Returns {"losses": [..], "grad_norms": {leaf: norm of step 1's
    gradient}, "delta_norms": {leaf: norm of the change after `steps`}}."""
    w0 = init_weights(cfg, seed)
    names = sorted(w0)
    rows = tokens.shape[0]

    @jax.jit
    def row_grad(w, row, acc, loss_acc):
        loss, g = jax.value_and_grad(
            lambda w32: loss_row(w32, row, cfg, q))(
                {k: v.astype(jnp.float32) for k, v in w.items()})
        return ({k: acc[k] + g[k] / rows for k in names},
                loss_acc + loss / rows)

    @partial(jax.jit, donate_argnums=(0, 1))
    def update(w, m, g):
        m = {k: momentum * m[k] + g[k] for k in names}
        w = {k: (w[k].astype(jnp.float32) - lr * m[k]).astype(w[k].dtype)
             for k in names}
        return w, m

    zeros = jax.jit(lambda w: {k: jnp.zeros(v.shape, jnp.float32)
                               for k, v in w.items()})
    w = {k: v + 0 for k, v in w0.items()}
    m = zeros(w0)
    out = {"losses": []}
    for step in range(steps):
        g, loss = zeros(w0), jnp.float32(0)
        for r in range(rows):
            g, loss = row_grad(w, tokens[r], g, loss)
        out["losses"].append(float(loss))
        if step == 0:
            out["grad_norms"] = {k: float(v)
                                 for k, v in leaf_norms(g).items()}
        w, m = update(w, m, g)
    delta = jax.jit(lambda a, b: leaf_norms(
        {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32)
         for k in names}))(w, w0)
    out["delta_norms"] = {k: float(v) for k, v in delta.items()}
    return out
