"""Plain reference of the decoder the `xing4.0-29b-a4b` configuration runs:
float32 `jax.numpy`, highest matmul precision, one sequence at a time, no
cache, no kernel, no batching. It imports nothing of the program. What a
layer's mixer and feed-forward compute is the Kimi-K2.6 reference's
(`kimi_k2.py`: latent attention with a low-rank query and a YaRN-rotated
shared key part; a dense gated MLP, or sigmoid-routed experts beside a
shared one; its docstring has the equations), at this model's numbers and
with every routed expert held here. What is new is the FRAME those
sub-layers sit in, written below straight from the equations.

The residual stream is n = `hc_mult` streams a token, x [n, C] (C =
hidden_size), mixed at every sub-layer by manifold-constrained
hyper-connections (Xie et al., arXiv:2512.24880, over Zhu et al.,
arXiv:2409.19606). A sub-layer F (rmsnorm + mixer, or rmsnorm + FFN) owns
phi_pre [nC, n], phi_post [nC, n], phi_res [nC, n*n], b_pre [n], b_post
[n], b_res [n, n] and three gains a = (a_pre, a_post, a_res):

    u      = vec(x) / sqrt(mean(vec(x)^2) + rms_norm_eps)     # all n*C features, no weight
    H_pre  = sigmoid(a_pre  * (u @ phi_pre)  + b_pre)         # [n]
    H_post = 2 * sigmoid(a_post * (u @ phi_post) + b_post)    # [n]
    M      = exp(clip(a_res * mat(u @ phi_res) + b_res, clamp_min, clamp_max))
    repeat hc_sinkhorn_iters times:
        M = M / (colsum(M) + hc_eps);  M = M / (rowsum(M) + hc_eps)
    H_res  = M
    h      = H_pre @ x                                        # [C]
    x'     = H_res @ x + outer(H_post, F(h))                  # [n, C]

A layer is two such sub-layers. The embedding enters as n copies of itself;
before the final norm the n streams are summed.

Assumed (the configuration file lists them): columns before rows inside an
iteration; the clamp on the logits before exp; `hc_eps` in both divisions;
u's norm weightless with `rms_norm_eps`; entry by replication, exit by sum;
every H in float32; the seeded gains and biases (HC_GAIN, hc_biases).

Each layer is one jitted call that takes its weights as served (bfloat16)
and widens them inside. Weights are a flat dict name -> array, made from
the seed by `init_weights` in ONE jitted call.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import kimi_k2 as k2
from .common import HIGHEST, einsum, exact
from .kimi_k2 import (DENSE_LEAVES, EXPERT_LEAVES, MLA_LEAVES, has_experts,
                      padded_width, rotation_of, routing_of)

HC_LEAVES = ("phi_pre", "phi_post", "phi_res", "b_pre", "b_post", "b_res", "a")
FRAMES = ("hc1", "hc2")             # around the mixer, around the FFN
# leaves kept in float32 whatever the model is served in
FLOAT32_LEAVES = ("gate_bias",) + tuple(
    "%s_%s" % (f, k) for f in FRAMES for k in ("b_pre", "b_post", "b_res", "a"))
# every frame's three gains with seeded weights, and its biases: stream 0
# is read with weight near 0.9 and written with weight near 1, the others
# with 0.1 and 0.25, and the mix starts 1.5 heavier on the diagonal, so
# that a token's H_res lies away from the identity and from the uniform
# matrix (its largest entry in 0.4-0.9)
HC_GAIN = 0.5


def hc_biases(n):
    first = np.arange(n) == 0
    return {"b_pre": np.where(first, 2.0, -2.0),
            "b_post": np.where(first, 0.0, -2.0), "b_res": 1.5 * np.eye(n)}


def layer_leaves(cfg, i):
    return ("ln1", "ln2") + MLA_LEAVES \
        + (EXPERT_LEAVES if has_experts(cfg, i) else DENSE_LEAVES) \
        + tuple("%s_%s" % (f, k) for f in FRAMES for k in HC_LEAVES)


def leaf_specs(cfg):
    """[(name, shape, init)] in a fixed order: the Kimi-K2 reference's
    leaves, and each layer's two frames behind them. init: a float =
    normal with that deviation; "ones"; an array = those values."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    frame = {"phi_pre": ((n * c, n), (n * c) ** -0.5),
             "phi_post": ((n * c, n), (n * c) ** -0.5),
             "phi_res": ((n * c, n * n), (n * c) ** -0.5),
             "a": ((3,), np.full((3,), HC_GAIN))}
    frame.update({k: (v.shape, v) for k, v in hc_biases(n).items()})
    out = list(k2.leaf_specs(cfg))
    for i in range(cfg["num_hidden_layers"]):
        for f in FRAMES:
            for k in HC_LEAVES:
                out.append(("layers.%d.%s_%s" % (i, f, k),) + frame[k])
    return out


def init_weights(cfg, seed, dtype=jnp.bfloat16):
    """All weights on the device in one jitted call from the seed."""
    specs = leaf_specs(cfg)

    def make_leaf(key, i, name, shape, init):
        if isinstance(init, str):
            x = jnp.ones(shape, jnp.float32)
        elif isinstance(init, float):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * init
        else:
            x = jnp.asarray(init, jnp.float32)
        return x.astype(jnp.float32 if name.rsplit(".", 1)[-1]
                        in FLOAT32_LEAVES else dtype)

    @jax.jit
    def make(seed_u32):
        key = jax.random.key(seed_u32, impl="rbg")
        return {name: make_leaf(key, i, name, shape, init)
                for i, (name, shape, init) in enumerate(specs)}

    return make(jnp.uint32(int(seed) % (2 ** 32)))


def as_tree(weights, cfg):
    """The flat dict arranged as {"embed", "head", "ln_f", "layers"}."""
    return {"embed": weights["embed"], "head": weights["head"],
            "ln_f": weights["ln_f"],
            "layers": [{name: weights["layers.%d.%s" % (i, name)]
                        for name in layer_leaves(cfg, i)}
                       for i in range(cfg["num_hidden_layers"])]}


# --------------------------------------------------------------- frame ---

def hc_of(cfg):
    """(iterations, epsilon, clamp min, clamp max) as static numbers."""
    return (cfg["hc_sinkhorn_iters"], cfg["hc_eps"],
            float(cfg["mhc_h_res_clamp_min"]),
            float(cfg["mhc_h_res_clamp_max"]))


def mixing(x, p, f, q, eps, hc):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) of the frame `f`
    for the stream x [T, n, C]."""
    iters, hc_eps, lo, hi = hc
    t, n, c = x.shape
    flat = x.reshape(t, n * c)
    u = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1,
                                      keepdims=True) + eps)
    a = p[f + "_a"]
    pre = jax.nn.sigmoid(
        a[0] * einsum("tk,kn->tn", u, p[f + "_phi_pre"], q) + p[f + "_b_pre"])
    post = 2.0 * jax.nn.sigmoid(
        a[1] * einsum("tk,kn->tn", u, p[f + "_phi_post"], q)
        + p[f + "_b_post"])
    m = jnp.exp(jnp.clip(
        a[2] * einsum("tk,km->tm", u, p[f + "_phi_res"], q).reshape(t, n, n)
        + p[f + "_b_res"], lo, hi))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + hc_eps)    # columns
        m = m / (jnp.sum(m, axis=2, keepdims=True) + hc_eps)    # rows
    return pre, post, m


def hyper_connect(x, p, f, sub, q, eps, hc):
    """x [T, n, C] through the sub-layer `sub` ([T, C] -> [T, C]) in the
    frame `f`."""
    pre, post, res = mixing(x, p, f, q, eps, hc)
    y = sub(jnp.einsum("tn,tnc->tc", pre, x, precision=HIGHEST))
    return jnp.einsum("tmn,tnc->tmc", res, x, precision=HIGHEST) \
        + post[:, :, None] * y[:, None, :]


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _layer(x, p, q, eps, routing, rotation, hc):
    """One layer on x [T, n, C] float32; p as served, widened here."""
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    x = hyper_connect(x, p, "hc1", lambda h: k2._mla(
        k2._rms_norm(h, p["ln1"], eps), p, q, eps, rotation), q, eps, hc)
    return hyper_connect(x, p, "hc2", lambda h: k2._ffn(
        k2._rms_norm(h, p["ln2"], eps), p, q, routing), q, eps, hc)


def hidden_rows(weights, tokens, cfg, q=exact):
    """tokens [T] int32 -> what the final norm reads, [T, C] float32."""
    eps = cfg["rms_norm_eps"]
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    x = jnp.broadcast_to(x[:, None, :], (x.shape[0], cfg["hc_mult"],
                                         x.shape[1]))
    for i in range(cfg["num_hidden_layers"]):
        p = {name: weights["layers.%d.%s" % (i, name)]
             for name in layer_leaves(cfg, i)}
        x = _layer(x, p, q, eps, routing_of(cfg), rotation_of(cfg),
                   hc_of(cfg))
    return jnp.sum(x, axis=1)


def forward_row(weights, tokens, cfg, q=exact, rows=None):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence; with
    `rows` (int32 [R]) only those positions' logits, [R, vocab]: at
    131,072 entries a row, a whole stream's would be 4.8 GB."""
    x = hidden_rows(weights, tokens, cfg, q)
    if rows is not None:
        x = jnp.take(x, rows, axis=0)
    return k2._head(x, weights["ln_f"], weights["head"], q,
                    cfg["rms_norm_eps"])


# ------------------------------------------------------------ serving ---

@jax.jit
def _gaps(logits, nxt, low):
    """best - served, and best - the logit of `low` (another forward's
    first choice), row by row."""
    best = jnp.max(logits, axis=-1)
    pick = lambda ids: jnp.take_along_axis(logits, ids[:, None], axis=-1)[:, 0]
    return best - pick(nxt), best - pick(low)


def stream_gaps(weights, cfg, t_p, toks, q_control=None):
    """One served stream's gaps token by token: (the served tokens', the
    lower-precision forward's first choices' or None), numpy [n]. The
    stream runs whole, at its padded width (causal, so the padding is
    inert); the head only on the rows that chose a served token, in a
    power of two of them so that few shapes compile."""
    toks = np.asarray(toks, np.int32)
    padded = np.zeros((padded_width(len(toks), cfg),), np.int32)
    padded[: len(toks)] = toks
    # logits at position i choose token i+1: generated tokens sit at
    # [t_p, len) so their choosing positions are [t_p-1, len-1)
    at = np.arange(t_p - 1, len(toks) - 1)
    rows = np.full((1 << max(len(at) - 1, 0).bit_length(),), at[-1],
                   np.int32)
    rows[: len(at)] = at
    tokens, rows_d = jnp.asarray(padded), jnp.asarray(rows)
    nxt = jnp.asarray(toks[rows + 1])
    logits = forward_row(weights, tokens, cfg, rows=rows_d)
    low = nxt if q_control is None else jnp.argmax(
        forward_row(weights, tokens, cfg, q_control, rows=rows_d), axis=-1)
    served, control = (np.asarray(o)[: len(at)]
                       for o in _gaps(logits, nxt, low))
    return served, None if q_control is None else control


def served_gaps(cfg, seed, streams, q_control=None):
    """For each served stream (prompt_len, tokens[prompt + generated]):
    by how much each served token's reference logit lies below the
    reference's best at its position, averaged over blocks of GAP_BLOCK
    consecutive served tokens (routed experts: PERF.md section 2 says
    why token by token has no room). With `q_control`, also the same for
    the token the lower-precision forward puts first there.

    Returns [{"gaps": [...], "control_gaps": [...] | None}] per stream,
    one entry a served token."""
    weights = init_weights(cfg, seed)
    results = []
    for t_p, toks in streams:
        served, control = stream_gaps(weights, cfg, t_p, toks, q_control)
        results.append({"gaps": k2._block_means(served).tolist(),
                        "control_gaps": None if control is None
                        else k2._block_means(control).tolist()})
    return results
