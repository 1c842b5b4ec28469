"""Plain reference of the decoder the `smallthinker-21b-a3b` configuration
runs: float32 `jax.numpy`, highest matmul precision, one sequence at a time,
attention without a cache and without a ring (every query against all the
rows, masked; queries in blocks so that the scores fit), experts by a loop
over all of them. It imports nothing of the program and was written from
the equations below.

Every layer is the same block (d = hidden_size, H = num_attention_heads,
KVH = num_key_value_heads, D = head_dim, and H x D need not be d; E =
moe_num_primary_experts, k = moe_num_active_primary_experts, F =
moe_ffn_hidden_size). For layer l with input x [T, d]:

    r   = x W_r                                  # [T, E]: the router reads the LAYER'S INPUT,
                                                 # before the norm and before attention
    h   = rmsnorm(x; g1)                         # eps rms_norm_eps
    q   = h W_q [T, H, D];  k = h W_k [T, KVH, D];  v = h W_v [T, KVH, D]      # no bias
    if rope_layout[l]:  q, k = rot_t(q), rot_t(k)                              # theta rope_theta, all D features
    s_ij = q_i . k_j / sqrt(D)   for j <= i   and, if sliding_window_layout[l],  i - j < sliding_window_size
           (query head n reads K/V head n // (H / KVH))
    a   = x + softmax_j(s) v W_o
    u   = rmsnorm(a; g2)
    top = the k largest of r's E;   w = softmax(r[top])   # moe_primary_router_apply_softmax: sums to 1
    y   = sum_{e in top} w_e (relu(u W_gate^e) * (u W_up^e)) W_down^e          # ReGLU, no shared expert
    out = a + y

rot_t turns pair i of the D features, (x_i, x_{i + D/2}), by the angle
t * theta^(-2i / D). Final rmsnorm, logits = x @ head.T (the head is not
tied to the embedding).

Assumed (the configuration file lists them): the router's input un-normed;
a query sees itself and the sliding_window_size - 1 positions before it;
the pairing in halves; seeded weights. `described_as`'s "secondary experts"
have no key in the config and are not built.

Each layer is one jitted call that takes its weights as served (bfloat16)
and widens them inside, so a float32 copy of the model never exists.
Weights are a flat dict name -> array, made from the seed by `init_weights`
in ONE jitted call, in the dtype they are served in; `as_tree` arranges the
same arrays into the program's tree.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import einsum, exact
from .kimi_k2 import _block_means, _head, _rms_norm, padded_width

ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")
EXPERT_LEAVES = ("gate", "w1", "w3", "w2")
LAYER_LEAVES = ("ln1", "ln2") + ATTENTION_LEAVES + EXPERT_LEAVES
# queries a block of the reference's attention: [H, BLOCK, T] float32
# scores are 0.47 GB at 28 heads and 16,384 positions
BLOCK = 256


def leaf_specs(cfg):
    """[(name, shape, init)] in a fixed order. init: a float = normal with
    that deviation; "ones"."""
    d, h, kvh = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, f, e = cfg["head_dim"], cfg["moe_ffn_hidden_size"], \
        cfg["moe_num_primary_experts"]
    layer = {"ln1": ((d,), "ones"), "ln2": ((d,), "ones"),
             "wq": ((d, h, hd), d ** -0.5), "wk": ((d, kvh, hd), d ** -0.5),
             "wv": ((d, kvh, hd), d ** -0.5),
             "wo": ((h, hd, d), (h * hd) ** -0.5),
             "gate": ((d, e), d ** -0.5),
             "w1": ((e, d, f), d ** -0.5), "w3": ((e, d, f), d ** -0.5),
             "w2": ((e, f, d), f ** -0.5)}
    out = [("embed", (cfg["vocab_size"], d), 0.02),
           ("head", (cfg["vocab_size"], d), 0.02), ("ln_f", (d,), "ones")]
    for i in range(cfg["num_hidden_layers"]):
        for name in LAYER_LEAVES:
            out.append(("layers.%d.%s" % (i, name),) + layer[name])
    return out


def init_weights(cfg, seed, dtype=jnp.bfloat16):
    """All weights on the device in one jitted call from the seed."""
    specs = leaf_specs(cfg)

    @jax.jit
    def make(seed_u32):
        key = jax.random.key(seed_u32, impl="rbg")
        return {name: (jnp.ones(shape, jnp.float32) if init == "ones"
                       else jax.random.normal(jax.random.fold_in(key, i),
                                              shape, jnp.float32) * init
                       ).astype(dtype)
                for i, (name, shape, init) in enumerate(specs)}

    return make(jnp.uint32(int(seed) % (2 ** 32)))


def as_tree(weights, cfg):
    """The flat dict arranged as {"embed", "head", "ln_f", "layers"}."""
    return {"embed": weights["embed"], "head": weights["head"],
            "ln_f": weights["ln_f"],
            "layers": [{name: weights["layers.%d.%s" % (i, name)]
                        for name in LAYER_LEAVES}
                       for i in range(cfg["num_hidden_layers"])]}


def layer_plan(cfg):
    """[(rotates, window or None)] of the layers held, from the two
    published layouts' first `num_hidden_layers` entries."""
    if cfg.get("rope_scaling") is not None:
        raise ValueError("the reference rotates by the base alone")
    n = cfg["num_hidden_layers"]
    return [(bool(r), cfg["sliding_window_size"] if w else None)
            for r, w in zip(cfg["rope_layout"][:n],
                            cfg["sliding_window_layout"][:n])]


# -------------------------------------------------------------- layers ---

def rotate(x, theta):
    """x [T, heads, D] float32, row t at position t: pair i = (x_i,
    x_{i + D/2}) turned by t * theta^(-2i / D)."""
    half = x.shape[-1] // 2
    freqs = jnp.asarray([theta ** (-i / half) for i in range(half)],
                        jnp.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(h, p, q, rotates, window, theta):
    """Causal attention of h [T, d] over all T rows, masked to the last
    `window` positions where the layer has one."""
    t = h.shape[0]
    qh = einsum("td,dhk->thk", h, p["wq"], q)
    kh = einsum("td,dhk->thk", h, p["wk"], q)
    vh = einsum("td,dhk->thk", h, p["wv"], q)
    if rotates:
        qh, kh = rotate(qh, theta), rotate(kh, theta)
    heads, hd = qh.shape[1:]
    group = heads // kh.shape[1]
    at = jnp.arange(t)
    size = min(BLOCK, t)
    if t % size:
        raise ValueError("the reference attends in blocks of %d" % size)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qh, start, size, axis=0)
        qb = qb.reshape(size, heads // group, group, hd)
        s = einsum("qkgd,tkd->kgqt", qb, kh, q) / math.sqrt(hd)
        mine = (start + jnp.arange(size))[:, None]
        seen = at[None, :] <= mine
        if window is not None:
            seen &= mine - at[None, :] < window
        s = jnp.where(seen[None, None], s, -1e30)
        o = einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), vh, q)
        return o.reshape(size, heads, hd)

    o = jax.lax.map(block, jnp.arange(0, t, size)).reshape(t, heads, hd)
    return einsum("thk,hkd->td", o, p["wo"], q)


def route(x, p, q, k):
    """[T, E] float32: each token's weight on each expert, 0 on those it
    did not choose: the softmax over its k largest router logits."""
    r = einsum("td,de->te", x, p["gate"], q)
    top_r, top = jax.lax.top_k(r, k)
    w = jax.nn.softmax(top_r, axis=-1)
    return jnp.zeros_like(r).at[jnp.arange(r.shape[0])[:, None], top].set(w)


def experts(u, weights, p, q):
    """sum_e weights[:, e] * (relu(u W_gate^e) * (u W_up^e)) W_down^e for
    u [T, d], by a loop over all the experts."""
    def one(y, xs):
        w1, w3, w2, w_e = xs
        hidden = jax.nn.relu(einsum("td,df->tf", u, w1, q)) \
            * einsum("td,df->tf", u, w3, q)
        return y + w_e[:, None] * einsum("tf,fd->td", hidden, w2, q), None

    return jax.lax.scan(one, jnp.zeros_like(u),
                        (p["w1"], p["w3"], p["w2"], weights.T))[0]


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def layer(x, p, q, eps, k, rotates, window, theta):
    """One layer on x [T, d] float32; p as served, widened here."""
    p = {name: v.astype(jnp.float32) for name, v in p.items()}
    weights = route(x, p, q, k)                 # from the layer's input
    a = x + attention(_rms_norm(x, p["ln1"], eps), p, q, rotates, window,
                      theta)
    return a + experts(_rms_norm(a, p["ln2"], eps), weights, p, q)


def routing_of(cfg):
    """k, checked against what the reference routes by."""
    if not cfg["moe_primary_router_apply_softmax"] \
            or not cfg["norm_topk_prob"]:
        raise ValueError("the reference routes by the softmax over the "
                         "chosen logits, which sums to 1")
    return cfg["moe_num_active_primary_experts"]


def hidden_rows(weights, tokens, cfg, q=exact):
    """tokens [T] int32 -> what the final norm reads, [T, d] float32."""
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i, (rotates, window) in enumerate(layer_plan(cfg)):
        p = {name: weights["layers.%d.%s" % (i, name)]
             for name in LAYER_LEAVES}
        x = layer(x, p, q, cfg["rms_norm_eps"], routing_of(cfg), rotates,
                  window, float(cfg["rope_theta"]))
    return x


def forward_row(weights, tokens, cfg, q=exact, rows=None):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence; with
    `rows` (int32 [R]) only those positions' logits, [R, vocab]: at
    151,936 entries a row, a whole stream's would be 10 GB."""
    x = hidden_rows(weights, tokens, cfg, q)
    if rows is not None:
        x = jnp.take(x, rows, axis=0)
    return _head(x, weights["ln_f"], weights["head"], q, cfg["rms_norm_eps"])


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
    reference's best at its position, averaged over blocks of 64
    consecutive served tokens (every routed expert is held, so a flipped
    pick of 6 in 64 moves a whole expert and token by token has no room:
    PERF.md section 2). With `q_control`, also the same for the token
    the lower-precision forward puts first there.

    Returns [{"gaps": [...], "control_gaps": [...] | None}] per stream,
    one entry a served token."""
    weights = init_weights(cfg, seed)
    results = []
    for t_p, toks in streams:
        served, control = stream_gaps(weights, cfg, t_p, toks, q_control)
        results.append({"gaps": _block_means(served).tolist(),
                        "control_gaps": None if control is None
                        else _block_means(control).tolist()})
    return results
