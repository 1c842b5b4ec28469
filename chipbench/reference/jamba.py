"""Plain reference of the Jamba hybrid decoder the `jamba2-3b`
configuration runs: float32 `jax.numpy`, highest matmul precision, one
sequence at a time, a sequential scan over positions, no cache, no
chunking. It imports nothing of the program.

Every layer: x = x + mixer(rms(x; g1)); x = x + W_down(silu(W_gate h) *
W_up h) with h = rms(x; g2). Final rms, logits = x @ embed.T. No positions
are added anywhere. Layer i is attention when i % attn_layer_period ==
attn_layer_offset, else a Mamba-1 mixer.

Attention: causal softmax, scores / sqrt(head size), the query heads
sharing num_key_value_heads K/V heads, no rotary, no bias.

Mamba, for a sequence x[t] (E = mamba_expand * hidden, N states, K taps,
R = mamba_dt_rank):

    [u, z]     = W_in x
    u[t]       = silu(b_c + sum_{j<K} w_c[j] * u[t - (K-1) + j])
    [dt, B, C] = W_x u;  dt, B, C = rms(dt; g_dt), rms(B; g_B), rms(C; g_C)
    delta      = softplus(W_dt dt + b_dt)
    h[t]       = exp(delta[t] (x) A) * h[t-1] + (delta[t] * u[t]) (x) B[t]
    y[t]       = h[t] . C[t] + D * u[t]
    out        = W_out (y * silu(z))                  A = -exp(A_log)

Each layer is one jitted call that takes its weights as served (bfloat16)
and widens them inside, so the float32 copy of the whole model (12 GB)
never exists: called layer by layer, the reference fits beside nothing.

Weights are a flat dict name -> array, made from the seed by
`init_weights` in ONE jitted call, in the dtype they are served in; the
runner arranges the same arrays into the program's tree.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import einsum, exact

ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")
MAMBA_LEAVES = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_norm", "b_norm",
                "c_norm", "dt_proj", "dt_bias", "A_log", "D", "out_proj")
FFN_LEAVES = ("ln1", "ln2", "w1", "w3", "w2")


def layer_kinds(cfg):
    """The family's rule for the order of the layer types."""
    return tuple(
        "attention" if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
        else "mamba" for i in range(cfg["num_hidden_layers"]))


def layer_leaves(kind):
    return FFN_LEAVES + (ATTENTION_LEAVES if kind == "attention"
                         else MAMBA_LEAVES)


def leaf_specs(cfg):
    """[(name, shape, init)] in a fixed order. init: a float = normal
    with that deviation; "ones"; "a_log" = log(1..N) on every channel;
    "dt_bias" = the inverse softplus of a step size drawn log-uniformly
    from [1e-3, 1e-1]."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    e, n = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    k, r = cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    ffn = {"ln1": ((d,), "ones"), "ln2": ((d,), "ones"),
           "w1": ((d, f), d ** -0.5), "w3": ((d, f), d ** -0.5),
           "w2": ((f, d), f ** -0.5)}
    kinds = {
        "attention": dict(ffn, **{
            "wq": ((d, h, hd), d ** -0.5), "wk": ((d, kvh, hd), d ** -0.5),
            "wv": ((d, kvh, hd), d ** -0.5), "wo": ((h, hd, d), d ** -0.5)}),
        "mamba": dict(ffn, **{
            "in_proj": ((d, 2 * e), d ** -0.5),
            "conv_w": ((k, e), k ** -0.5), "conv_b": ((e,), 0.02),
            "x_proj": ((e, r + 2 * n), e ** -0.5),
            "dt_norm": ((r,), "ones"), "b_norm": ((n,), "ones"),
            "c_norm": ((n,), "ones"),
            "dt_proj": ((r, e), r ** -0.5), "dt_bias": ((e,), "dt_bias"),
            "A_log": ((n, e), "a_log"), "D": ((e,), "ones"),
            "out_proj": ((e, d), e ** -0.5)})}
    out = [("embed", (cfg["vocab_size"], d), 0.02), ("ln_f", (d,), "ones")]
    for i, kind in enumerate(layer_kinds(cfg)):
        for name in layer_leaves(kind):
            out.append(("layers.%d.%s" % (i, name),) + kinds[kind][name])
    return out


def _draw(key, shape, init):
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "a_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1.0, shape[0] + 1.0))[:, None], shape)
    if init == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return jax.random.normal(key, shape, jnp.float32) * init


def init_weights(cfg, seed, dtype=jnp.bfloat16):
    """All weights on the device in one jitted call from the seed."""
    specs = leaf_specs(cfg)

    @jax.jit
    def make(seed_u32):
        key = jax.random.key(seed_u32, impl="rbg")
        return {name: _draw(jax.random.fold_in(key, i), shape,
                            init).astype(dtype)
                for i, (name, shape, init) in enumerate(specs)}

    return make(jnp.uint32(int(seed) % (2 ** 32)))


def as_tree(weights, cfg):
    """The flat dict arranged as {"embed", "ln_f", "layers": [..]}."""
    return {"embed": weights["embed"], "ln_f": weights["ln_f"],
            "layers": [{name: weights["layers.%d.%s" % (i, name)]
                        for name in layer_leaves(kind)}
                       for i, kind in enumerate(layer_kinds(cfg))]}


def _rms_norm(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def _attention(h, p, q):
    t = h.shape[0]
    qh = einsum("td,dhk->thk", h, p["wq"], q)
    kh = einsum("td,dhk->thk", h, p["wk"], q)
    vh = einsum("td,dhk->thk", h, p["wv"], q)
    group = qh.shape[1] // kh.shape[1]
    kh, vh = jnp.repeat(kh, group, axis=1), jnp.repeat(vh, group, axis=1)
    s = einsum("qhd,khd->hqk", qh, kh, q) / math.sqrt(qh.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -1e30)
    o = einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vh, q)
    return einsum("thk,hkd->td", o, p["wo"], q)


def _mamba(h, p, q, eps):
    t = h.shape[0]
    n, e = p["A_log"].shape
    taps, r = p["conv_w"].shape[0], p["dt_proj"].shape[0]
    uz = einsum("td,df->tf", h, p["in_proj"], q)
    u, z = uz[:, :e], uz[:, e:]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    u = jax.nn.silu(p["conv_b"] + sum(p["conv_w"][j] * padded[j:j + t]
                                      for j in range(taps)))
    dbc = einsum("te,ef->tf", u, p["x_proj"], q)
    dt = _rms_norm(dbc[:, :r], p["dt_norm"], eps)
    b = _rms_norm(dbc[:, r:r + n], p["b_norm"], eps)
    c = _rms_norm(dbc[:, r + n:], p["c_norm"], eps)
    delta = jax.nn.softplus(
        einsum("tr,re->te", dt, p["dt_proj"], q) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])                                   # [N, E]

    def step(state, xs):
        d_t, u_t, b_t, c_t = xs
        state = jnp.exp(d_t[None, :] * a) * state \
            + (d_t * u_t)[None, :] * b_t[:, None]
        return state, jnp.sum(state * c_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((n, e), jnp.float32),
                        (delta, u, b, c))
    y = (y + p["D"] * u) * jax.nn.silu(z)
    return einsum("te,ed->td", y, p["out_proj"], q)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, p, kind, q, eps):
    """One layer on x [T, D] float32; p as served, widened here."""
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    h = _rms_norm(x, p["ln1"], eps)
    x = x + (_attention(h, p, q) if kind == "attention"
             else _mamba(h, p, q, eps))
    h = _rms_norm(x, p["ln2"], eps)
    gated = jax.nn.silu(einsum("td,df->tf", h, p["w1"], q)) \
        * einsum("td,df->tf", h, p["w3"], q)
    return x + einsum("tf,fd->td", gated, p["w2"], q)


@partial(jax.jit, static_argnums=(3, 4))
def _head(x, ln_f, embed, q, eps):
    x = _rms_norm(x, ln_f.astype(jnp.float32), eps)
    return einsum("td,vd->tv", x, embed.astype(jnp.float32), q)


def forward_row(weights, tokens, cfg, q=exact):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    eps = cfg["rms_norm_eps"]
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i, kind in enumerate(layer_kinds(cfg)):
        p = {name: weights["layers.%d.%s" % (i, name)]
             for name in layer_leaves(kind)}
        x = _layer(x, p, kind, q, eps)
    return _head(x, weights["ln_f"], weights["embed"], q, eps)


# ------------------------------------------------------------ serving ---

@jax.jit
def _gaps(logits, tokens, low):
    """best - served, and best - the logit of `low` (another forward's
    first choice) at every position."""
    best = jnp.max(logits, axis=-1)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    pick = lambda ids: jnp.take_along_axis(logits, ids[:, None], axis=-1)[:, 0]
    return best - pick(nxt), best - pick(low)


def served_gaps(cfg, seed, streams, q_control=None):
    """For each served stream (prompt_len, tokens[prompt + generated]):
    by how much each served token's reference logit lies below the
    reference's best at its position. With `q_control`, also the same gap
    for the token the lower-precision forward puts first there.

    Returns [{"gaps": [...], "control_gaps": [...] | None}] per stream.
    One compiled shape: every stream is padded to max_len (causal in both
    kinds of layer, so the padding is inert)."""
    weights = init_weights(cfg, seed)
    width = cfg["max_len"]
    results = []
    for t_p, toks in streams:
        padded = np.zeros((width,), np.int32)
        padded[: len(toks)] = toks
        tokens = jnp.asarray(padded)
        logits = forward_row(weights, tokens, cfg)
        low = tokens if q_control is None else jnp.argmax(
            forward_row(weights, tokens, cfg, q_control), axis=-1)
        out = [np.asarray(o) for o in _gaps(logits, tokens, low)]
        # logits at position i choose token i+1: generated tokens sit at
        # [t_p, len) so their choosing positions are [t_p-1, len-1)
        sl = slice(t_p - 1, len(toks) - 1)
        results.append({"gaps": out[0][sl].tolist(),
                        "control_gaps": out[1][sl].tolist()
                        if q_control is not None else None})
    return results
