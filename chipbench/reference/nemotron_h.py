"""Plain reference of the decoder the `nemotron3-nano-30b-a3b` configuration
runs (model_type `nemotron_h`; the family's paper is Nemotron-H,
arXiv:2504.03624): float32 `jax.numpy`, highest matmul precision, one
sequence at a time, the state-space recurrence POSITION BY POSITION (no
chunks), attention without a cache (queries in blocks, so that the scores
fit), experts by a loop over the experts held. It imports nothing of the
program and was written from the equations below.

A block holds ONE sub-layer. Block i, of the kind `hybrid_override_pattern[i]`
gives, is x = x + f_i(rms(x; g_i)), RMSNorm with eps `layer_norm_epsilon`;
after the last block the final rms and logits = x @ head.T (the head is not
tied to the embedding). No positional encoding anywhere.

`M`, a Mamba-2 mixer (H = mamba_num_heads heads of P = mamba_head_dim
channels, E = H P; N = ssm_state_size states; G = n_groups groups of H / G
heads; K = conv_kernel taps), for the rows h[t] of one sequence:

    [z | xBC | dt] = W_in h                              # d -> E + (E + 2GN) + H, no bias
    xBC[t]    = silu(b_c + sum_{j<K} w_c[j] * xBC[t - (K-1) + j])    # depthwise, causal, all E + 2GN channels
    [u | B | C] = xBC                   # u [H, P]; B, C [G, N]; head i reads group i // (H / G)
    delta     = softplus(dt + dt_bias)                   # [H]
    S_i[t]    = exp(delta_i[t] a_i) S_i[t-1] + delta_i[t] u_i[t] B_g[t]^T       # [P, N], a_i = -exp(A_log_i)
    y_i[t]    = S_i[t] C_g[t] + D_i u_i[t]
    out       = W_out group_rms(y * silu(z); g_n)        # the gate FIRST, then the norm over each of G groups of E / G channels

`*`, attention: num_attention_heads query heads and num_key_value_heads K/V
heads of head_dim, causal softmax, scores / sqrt(head_dim), no rotary, no
bias.

`E`, routed experts, no mixer (E_all = the published `n_routed_experts`, k =
num_experts_per_tok, `norm_topk_prob`, f = `routed_scaling_factor`,
`n_group` = `topk_group` = 1):

    sc   = sigmoid(W_g h)                                # [E_all], float32
    top  = top_k(sc + b)                                 # b orders only
    w_e  = f * sc_e / sum_{e' in top} sc_e'              # over ALL k chosen, held here or not
    y    = sum_{e in top, e held here} w_e W2_e relu(W1_e h)^2  +  Ws2 relu(Ws1 h)^2

experts `moe_intermediate_size` wide, the one shared expert
`moe_shared_expert_intermediate_size` wide: the non-gated squared ReLU
(`mlp_hidden_act` relu2), no bias.

The configuration is ONE CHIP'S SHARE of a deployment in which
`chips_per_layer` chips share each layer: `n_routed_experts` of the file is
the experts HELD HERE, the contiguous range from `expert_offset`; the
router keeps all `published.n_routed_experts` outputs and k a token; what
the absent experts would add is left out, here as in the program, and the
partial result goes on to the next block. `vocab_size` is this chip's slice
of the vocabulary: embedding, head, logits and token ids are over the
slice. `num_hidden_layers` and `hybrid_override_pattern` are the first
pipeline stage.

Assumed (the configuration file lists them, with reasons): no rotary in
attention; E = mamba_num_heads x mamba_head_dim; float32 for the router,
the recurrence and every norm's statistics; the seeded initialisation.

Each block is one jitted call that takes its weights as served (bfloat16)
and widens them inside (an expert's as the loop reaches it), so a float32
copy of the model never exists. Weights are a flat dict name -> array, made
from the seed by `init_weights` in ONE jitted call, in the dtype they are
served in; the runner arranges the same arrays into the program's tree.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import einsum, exact

# a block's leaves by its letter in the pattern; the one norm of a block is
# "ln1" before a mixer and "ln2" before the experts
LEAVES = {
    "M": ("ln1", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
          "y_norm", "out_proj"),
    "*": ("ln1", "wq", "wk", "wv", "wo"),
    "E": ("ln2", "gate", "gate_bias", "w1", "w2", "ws1", "ws2")}
# leaves kept in float32 whatever the model is served in: it only orders
# the experts
FLOAT32_LEAVES = ("gate_bias",)
# an E block's leaves that are widened expert by expert inside the loop
EXPERT_STACKS = ("w1", "w2")
# queries a block of the reference's attention ([KVH, G, BLOCK, T] float32
# scores: 268 MB at 32 heads and 8,192 positions), and the floor of the
# width a served stream is padded to (a power of two of it, or max_len)
BLOCK = 256
WIDTH = 1024


def layer_plan(cfg):
    """The kind of every block held, a letter each: M, E or *."""
    plan = cfg["hybrid_override_pattern"]
    if len(plan) != cfg["num_hidden_layers"] or set(plan) - set(LEAVES):
        raise ValueError("hybrid_override_pattern %r: %d letters of M, E, *"
                         % (plan, cfg["num_hidden_layers"]))
    return tuple(plan)


def layer_leaves(cfg, i):
    return LEAVES[layer_plan(cfg)[i]]


def routed_experts(cfg):
    """The router's width: the published count, of which
    `n_routed_experts` are held here."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def mamba_sizes(cfg):
    """(H, P, N, G, K) of a Mamba-2 mixer."""
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"])


def leaf_specs(cfg):
    """[(name, shape, init)] in a fixed order. init: a float = normal with
    that deviation; "ones"; "a_log" = log U(1, 16) a head; ("dt_bias", lo,
    hi, floor) = the inverse softplus of a step drawn log-uniformly from
    [lo, hi] and floored."""
    d = cfg["hidden_size"]
    h, kvh, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    mh, mp, n, g, k = mamba_sizes(cfg)
    e, conv = mh * mp, mh * mp + 2 * g * n
    fe = cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]
    held, every = cfg["n_routed_experts"], routed_experts(cfg)
    shapes = {
        "M": {"ln1": ((d,), "ones"),
              "in_proj": ((d, e + conv + mh), d ** -0.5),
              "conv_w": ((k, conv), k ** -0.5), "conv_b": ((conv,), 0.02),
              "dt_bias": ((mh,), ("dt_bias", cfg["time_step_min"],
                                  cfg["time_step_max"],
                                  cfg["time_step_floor"])),
              "A_log": ((mh,), "a_log"), "D": ((mh,), "ones"),
              "y_norm": ((e,), "ones"), "out_proj": ((e, d), e ** -0.5)},
        "*": {"ln1": ((d,), "ones"),
              "wq": ((d, h, hd), d ** -0.5), "wk": ((d, kvh, hd), d ** -0.5),
              "wv": ((d, kvh, hd), d ** -0.5),
              "wo": ((h, hd, d), (h * hd) ** -0.5)},
        "E": {"ln2": ((d,), "ones"),
              "gate": ((d, every), d ** -0.5), "gate_bias": ((every,), 0.02),
              "w1": ((held, d, fe), d ** -0.5),
              "w2": ((held, fe, d), fe ** -0.5),
              "ws1": ((d, fs), d ** -0.5), "ws2": ((fs, d), fs ** -0.5)}}
    out = [("embed", (cfg["vocab_size"], d), 0.02),
           ("head", (cfg["vocab_size"], d), 0.02), ("ln_f", (d,), "ones")]
    for i, kind in enumerate(layer_plan(cfg)):
        for name in LEAVES[kind]:
            out.append(("layers.%d.%s" % (i, name),) + shapes[kind][name])
    return out


def _draw(key, shape, init):
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if isinstance(init, tuple):
        _, lo, hi, floor = init
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(lo), math.log(hi))), floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return jax.random.normal(key, shape, jnp.float32) * init


def init_weights(cfg, seed, dtype=jnp.bfloat16):
    """All weights on the device in one jitted call from the seed."""
    specs = leaf_specs(cfg)

    @jax.jit
    def make(seed_u32):
        key = jax.random.key(seed_u32, impl="rbg")
        return {name: _draw(jax.random.fold_in(key, i), shape, init).astype(
                    jnp.float32 if name.rsplit(".", 1)[-1] in FLOAT32_LEAVES
                    else dtype)
                for i, (name, shape, init) in enumerate(specs)}

    return make(jnp.uint32(int(seed) % (2 ** 32)))


def as_tree(weights, cfg):
    """The flat dict arranged as {"embed", "head", "ln_f", "layers"}."""
    return {"embed": weights["embed"], "head": weights["head"],
            "ln_f": weights["ln_f"],
            "layers": [{name: weights["layers.%d.%s" % (i, name)]
                        for name in layer_leaves(cfg, i)}
                       for i in range(cfg["num_hidden_layers"])]}


# -------------------------------------------------------------- blocks ---

def _rms_norm(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def mamba2(h, p, q, eps, sizes, state=None):
    """The mixer on h [T, d] from `state` [H, P, N] (None = zeros), one
    position at a time; returns (out [T, d], the state after the last
    position)."""
    t = h.shape[0]
    mh, mp, n, g, taps = sizes
    e, r = mh * mp, mh // g
    proj = einsum("td,df->tf", h, p["in_proj"], q)
    z, xbc, dt = proj[:, :e], proj[:, e:-mh], proj[:, -mh:]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(p["conv_w"][j] * padded[j:j + t]
                                        for j in range(taps)))
    u = xbc[:, :e].reshape(t, mh, mp)
    b = xbc[:, e:e + g * n].reshape(t, g, n)
    c = xbc[:, e + g * n:].reshape(t, g, n)
    delta = jax.nn.softplus(dt + p["dt_bias"])                  # [T, H]
    a = -jnp.exp(p["A_log"])                                    # [H]

    def step(s, xs):
        d_t, u_t, b_t, c_t = xs
        b_t, c_t = jnp.repeat(b_t, r, axis=0), jnp.repeat(c_t, r, axis=0)
        s = jnp.exp(d_t * a)[:, None, None] * s \
            + (d_t[:, None] * u_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    s, y = jax.lax.scan(
        step, jnp.zeros((mh, mp, n), jnp.float32) if state is None else state,
        (delta, u, b, c))
    y = (y + p["D"][:, None] * u).reshape(t, e) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(t, g, e // g), 1.0, eps).reshape(t, e) \
        * p["y_norm"]
    return einsum("te,ed->td", y, p["out_proj"], q), s


def attention(h, p, q):
    """Causal grouped-query attention of h [T, d] over all T rows, no
    positional encoding, the queries in blocks of BLOCK."""
    t = h.shape[0]
    qh = einsum("td,dhk->thk", h, p["wq"], q)
    kh = einsum("td,dhk->thk", h, p["wk"], q)
    vh = einsum("td,dhk->thk", h, p["wv"], q)
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
        seen = at[None, :] <= (start + jnp.arange(size))[:, None]
        s = jnp.where(seen[None, None], s, -1e30)
        o = einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), vh, q)
        return o.reshape(size, heads, hd)

    o = jax.lax.map(block, jnp.arange(0, t, size)).reshape(t, heads, hd)
    return einsum("thk,hkd->td", o, p["wo"], q)


def _relu2_mlp(h, w1, w2, q):
    return einsum("tf,fd->td",
                  jnp.square(jax.nn.relu(einsum("td,df->tf", h, w1, q))),
                  w2, q)


def route(h, p, q, k, scale):
    """[T, E_all] float32: each token's weight on each routed expert, 0
    on those it did not choose."""
    s = jax.nn.sigmoid(einsum("td,de->te", h, p["gate"], q))
    _, top = jax.lax.top_k(s + p["gate_bias"], k)
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None],
                                  top].set(1.0) * s
    return scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def experts_part(h, p, q, k, scale, offset):
    """What the experts held here (the router's outputs `offset` onward)
    add for h [T, d], by a loop over them; without the shared expert."""
    held = p["w1"].shape[0]
    w = jax.lax.dynamic_slice_in_dim(route(h, p, q, k, scale), offset, held,
                                     axis=1)

    def one(y, xs):
        w1, w2, w_e = xs
        return y + w_e[:, None] * _relu2_mlp(
            h, w1.astype(jnp.float32), w2.astype(jnp.float32), q), None

    return jax.lax.scan(one, jnp.zeros_like(h), (p["w1"], p["w2"], w.T))[0]


def shared_part(h, p, q):
    return _relu2_mlp(h, p["ws1"], p["ws2"], q)


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def block(x, p, kind, q, eps, routing, sizes):
    """One block on x [T, d] float32; p as served, widened here (an
    expert's stacks inside the loop over them)."""
    p = {name: v if name in EXPERT_STACKS else v.astype(jnp.float32)
         for name, v in p.items()}
    if kind == "M":
        return x + mamba2(_rms_norm(x, p["ln1"], eps), p, q, eps, sizes)[0]
    if kind == "*":
        return x + attention(_rms_norm(x, p["ln1"], eps), p, q)
    h = _rms_norm(x, p["ln2"], eps)
    return x + experts_part(h, p, q, *routing) + shared_part(h, p, q)


@partial(jax.jit, static_argnums=(3, 4))
def _head(x, ln_f, head, q, eps):
    x = _rms_norm(x, ln_f.astype(jnp.float32), eps)
    return einsum("td,vd->tv", x, head.astype(jnp.float32), q)


def routing_of(cfg):
    """(k, scale, offset of the first expert held) as static numbers."""
    if not cfg["norm_topk_prob"] or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["mlp_hidden_act"] != "relu2":
        raise ValueError("the reference routes by renormalised sigmoid "
                         "scores over one group, to relu2 experts")
    return (cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg.get("expert_offset", 0))


def hidden_rows(weights, tokens, cfg, q=exact):
    """tokens [T] int32 -> what the final norm reads, [T, d] float32."""
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i, kind in enumerate(layer_plan(cfg)):
        p = {name: weights["layers.%d.%s" % (i, name)]
             for name in LEAVES[kind]}
        x = block(x, p, kind, q, cfg["layer_norm_epsilon"], routing_of(cfg),
                  mamba_sizes(cfg))
    return x


def forward_row(weights, tokens, cfg, q=exact, rows=None):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence; with
    `rows` (int32 [R]) only those positions' logits, [R, vocab]: at
    65,536 entries a row, a whole stream's would be 2 GB."""
    x = hidden_rows(weights, tokens, cfg, q)
    if rows is not None:
        x = jnp.take(x, rows, axis=0)
    return _head(x, weights["ln_f"], weights["head"], q,
                 cfg["layer_norm_epsilon"])


# ------------------------------------------------------------ serving ---

@jax.jit
def _gaps(logits, nxt, low):
    """best - served, and best - the logit of `low` (another forward's
    first choice), row by row."""
    best = jnp.max(logits, axis=-1)
    pick = lambda ids: jnp.take_along_axis(logits, ids[:, None], axis=-1)[:, 0]
    return best - pick(nxt), best - pick(low)


def padded_width(n, cfg):
    """The width a stream of n tokens is run at: WIDTH times a power of
    two, or max_len, so that a few compiled shapes serve every stream
    (causal in every kind of block, so the padding is inert)."""
    width = WIDTH
    while width < n:
        width *= 2
    return max(min(width, cfg["max_len"]), n)


# served tokens a gap is averaged over: see served_gaps
GAP_BLOCK = 64


def _block_means(gaps):
    """gaps [n] -> [n]: every gap replaced by the mean of its block of
    about GAP_BLOCK consecutive gaps (a stream's short tail joins the
    blocks before it), so the list keeps one entry a served token and
    its mean."""
    blocks = np.array_split(gaps, max(1, len(gaps) // GAP_BLOCK))
    return np.concatenate([np.full(len(b), b.mean()) for b in blocks])


def stream_gaps(weights, cfg, t_p, toks, q_control=None):
    """One served stream's gaps token by token: (the served tokens', the
    lower-precision forward's first choices' or None), numpy [n]. The
    stream runs whole, at its padded width; the head only on the rows
    that chose a served token, in a power of two of them so that few
    shapes compile."""
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
    consecutive served tokens (half of the routed experts are held, so a
    pick of 6 in 128 that bfloat16 orders otherwise than float32 moves a
    whole expert and token by token has little room: PERF.md section 2,
    this cell's row). With `q_control`, also the same for the token the
    lower-precision forward puts first there.

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
