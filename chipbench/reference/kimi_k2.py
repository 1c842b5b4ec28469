"""Plain reference of the decoder the `kimi-k2.6` configuration runs: float32
`jax.numpy`, highest matmul precision, one sequence at a time, attention
without a cache through up-projected heads (queries in blocks, so that the
scores fit), experts by a loop over the experts held. It imports nothing of
the program and was written from the equations below.

Every layer: x = x + mixer(rms(x; g1)); x = x + ffn(rms(x; g2)), RMSNorm
with eps `rms_norm_eps`. Final rms, logits = x @ head.T (the head is not
tied to the embedding). Every mixer is latent attention (H =
num_attention_heads, Q = q_lora_rank, R = kv_lora_rank, N =
qk_nope_head_dim, E = qk_rope_head_dim, V = v_head_dim), for the row h of
the token at position t:

    c_q          = rmsnorm(W_qa h; g_q)                 # d -> Q
    [q_n, q_r]   = W_qb c_q                             # Q -> H x (N + E)
    [c, k_r]     = W_kva h                              # d -> R + E
    c            = rmsnorm(c; g_kv)
    [k_n, v]     = W_kvb c                              # R -> H x (N + V)
    q_r, k_r     = rot_t(q_r), rot_t(k_r)               # k_r: one a position, shared by all heads
    k            = [k_n, k_r]
    out          = W_o softmax(s q k^T + causal) v,     s = mscale^2 / sqrt(N + E)

rot_t turns pair i of the E features, (x_i, x_{i + E/2}), by the angle
t * f_i (i = 0 .. E/2 - 1): (x_i cos - x_{i+E/2} sin, x_{i+E/2} cos + x_i
sin). The frequencies are YaRN's (`rope_scaling`: factor F, original
positions L, beta_fast, beta_slow; theta = `rope_theta`):

    theta_i = theta^(-2i / E)
    r(n)    = E ln(L / (2 pi n)) / (2 ln theta)         # the pair that turns n times in L positions
    lo, hi  = max(floor(r(beta_fast)), 0), min(ceil(r(beta_slow)), E - 1)
    m_i     = 1 - clip((i - lo) / (hi - lo), 0, 1)
    f_i     = (1 - m_i) theta_i / F + m_i theta_i
    mscale(F, a) = 0.1 a ln F + 1;  cos and sin times mscale(F, mscale) / mscale(F, mscale_all_dim);
    s uses mscale(F, mscale_all_dim)

Feed-forward. The first `first_k_dense_replace` layers: W_2 (silu(W_1 h) *
W_3 h) of `intermediate_size`. The others (E_all = the published
`n_routed_experts`, k = num_experts_per_tok, `norm_topk_prob`,
`routed_scaling_factor` f, `n_group` = `topk_group` = 1):

    sc     = sigmoid(W_g h)                             # [E_all], float32
    top    = top_k(sc + b)                              # b orders only
    w_e    = f * sc_e / sum_{e' in top} sc_e'           # over ALL k chosen, held here or not
    y      = sum_{e in top, e held here} w_e * W2_e(silu(W1_e h) * W3_e h)  +  shared(h)

The configuration is ONE CHIP'S SHARE of a deployment in which
`chips_per_layer` chips share each layer: `n_routed_experts` of the file is
the experts HELD HERE, the contiguous range from `expert_offset`; the
router keeps all `published.n_routed_experts` outputs and k a token; what
the absent experts would add is left out, here as in the program, and the
partial result goes on to the next layer. `vocab_size` is this chip's slice
of the vocabulary: embedding, head, logits and token ids are over the
slice. `num_hidden_layers` is the first pipeline stage.

Assumed (the configuration file lists them): which two features make a
pair (halves, above; the family's checkpoints interleave them, a fixed
permutation of W_qb's and W_kva's columns that changes no score); the YaRN
blend above (the convention these keys name); seeded weights.

Each layer is one jitted call that takes its weights as served (bfloat16)
and widens them inside, so a float32 copy of the model never exists.
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

MLA_LEAVES = ("wq_a", "q_norm", "wq_b", "wkva", "kv_norm", "wkvb", "wo")
DENSE_LEAVES = ("w1", "w3", "w2")
EXPERT_LEAVES = ("gate", "gate_bias", "w1", "w3", "w2", "ws1", "ws3", "ws2")
# leaves kept in float32 whatever the model is served in: it only orders
# the experts
FLOAT32_LEAVES = ("gate_bias",)
# queries a block of the reference's attention ([H, BLOCK, T] float32
# scores: 1.3 GB at 64 heads and 19,456 positions), and the floor of the
# width a served stream is padded to (a power of two of it, or max_len)
BLOCK = 256
WIDTH = 1024


def has_experts(cfg, i):
    """Whether layer i (from 0) has routed experts or the dense MLP."""
    return i >= cfg["first_k_dense_replace"]


def layer_leaves(cfg, i):
    return ("ln1", "ln2") + MLA_LEAVES \
        + (EXPERT_LEAVES if has_experts(cfg, i) else DENSE_LEAVES)


def routed_experts(cfg):
    """The router's width: the published count, of which
    `n_routed_experts` are held here."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def leaf_specs(cfg):
    """[(name, shape, init)] in a fixed order. init: a float = normal with
    that deviation; "ones"."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, e, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, fs = cfg["n_routed_experts"], cfg["n_shared_experts"] * fe
    every = routed_experts(cfg)
    mla = {"wq_a": ((d, rq), d ** -0.5), "q_norm": ((rq,), "ones"),
           "wq_b": ((rq, h, n + e), rq ** -0.5),
           "wkva": ((d, r + e), d ** -0.5), "kv_norm": ((r,), "ones"),
           "wkvb": ((r, h, n + v), r ** -0.5),
           "wo": ((h, v, d), (h * v) ** -0.5)}
    dense = {"w1": ((d, f), d ** -0.5), "w3": ((d, f), d ** -0.5),
             "w2": ((f, d), f ** -0.5)}
    experts = {"gate": ((d, every), d ** -0.5),
               "gate_bias": ((every,), 0.02),
               "w1": ((held, d, fe), d ** -0.5),
               "w3": ((held, d, fe), d ** -0.5),
               "w2": ((held, fe, d), fe ** -0.5),
               "ws1": ((d, fs), d ** -0.5), "ws3": ((d, fs), d ** -0.5),
               "ws2": ((fs, d), fs ** -0.5)}
    out = [("embed", (cfg["vocab_size"], d), 0.02),
           ("head", (cfg["vocab_size"], d), 0.02), ("ln_f", (d,), "ones")]
    for i in range(cfg["num_hidden_layers"]):
        shapes = dict(mla, ln1=((d,), "ones"), ln2=((d,), "ones"),
                      **(experts if has_experts(cfg, i) else dense))
        for name in layer_leaves(cfg, i):
            out.append(("layers.%d.%s" % (i, name),) + shapes[name])
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
                       ).astype(jnp.float32 if name.rsplit(".", 1)[-1]
                                in FLOAT32_LEAVES else dtype)
                for i, (name, shape, init) in enumerate(specs)}

    return make(jnp.uint32(int(seed) % (2 ** 32)))


def as_tree(weights, cfg):
    """The flat dict arranged as {"embed", "head", "ln_f", "layers"}."""
    return {"embed": weights["embed"], "head": weights["head"],
            "ln_f": weights["ln_f"],
            "layers": [{name: weights["layers.%d.%s" % (i, name)]
                        for name in layer_leaves(cfg, i)}
                       for i in range(cfg["num_hidden_layers"])]}


# ------------------------------------------------------------ rotation ---

def _mscale(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotation_of(cfg):
    """(frequencies, one a pair; the factor on cos and sin; the factor on
    the scores) as static numbers, from `rope_theta` and `rope_scaling`."""
    e, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    pairs = e // 2
    freqs = [theta ** (-2.0 * i / e) for i in range(pairs)]
    ys = cfg.get("rope_scaling")
    if ys is None:
        return tuple(freqs), 1.0, 1.0
    if ys["type"] != "yarn":
        raise ValueError("the reference scales rotation by yarn only")
    span = ys["original_max_position_embeddings"]

    def pair_that_turns(n):
        return e * math.log(span / (2 * math.pi * n)) / (2 * math.log(theta))

    lo = max(math.floor(pair_that_turns(ys["beta_fast"])), 0)
    hi = min(math.ceil(pair_that_turns(ys["beta_slow"])), e - 1)
    if hi == lo:
        hi += 0.001
    out = []
    for i, f in enumerate(freqs):
        m = 1.0 - min(max((i - lo) / (hi - lo), 0.0), 1.0)
        out.append((1.0 - m) * f / ys["factor"] + m * f)
    return (tuple(out),
            _mscale(ys["factor"], ys.get("mscale", 1))
            / _mscale(ys["factor"], ys.get("mscale_all_dim", 0)),
            _mscale(ys["factor"], ys.get("mscale_all_dim", 0)) ** 2)


def rotate(x, positions, freqs, gain=1.0):
    """x [T, ..., E] float32 with positions [T]: pair i = (x_i,
    x_{i + E/2}) turned by positions * freqs[i]."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# -------------------------------------------------------------- layers ---

def _rms_norm(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def _mla(h, p, q, eps, rotation):
    freqs, gain, score_gain = rotation
    t = h.shape[0]
    r = p["kv_norm"].shape[0]
    v_dim = p["wo"].shape[1]
    n = p["wkvb"].shape[2] - v_dim
    at = jnp.arange(t)
    c_q = _rms_norm(einsum("td,dr->tr", h, p["wq_a"], q), p["q_norm"], eps)
    qh = einsum("tr,rhk->thk", c_q, p["wq_b"], q)
    qh = jnp.concatenate([qh[..., :n], rotate(qh[..., n:], at, freqs, gain)],
                         axis=-1)
    ckr = einsum("td,df->tf", h, p["wkva"], q)
    c = _rms_norm(ckr[:, :r], p["kv_norm"], eps)
    k_r = rotate(ckr[:, r:], at, freqs, gain)
    kv = einsum("tr,rhk->thk", c, p["wkvb"], q)
    kh = jnp.concatenate([kv[..., :n], jnp.broadcast_to(
        k_r[:, None, :], (t, kv.shape[1], k_r.shape[-1]))], axis=-1)
    vh = kv[..., n:]
    size = min(BLOCK, t)
    if t % size:
        raise ValueError("the reference attends in blocks of %d" % size)
    scale = score_gain / math.sqrt(qh.shape[-1])

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qh, start, size, axis=0)
        s = einsum("qhd,khd->hqk", qb, kh, q) * scale
        seen = at[None, :] <= (start + jnp.arange(size))[:, None]
        s = jnp.where(seen[None], s, -1e30)
        return einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vh, q)

    o = jax.lax.map(block, jnp.arange(0, t, size)).reshape(t, -1, v_dim)
    return einsum("thk,hkd->td", o, p["wo"], q)


def _gated_mlp(h, w1, w3, w2, q):
    return einsum("tf,fd->td", jax.nn.silu(einsum("td,df->tf", h, w1, q))
                  * einsum("td,df->tf", h, w3, q), w2, q)


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
        w1, w3, w2, w_e = xs
        return y + w_e[:, None] * _gated_mlp(h, w1, w3, w2, q), None

    return jax.lax.scan(one, jnp.zeros_like(h),
                        (p["w1"], p["w3"], p["w2"], w.T))[0]


def shared_part(h, p, q):
    return _gated_mlp(h, p["ws1"], p["ws3"], p["ws2"], q)


def _ffn(h, p, q, routing):
    if "gate" not in p:
        return _gated_mlp(h, p["w1"], p["w3"], p["w2"], q)
    return experts_part(h, p, q, *routing) + shared_part(h, p, q)


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(x, p, q, eps, routing, rotation):
    """One layer on x [T, d] float32; p as served, widened here."""
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    x = x + _mla(_rms_norm(x, p["ln1"], eps), p, q, eps, rotation)
    return x + _ffn(_rms_norm(x, p["ln2"], eps), p, q, routing)


@partial(jax.jit, static_argnums=(3, 4))
def _head(x, ln_f, head, q, eps):
    x = _rms_norm(x, ln_f.astype(jnp.float32), eps)
    return einsum("td,vd->tv", x, head.astype(jnp.float32), q)


def routing_of(cfg):
    """(k, scale, offset of the first expert held) as static numbers."""
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"] \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("the reference routes by renormalised sigmoid "
                         "scores over one group")
    return (cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg.get("expert_offset", 0))


def forward_row(weights, tokens, cfg, q=exact):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    eps = cfg["rms_norm_eps"]
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        p = {name: weights["layers.%d.%s" % (i, name)]
             for name in layer_leaves(cfg, i)}
        x = _layer(x, p, q, eps, routing_of(cfg), rotation_of(cfg))
    return _head(x, weights["ln_f"], weights["head"], q, eps)


# ------------------------------------------------------------ serving ---

@jax.jit
def _gaps(logits, tokens, low):
    """best - served, and best - the logit of `low` (another forward's
    first choice) at every position."""
    best = jnp.max(logits, axis=-1)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    pick = lambda ids: jnp.take_along_axis(logits, ids[:, None], axis=-1)[:, 0]
    return best - pick(nxt), best - pick(low)


def padded_width(n, cfg):
    """The width a stream of n tokens is run at: WIDTH times a power of
    two, or max_len, so that a few compiled shapes serve every stream
    (causal, so the padding is inert)."""
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
    """One served stream's gaps token by token: (the served tokens',
    the lower-precision forward's first choices' or None), numpy [n]."""
    padded = np.zeros((padded_width(len(toks), cfg),), np.int32)
    padded[: len(toks)] = toks
    tokens = jnp.asarray(padded)
    logits = forward_row(weights, tokens, cfg)
    low = tokens if q_control is None else jnp.argmax(
        forward_row(weights, tokens, cfg, q_control), axis=-1)
    served, control = (np.asarray(o) for o in _gaps(logits, tokens, low))
    # logits at position i choose token i+1: generated tokens sit at
    # [t_p, len) so their choosing positions are [t_p-1, len-1)
    sl = slice(t_p - 1, len(toks) - 1)
    return served[sl], None if q_control is None else control[sl]


def served_gaps(cfg, seed, streams, q_control=None):
    """For each served stream (prompt_len, tokens[prompt + generated]):
    by how much each served token's reference logit lies below the
    reference's best at its position, averaged over blocks of GAP_BLOCK
    consecutive served tokens (why blocks: PERF.md section 2, this
    cell's row). With `q_control`, also the same for the token the
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
