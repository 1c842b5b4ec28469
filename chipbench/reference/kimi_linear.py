"""Plain reference of the Kimi-Linear decoder the `kimi-linear-48b-a3b`
configuration runs: float32 `jax.numpy`, highest matmul precision, one
sequence at a time, the linear-attention recurrence position by position,
attention without a cache (queries in blocks, so that the scores fit),
experts by a loop over the experts held. It imports nothing of the
program.

Every layer: x = x + mixer(rms(x; g1)); x = x + ffn(rms(x; g2)), RMSNorm
with eps `rms_norm_eps`. Final rms, logits = x @ head.T (the head is not
tied to the embedding). No positions are added or rotated anywhere
(`mla_use_nope`). Layers are numbered from 1 in `linear_attn_config`:
`kda_layers` are KDA mixers, `full_attn_layers` are latent attention (MLA).

KDA mixer (H = num_heads, Dk = Dv = head_dim, K = short_conv_kernel_size):

    q, k, v  = W_q x, W_k x, W_v x                     # d -> H x Dk each
    q, k, v  = silu(conv_K(q)), silu(conv_K(k)), silu(conv_K(v))   # depthwise, causal, no bias
    q, k     = q / |q|_2, k / |k|_2  (per head);  q = q * Dk^-1/2
    g_t      = -exp(A_log_h) * softplus(W_f2 (W_f1 x) + dt_bias)   # [H, Dk], log-decay a channel
    beta_t   = sigmoid(W_b x)                           # [H]
    S_t      = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T     # S: [Dk, Dv] a head
    o_t      = S_t^T q_t
    out      = W_o ( rmsnorm_head(o_t; g_o) * sigmoid(W_g2 (W_g1 x)) )

MLA, no rotary (H = num_attention_heads, R = kv_lora_rank, N =
qk_nope_head_dim, E = qk_rope_head_dim, V = v_head_dim; q_lora_rank null):

    q            = W_q x                                # d -> H x (N + E)
    [c, k_r]     = W_kva x                              # d -> R + E
    c            = rmsnorm(c; g_kv)
    [k_n, v]     = W_kvb c                              # R -> H x (N + V)
    k            = [k_n, k_r shared by all heads]       # no rotation on q or k
    out          = W_o softmax(q k^T / sqrt(N + E) + causal) v

Experts (E_all = the published `num_experts`, k = num_experts_per_token,
sigmoid scores, `moe_renormalize`, `routed_scaling_factor` f):

    s      = sigmoid(W_r h)                             # [E_all]
    top    = top_k(s + b_corr)                          # selection only
    w_e    = f * s_e / sum_{e' in top} s_e'             # over ALL k chosen, held here or not
    y      = sum_{e in top, e held here} w_e * W2_e(silu(W1_e h) * W3_e h)  +  shared(h)

The first `first_k_dense_replace` layers have a dense gated-SiLU MLP of
`intermediate_size` instead. The configuration is ONE CHIP'S SHARE of a
deployment in which `chips_per_layer` chips share each layer: `num_experts`
of the file is the experts HELD HERE, the contiguous range from
`expert_offset`; the router keeps all `published.num_experts` outputs and
k a token; what the absent experts would add is left out, here as in the
program, and the partial result goes on to the next layer. `vocab_size` is
this chip's slice of the vocabulary: embedding, head, logits and token ids
are over the slice.

Departures and arrangements (the equations are the published ones):
W_q, W_k, W_v of a KDA mixer and their three convolutions are stored
stacked as one `wqkv` / `conv_w` (q | k | v along the output axis); the
catalog gives neither the gates' inner width (= head_dim), the q scale,
the L2 normalisation (x * rsqrt(sum x^2 + 1e-6)) nor the initialisation:
they are the family's published convention (arXiv:2510.26692), listed under
`assumed` in the configuration file; the config's top-level `head_dim` is
used by neither mixer.

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

KDA_LEAVES = ("wqkv", "conv_w", "f_a", "f_b", "dt_bias", "A_log", "b_proj",
              "g_a", "g_b", "o_norm", "out_proj")
MLA_LEAVES = ("wq", "wkva", "kv_norm", "wkvb", "wo")
DENSE_LEAVES = ("w1", "w3", "w2")
EXPERT_LEAVES = ("gate", "gate_bias", "w1", "w3", "w2", "ws1", "ws3", "ws2")
# queries a block of the reference's attention, and the floor of the width
# a served stream is padded to (a power of two of it, or max_len)
BLOCK = 1024


def layer_kinds(cfg):
    """"kda" or "mla" for each of the layers this configuration keeps
    (the source numbers its layers from 1)."""
    lin = cfg["linear_attn_config"]
    kinds = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        if (i in lin["kda_layers"]) == (i in lin["full_attn_layers"]):
            raise ValueError("layer %d is not exactly one of kda / full" % i)
        kinds.append("kda" if i in lin["kda_layers"] else "mla")
    return tuple(kinds)


def has_experts(cfg, i):
    """Whether layer i (from 0) has routed experts or the dense MLP."""
    return i >= cfg["first_k_dense_replace"]


def layer_leaves(cfg, i):
    return ("ln1", "ln2") \
        + (KDA_LEAVES if layer_kinds(cfg)[i] == "kda" else MLA_LEAVES) \
        + (EXPERT_LEAVES if has_experts(cfg, i) else DENSE_LEAVES)


def routed_experts(cfg):
    """The router's width: the published count, of which `num_experts`
    are held here."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def leaf_specs(cfg):
    """[(name, shape, init)] in a fixed order. init: a float = normal with
    that deviation; "ones"; "a_log" = log of U(1, 16) a head; "dt_bias" =
    the inverse softplus of a step drawn log-uniformly from [1e-3, 1e-1]."""
    d = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    hk, dk, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    n, e, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, fs = cfg["num_experts"], cfg["num_shared_experts"] * fe
    kinds = {
        "kda": {"wqkv": ((d, 3 * hk * dk), d ** -0.5),
                "conv_w": ((taps, 3 * hk * dk), taps ** -0.5),
                "f_a": ((d, dk), d ** -0.5), "f_b": ((dk, hk * dk), dk ** -0.5),
                "dt_bias": ((hk * dk,), "dt_bias"), "A_log": ((hk,), "a_log"),
                "b_proj": ((d, hk), d ** -0.5),
                "g_a": ((d, dk), d ** -0.5), "g_b": ((dk, hk * dk), dk ** -0.5),
                "o_norm": ((dk,), "ones"),
                "out_proj": ((hk * dk, d), (hk * dk) ** -0.5)},
        "mla": {"wq": ((d, h, n + e), d ** -0.5),
                "wkva": ((d, r + e), d ** -0.5), "kv_norm": ((r,), "ones"),
                "wkvb": ((r, h, n + v), r ** -0.5),
                "wo": ((h, v, d), (h * v) ** -0.5)}}
    dense = {"w1": ((d, f), d ** -0.5), "w3": ((d, f), d ** -0.5),
             "w2": ((f, d), f ** -0.5)}
    experts = {"gate": ((d, routed_experts(cfg)), d ** -0.5),
               "gate_bias": ((routed_experts(cfg),), 0.02),
               "w1": ((held, d, fe), d ** -0.5),
               "w3": ((held, d, fe), d ** -0.5),
               "w2": ((held, fe, d), fe ** -0.5),
               "ws1": ((d, fs), d ** -0.5), "ws3": ((d, fs), d ** -0.5),
               "ws2": ((fs, d), fs ** -0.5)}
    out = [("embed", (cfg["vocab_size"], d), 0.02),
           ("head", (cfg["vocab_size"], d), 0.02), ("ln_f", (d,), "ones")]
    for i, kind in enumerate(layer_kinds(cfg)):
        shapes = dict(kinds[kind], ln1=((d,), "ones"), ln2=((d,), "ones"),
                      **(experts if has_experts(cfg, i) else dense))
        for name in layer_leaves(cfg, i):
            out.append(("layers.%d.%s" % (i, name),) + shapes[name])
    return out


# leaves kept in float32 whatever the model is served in: they set a decay
# or only order the experts
FLOAT32_LEAVES = ("A_log", "dt_bias", "gate_bias")


def _draw(key, shape, init):
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
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
        return {name: _draw(jax.random.fold_in(key, i), shape, init).astype(
            jnp.float32 if name.rsplit(".", 1)[-1] in FLOAT32_LEAVES
            else dtype) for i, (name, shape, init) in enumerate(specs)}

    return make(jnp.uint32(int(seed) % (2 ** 32)))


def as_tree(weights, cfg):
    """The flat dict arranged as {"embed", "head", "ln_f", "layers"}."""
    return {"embed": weights["embed"], "head": weights["head"],
            "ln_f": weights["ln_f"],
            "layers": [{name: weights["layers.%d.%s" % (i, name)]
                        for name in layer_leaves(cfg, i)}
                       for i in range(cfg["num_hidden_layers"])]}


def _rms_norm(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def kda_recurrence(state, q, k, v, g, beta):
    """The delta rule position by position. state [H, Dk, Dv]; q, k, g
    [T, H, Dk]; v [T, H, Dv]; beta [T, H] -> (state', o [T, H, Dv])."""
    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[:, :, None] * s
        ks = jnp.sum(k_t[:, :, None] * s, axis=1)               # k^T S
        s = s + b_t[:, None, None] * k_t[:, :, None] * (v_t - ks)[:, None, :]
        return s, jnp.sum(q_t[:, :, None] * s, axis=1)          # S^T q

    return jax.lax.scan(step, state, (q, k, v, g, beta))


def _kda(h, p, q, eps):
    t = h.shape[0]
    taps = p["conv_w"].shape[0]
    hk, dk = p["A_log"].shape[0], p["o_norm"].shape[0]
    qkv = einsum("td,df->tf", h, p["wqkv"], q)
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(p["conv_w"][j] * padded[j:j + t]
                          for j in range(taps)))
    qh, kh, vh = (qkv[:, i * hk * dk:(i + 1) * hk * dk].reshape(t, hk, dk)
                  for i in range(3))
    qh, kh = _l2_norm(qh) * dk ** -0.5, _l2_norm(kh)
    f = einsum("tr,rf->tf", einsum("td,dr->tr", h, p["f_a"], q), p["f_b"], q)
    g = -jnp.exp(p["A_log"])[None, :, None] * jax.nn.softplus(
        (f + p["dt_bias"]).reshape(t, hk, dk))
    beta = jax.nn.sigmoid(einsum("td,dh->th", h, p["b_proj"], q))
    _, o = kda_recurrence(jnp.zeros((hk, dk, dk), jnp.float32),
                          qh, kh, vh, g, beta)
    gate = einsum("tr,rf->tf", einsum("td,dr->tr", h, p["g_a"], q),
                  p["g_b"], q).reshape(t, hk, dk)
    o = _rms_norm(o, p["o_norm"], eps) * jax.nn.sigmoid(gate)
    return einsum("tf,fd->td", o.reshape(t, hk * dk), p["out_proj"], q)


def _mla(h, p, q, eps):
    t = h.shape[0]
    r = p["kv_norm"].shape[0]
    v_dim = p["wo"].shape[1]
    n = p["wkvb"].shape[2] - v_dim
    qh = einsum("td,dhk->thk", h, p["wq"], q)
    ckr = einsum("td,df->tf", h, p["wkva"], q)
    c = _rms_norm(ckr[:, :r], p["kv_norm"], eps)
    kv = einsum("tr,rhk->thk", c, p["wkvb"], q)
    kh = jnp.concatenate([kv[..., :n], jnp.broadcast_to(
        ckr[:, None, r:], (t, kv.shape[1], ckr.shape[1] - r))], axis=-1)
    vh = kv[..., n:]
    size = min(BLOCK, t)
    if t % size:
        raise ValueError("the reference attends in blocks of %d" % size)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qh, start, size, axis=0)
        s = einsum("qhd,khd->hqk", qb, kh, q) / math.sqrt(qh.shape[-1])
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(size))[:, None]
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


def _ffn(h, p, q, routing):
    if "gate" not in p:
        return _gated_mlp(h, p["w1"], p["w3"], p["w2"], q)
    return experts_part(h, p, q, *routing) \
        + _gated_mlp(h, p["ws1"], p["ws3"], p["ws2"], q)


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(x, p, kind, q, eps, routing):
    """One layer on x [T, d] float32; p as served, widened here."""
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    h = _rms_norm(x, p["ln1"], eps)
    x = x + (_kda if kind == "kda" else _mla)(h, p, q, eps)
    return x + _ffn(_rms_norm(x, p["ln2"], eps), p, q, routing)


@partial(jax.jit, static_argnums=(3, 4))
def _head(x, ln_f, head, q, eps):
    x = _rms_norm(x, ln_f.astype(jnp.float32), eps)
    return einsum("td,vd->tv", x, head.astype(jnp.float32), q)


def routing_of(cfg):
    """(k, scale, offset of the first expert held) as static numbers."""
    if cfg["moe_router_activation_func"] != "sigmoid" \
            or not cfg["moe_renormalize"]:
        raise ValueError("the reference routes by renormalised sigmoid scores")
    return (cfg["num_experts_per_token"], cfg["routed_scaling_factor"],
            cfg.get("expert_offset", 0))


def forward_row(weights, tokens, cfg, q=exact):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    eps = cfg["rms_norm_eps"]
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i, kind in enumerate(layer_kinds(cfg)):
        p = {name: weights["layers.%d.%s" % (i, name)]
             for name in layer_leaves(cfg, i)}
        x = _layer(x, p, kind, q, eps, routing_of(cfg))
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
    """The width a stream of n tokens is run at: BLOCK times a power of
    two, or max_len, so that a few compiled shapes serve every stream
    (causal in both kinds of layer, so the padding is inert)."""
    width = BLOCK
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


def served_gaps(cfg, seed, streams, q_control=None):
    """For each served stream (prompt_len, tokens[prompt + generated]):
    by how much each served token's reference logit lies below the
    reference's best at its position, AVERAGED OVER BLOCKS of GAP_BLOCK
    consecutive served tokens. With `q_control`, also the same for the
    token the lower-precision forward puts first there.

    Why blocks, where the other serving references compare token by
    token: with routed experts the widest single gap does not tell a
    precision from the one below it. A bfloat16 stream differs from the
    float32 one by about 1% after a layer, the 8th and 9th of 256 random
    router scores lie 0.05 apart, so one token in nine picks another
    expert than the reference in the first expert layer and most do by
    the last; each such pick moves a whole expert's output, and the
    widest of some thousands of gaps is then set by the logits' own
    spread (on the chip, token by token: 0.86-1.40 sound against
    1.71-1.91 for the float8 control). How OFTEN that happens is what
    the precision sets: the mean gap is 0.03-0.04 sound and 0.27-0.29
    for the control, and a block's mean separates them several times
    over while a fault of a whole request (a state that kept its
    padding, a share renormalised over its own picks) still moves every
    block (PERF.md section 2, PR 36).

    Returns [{"gaps": [...], "control_gaps": [...] | None}] per stream,
    one entry a served token."""
    weights = init_weights(cfg, seed)
    results = []
    for t_p, toks in streams:
        padded = np.zeros((padded_width(len(toks), cfg),), np.int32)
        padded[: len(toks)] = toks
        tokens = jnp.asarray(padded)
        logits = forward_row(weights, tokens, cfg)
        low = tokens if q_control is None else jnp.argmax(
            forward_row(weights, tokens, cfg, q_control), axis=-1)
        out = [np.asarray(o) for o in _gaps(logits, tokens, low)]
        # logits at position i choose token i+1: generated tokens sit at
        # [t_p, len) so their choosing positions are [t_p-1, len-1)
        sl = slice(t_p - 1, len(toks) - 1)
        results.append({"gaps": _block_means(out[0][sl]).tolist(),
                        "control_gaps": _block_means(out[1][sl]).tolist()
                        if q_control is not None else None})
    return results
