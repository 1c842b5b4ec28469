"""The KDA mixer: gated delta-rule linear attention with a matrix-valued
state, in a sequence form (training forward and prefill: chunks of CHUNK
positions, the state carried between them) and a step form (decode: one
position, carrying the state).

For a sequence x[t] (D wide; H heads of Dk = Dv = head_dim; K conv taps;
the gates' inner width is head_dim too):

    q, k, v  = W_q x, W_k x, W_v x                     # D -> H x Dk each
    q, k, v  = silu(conv_K(q)), silu(conv_K(k)), silu(conv_K(v))   # depthwise, causal, no bias
    q, k     = q / |q|_2, k / |k|_2  (per head);  q = q * Dk^-1/2
    g[t]     = -exp(A_log_h) * softplus(W_f2 (W_f1 x) + dt_bias)   # [H, Dk], log-decay a channel
    beta[t]  = sigmoid(W_b x)                           # [H]
    S[t]     = (I - beta[t] k[t] k[t]^T) diag(exp(g[t])) S[t-1] + beta[t] k[t] v[t]^T
    o[t]     = S[t]^T q[t]
    out      = W_o ( rmsnorm_head(o[t]; g_o) * sigmoid(W_g2 (W_g1 x)) )

W_q, W_k, W_v and their three convolutions are one stacked `wqkv` /
`conv_w` (q | k | v along the output axis). The projections are matmuls
at the parameters' dtype; the convolution, g, beta, the recurrence and
the state S are float32. What a layer carries between calls is {"conv":
the last K-1 pre-convolution inputs [B, K-1, 3*H*Dk], "kda": S
[B, H, Dk, Dv] float32}: a MATRIX a head, batch first like a K/V row.

The sequence form is the delta rule's chunked (WY / UT-transform) form.
Inside a chunk, with G[t] the running sum of g and S0 the state it
starts from, u[t] = beta[t] (v[t] - S[t-1]^T (exp(g[t]) k[t])) solves

    (I + diag(beta) L) U = diag(beta) (V - (exp(G) K) S0),
    L[t, i] = sum_c k[t, c] k[i, c] exp(G[t, c] - G[i, c])   for i < t

and then O = (exp(G) Q) S0 + M U with M[t, i] the same sum with q[t] for
i <= t, and S' = exp(G[C]) S0 + (exp(G[C] - G) K)^T U. Every exponent is
a difference of G between a later and an earlier position, never
positive, so nothing overflows however fast a channel decays (1 / exp(G)
would). L and M are built in sub-blocks of SUB positions
(_decayed_pairs): pair by pair inside one, one matmul across two.

Like a state-space layer's state, S cannot be healed after the fact.
`mixer_seq(valid_len=n)` returns the state after position n-1 exactly:
beta and g are zeroed on the rows from n on (exp(0) = 1 and the rank-one
terms vanish, so S does not move) and the conv window is cut at the real
end. A sequence is padded to whole chunks the same way.

Plain jax.numpy / lax, no kernel. The parts carry `jax.named_scope`s
(mx.kda.conv, mx.kda.chunk, mx.kda.step) so that a device trace's
operations can be told apart.
"""

import jax
import jax.numpy as jnp

__all__ = ["init_state", "mixer_seq", "mixer_step"]

# positions a chunk of the sequence form covers: the work inside a chunk
# is matmuls of this size and one triangular solve, the chunks are a
# lax.scan over the state
CHUNK = 64
# positions a sub-block of a chunk covers (_decayed_pairs)
SUB = 16
_HIGHEST = jax.lax.Precision.HIGHEST


def _sizes(p):
    """(H, Dk, K) read off the parameters' shapes."""
    return p["A_log"].shape[0], p["o_norm"].shape[0], p["conv_w"].shape[0]


def init_state(heads, head_dim, d_conv, batch, dtype):
    """A layer's zeroed state for `batch` lanes."""
    return {"conv": jnp.zeros((batch, d_conv - 1, 3 * heads * head_dim),
                              dtype),
            "kda": jnp.zeros((batch, heads, head_dim, head_dim),
                             jnp.float32)}


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def _two(x, a, b):
    """The two-matrix projections of the gates: (x a) b, float32 out."""
    return jnp.einsum("...r,rf->...f", jnp.einsum("...d,dr->...r", x, a), b,
                      preferred_element_type=jnp.float32)


def _heads(qkv, x, p):
    """qkv [..., 3*H*Dk] float32 after the convolution, x [..., D] ->
    q, k, v [..., H, Dk], g [..., H, Dk], beta [..., H], all float32."""
    h, dk, _ = _sizes(p)
    q, k, v = (qkv[..., i * h * dk:(i + 1) * h * dk].reshape(
        qkv.shape[:-1] + (h, dk)) for i in range(3))
    q, k = _l2_norm(q) * dk ** -0.5, _l2_norm(k)
    f = _two(x, p["f_a"], p["f_b"]) + p["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] \
        * jax.nn.softplus(f.reshape(f.shape[:-1] + (h, dk)))
    beta = jax.nn.sigmoid(jnp.einsum("...d,dh->...h", x, p["b_proj"],
                                     preferred_element_type=jnp.float32))
    return q, k, v, g, beta


def _out(o, x, p, eps):
    """o [..., H, Dv] float32 -> the mixer's output [..., D]."""
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(var + eps) * p["o_norm"].astype(jnp.float32)
    gate = _two(x, p["g_a"], p["g_b"]).reshape(o.shape)
    o = (o * jax.nn.sigmoid(gate)).reshape(o.shape[:-2] + (-1,))
    return jnp.einsum("...f,fd->...d", o.astype(p["out_proj"].dtype),
                      p["out_proj"])


def _advance(s, q, k, v, g, beta):
    """One position of the recurrence: s [B, H, Dk, Dv], q/k/g [B, H, Dk],
    v [B, H, Dv], beta [B, H] -> (s', o [B, H, Dv])."""
    s = jnp.exp(g)[..., None] * s
    ks = jnp.sum(k[..., None] * s, axis=-2)                     # k^T S
    s = s + (beta[..., None] * k)[..., None] * (v - ks)[..., None, :]
    return s, jnp.sum(q[..., None] * s, axis=-2)                # S^T q


def _decayed_pairs(q, k, cum):
    """lower[t, i] = sum_c k[t, c] k[i, c] exp(G[t, c] - G[i, c]) and reach,
    the same with q[t], for i <= t (0 beyond), [B, H, C, C] each, from q,
    k and G = cum [B, H, C, Dk].

    In sub-blocks of SUB positions. A pair inside one sub-block takes its
    own exponent (an [R, R, Dk] tensor a sub-block). A pair across two
    goes through r, the value of G at the end of the sub-block before the
    row's: exp(G[t] - r) k[t] against exp(r - G[i]) k[i], one matmul a
    sub-block of rows, and both exponents are <= 0 because i lies before
    the row's sub-block and t in it."""
    c = q.shape[2]
    r = min(SUB, c)
    a = c // r
    mm = lambda spec, x, y: jnp.einsum(spec, x, y, precision=_HIGHEST)
    blocks = lambda x: x.reshape(x.shape[:2] + (a, r) + x.shape[3:])
    qb, kb, cb = blocks(q), blocks(k), blocks(cum)
    seen = jnp.tril(jnp.ones((r, r), bool))[:, :, None]
    pair = jnp.where(seen, jnp.exp(jnp.where(
        seen, cb[:, :, :, :, None] - cb[:, :, :, None, :], 0.0)), 0.0) \
        * kb[:, :, :, None]                                # [B, H, A, R, R, Dk]
    start = jnp.pad(cb[:, :, :-1, -1:],                    # [B, H, A, 1, Dk]
                    ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))
    before = (jnp.arange(c) < r * jnp.arange(a)[:, None])[:, :, None]
    cols = jnp.where(before, jnp.exp(jnp.where(
        before, start - cum[:, :, None], 0.0)), 0.0) * k[:, :, None]
    rows = jnp.exp(cb - start)                             # [B, H, A, R, Dk]
    own = jnp.eye(a, dtype=bool)[:, None, :, None]         # [A, 1, A, 1]

    def whole(x):
        inside = jnp.sum(x[:, :, :, :, None] * pair, axis=-1)
        across = mm("bhatc,bhaic->bhati", rows * x, cols)
        inside = jnp.where(own, inside[:, :, :, :, None], 0.0)
        return (across + inside.reshape(across.shape)).reshape(
            across.shape[:2] + (c, c))

    return whole(kb), whole(qb)


def _chunk(s, xs):
    """CHUNK positions from state s [B, H, Dk, Dv]: q, k, g [B, H, C, Dk],
    v [B, H, C, Dv], beta [B, H, C] -> (s', o [B, H, C, Dv])."""
    q, k, v, g, beta = xs
    c = q.shape[2]
    cum = jnp.cumsum(g, axis=2)                                 # G
    lower, reach = _decayed_pairs(q, k, cum)
    decay = jnp.exp(cum)
    mm = lambda spec, a, b: jnp.einsum(spec, a, b, precision=_HIGHEST)
    rhs = beta[..., None] * (v - mm("bhck,bhkv->bhcv", decay * k, s))
    system = jnp.eye(c) + beta[..., None] * jnp.tril(lower, -1)
    u = jax.lax.linalg.triangular_solve(system, rhs, left_side=True,
                                        lower=True, unit_diagonal=True)
    o = mm("bhck,bhkv->bhcv", decay * q, s) + mm("bhti,bhiv->bhtv", reach, u)
    last = cum[:, :, -1:, :]
    s = jnp.exp(last[:, :, 0])[..., None] * s \
        + mm("bhck,bhcv->bhkv", jnp.exp(last - cum) * k, u)
    return s, o


def _scan(s, q, k, v, g, beta, valid_len=None):
    """The recurrence over [B, T, H, ...] from state s, chunk by chunk.
    Rows whose beta and g are 0 leave s as it is, which is how T is
    padded to whole chunks; with `valid_len` the chunks wholly behind it,
    which hold such rows only, are not run (their outputs stay 0)."""
    bsz, t = q.shape[:2]
    size = min(CHUNK, t + -t % SUB)
    pad = -t % size

    def chunks(x):      # [B, T, H, ...] -> [T/size, B, H, size, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((bsz, -1, size) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    xs = tuple(chunks(x) for x in (q, k, v, g, beta))
    if valid_len is None:
        s, o = jax.lax.scan(_chunk, s, xs)
    else:
        def step(i, carry):
            s, o = _chunk(carry[0], tuple(x[i] for x in xs))
            return s, jax.lax.dynamic_update_index_in_dim(carry[1], o, i, 0)
        s, o = jax.lax.fori_loop(
            0, jnp.minimum(xs[0].shape[0], -(-valid_len // size)), step,
            (s, jnp.zeros_like(xs[2])))
    # [T/size, B, H, size, Dv] -> [B, T, H, Dv]
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4)
    return s, o.reshape((bsz, -1) + o.shape[3:])[:, :t]


def mixer_seq(x, p, state, valid_len=None, eps=1e-6):
    """x [B, T, D] continuing from `state` -> (out [B, T, D], state').

    state' is the state after position valid_len - 1 (dynamic scalar;
    None = T): the rows from valid_len on are padding, their outputs are
    garbage the caller does not read, and they leave no trace."""
    t = x.shape[1]
    taps = _sizes(p)[2]
    qkv_in = jnp.einsum("btd,df->btf", x, p["wqkv"])
    with jax.named_scope("mx.kda.conv"):
        # [B, K-1 + T, F]: row j holds the input of position j - (K-1)
        window = jnp.concatenate(
            [state["conv"].astype(qkv_in.dtype), qkv_in], axis=1)
        w = p["conv_w"].astype(jnp.float32)
        qkv = jax.nn.silu(sum(w[j] * window[:, j:j + t].astype(jnp.float32)
                              for j in range(taps)))
        end = t if valid_len is None else valid_len
        conv = jax.lax.dynamic_slice_in_dim(window, end, taps - 1, axis=1)
    q, k, v, g, beta = _heads(qkv, x, p)
    if valid_len is not None:
        real = jnp.arange(t) < valid_len
        g = jnp.where(real[None, :, None, None], g, 0.0)
        beta = jnp.where(real[None, :, None], beta, 0.0)
    with jax.named_scope("mx.kda.chunk"):
        s, o = _scan(state["kda"], q, k, v, g, beta, valid_len)
    return _out(o, x, p, eps), {"conv": conv.astype(state["conv"].dtype),
                                "kda": s}


def mixer_step(x, p, state, eps=1e-6):
    """x [B, D], one position a lane -> (out [B, D], state')."""
    taps = _sizes(p)[2]
    qkv_in = jnp.einsum("bd,df->bf", x, p["wqkv"])
    with jax.named_scope("mx.kda.step"):
        window = jnp.concatenate(
            [state["conv"].astype(qkv_in.dtype), qkv_in[:, None]], axis=1)
        w = p["conv_w"].astype(jnp.float32)
        qkv = jax.nn.silu(sum(w[j] * window[:, j].astype(jnp.float32)
                              for j in range(taps)))
        s, o = _advance(state["kda"], *_heads(qkv, x, p))
    return _out(o, x, p, eps), {
        "conv": window[:, 1:].astype(state["conv"].dtype), "kda": s}
