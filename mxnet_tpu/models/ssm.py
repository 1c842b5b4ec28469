"""The Mamba-1 mixer: a selective state-space layer in a sequence form
(training forward and prefill: a scan over positions) and a step form
(decode: one position, carrying the state).

For a sequence x[t] (D wide; E = expand * D channels, N states a
channel, K conv taps, R the rank of the step-size projection):

    [u, z]     = W_in x                                   # D -> 2E
    u[t]       = silu(b_c + sum_j w_c[j] * u[t-(K-1)+j])  # depthwise, causal
    [dt, B, C] = W_x u                                    # E -> R + 2N
    dt, B, C   = rms(dt; g_dt), rms(B; g_B), rms(C; g_C)  # Jamba's inner norms
    delta      = softplus(W_dt dt + b_dt)                 # R -> E
    h[t]       = exp(delta[t] * A) h[t-1] + (delta[t] u[t]) B[t]   # [N, E]
    y[t]       = h[t] . C[t] + D u[t]
    out        = W_out (y * silu(z))                      # E -> D

with A = -exp(A_log). The projections are matmuls at the parameters'
dtype; the convolution, delta, A, the recurrence and the state h are
float32. What a layer carries between calls is {"conv": the last K-1
pre-convolution inputs [B, K-1, E], "ssm": h [B, N, E] float32}: batch
first like a K/V row, E last so that a lane's state lies dense on the
chip's (8, 128) tiles.

Unlike a K/V row, the state cannot be healed after the fact: a padded
position folded into h stays there. `mixer_seq(valid_len=n)` therefore
returns the state after position n-1 exactly: delta is zeroed on the rows
from n on (exp(0) = 1 and the input term vanishes, so h does not move)
and the conv window is cut at the real end.

Plain jax.numpy / lax, no kernel. The three parts carry
`jax.named_scope`s (mx.ssm.conv, mx.ssm.scan, mx.ssm.step) so that a
device trace's operations can be told apart.
"""

import jax
import jax.numpy as jnp

__all__ = ["init_state", "mixer_seq", "mixer_step"]

# positions the sequence form advances in one iteration of its outer
# lax.scan: the inner steps are unrolled, so XLA sees straight-line code
# and the float32 intermediates are bounded by the chunk, not the prompt
# ([T, N, E] float32 at T = 2048 would be 671 MB a layer at E = 5120)
SCAN_CHUNK = 8


def _sizes(p):
    """(E, N, K, R) read off the parameters' shapes."""
    n, e = p["A_log"].shape
    return e, n, p["conv_w"].shape[0], p["dt_proj"].shape[0]


def init_state(d_inner, d_state, d_conv, batch, dtype):
    """A layer's zeroed recurrent state for `batch` lanes."""
    return {"conv": jnp.zeros((batch, d_conv - 1, d_inner), dtype),
            "ssm": jnp.zeros((batch, d_state, d_inner), jnp.float32)}


def _rms(x, g):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * g.astype(jnp.float32)


def _selective(u, p):
    """u [..., E] float32 (after the convolution) -> delta [..., E],
    B [..., N], C [..., N], all float32."""
    e, n, _, r = _sizes(p)
    dbc = jnp.einsum("...e,ef->...f", u.astype(p["x_proj"].dtype),
                     p["x_proj"], preferred_element_type=jnp.float32)
    dt = _rms(dbc[..., :r], p["dt_norm"])
    b = _rms(dbc[..., r:r + n], p["b_norm"])
    c = _rms(dbc[..., r + n:], p["c_norm"])
    delta = jnp.einsum("...r,re->...e", dt.astype(p["dt_proj"].dtype),
                       p["dt_proj"], preferred_element_type=jnp.float32)
    delta = jax.nn.softplus(delta + p["dt_bias"].astype(jnp.float32))
    return delta, b, c


def _advance(h, delta, u, b, c, a):
    """One position of the recurrence: h [B, N, E], delta/u [B, E],
    b/c [B, N], a [N, E] -> (h', y [B, E])."""
    h = jnp.exp(delta[:, None, :] * a) * h \
        + (delta * u)[:, None, :] * b[:, :, None]
    return h, jnp.sum(h * c[:, :, None], axis=1)


def _scan(h, delta, u, b, c, a):
    """The recurrence over [B, T, ...] from state h: a lax.scan over
    chunks of SCAN_CHUNK positions, unrolled inside. Rows whose delta is
    0 leave h as it is, which is how T is padded to whole chunks."""
    bsz, t, _ = u.shape
    size = min(SCAN_CHUNK, t)
    pad = -t % size

    def chunks(x):          # [B, T, F] -> [T/size, size, B, F]
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        return x.reshape(bsz, -1, size, x.shape[-1]).transpose(1, 2, 0, 3)

    def body(h, xs):
        ys = []
        for i in range(size):
            h, y = _advance(h, *(x[i] for x in xs), a)
            ys.append(y)
        return h, jnp.stack(ys)

    h, ys = jax.lax.scan(body, h, tuple(chunks(x)
                                        for x in (delta, u, b, c)))
    # [T/size, size, B, E] -> [B, T, E]
    ys = ys.reshape(-1, bsz, ys.shape[-1]).transpose(1, 0, 2)
    return h, ys[:, :t]


def _out(y, u, z, p):
    y = (y + p["D"].astype(jnp.float32) * u) \
        * jax.nn.silu(z.astype(jnp.float32))
    return jnp.einsum("...e,ed->...d", y.astype(p["out_proj"].dtype),
                      p["out_proj"])


def mixer_seq(x, p, state, valid_len=None):
    """x [B, T, D] continuing from `state` -> (out [B, T, D], state').

    state' is the state after position valid_len - 1 (dynamic scalar;
    None = T): the rows from valid_len on are padding, their outputs
    are garbage the caller does not read, and they leave no trace."""
    t = x.shape[1]
    e, _, k, _ = _sizes(p)
    uz = jnp.einsum("btd,df->btf", x, p["in_proj"])
    u_in, z = uz[..., :e], uz[..., e:]
    with jax.named_scope("mx.ssm.conv"):
        # [B, K-1 + T, E]: row j holds the input of position j - (K-1)
        window = jnp.concatenate(
            [state["conv"].astype(u_in.dtype), u_in], axis=1)
        w = p["conv_w"].astype(jnp.float32)
        u = p["conv_b"].astype(jnp.float32) + sum(
            w[j] * window[:, j:j + t].astype(jnp.float32)
            for j in range(k))
        u = jax.nn.silu(u)
        end = t if valid_len is None else valid_len
        conv = jax.lax.dynamic_slice_in_dim(window, end, k - 1, axis=1)
    delta, b, c = _selective(u, p)
    if valid_len is not None:
        delta = jnp.where((jnp.arange(t) < valid_len)[None, :, None],
                          delta, 0.0)
    with jax.named_scope("mx.ssm.scan"):
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        h, y = _scan(state["ssm"], delta, u, b, c, a)
    return _out(y, u, z, p), {"conv": conv.astype(state["conv"].dtype),
                              "ssm": h}


def mixer_step(x, p, state):
    """x [B, D], one position a lane -> (out [B, D], state')."""
    e, _, k, _ = _sizes(p)
    uz = jnp.einsum("bd,df->bf", x, p["in_proj"])
    u_in, z = uz[..., :e], uz[..., e:]
    with jax.named_scope("mx.ssm.step"):
        window = jnp.concatenate(
            [state["conv"].astype(u_in.dtype), u_in[:, None]], axis=1)
        w = p["conv_w"].astype(jnp.float32)
        u = p["conv_b"].astype(jnp.float32) + sum(
            w[j] * window[:, j].astype(jnp.float32) for j in range(k))
        u = jax.nn.silu(u)
        delta, b, c = _selective(u, p)
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        h, y = _advance(state["ssm"], delta, u, b, c, a)
    return _out(y, u, z, p), {
        "conv": window[:, 1:].astype(state["conv"].dtype), "ssm": h}
