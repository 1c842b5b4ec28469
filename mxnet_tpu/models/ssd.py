"""The Mamba-2 mixer: the scalar-decay state-space layer (one decay a
HEAD, where Mamba-1 in ssm.py has one a channel and a state), in a
sequence form (training forward and prefill: the chunked, "dual" form,
matmuls inside a chunk and one state passed between chunks) and a step
form (decode: one position, carrying the state).

For a sequence x[t] (D wide; H heads of P channels, E = H * P; N states;
G groups of H / G heads sharing one B and one C; K conv taps):

    [z | xBC | dt] = W_in x                              # D -> E + (E + 2GN) + H
    xBC[t]    = silu(b_c + sum_j w_c[j] * xBC[t-(K-1)+j])   # depthwise, causal
    [u | B | C] = xBC                    # u [H, P]; B, C [G, N]; head h reads group h // (H / G)
    delta     = softplus(dt + dt_bias)                   # [H]
    S_h[t]    = exp(delta_h[t] a_h) S_h[t-1] + delta_h[t] u_h[t] B_g[t]^T     # [P, N]
    y_h[t]    = S_h[t] C_g[t] + D_h u_h[t]
    out       = W_out group_rms(y * silu(z); g_n)        # the gate FIRST; G groups of E / G

with a = -exp(A_log), a scalar a head. The projections are matmuls at
the parameters' dtype; the convolution, delta, the decays, the
recurrence, the state S and the norm's statistics are float32. What a
layer carries between calls is {"conv": the last K-1 pre-convolution
rows of xBC [B, K-1, E + 2GN], "ssm": S [B, H, P, N] float32}: batch
first like a K/V row, the N states last so that a lane's state lies
dense on the chip's (8, 128) tiles.

The sequence form, in chunks of `chunk` positions. With l[t] the running
sum of delta a inside a chunk (never positive) and S_in the state the
chunk starts from,

    Y     = (L o (C B^T)) (delta u) + exp(l) C S_in,   L[t, s] = exp(l[t] - l[s]) for t >= s
    S_out = exp(l[end]) S_in + sum_s exp(l[end] - l[s]) delta_s u_s B_s^T

Every exponent is a difference of l between a later and an earlier
position, so nothing overflows however fast a head decays. The first
term and each chunk's own addition to the state are computed for ALL
chunks at once (batched matmuls); a lax.scan over the chunks carries
only the state, one decay and one addition a chunk, and hands back the
state every chunk starts from; the second term is one more batched
matmul over those. The state is touched once a chunk where the
position-by-position form (ssm.py's) reads and writes it every position.

Like any recurrent state, S cannot be healed after the fact.
`mixer_seq(valid_len=n)` returns the state after position n-1 exactly:
delta is zeroed on the rows from n on (exp(0) = 1 and the input term
vanishes, so S does not move) and the conv window is cut at the real
end. A sequence is padded to whole chunks the same way.

Plain jax.numpy / lax, no kernel. The parts carry `jax.named_scope`s
(mx.ssd.conv, mx.ssd.chunk, mx.ssd.step) so that a device trace's
operations can be told apart.
"""

import jax
import jax.numpy as jnp

__all__ = ["init_state", "mixer_seq", "mixer_step"]

# positions a chunk of the sequence form covers where the caller names
# no other (TransformerConfig.ssd_chunk)
CHUNK = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def init_state(heads, head_dim, d_state, groups, d_conv, batch, dtype):
    """A layer's zeroed recurrent state for `batch` lanes."""
    return {"conv": jnp.zeros(
                (batch, d_conv - 1,
                 heads * head_dim + 2 * groups * d_state), dtype),
            "ssm": jnp.zeros((batch, heads, head_dim, d_state),
                             jnp.float32)}


def _sizes(p, state):
    """(H, P, N, G, K) read off the parameters' and the state's shapes."""
    _, h, hp, n = state["ssm"].shape
    k, width = p["conv_w"].shape
    return h, hp, n, (width - h * hp) // (2 * n), k


def _conv(window, p, t):
    """The depthwise causal convolution over window [B, K-1 + T, F] (row
    j holds the input of position j - (K-1)) -> silu(.) [B, T, F]
    float32."""
    w = p["conv_w"].astype(jnp.float32)
    return jax.nn.silu(p["conv_b"].astype(jnp.float32) + sum(
        w[j] * window[:, j:j + t].astype(jnp.float32)
        for j in range(w.shape[0])))


def _split(xbc, dt, p, sizes):
    """xbc [..., E + 2GN] float32 (after the convolution), dt [..., H] ->
    u [..., H, P], B and C [..., G, N], delta [..., H], all float32."""
    h, hp, n, g, _ = sizes
    e = h * hp
    u = xbc[..., :e].reshape(xbc.shape[:-1] + (h, hp))
    b = xbc[..., e:e + g * n].reshape(xbc.shape[:-1] + (g, n))
    c = xbc[..., e + g * n:].reshape(xbc.shape[:-1] + (g, n))
    delta = jax.nn.softplus(dt.astype(jnp.float32)
                            + p["dt_bias"].astype(jnp.float32))
    return u, b, c, delta


def _out(y, u, z, p, groups, eps):
    """y, u [..., H, P] float32, z [..., E] -> the mixer's output
    [..., D]: the skip, the gate, the norm over each of the `groups`
    groups of channels, the projection."""
    y = y + p["D"].astype(jnp.float32)[:, None] * u
    y = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(y.shape[:-1] + (groups, -1))
    var = jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
    y = (yg * jax.lax.rsqrt(var + eps)).reshape(y.shape) \
        * p["y_norm"].astype(jnp.float32)
    return jnp.einsum("...e,ed->...d", y.astype(p["out_proj"].dtype),
                      p["out_proj"])


def _chunks(s, u, b, c, delta, a, size):
    """The recurrence over u [B, T, H, P], b/c [B, T, G, N], delta
    [B, T, H] from state s [B, H, P, N], T a whole number of chunks of
    `size` -> (s', y [B, T, H, P])."""
    bsz, t, h, hp = u.shape
    g, n = b.shape[2:]
    r = h // g
    mm = lambda spec, x, y: jnp.einsum(spec, x, y, precision=_HIGHEST)
    # [B, T, ...] -> [B, chunks, size, ...], a head as (group, its place)
    cut = lambda x, *tail: x.reshape((bsz, t // size, size) + tail)
    du = cut(delta[..., None] * u, g, r, hp)
    b, c = cut(b, g, n), cut(c, g, n)
    # l, the heads before the positions: [B, chunks, G, R, size]
    cum = jnp.moveaxis(jnp.cumsum(cut(delta * a, g, r), axis=2), 2, -1)
    # inside a chunk: position t reads every s <= t through L o (C B^T)
    seen = jnp.tril(jnp.ones((size, size), bool))
    decay = jnp.exp(jnp.where(
        seen, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    pairs = mm("bctgn,bcsgn->bcgts", c, b)[:, :, :, None] * decay
    y = mm("bcgrts,bcsgrp->bctgrp", pairs, du)
    # what each chunk adds to the state, and its decay over the chunk
    last = cum[..., -1]                                         # [B, c, G, R]
    adds = mm("bcsgn,bcgrsp->bcgrpn", b, jnp.exp(
        last[..., None] - cum)[..., None] * jnp.moveaxis(du, 2, 4))

    def carry(s, xs):
        keep, add = xs
        return keep[..., None, None] * s + add, s

    s, starts = jax.lax.scan(
        carry, s.reshape(bsz, g, r, hp, n),
        (jnp.moveaxis(jnp.exp(last), 1, 0), jnp.moveaxis(adds, 1, 0)))
    # the state each chunk started from, read by its positions
    y = y + mm("bctgn,bcgrpn->bctgrp", c, jnp.moveaxis(starts, 0, 1)) \
        * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return s.reshape(bsz, h, hp, n), y.reshape(bsz, t, h, hp)


def mixer_seq(x, p, state, valid_len=None, eps=1e-5, chunk=CHUNK):
    """x [B, T, D] continuing from `state` -> (out [B, T, D], state').

    state' is the state after position valid_len - 1 (dynamic scalar;
    None = T): the rows from valid_len on are padding, their outputs
    are garbage the caller does not read, and they leave no trace."""
    t = x.shape[1]
    sizes = h, hp, _, g, k = _sizes(p, state)
    e = h * hp
    proj = jnp.einsum("btd,df->btf", x, p["in_proj"])
    z, xbc_in, dt = proj[..., :e], proj[..., e:-h], proj[..., -h:]
    with jax.named_scope("mx.ssd.conv"):
        window = jnp.concatenate(
            [state["conv"].astype(xbc_in.dtype), xbc_in], axis=1)
        xbc = _conv(window, p, t)
        end = t if valid_len is None else valid_len
        conv = jax.lax.dynamic_slice_in_dim(window, end, k - 1, axis=1)
    u, b, c, delta = _split(xbc, dt, p, sizes)
    if valid_len is not None:
        delta = jnp.where((jnp.arange(t) < valid_len)[None, :, None],
                          delta, 0.0)
    with jax.named_scope("mx.ssd.chunk"):
        size = min(chunk, t)
        pad = lambda v: jnp.pad(
            v, ((0, 0), (0, -t % size)) + ((0, 0),) * (v.ndim - 2))
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        s, y = _chunks(state["ssm"], pad(u), pad(b), pad(c), pad(delta), a,
                       size)
    return _out(y[:, :t], u, z, p, g, eps), {
        "conv": conv.astype(state["conv"].dtype), "ssm": s}


def mixer_step(x, p, state, eps=1e-5):
    """x [B, D], one position a lane -> (out [B, D], state')."""
    sizes = h, hp, _, g, _ = _sizes(p, state)
    e = h * hp
    proj = jnp.einsum("bd,df->bf", x, p["in_proj"])
    z, xbc_in, dt = proj[..., :e], proj[..., e:-h], proj[..., -h:]
    with jax.named_scope("mx.ssd.step"):
        window = jnp.concatenate(
            [state["conv"].astype(xbc_in.dtype), xbc_in[:, None]], axis=1)
        u, b, c, delta = _split(_conv(window, p, 1)[:, 0], dt, p, sizes)
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        b, c = (jnp.repeat(v, h // g, axis=1) for v in (b, c))  # [B, H, N]
        s = jnp.exp(delta * a)[..., None, None] * state["ssm"] \
            + (delta[..., None] * u)[..., None] * b[:, :, None, :]
        y = jnp.sum(s * c[:, :, None, :], axis=-1)
    return _out(y, u, z, p, g, eps), {
        "conv": window[:, 1:].astype(state["conv"].dtype), "ssm": s}
