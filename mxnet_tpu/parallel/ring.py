"""Ring attention — sequence/context parallelism over a mesh axis.

Long-context capability the reference lacks entirely (SURVEY §5
"Long-context / sequence parallelism: Absent"): sequences are sharded
over the 'sp' mesh axis and attention runs blockwise, rotating K/V
shards around the ring with lax.ppermute so no device ever materialises
the full sequence. Softmax is accumulated in flash-attention style
(running max / running sum), so results match full attention to fp
tolerance.

ICI mapping: each step overlaps the Q·K/softmax/PV block compute with a
neighbour ppermute of the K/V block (XLA schedules the collective-
permute concurrently with the matmuls, which is the whole point of the
ring schedule on TPU).
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import ring_permute
from ..observability import chaos as _chaos
from ..observability import watchdog as _wd

__all__ = ["ring_attention", "local_attention_block",
           "ring_attention_sharded", "sp_flash_decode"]


def _watched_dispatch(name, fn, *args, **info):
    """Run one collective program under the hang watchdog. With the
    watchdog off (the default) this is a single guarded branch around a
    plain call; armed, completion is awaited inside the watched window
    so a rank stuck in the ring's ppermute/psum rendezvous produces a
    post-mortem instead of a silent stall. The chaos site of the same
    name can delay/hang/fail the dispatch for the injection harness."""
    if not _wd.enabled():
        if _chaos.enabled():
            _chaos.fire(name, **{k: str(v) for k, v in info.items()})
        return fn(*args)
    with _wd.watch(name, **info):
        if _chaos.enabled():
            _chaos.fire(name, **{k: str(v) for k, v in info.items()})
        out = fn(*args)
        jax.block_until_ready(out)
    return out

_NEG_INF = -1e30


def local_attention_block(q, k, v, q_offset, kv_offset, causal, scale,
                          carry=None, use_flash_kernel=False, vma=None):
    """One flash-attention block update.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D]. Offsets are the global
    positions of element 0 of the q/kv blocks (for causal masking).
    carry = (o, m, l) running output/max/denominator, or None to start.

    use_flash_kernel routes the block through the Pallas streamed
    kernel (kernels/flash_attention.flash_carry_block): the [Tq, Tk]
    score matrix then never exists in HBM, so per-device shards are
    bounded by HBM capacity rather than the score-matrix footprint.
    Requires shard lengths divisible by the kernel blocks (clamped to
    the shard).
    """
    if use_flash_kernel:
        return _flash_block(q, k, v, q_offset, kv_offset, causal, carry,
                            vma)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_offset + jnp.arange(Tq)
        kv_pos = kv_offset + jnp.arange(Tk)
        mask = q_pos[:, None] >= kv_pos[None, :]
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    if carry is None:
        o = jnp.zeros((B, Tq, H, D), dtype=jnp.float32)
        m = jnp.full((B, H, Tq), _NEG_INF, dtype=jnp.float32)
        l = jnp.zeros((B, H, Tq), dtype=jnp.float32)
    else:
        o, m, l = carry
    m_new = jnp.maximum(m, scores.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l_new = alpha * l + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o_new = alpha.transpose(0, 2, 1)[..., None] * o + pv
    return o_new, m_new, l_new


def _flash_block(q, k, v, q_offset, kv_offset, causal, carry, vma=None):
    """local_attention_block via the Pallas carry kernel; carries the
    same (o [B,Tq,H,D] f32, m/l [B,H,Tq] f32) layout as the jnp path."""
    from ..kernels.flash_attention import flash_carry_block
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(
        B * x.shape[2], x.shape[1], D)
    if carry is None:
        o = jnp.zeros((B * H, Tq, D), jnp.float32)
        m = jnp.full((B * H, Tq), _NEG_INF, jnp.float32)
        l = jnp.zeros((B * H, Tq), jnp.float32)
        if vma:
            # fresh accumulators are mesh-invariant while q/k/v are
            # sp-varying; pallas + the vma checker need them to agree
            def _v(x):
                try:
                    return jax.lax.pcast(x, tuple(vma), to="varying")
                except ValueError:       # already varying
                    return x
            o, m, l = _v(o), _v(m), _v(l)
    else:
        o_c, m_c, l_c = carry
        o = to_bh(o_c)
        m = m_c.reshape(B * H, Tq)
        l = l_c.reshape(B * H, Tq)
    o, m, l = flash_carry_block(to_bh(q), to_bh(k), to_bh(v), o, m, l,
                                q_offset, kv_offset, causal,
                                vma=None if vma is None
                                else tuple(sorted(vma)))
    o_out = o.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)
    return o_out, m.reshape(B, H, Tq), l.reshape(B, H, Tq)


def ring_attention(q, k, v, axis_name="sp", causal=True,
                   use_flash_kernel=False):
    """Blockwise ring attention. Must run inside shard_map (or pmap) with
    the sequence dimension sharded over `axis_name`.

    q, k, v: [B, T_local, H, D] — this device's sequence shard.
    Returns [B, T_local, H, D].
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    T = q.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    q_offset = idx * T

    def body(i, carry):
        o, m, l, k_blk, v_blk, kv_idx = carry
        # rotate K/V to the next device; we now hold our left
        # neighbour's block, whose global index is one lower (mod n)
        k_blk = ring_permute(k_blk, axis_name)
        v_blk = ring_permute(v_blk, axis_name)
        kv_idx = (kv_idx - 1) % n
        o, m, l = local_attention_block(
            q, k_blk, v_blk, q_offset, kv_idx * T, causal, scale,
            carry=(o, m, l), use_flash_kernel=use_flash_kernel,
            vma=(axis_name,))
        return (o, m, l, k_blk, v_blk, kv_idx)

    B, T, H, D = q.shape

    def _varying(x):
        # mark freshly-created accumulators as device-varying so the
        # fori_loop carry type matches its (sp-varying) outputs
        try:
            return jax.lax.pcast(x, (axis_name,), to="varying")
        except ValueError:
            return x  # already varying

    # own block first (no permute), then n-1 rotate+accumulate rounds —
    # exactly n-1 collective-permutes per call
    o0, m0, l0 = local_attention_block(q, k, v, q_offset, idx * T, causal,
                                       scale, carry=None,
                                       use_flash_kernel=use_flash_kernel,
                                       vma=(axis_name,))
    init = (_varying(o0), _varying(m0), _varying(l0), k, v, idx)
    o, m, l, _, _, _ = jax.lax.fori_loop(0, n - 1, body, init)
    # fully-masked rows (can't happen for causal same-length rings, but
    # guard anyway) would have l == 0
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _kernel_off_tpu(what):
    """A requested Pallas kernel met a backend that is not a TPU. Under
    the tests' explicit CPU pin the caller substitutes its jnp path
    (same numerics); anywhere else the request cannot be honoured, and
    a silent substitute would hide that."""
    from ..base import MXNetError
    from ..context import _cpu_pinned
    if not _cpu_pinned():
        raise MXNetError(
            "%s: the Pallas kernel was requested but backend %r is not "
            "a TPU" % (what, jax.default_backend()))


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=True,
                           batch_axis=None, use_flash_kernel=False):
    """Convenience wrapper: apply ring attention to GLOBAL arrays
    [B, T, H, D] whose T dim is (or will be) sharded over `axis_name`.
    Usable inside jit — shard_map is restricted to the sp (and optional
    batch) mesh axes, all other mesh axes stay auto-sharded."""
    spec = P(batch_axis, axis_name, None, None)
    manual = (axis_name,) if batch_axis is None else (axis_name, batch_axis)
    kw = {}
    if use_flash_kernel and jax.default_backend() != "tpu":
        _kernel_off_tpu("ring_attention_sharded(use_flash_kernel=True)")
        if set(mesh.axis_names) - set(manual):
            # interpret-mode pallas (CPU testing) cannot run under a
            # vma-checked partially-manual shard_map (jax interpreter
            # lowers block fetches to dynamic_slice with mesh-invariant
            # indices). On real TPU the compiled kernel carries vma
            # annotations and this limitation does not apply; on CPU
            # keep the numerics via the jnp blockwise path.
            use_flash_kernel = False
        else:
            # fully-manual mesh: disable the checker instead (outputs
            # are per-shard by construction)
            kw["check_vma"] = False
    fn = functools.partial(ring_attention, axis_name=axis_name,
                           causal=causal,
                           use_flash_kernel=use_flash_kernel)
    smapped = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, axis_names=set(manual), **kw)
    # jit the mapped program: compiled is what a train step wants
    return _watched_dispatch(
        "ring.attention", jax.jit(smapped), q, k, v,
        axis=axis_name, shape=str(tuple(q.shape)))


def sp_flash_decode(q, k_cache, v_cache, lengths, mesh, axis_name="sp",
                    batch_axis=None, block_k=None, interpret=None,
                    use_pallas=None):
    """Sequence-parallel DECODING: single-token attention against a KV
    cache sharded over `axis_name` along its sequence dim.

    q: [B, H, D] (replicated over sp); k_cache/v_cache: [B, Tmax, H, D]
    with Tmax sharded over sp; lengths: [B] (or scalar) GLOBAL valid
    lengths. Each device computes (o, lse) over its cache slice with
    the length clipped to the slice, then the partial results combine
    with their log-sum-exp weights — one psum over sp instead of
    gathering the cache (flash-decoding decomposition; the
    long-context serving complement of ring_attention).

    The per-shard compute defaults to dense_decode_with_lse (plain
    XLA): decode reads [1, T] scores, so there is nothing for a flash
    schedule to tile away, and the chip A/B measured the Pallas decode
    kernel ~5x slower at serving shapes (PERF.md "Chip numbers of
    2026-08-01", decode_dense vs decode_flash — a claim until
    re-measured). `use_pallas=True` (or MXNET_SP_DECODE_PALLAS=1)
    restores the kernel path."""
    from ..kernels.flash_attention import (dense_decode_with_lse,
                                           flash_decode_with_lse)

    explicit_pallas = use_pallas is True
    if use_pallas is None:
        import os
        use_pallas = os.environ.get(
            "MXNET_SP_DECODE_PALLAS", "0").lower() in ("1", "true")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if interpret:
        if explicit_pallas:
            _kernel_off_tpu("sp_flash_decode(use_pallas=True)")
            # under the CPU pin the deliberate fallback must still be
            # distinguishable from misconfiguration (ADVICE r5)
            import warnings
            warnings.warn(
                "sp_flash_decode: use_pallas=True ignored — interpret "
                "mode is active (backend %r is not TPU), and "
                "interpret-mode pallas cannot run under a partially-"
                "manual shard_map; computing with dense_decode_with_lse "
                "instead" % jax.default_backend(), stacklevel=2)
        use_pallas = False   # interpret-mode pallas can't run under a
        #                      partially-manual shard_map

    def local(q_l, k_l, v_l, len_l):
        idx = jax.lax.axis_index(axis_name)
        t_shard = k_l.shape[1]
        local_len = jnp.clip(len_l - idx * t_shard, 0, t_shard)
        if use_pallas:
            o_i, lse_i = flash_decode_with_lse(
                q_l, k_l, v_l, local_len, block_k=block_k,
                interpret=False)
            o_i = o_i.astype(jnp.float32)
        else:
            # zero-valid-key shards come back o=0, lse~-1e30 and drop
            # out of the combine below
            o_i, lse_i = dense_decode_with_lse(q_l, k_l, v_l, local_len)
        # combine partial softmaxes across the sp shards
        m_g = jax.lax.pmax(lse_i, axis_name)
        w = jnp.exp(lse_i - m_g)
        num = jax.lax.psum(w[..., None] * o_i, axis_name)
        den = jax.lax.psum(w, axis_name)
        return (num / jnp.maximum(den, 1e-30)[..., None]).astype(q_l.dtype)

    qspec = P(batch_axis, None, None)
    cspec = P(batch_axis, axis_name, None, None)
    lspec = P(batch_axis)
    manual = {axis_name} if batch_axis is None else {axis_name, batch_axis}
    b = q.shape[0]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    smapped = jax.shard_map(
        local, mesh=mesh, in_specs=(qspec, cspec, cspec, lspec),
        out_specs=qspec, axis_names=manual)
    return _watched_dispatch(
        "ring.sp_flash_decode", jax.jit(smapped),
        q, k_cache, v_cache, lengths,
        axis=axis_name, batch=q.shape[0])
