"""Neural-network operators.

Reference: src/operator/nn/ (Convolution, Pooling, FullyConnected, BatchNorm,
LayerNorm, GroupNorm, LRN, Activation, Dropout, softmax family, CTCLoss,
Upsampling), src/operator/rnn.cc (fused RNN), src/operator/leaky_relu.cc,
src/operator/softmax_output.cc, src/operator/instance_norm.cc.

TPU-native mapping: convs/matmuls are lax.conv_general_dilated/dot_general on
the MXU (bf16-friendly); max pooling is native lax.reduce_window with XLA's
select-and-scatter backward (first-max ties, the reference convention);
avg/sum/lp pooling is a strided-slice window accumulation; the fused RNN is a
lax.scan over time steps (XLA pipelines the per-step matmuls); there are no
cuDNN/MKLDNN forks — one implementation, every backend.
"""

import functools

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax

from . import register
from .. import _fastenv as _fe


def _tuplize(v, n):
    if v is None or v == ():
        return (1,) * n if n else ()
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    if len(v) == 1:
        return v * n
    return v


# ---------------------------------------------------------- convolution --
def _conv_dnums(nd):
    # MXNet default layouts: NCW / NCHW / NCDHW, weights OIHW-style
    spatial = "DHW"[-nd:] if nd <= 3 else None
    lhs = "NC" + spatial
    rhs = "OI" + spatial
    return lax.conv_dimension_numbers((0,) * (nd + 2), (0,) * (nd + 2),
                                      (lhs, rhs, lhs))


def _conv_core(data, weight, stride, dilate, pad, num_group):
    # bf16 convs: no preferred_element_type — the MXU already accumulates
    # bf16 products in fp32, and forcing an fp32 output dtype breaks the
    # conv transpose rule (fp32 cotangent meets bf16 operand in the
    # weight-gradient conv)
    return lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=_conv_dnums(data.ndim - 2),
        feature_group_count=num_group)


def _int8_residual_enabled():
    # OPT-IN (lossy): MXNET_INT8_RESIDUAL=1 saves each conv's input
    # activation as symmetric per-channel int8 (plus an fp32 scale) for
    # the weight-gradient conv — halving the largest residual class of
    # an AMP ResNet step at a ~1e-2 relative dW error (dX stays exact:
    # it only needs the weights). This is PERF.md's "8-bit
    # saved-activation compression" intensity lever; default OFF
    # because it changes training numerics.
    import os
    return os.environ.get("MXNET_INT8_RESIDUAL", "0").lower() in (
        "1", "true")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _conv_int8_residual(data, weight, stride, dilate, pad, num_group):
    return _conv_core(data, weight, stride, dilate, pad, num_group)


def _conv_i8_fwd(data, weight, stride, dilate, pad, num_group):
    out = _conv_core(data, weight, stride, dilate, pad, num_group)
    red = tuple(i for i in range(data.ndim) if i != 1)
    amax = jnp.max(jnp.abs(data.astype(jnp.float32)), axis=red,
                   keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(data.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    return out, (q, scale, weight)


def _conv_i8_bwd(stride, dilate, pad, num_group, res, ct):
    q, scale, weight = res
    deq = (q.astype(jnp.float32) * scale).astype(weight.dtype)
    # conv is bilinear: its transpose evaluated at the dequantized
    # input gives dW from the int8 reconstruction (lossy) and dX from
    # the exact weights
    _, vjp = jax.vjp(
        lambda d, w: _conv_core(d, w, stride, dilate, pad, num_group),
        deq, weight)
    return vjp(ct)


_conv_int8_residual.defvjp(_conv_i8_fwd, _conv_i8_bwd)


@register(name="Convolution")
def convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                pad=(), num_filter=1, num_group=1, no_bias=False,
                layout=None, workspace=1024, cudnn_tune=None, cudnn_off=False):
    """src/operator/nn/convolution.cc — N-D convolution, NC[DHW] layout.

    `workspace`/`cudnn_*` are accepted for source compat and ignored (XLA
    picks MXU tilings; there is no algo autotune registry to manage —
    reference kept one in src/operator/nn/cudnn/cudnn_algoreg-inl.h).
    """
    nd = data.ndim - 2
    stride = _tuplize(stride, nd)
    dilate = _tuplize(dilate, nd)
    pad = _tuplize(pad if pad != () else 0, nd)
    if _int8_residual_enabled():
        out = _conv_int8_residual(data, weight, stride, dilate, pad,
                                  num_group)
    else:
        out = _conv_core(data, weight, stride, dilate, pad, num_group)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


@register(name="Deconvolution")
def deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                  pad=(), adj=(), target_shape=(), num_filter=1, num_group=1,
                  no_bias=True, layout=None, workspace=1024, cudnn_tune=None,
                  cudnn_off=False):
    """src/operator/nn/deconvolution.cc — transposed conv (gradient of conv
    w.r.t. its input, lowered via lax.conv_transpose semantics)."""
    nd = data.ndim - 2
    stride = _tuplize(stride, nd)
    dilate = _tuplize(dilate, nd)
    pad = _tuplize(pad if pad != () else 0, nd)
    adj = _tuplize(adj if adj != () else 0, nd)
    dn = _conv_dnums(nd)
    kshape = weight.shape[2:]
    if target_shape not in ((), None) and any(target_shape):
        # reference semantics (deconvolution-inl.h InferPad): a given
        # target_shape DISCARDS user pad/adj and derives both — the
        # zero-pad natural output stride*(in-1)+k_dilated must be >=
        # target ("too big target shape" otherwise); the excess splits
        # into pad = ceil(excess/2), adj = excess % 2, which lands the
        # output exactly on target.
        target_shape = _tuplize(target_shape, nd)
        pad, adj = [], []
        for i in range(nd):
            k = (kshape[i] - 1) * dilate[i] + 1
            natural = (data.shape[2 + i] - 1) * stride[i] + k
            if int(target_shape[i]) > natural:
                raise ValueError(
                    "too big target shape: target_shape[%d]=%d exceeds "
                    "the zero-pad output %d (= stride*(in-1) + "
                    "dilated_kernel)" % (i, target_shape[i], natural))
            excess = natural - int(target_shape[i])
            adj.append(excess % 2)
            pad.append((excess + 1) // 2)
        pad, adj = tuple(pad), tuple(adj)
    # transposed conv = lhs-dilated conv with flipped kernel, swapped I/O
    pads = []
    for i in range(nd):
        k = (kshape[i] - 1) * dilate[i] + 1
        lo = k - 1 - pad[i]
        hi = k - 1 - pad[i] + adj[i]
        pads.append((lo, hi))
    if num_group > 1:
        ws = weight.shape
        w = weight.reshape(num_group, ws[0] // num_group, ws[1], *kshape)
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape(ws[1] * num_group, ws[0] // num_group, *kshape)
    else:
        w = jnp.swapaxes(weight, 0, 1)
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# -------------------------------------------------------------- pooling --
def _window_reduce(data, kernel, stride, pads, combine, init_val, use_np=False):
    """Reduce over sliding windows via one strided slice per kernel offset.

    `data` is NC<spatial> (or bare <spatial> with use_np=True for static
    count computation). `pads` is [(lo, hi)] per spatial dim."""
    import itertools
    xp = _np if use_np else jnp
    nsp = len(kernel)
    nbatch = data.ndim - nsp
    pad_cfg = [(0, 0)] * nbatch + list(pads)
    if use_np:
        padded = _np.pad(data, pad_cfg, constant_values=init_val)
    else:
        padded = jnp.pad(data, pad_cfg, constant_values=init_val)
    out_len = [(padded.shape[nbatch + d] - kernel[d]) // stride[d] + 1
               for d in range(nsp)]
    acc = None
    for off in itertools.product(*[range(k) for k in kernel]):
        starts = [0] * nbatch + list(off)
        limits = list(padded.shape[:nbatch]) + \
            [off[d] + (out_len[d] - 1) * stride[d] + 1 for d in range(nsp)]
        strides = [1] * nbatch + list(stride)
        if use_np:
            sl = tuple(slice(s, l, st)
                       for s, l, st in zip(starts, limits, strides))
            piece = padded[sl]
        else:
            piece = lax.slice(padded, starts, limits, strides)
        acc = piece if acc is None else combine(acc, piece)
    return acc


_KNOB_CACHE = (None, None)      # (raw strings, parsed bools) — one
# tuple so readers always see a matching pair (atomic publish)


def residual_knobs():
    """The trace-time residual-format flags as one tuple. Compiled-fn
    caches (CachedOp._get_fn, the eager record-vjp cache) include it in
    their keys so toggling an env knob in-process retraces instead of
    silently reusing a stale program (the MXNET_BACKWARD_DO_MIRROR
    cache-aliasing class). Executor latches them at bind time, like
    mirror.

    Called on EVERY recorded eager dispatch, so the parse is memoized
    against the raw env strings — ~0.5 us instead of ~4 (the dispatch
    ladder budget is ~10 us/op, benchmark/opperf.py --dispatch)."""
    global _KNOB_CACHE
    raw = (_fe.get("MXNET_INT8_RESIDUAL"),
           _fe.get("MXNET_BN_BF16_RESIDUAL"),
           _fe.get("MXNET_RELU_MASK_RESIDUAL"),
           _fe.get("MXNET_POOL_INDEX_RESIDUAL"))
    cached = _KNOB_CACHE
    if raw == cached[0]:
        return cached[1]

    def flag(v, default):
        # parse the strings we ALREADY read: same rule as the
        # _*_enabled() trace-site readers, without re-reading env
        # (which would reopen the raw/parsed mismatch window)
        return (v if v is not None else default).lower() in ("1", "true")

    parsed = (flag(raw[0], "0"), flag(raw[1], "1"),
              flag(raw[2], "1"), flag(raw[3], "1"))
    _KNOB_CACHE = (raw, parsed)
    return parsed


def _pool_index_residual():
    import os
    # default OFF since the round-5 HLO diff (benchmark/hlo_diff.py):
    # the index path's stacked-window forward materializes a K-times
    # activation buffer and its backward runs K sequential full-buffer
    # scatter-adds — on chip that was most of the 10 GB/step gap
    # between the shipped ResNet step (56.2 GB, 2187 img/s) and the
    # hand-built step (45.8 GB, 2461 img/s) in the same session
    # (PERF.md "Chip numbers of 2026-08-01", cost_compare_timed — a
    # claim until re-measured). The native lax.reduce_window
    # path lowers to one fused window reduce + select-and-scatter and
    # carries the SAME first-max tie convention the reference uses
    # (mshadow pooling; verified: gradient of an all-equal window lands
    # entirely on the first position), so the semantic argument that
    # originally motivated the index path holds natively.
    # MXNET_POOL_INDEX_RESIDUAL=1 re-enables the 1-byte-index variant
    # (its residual is smaller; useful when memory capacity, not
    # bandwidth, binds).
    return os.environ.get("MXNET_POOL_INDEX_RESIDUAL", "0").lower() in (
        "1", "true")


def _max_windows(data, kernel, stride, pads, init_val):
    """All kernel-offset strided slices stacked on a leading K axis."""
    import itertools
    nsp = len(kernel)
    nbatch = data.ndim - nsp
    pad_cfg = [(0, 0)] * nbatch + list(pads)
    padded = jnp.pad(data, pad_cfg, constant_values=init_val)
    out_len = [(padded.shape[nbatch + d] - kernel[d]) // stride[d] + 1
               for d in range(nsp)]
    pieces = []
    offsets = list(itertools.product(*[range(k) for k in kernel]))
    for off in offsets:
        starts = [0] * nbatch + list(off)
        limits = list(padded.shape[:nbatch]) + \
            [off[d] + (out_len[d] - 1) * stride[d] + 1 for d in range(nsp)]
        strides = [1] * nbatch + list(stride)
        pieces.append(lax.slice(padded, starts, limits, strides))
    return jnp.stack(pieces), offsets, padded.shape, out_len


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _maxpool_index(data, kernel, stride, pads, in_shape, dtype_name):
    out, _ = _maxpool_index_fwd(data, kernel, stride, pads, in_shape,
                                dtype_name)
    return out


def _maxpool_index_fwd(data, kernel, stride, pads, in_shape, dtype_name):
    init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
        else jnp.iinfo(data.dtype).min
    win, _, padded_shape, _ = _max_windows(data, kernel, stride, pads,
                                           init)
    # narrowest index type that can hold every window offset (a uint8
    # would silently WRAP for kernels with >256 elements, scattering
    # gradients to wrong positions)
    n_off = 1
    for kd in kernel:
        n_off *= kd
    idx_dt = jnp.uint8 if n_off <= 256 else (
        jnp.uint16 if n_off <= 65536 else jnp.int32)
    idx = jnp.argmax(win, axis=0).astype(idx_dt)      # first max wins
    out = jnp.max(win, axis=0)
    return out, idx


def _maxpool_index_bwd(kernel, stride, pads, in_shape, dtype_name, res,
                       ct):
    import itertools
    idx = res
    in_dtype = jnp.dtype(dtype_name)
    nsp = len(kernel)
    nbatch = len(in_shape) - nsp
    pad_cfg = [(0, 0)] * nbatch + list(pads)
    padded_shape = list(in_shape)
    for d in range(nsp):
        padded_shape[nbatch + d] += pads[d][0] + pads[d][1]
    g = jnp.zeros(padded_shape, jnp.float32)
    ct32 = ct.astype(jnp.float32)
    out_len = list(ct.shape[nbatch:])
    for k, off in enumerate(
            itertools.product(*[range(kd) for kd in kernel])):
        contrib = jnp.where(idx == k, ct32, 0.0)
        starts = [0] * nbatch + list(off)
        limits = list(padded_shape[:nbatch]) + \
            [off[d] + (out_len[d] - 1) * stride[d] + 1 for d in range(nsp)]
        strides = [1] * nbatch + list(stride)
        # transpose of lax.slice: scatter-add the contribution back
        g = g.at[tuple(
            slice(starts[i], limits[i], strides[i])
            for i in range(len(padded_shape)))].add(contrib)
    # un-pad
    unpad = tuple(slice(pad_cfg[i][0],
                        g.shape[i] - pad_cfg[i][1] or None)
                  for i in range(len(padded_shape)))
    g = g[unpad]
    return (g.astype(in_dtype),)


_maxpool_index.defvjp(_maxpool_index_fwd, _maxpool_index_bwd)


@register(name="Pooling")
def pooling(data, kernel=(), pool_type="max", stride=(), pad=(),
            global_pool=False, pooling_convention="valid", cudnn_off=False,
            count_include_pad=True, layout=None, p_value=2):
    """src/operator/nn/pooling.cc — max/avg/sum/lp, valid/full conventions."""
    nd = data.ndim - 2
    if global_pool:
        axes = tuple(range(2, data.ndim))
        if pool_type == "max":
            out = jnp.max(data, axis=axes, keepdims=True)
        elif pool_type == "sum":
            out = jnp.sum(data, axis=axes, keepdims=True)
        elif pool_type == "lp":
            out = jnp.power(jnp.sum(jnp.power(jnp.abs(data), p_value), axis=axes,
                                    keepdims=True), 1.0 / p_value)
        else:
            out = jnp.mean(data, axis=axes, keepdims=True)
        return out
    kernel = _tuplize(kernel, nd)
    stride = _tuplize(stride, nd)
    pad = _tuplize(pad if pad != () else 0, nd)
    for i in range(nd):
        if pooling_convention != "full" and \
                kernel[i] > data.shape[2 + i] + 2 * pad[i]:
            raise ValueError(
                "Pooling kernel %s exceeds padded input %s on axis %d "
                "(valid convention); shrink the kernel, pad, or use "
                "global_pool" % (kernel, data.shape[2:], i))

    pads = []
    for i in range(nd):
        lo = hi = pad[i]
        if pooling_convention == "full":
            # ceil convention (pooling-inl.h): pad extra on the high side
            size = data.shape[2 + i] + 2 * pad[i] - kernel[i]
            rem = size % stride[i]
            if rem != 0:
                hi += stride[i] - rem
        pads.append((lo, hi))

    if pool_type == "max":
        # Opt-in 1-byte-index residual variant (capacity lever; see
        # _pool_index_residual for the chip evidence that retired it
        # as the default).
        if _pool_index_residual():
            return _maxpool_index(data, tuple(kernel), tuple(stride),
                                  tuple(tuple(p) for p in pads),
                                  tuple(data.shape), str(data.dtype))
        # Native windowed max: one fused reduce-window forward, XLA
        # select-and-scatter backward that assigns each window's
        # gradient to its FIRST max (the reference's mshadow tie
        # convention — all-equal windows, common after relu, send the
        # whole cotangent to position 0, not a 1/K split). It also
        # linearizes (jax.linearize / double-grad verified), so vjp
        # over jitted CachedOp graphs works. The init value must be a
        # PYTHON literal: jax only dispatches to the differentiable
        # reduce_window_max primitive when it recognizes the monoid
        # identity; a concrete device array falls back to the generic
        # reduce_window primitive, which has no autodiff rule.
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else int(jnp.iinfo(data.dtype).min)
        nbatch = data.ndim - nd
        return lax.reduce_window(
            data, init, lax.max,
            (1,) * nbatch + tuple(kernel),
            (1,) * nbatch + tuple(stride),
            [(0, 0)] * nbatch + [tuple(p) for p in pads])
    if pool_type == "lp":
        s = _window_reduce(jnp.power(jnp.abs(data), p_value), kernel, stride,
                           pads, jnp.add, 0)
        return jnp.power(s, 1.0 / p_value)
    s = _window_reduce(data, kernel, stride, pads, jnp.add, 0)
    if pool_type == "sum":
        return s
    # avg
    if count_include_pad:
        denom = float(_np.prod(kernel))
        return s / jnp.asarray(denom, data.dtype)
    # denominators depend only on static shapes — computed host-side
    cnt = _window_reduce(_np.ones(data.shape[2:], dtype=_np.float32),
                         kernel, stride, pads, _np.add, 0, use_np=True)
    return s / jnp.asarray(cnt, data.dtype)


# ------------------------------------------------------------- fully-connected --
@register(name="FullyConnected")
def fully_connected(data, weight, bias=None, num_hidden=1, no_bias=False,
                    flatten=True):
    """src/operator/nn/fully_connected.cc — y = x W^T + b on the MXU."""
    x = data.reshape(data.shape[0], -1) if flatten else data
    y = jnp.matmul(x, weight.T)
    if not no_bias and bias is not None:
        y = y + bias
    return y


# ---------------------------------------------------------------- norms --
@register(name="BatchNorm", aliases=("BatchNorm_v1",), num_outputs=3)
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False, is_train=False):
    """src/operator/nn/batch_norm.cc.

    Functional formulation: returns (out, batch_mean, batch_var); the caller
    (gluon.nn.BatchNorm / executor aux-state machinery) folds the running
    stats update `moving = momentum*moving + (1-m)*batch` — the reference op
    mutates its aux states in-place instead (batch_norm.cc:~400).
    """
    ax = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if is_train and not use_global_stats:
        # Single fused pass over the activation stream: E[x-s] and
        # E[(x-s)^2] accumulate in fp32 together (one reduction kernel,
        # often folded into the producing conv's epilogue), instead of the
        # mean-then-var two-pass formulation which re-reads `data` — BN is
        # HBM-bound on TPU, so the extra pass is ~40% of ResNet step time.
        # The shift s = running mean keeps the E[y^2]-E[y]^2 algebra
        # well-conditioned: raw E[x^2]-E[x]^2 cancels catastrophically in
        # fp32 when |mean| >> std, and the running mean tracks the batch
        # mean after the first few updates, making y near zero-mean.
        stat_shape = [1] * data.ndim
        stat_shape[ax] = data.shape[ax]
        shift = lax.stop_gradient(
            moving_mean.astype(jnp.float32)).reshape(stat_shape)
        if _bn_bf16_residual() and data.dtype == jnp.bfloat16:
            # keep `centered` in the ACTIVATION dtype: the backward
            # saves it as a residual on every BN input, and the fp32
            # form pins 2x the bf16 bytes (PERF.md ~22 GB/step suspect;
            # benchmark/bn_residual_ab.py + activation_residual_ab.py).
            # The reductions still accumulate in fp32.
            centered = data - shift.astype(data.dtype)
            mean_c = jnp.mean(centered, axis=red, dtype=jnp.float32)
            var = jnp.maximum(
                jnp.mean(centered * centered, axis=red,
                         dtype=jnp.float32) - mean_c * mean_c, 0.0)
        else:
            centered = data.astype(jnp.float32) - shift
            mean_c = jnp.mean(centered, axis=red)
            var = jnp.maximum(
                jnp.mean(centered * centered, axis=red)
                - mean_c * mean_c, 0.0)
        mean = (mean_c + shift.reshape(-1)).astype(moving_mean.dtype)
        var = var.astype(moving_var.dtype)
    else:
        mean, var = moving_mean, moving_var
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    # Precompute per-channel scale/bias in fp32 (tiny), then apply as one
    # fused multiply-add in the activation dtype: out = x*scale + bias.
    # AMP keeps norm params fp32; the bf16 stream is never upcast in HBM.
    inv = lax.rsqrt(var.astype(jnp.float32) + eps)
    scale = (g.astype(jnp.float32) * inv).astype(data.dtype)
    bias = (beta.astype(jnp.float32)
            - g.astype(jnp.float32) * mean.astype(jnp.float32) * inv
            ).astype(data.dtype)
    out = data * scale.reshape(shape) + bias.reshape(shape)
    return out.astype(data.dtype), mean, var


@register(name="_contrib_SyncBatchNorm", num_outputs=3)
def sync_batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                    momentum=0.9, fix_gamma=True, use_global_stats=False,
                    output_mean_var=False, ndev=1, key="", is_train=False):
    """src/operator/contrib/sync_batch_norm.cc — cross-device BN.

    TPU-native: under GSPMD the batch axis is a global array dimension,
    so BatchNorm's reduction already spans every device (XLA inserts the
    psum over the data-parallel axis). The op therefore shares the
    BatchNorm kernel — including its (out, mean, var) contract so the
    executor folds the running-stat update identically. ndev/key are
    accepted for signature parity; the engine-barrier machinery they
    configured has no analogue here.
    """
    return batch_norm(
        data, gamma, beta, moving_mean, moving_var, eps=eps,
        momentum=momentum, fix_gamma=fix_gamma,
        use_global_stats=use_global_stats,
        output_mean_var=output_mean_var, is_train=is_train)


@register(name="LayerNorm")
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    """src/operator/nn/layer_norm.cc."""
    ax = axis % data.ndim
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    xhat = (data - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    return xhat * gamma.reshape(shape) + beta.reshape(shape)


@register(name="GroupNorm")
def group_norm(data, gamma, beta, num_groups=1, eps=1e-5, output_mean_var=False):
    """src/operator/nn/group_norm.cc — NC... input, groups over C."""
    n, c = data.shape[:2]
    rest = data.shape[2:]
    x = data.reshape(n, num_groups, c // num_groups, *rest)
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    xhat = ((x - mean) * lax.rsqrt(var + eps)).reshape(data.shape)
    shape = (1, c) + (1,) * len(rest)
    return xhat * gamma.reshape(shape) + beta.reshape(shape)


@register(name="InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3):
    """src/operator/instance_norm.cc."""
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    xhat = (data - mean) * lax.rsqrt(var + eps)
    shape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    return xhat * gamma.reshape(shape) + beta.reshape(shape)


@register(name="LRN")
def lrn(data, nsize=5, alpha=1e-4, beta=0.75, knorm=2.0):
    """src/operator/nn/lrn.cc — cross-channel local response norm."""
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half)) + ((0, 0),) * (data.ndim - 2))
    c = data.shape[1]
    s = None
    for off in range(nsize):  # channel-window sum as shifted slices
        piece = lax.slice_in_dim(padded, off, off + c, axis=1)
        s = piece if s is None else s + piece
    return data / jnp.power(knorm + alpha / nsize * s, beta)


def _bn_bf16_residual():
    # default ON: for bf16 activation streams the bf16-centered form
    # halves the BN backward residual (measured -19% of total step
    # residual bytes, benchmark/activation_residual_ab.py) with fp32
    # accumulation for the statistics; MXNET_BN_BF16_RESIDUAL=0 reverts
    # to fp32-centered residuals (the round-2 formulation). fp32
    # activation streams are numerically identical either way.
    import os
    return os.environ.get("MXNET_BN_BF16_RESIDUAL", "1").lower() in (
        "1", "true")


# ----------------------------------------------------------- activation --
@jax.custom_vjp
def _relu_mask_residual(x):
    return jnp.maximum(x, 0)


def _relu_mr_fwd(x):
    # save the SIGN MASK (1 byte/elem) instead of the activation
    # (2-4 bytes/elem): relu backward needs only where(x > 0). This is
    # the "8-bit activation compression for backward" lever from
    # PERF.md. Subgradient at x == 0 is 0 (the torch/standard
    # convention) whereas jnp.maximum's tie rule gives 0.5 — a
    # measure-zero divergence between the two paths, both valid
    # subgradients.
    return jnp.maximum(x, 0), x > 0


def _relu_mr_bwd(mask, ct):
    return (jnp.where(mask, ct, jnp.zeros_like(ct)),)


_relu_mask_residual.defvjp(_relu_mr_fwd, _relu_mr_bwd)


def _relu_mask_enabled():
    # default ON: the saved residual is a 1-byte sign mask instead of
    # the bf16 activation (-11% of ResNet step residual bytes,
    # benchmark/activation_residual_ab.py), and the subgradient at
    # x == 0 is 0 — the REFERENCE convention (mshadow_op.h relu_grad:
    # a > 0 ? 1 : 0) and torch's, vs jnp.maximum's 0.5 tie split.
    # MXNET_RELU_MASK_RESIDUAL=0 reverts.
    import os
    return os.environ.get("MXNET_RELU_MASK_RESIDUAL", "1").lower() in (
        "1", "true")


@register(name="Activation")
def activation(data, act_type="relu"):
    """src/operator/nn/activation.cc."""
    if act_type == "relu":
        if _relu_mask_enabled():
            return _relu_mask_residual(data)
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return lax.logistic(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jnp.logaddexp(data, 0.0)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    raise ValueError("unknown act_type %s" % act_type)


@register(name="LeakyReLU", stateful_rng=True)
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334, rng_key=None,
               is_train=False):
    """src/operator/leaky_relu.cc — leaky/prelu/elu/selu/gelu/rrelu."""
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        g = gamma
        if g.ndim < data.ndim:
            shape = [1] * data.ndim
            if g.size > 1 and data.ndim > 1:
                shape[1] = g.size
            g = g.reshape(shape)
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        a, s = 1.6732632423543772, 1.0507009873554805
        return s * jnp.where(data >= 0, data, a * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        if is_train and rng_key is not None:
            r = jax.random.uniform(rng_key, data.shape, dtype=data.dtype,
                                   minval=lower_bound, maxval=upper_bound)
        else:
            r = jnp.asarray((lower_bound + upper_bound) / 2.0, data.dtype)
        return jnp.where(data >= 0, data, r * data)
    raise ValueError("unknown act_type %s" % act_type)


# -------------------------------------------------------------- softmax --
@register(name="softmax")
def softmax(data, axis=-1, temperature=None, length=None, use_length=False,
            dtype=None):
    """src/operator/nn/softmax.cc."""
    x = data / temperature if temperature not in (None, 1.0, 0.0) else data
    if use_length and length is not None:
        pos = jnp.arange(x.shape[axis])
        shape = [1] * x.ndim
        shape[axis % x.ndim] = x.shape[axis]
        mask = pos.reshape(shape) < length.reshape([-1] + [1] * (x.ndim - 1))
        x = jnp.where(mask, x, -jnp.inf)
    out = jax.nn.softmax(x, axis=axis)
    if dtype is not None:
        out = out.astype(jnp.dtype(dtype))
    return out


@register(name="log_softmax")
def log_softmax(data, axis=-1, temperature=None, dtype=None):
    x = data / temperature if temperature not in (None, 1.0, 0.0) else data
    out = jax.nn.log_softmax(x, axis=axis)
    if dtype is not None:
        out = out.astype(jnp.dtype(dtype))
    return out


@register(name="softmin")
def softmin(data, axis=-1, temperature=None, dtype=None):
    return softmax(-data, axis=axis, temperature=temperature, dtype=dtype)


@register(name="SoftmaxActivation")
def softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, multi_output,
                        use_ignore, preserve_shape, normalization, smooth_alpha):
    axis = 1 if (multi_output and data.ndim > 2) else -1
    return jax.nn.softmax(data, axis=axis)


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8))
def _softmax_output(data, label, grad_scale, ignore_label, multi_output,
                    use_ignore, preserve_shape, normalization, smooth_alpha):
    return _softmax_output_fwd(data, label, grad_scale, ignore_label,
                               multi_output, use_ignore, preserve_shape,
                               normalization, smooth_alpha)


def _so_fwd(data, label, grad_scale, ignore_label, multi_output, use_ignore,
            preserve_shape, normalization, smooth_alpha):
    out = _softmax_output_fwd(data, label, grad_scale, ignore_label,
                              multi_output, use_ignore, preserve_shape,
                              normalization, smooth_alpha)
    return out, (out, label)


def _so_bwd(grad_scale, ignore_label, multi_output, use_ignore,
            preserve_shape, normalization, smooth_alpha, res, g):
    out, label = res
    axis = 1 if (multi_output and out.ndim > 2) else -1
    nclass = out.shape[axis]
    lbl = label.astype("int32")
    oh = jax.nn.one_hot(lbl, nclass, axis=axis, dtype=out.dtype)
    if smooth_alpha:
        oh = oh * (1.0 - smooth_alpha - smooth_alpha / (nclass - 1)) \
            + smooth_alpha / (nclass - 1)
    grad = out - oh
    if use_ignore:
        keep = (lbl != int(ignore_label)).astype(out.dtype)
        keep = jnp.expand_dims(keep, axis % out.ndim)
        grad = grad * keep
    scale = grad_scale
    if normalization == "batch":
        scale = scale / out.shape[0]
    elif normalization == "valid" and use_ignore:
        valid = jnp.maximum(jnp.sum((lbl != int(ignore_label)).astype(out.dtype)), 1.0)
        grad = grad / valid
    grad = grad * scale
    return grad, jnp.zeros_like(label)


_softmax_output.defvjp(_so_fwd, _so_bwd)


@register(name="SoftmaxOutput", aliases=("Softmax",))
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    """src/operator/softmax_output.cc — softmax fwd; bwd is (p - onehot)
    (the classic fused softmax+CE gradient), via jax.custom_vjp."""
    lbl = label if jnp.issubdtype(label.dtype, jnp.floating) else label.astype("float32")
    return _softmax_output(data, lbl, grad_scale, ignore_label, multi_output,
                           use_ignore, preserve_shape, normalization, smooth_alpha)


@register(name="softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    """src/operator/loss_binary_op.cc — summed CE over the batch."""
    logp = jax.nn.log_softmax(data, axis=-1)
    lbl = label.astype("int32").reshape(-1)
    picked = jnp.take_along_axis(logp, lbl[:, None], axis=-1)
    return -jnp.sum(picked)


# -------------------------------------------------------------- dropout --
@register(name="Dropout", stateful_rng=True)
def dropout(data, p=0.5, mode="training", axes=(), cudnn_off=False,
            rng_key=None, is_train=False):
    """src/operator/nn/dropout.cc — inverted dropout; counter-based
    (threefry) RNG instead of per-resource Philox states (divergence noted
    in SURVEY §7 hard parts (f))."""
    if (not is_train and mode != "always") or p <= 0.0 or rng_key is None:
        return data
    shape = list(data.shape)
    for ax in axes:
        shape[ax] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng_key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


# ------------------------------------------------------------------ rnn --
def _lstm_cell(x, h, c, wx, wh, bx, bh):
    gates = x @ wx.T + h @ wh.T + bx + bh
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i = lax.logistic(i); f = lax.logistic(f)
    g = jnp.tanh(g); o = lax.logistic(o)
    c2 = f * c + i * g
    return o * jnp.tanh(c2), c2


def _gru_cell(x, h, wx, wh, bx, bh):
    xr, xz, xn = jnp.split(x @ wx.T + bx, 3, axis=-1)
    hr, hz, hn = jnp.split(h @ wh.T + bh, 3, axis=-1)
    r = lax.logistic(xr + hr)
    z = lax.logistic(xz + hz)
    n = jnp.tanh(xn + r * hn)
    return (1 - z) * n + z * h


def _rnn_cell(x, h, wx, wh, bx, bh, act):
    return act(x @ wx.T + h @ wh.T + bx + bh)


def _gates(mode):
    return {"lstm": 4, "gru": 3, "rnn_relu": 1, "rnn_tanh": 1}[mode]


def _unpack_rnn_params(params, mode, num_layers, input_size, state_size, bidirectional):
    """Unpack MXNet's flat RNN parameter vector (rnn-inl.h layout: all
    weights layer-major then all biases)."""
    ng = _gates(mode)
    d = 2 if bidirectional else 1
    ws, bs = [], []
    off = 0
    for layer in range(num_layers):
        isz = input_size if layer == 0 else state_size * d
        for _dir in range(d):
            wx = lax.dynamic_slice(params, (off,), (ng * state_size * isz,)) \
                .reshape(ng * state_size, isz)
            off += ng * state_size * isz
            wh = lax.dynamic_slice(params, (off,), (ng * state_size * state_size,)) \
                .reshape(ng * state_size, state_size)
            off += ng * state_size * state_size
            ws.append((wx, wh))
    for layer in range(num_layers):
        for _dir in range(d):
            bx = lax.dynamic_slice(params, (off,), (ng * state_size,)); off += ng * state_size
            bh = lax.dynamic_slice(params, (off,), (ng * state_size,)); off += ng * state_size
            bs.append((bx, bh))
    return ws, bs


def rnn_param_size(mode, num_layers, input_size, state_size, bidirectional=False):
    ng = _gates(mode)
    d = 2 if bidirectional else 1
    total = 0
    for layer in range(num_layers):
        isz = input_size if layer == 0 else state_size * d
        total += d * ng * state_size * (isz + state_size + 2)
    return total


@register(name="RNN", num_outputs="n", stateful_rng=True)
def rnn(data, parameters, state=None, state_cell=None, state_size=1,
        num_layers=1,
        mode="lstm", bidirectional=False, p=0.0, state_outputs=False,
        projection_size=None, lstm_state_clip_min=None,
        lstm_state_clip_max=None, lstm_state_clip_nan=False,
        use_sequence_length=False, sequence_length=None, rng_key=None,
        is_train=False):
    """src/operator/rnn.cc — fused multi-layer (bi)RNN/LSTM/GRU.

    data: (seq_len, batch, input); scanned with lax.scan so XLA pipelines
    the per-step MXU matmuls (the reference reaches cuDNN's fused kernels
    on GPU; lax.scan + fusion is the TPU analogue).
    """
    seq_len, batch, input_size = data.shape
    d = 2 if bidirectional else 1
    ws, bs = _unpack_rnn_params(parameters, mode, num_layers, input_size,
                                state_size, bidirectional)

    # omitted initial states default to zeros (lets hybridized graphs
    # avoid baking a batch-size constant for begin_state)
    if state is None:
        state = jnp.zeros((num_layers * d, batch, state_size), data.dtype)
    h0 = state  # (num_layers*d, batch, state_size)
    c0 = state_cell if mode == "lstm" else None
    if mode == "lstm" and c0 is None:
        c0 = jnp.zeros_like(h0)
    x = data
    h_last, c_last = [], []
    key = rng_key
    for layer in range(num_layers):
        outs = []
        for dr in range(d):
            li = layer * d + dr
            wx, wh = ws[li]
            bx, bh = bs[li]
            xs = jnp.flip(x, axis=0) if dr == 1 else x
            h_init = h0[li]
            if mode == "lstm":
                c_init = c0[li]

                def step(carry, xt, wx=wx, wh=wh, bx=bx, bh=bh):
                    h, c = carry
                    h2, c2 = _lstm_cell(xt, h, c, wx, wh, bx, bh)
                    return (h2, c2), h2
                (hT, cT), ys = lax.scan(step, (h_init, c_init), xs)
                c_last.append(cT)
            elif mode == "gru":
                def step(h, xt, wx=wx, wh=wh, bx=bx, bh=bh):
                    h2 = _gru_cell(xt, h, wx, wh, bx, bh)
                    return h2, h2
                hT, ys = lax.scan(step, h_init, xs)
            else:
                act = jnp.tanh if mode == "rnn_tanh" else (lambda v: jnp.maximum(v, 0))

                def step(h, xt, wx=wx, wh=wh, bx=bx, bh=bh, act=act):
                    h2 = _rnn_cell(xt, h, wx, wh, bx, bh, act)
                    return h2, h2
                hT, ys = lax.scan(step, h_init, xs)
            h_last.append(hT)
            if dr == 1:
                ys = jnp.flip(ys, axis=0)
            outs.append(ys)
        x = jnp.concatenate(outs, axis=-1) if d == 2 else outs[0]
        if p > 0.0 and is_train and layer < num_layers - 1 and key is not None:
            key, sub = jax.random.split(key)
            mask = jax.random.bernoulli(sub, 1.0 - p, x.shape).astype(x.dtype)
            x = x * mask / (1.0 - p)
    hN = jnp.stack(h_last, axis=0)
    if mode == "lstm":
        cN = jnp.stack(c_last, axis=0)
        return x, hN, cN
    return x, hN


# ------------------------------------------------------------- ctc loss --
@register(name="CTCLoss", aliases=("ctc_loss",))
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False,
             blank_label="first"):
    """src/operator/nn/ctc_loss.cc — forward algorithm in log space via
    lax.scan (reference uses 3rdparty/ctc_include warp-ctc)."""
    # data: (seq, batch, alphabet); label: (batch, label_len)
    seq_len, batch, alphabet = data.shape
    logp = jax.nn.log_softmax(data.astype("float32"), axis=-1)
    blank = 0 if blank_label == "first" else alphabet - 1
    lab = label.astype("int32")
    if blank_label == "first":
        lab = lab - 0  # labels already 1-based w/ blank=0 in MXNet convention? keep as-is
    L = lab.shape[1]
    # extended label: blank l1 blank l2 ... blank
    ext_len = 2 * L + 1
    ext = jnp.full((batch, ext_len), blank, dtype="int32")
    ext = ext.at[:, 1::2].set(lab)
    lab_lens = (label_lengths.astype("int32") if use_label_lengths and label_lengths is not None
                else jnp.sum((lab != blank) & (lab >= 0), axis=1).astype("int32"))
    dat_lens = (data_lengths.astype("int32") if use_data_lengths and data_lengths is not None
                else jnp.full((batch,), seq_len, dtype="int32"))
    ninf = jnp.asarray(-1e30, "float32")

    emit = jnp.take_along_axis(
        jnp.transpose(logp, (1, 0, 2)), ext[:, None, :], axis=2)  # (batch, seq, ext)
    emit = jnp.transpose(emit, (1, 0, 2))  # (seq, batch, ext)

    same = jnp.concatenate(
        [jnp.zeros((batch, 2), bool),
         ext[:, 2:] == ext[:, :-2]], axis=1)  # can't skip if same label

    alpha0 = jnp.full((batch, ext_len), ninf)
    alpha0 = alpha0.at[:, 0].set(emit[0, :, 0])
    alpha0 = alpha0.at[:, 1].set(jnp.where(lab_lens > 0, emit[0, :, 1], ninf))

    def logsumexp3(a, b, c):
        m = jnp.maximum(jnp.maximum(a, b), c)
        m_safe = jnp.where(m == ninf, 0.0, m)
        return jnp.where(
            m == ninf, ninf,
            m_safe + jnp.log(jnp.exp(a - m_safe) + jnp.exp(b - m_safe) + jnp.exp(c - m_safe)))

    def step(alpha, t_emit_t):
        t, emit_t = t_emit_t
        shift1 = jnp.concatenate([jnp.full((batch, 1), ninf), alpha[:, :-1]], axis=1)
        shift2 = jnp.concatenate([jnp.full((batch, 2), ninf), alpha[:, :-2]], axis=1)
        shift2 = jnp.where(same, ninf, shift2)
        new = logsumexp3(alpha, shift1, shift2) + emit_t
        new = jnp.where(t < dat_lens[:, None], new, alpha)
        return new, None

    ts = jnp.arange(1, seq_len)
    alphaT, _ = lax.scan(step, alpha0, (ts, emit[1:]))
    end1 = 2 * lab_lens
    end2 = 2 * lab_lens - 1
    aT1 = jnp.take_along_axis(alphaT, end1[:, None], axis=1)[:, 0]
    aT2 = jnp.take_along_axis(alphaT, jnp.maximum(end2, 0)[:, None], axis=1)[:, 0]
    m = jnp.maximum(aT1, aT2)
    m_safe = jnp.where(m == ninf, 0.0, m)
    ll = m_safe + jnp.log(jnp.exp(aT1 - m_safe) + jnp.exp(aT2 - m_safe))
    return (-ll).astype(data.dtype)


# ---------------------------------------------------- spatial transformer --
@register(name="SpatialTransformer")
def spatial_transformer(data, loc, target_shape=(0, 0),
                        transform_type="affine", sampler_type="bilinear",
                        cudnn_off=False):
    """src/operator/spatial_transformer.cc = GridGenerator + BilinearSampler."""
    from .matrix import grid_generator, bilinear_sampler
    grid = grid_generator(loc, transform_type="affine", target_shape=target_shape)
    return bilinear_sampler(data, grid)


@register(name="ROIPooling")
def roi_pooling(data, rois, pooled_size=(1, 1), spatial_scale=1.0):
    """src/operator/roi_pooling.cc — max pool over ROI grid cells."""
    n, c, h, w = data.shape
    ph, pw = pooled_size

    def one_roi(roi):
        bidx = roi[0].astype("int32")
        x1 = jnp.round(roi[1] * spatial_scale)
        y1 = jnp.round(roi[2] * spatial_scale)
        x2 = jnp.round(roi[3] * spatial_scale)
        y2 = jnp.round(roi[4] * spatial_scale)
        rh = jnp.maximum(y2 - y1 + 1, 1.0)
        rw = jnp.maximum(x2 - x1 + 1, 1.0)
        bh, bw = rh / ph, rw / pw
        img = data[bidx]
        ys = jnp.arange(h).reshape(1, 1, h, 1)
        xs = jnp.arange(w).reshape(1, 1, 1, w)
        py = jnp.arange(ph).reshape(ph, 1, 1, 1)
        px = jnp.arange(pw).reshape(1, pw, 1, 1)
        y_lo = jnp.floor(y1 + py * bh); y_hi = jnp.ceil(y1 + (py + 1) * bh)
        x_lo = jnp.floor(x1 + px * bw); x_hi = jnp.ceil(x1 + (px + 1) * bw)
        mask = ((ys >= y_lo) & (ys < y_hi) & (xs >= x_lo) & (xs < x_hi))
        masked = jnp.where(mask[None], img[:, None, None], -jnp.inf)
        pooled = jnp.max(masked, axis=(3, 4))
        pooled = jnp.where(jnp.isfinite(pooled), pooled, 0.0)
        return pooled  # (c, ph, pw)

    return jax.vmap(one_roi)(rois)


# ------------------------------------------------- regression outputs ---
# src/operator/regression_output.cc — identity-ish forward, fixed bwd
# (pred - label) * grad_scale. Implemented with custom_vjp like
# SoftmaxOutput so Module loss heads train identically to the reference.

def _make_regression_output(fwd, bwd_from):
    @_functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def _core(data, label, grad_scale):
        return fwd(data)

    def _fvjp(data, label, grad_scale):
        return fwd(data), (data, label)

    def _bvjp(grad_scale, res, g):
        data, label = res
        # reference scales by grad_scale / num_output (outputs per sample,
        # regression_output-inl.h:201-207)
        num_output = data.size // data.shape[0] if data.ndim else 1
        grad = bwd_from(data, label) * (grad_scale / num_output)
        return grad, jnp.zeros_like(label)

    _core.defvjp(_fvjp, _bvjp)
    return _core


_linreg_core = _make_regression_output(
    lambda d: d,
    lambda d, l: d - l.reshape(d.shape))
_maereg_core = _make_regression_output(
    lambda d: d,
    lambda d, l: jnp.sign(d - l.reshape(d.shape)))
_logreg_core = _make_regression_output(
    jax.nn.sigmoid,
    lambda d, l: jax.nn.sigmoid(d) - l.reshape(d.shape))


# SVM head (src/operator/svm_output.cc): identity forward; backward is
# the multiclass hinge gradient (L2-SVM by default, L1 with use_linear).
@_functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _svm_core(data, label, margin, reg_coef, use_linear):
    return data


def _svm_fvjp(data, label, margin, reg_coef, use_linear):
    return data, (data, label)


def _svm_bvjp(margin, reg_coef, use_linear, res, g):
    data, label = res
    lab = label.reshape(-1).astype(jnp.int32)
    x_y = jnp.take_along_axis(data, lab[:, None], axis=1)
    z = margin - x_y + data                      # (N, C); z at y == margin
    onehot = jax.nn.one_hot(lab, data.shape[1], dtype=data.dtype)
    if use_linear:
        viol = ((z > 0) & (onehot == 0)).astype(data.dtype)
    else:
        viol = jnp.where(onehot == 0, 2.0 * jnp.maximum(z, 0.0), 0.0)
    grad = reg_coef * (viol - onehot * viol.sum(axis=1, keepdims=True))
    return grad * jnp.ones_like(g), jnp.zeros_like(label)


_svm_core.defvjp(_svm_fvjp, _svm_bvjp)


@register(name="SVMOutput")
def svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
               use_linear=False):
    """src/operator/svm_output.cc — SVM loss head."""
    return _svm_core(data, label, float(margin),
                     float(regularization_coefficient), bool(use_linear))


# KL sparsity regularizer (src/operator/identity_attach_KL_sparse_reg.cc):
# identity forward; backward adds the KL(ρ||ρ̂) gradient pushing each
# unit's batch-mean activation toward sparseness_target. The reference
# keeps ρ̂ as a momentum-smoothed aux state; here ρ̂ is the batch mean
# (momentum accepted for signature parity).
@_functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _kl_sparse_core(data, sparseness_target, penalty):
    return data


def _kl_fvjp(data, sparseness_target, penalty):
    return data, data


def _kl_bvjp(sparseness_target, penalty, data, g):
    rho_hat = jnp.clip(jnp.mean(data, axis=0, keepdims=True), 1e-6,
                       1.0 - 1e-6)
    t = sparseness_target
    kl_grad = penalty * (-t / rho_hat + (1.0 - t) / (1.0 - rho_hat))
    return (g + kl_grad * jnp.ones_like(data) / data.shape[0],)


_kl_sparse_core.defvjp(_kl_fvjp, _kl_bvjp)


@register(name="IdentityAttachKLSparseReg")
def identity_attach_kl_sparse_reg(data, sparseness_target=0.1,
                                  penalty=0.001, momentum=0.9):
    """src/operator/identity_attach_KL_sparse_reg.cc."""
    return _kl_sparse_core(data, float(sparseness_target), float(penalty))


@register(name="LinearRegressionOutput")
def linear_regression_output(data, label, grad_scale=1.0):
    """src/operator/regression_output.cc:xx — identity fwd, (pred-label) bwd."""
    return _linreg_core(data, label, grad_scale)


@register(name="MAERegressionOutput")
def mae_regression_output(data, label, grad_scale=1.0):
    """src/operator/regression_output.cc — identity fwd, sign(pred-label) bwd."""
    return _maereg_core(data, label, grad_scale)


@register(name="LogisticRegressionOutput")
def logistic_regression_output(data, label, grad_scale=1.0):
    """src/operator/regression_output.cc — sigmoid fwd, (sigmoid-label) bwd."""
    return _logreg_core(data, label, grad_scale)
