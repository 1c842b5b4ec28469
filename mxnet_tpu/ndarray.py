"""NDArray — the imperative value type, and the `nd.*` op namespace.

Reference: include/mxnet/ndarray.h + src/ndarray/ (the C++ value type:
ref-counted chunk, engine var, storage types) and python/mxnet/ndarray/
(the Python class + autogenerated op functions, register.py:258-270).

TPU-native design: an NDArray wraps a jax.Array. There is no user-space
dependency engine — jax.Arrays are futures and XLA orders execution — and
no ctypes ABI: the "autogenerated op namespace" is synthesized directly
from the op registry (mxnet_tpu.ops) at import, mirroring how the
reference synthesizes Python functions from C++ registrations at import
(python/mxnet/base.py:580-647).

Sparse note: row_sparse/CSR (ndarray.h:61-66) have no TPU memory-op
equivalent; `mxnet_tpu.sparse` keeps the API with compressed parts +
storage and documents the divergence (SURVEY §7 hard part (a)).
"""

import builtins
import inspect
import json
import struct

import numpy as np

import jax
import jax.numpy as jnp

from . import autograd, engine, ops
from . import random as _random_mod
from . import storage as _storage
from .base import MXNetError
from .context import Context, current_context

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange",
           "concat", "concatenate", "stack", "save", "load", "waitall",
           "imperative_invoke"]

_DTYPE_ALIAS = {"float16": "bfloat16"}  # fp16 requests map to TPU bf16

def _dtype_name(dtype):
    """Canonical dtype name: accepts strings, numpy scalar classes
    (np.float16), and dtype objects; 'bfloat16' passes through (numpy's
    np.dtype can't parse it)."""
    if isinstance(dtype, str):
        return dtype
    try:
        return np.dtype(dtype).name
    except TypeError:
        return str(dtype)


def _to_jnp_dtype(dtype):
    if dtype is None:
        return jnp.float32
    name = _dtype_name(dtype)
    if name in _DTYPE_ALIAS:
        # fp16 requests run as bf16: same byte width, the MXU's native
        # low-precision type, none of fp16's range hazards (documented
        # divergence; amp does the same mapping)
        name = _DTYPE_ALIAS[name]
    return jnp.dtype(name)


class NDArray:
    """Multi-dimensional array on a device (include/mxnet/ndarray.h)."""

    __slots__ = ("_data", "_ctx", "_grad", "_grad_req", "_ag_node", "_ag_leaf",
                 "_stype", "_fresh_grad", "__weakref__")

    def __init__(self, data, ctx=None, stype="default"):
        self._data = data
        self._ctx = ctx or current_context()
        if _storage._TRACKING:
            _storage._note_alloc(self)
        self._grad = None
        self._grad_req = "null"
        self._ag_node = None
        self._ag_leaf = False
        self._stype = stype
        self._fresh_grad = False

    # ------------------------------------------------------------ props --
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return self._stype

    @property
    def T(self):
        return transpose(self)

    @property
    def grad(self):
        return self._grad

    # ------------------------------------------------------- conversion --
    def asnumpy(self):
        return np.asarray(self._data)

    def __reduce__(self):
        # pickling (DataLoader spawn workers, kvstore snapshots): carry
        # the host bytes + dtype name + device type; the unpickler
        # re-places on its own default device of that type. bf16 rides
        # as ml_dtypes numpy arrays.
        return (_ndarray_unpickle,
                (self.asnumpy(), str(self._data.dtype),
                 self._ctx.device_type))

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(-1)[0].item()

    def item(self):
        return self.asscalar()

    def astype(self, dtype, copy=True):
        # route through the Cast op so the autograd tape sees the cast
        # (Cast is differentiable: the vjp casts the cotangent back)
        if autograd.is_recording() and self._requires_tape():
            return imperative_invoke(
                "Cast", [self], {"dtype": np.dtype(_to_jnp_dtype(dtype)).name})
        return NDArray(self._data.astype(_to_jnp_dtype(dtype)), self._ctx)

    def asjax(self):
        """The underlying jax.Array (TPU-native escape hatch)."""
        return self._data

    def as_in_context(self, ctx):
        if ctx == self._ctx:
            return self
        data = jax.device_put(self._data, ctx.jax_device)
        return NDArray(data, ctx)

    as_in_ctx = as_in_context

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._data = jax.device_put(self._data, other._ctx.jax_device)
            return other
        return self.as_in_context(other)

    def copy(self):
        return NDArray(self._data + 0 if self._data.dtype != bool else self._data,
                       self._ctx)

    def tostype(self, stype):
        return NDArray(self._data, self._ctx, stype=stype)

    def wait_to_read(self):
        if hasattr(self._data, "block_until_ready"):
            self._data.block_until_ready()

    # --------------------------------------------------------- autograd --
    def _requires_tape(self):
        return self._ag_leaf or self._ag_node is not None

    def _mark_variable(self, grad, grad_req):
        self._ag_leaf = True
        self._grad = grad
        self._grad_req = grad_req

    def attach_grad(self, grad_req="write", stype=None):
        """python/mxnet/ndarray/ndarray.py attach_grad."""
        self._ag_leaf = True
        self._grad_req = grad_req
        self._grad = zeros(self.shape, dtype=self.dtype, ctx=self._ctx)

    def detach(self):
        out = NDArray(self._data, self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # --------------------------------------------------------- reshape --
    def reshape(self, *shape, **kwargs):
        if "shape" in kwargs:
            shape = kwargs["shape"]
        elif len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return reshape(self, shape=tuple(shape),
                       reverse=kwargs.get("reverse", False))

    def reshape_like(self, other):
        return reshape_like(self, other)

    def expand_dims(self, axis):
        return expand_dims(self, axis=axis)

    def squeeze(self, axis=None):
        return squeeze(self, axis=axis)

    def flatten(self):
        return flatten(self)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return transpose(self, axes=axes if axes else None)

    def swapaxes(self, dim1, dim2):
        return swapaxes(self, dim1=dim1, dim2=dim2)

    def flip(self, axis):
        return reverse(self, axis=axis)

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return split(self, num_outputs=num_outputs, axis=axis,
                     squeeze_axis=squeeze_axis)

    def tile(self, reps):
        return tile(self, reps=reps)

    def repeat(self, repeats, axis=None):
        return repeat(self, repeats=repeats, axis=axis)

    def pad(self, mode, pad_width, constant_value=0.0):
        return pad(self, mode=mode, pad_width=pad_width,
                   constant_value=constant_value)

    def broadcast_to(self, shape):
        return broadcast_to(self, shape=shape)

    def broadcast_like(self, other):
        return broadcast_like(self, other)

    def one_hot(self, depth, **kw):
        return one_hot(self, depth=depth, **kw)

    def take(self, indices, axis=0, mode="clip"):
        return take(self, indices, axis=axis, mode=mode)

    def pick(self, index, axis=-1, keepdims=False):
        return pick(self, index, axis=axis, keepdims=keepdims)

    def topk(self, **kw):
        return topk(self, **kw)

    def sort(self, axis=-1, is_ascend=True):
        return sort(self, axis=axis, is_ascend=is_ascend)

    def argsort(self, axis=-1, is_ascend=True):
        return argsort(self, axis=axis, is_ascend=is_ascend)

    def argmax(self, axis=None, keepdims=False):
        return argmax(self, axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False):
        return argmin(self, axis=axis, keepdims=keepdims)

    def clip(self, a_min, a_max):
        return clip(self, a_min=a_min, a_max=a_max)

    def abs(self):
        return abs_op(self)

    def sign(self):
        return sign(self)

    def sqrt(self):
        return sqrt(self)

    def square(self):
        return square(self)

    def exp(self):
        return exp(self)

    def log(self):
        return log(self)

    def sigmoid(self):
        return sigmoid(self)

    def tanh(self):
        return tanh(self)

    def relu(self):
        return relu(self)

    def softmax(self, axis=-1):
        return softmax(self, axis=axis)

    def log_softmax(self, axis=-1):
        return log_softmax(self, axis=axis)

    def sum(self, axis=None, keepdims=False, **kw):
        return sum(self, axis=axis, keepdims=keepdims, **kw)

    def mean(self, axis=None, keepdims=False, **kw):
        return mean(self, axis=axis, keepdims=keepdims, **kw)

    def max(self, axis=None, keepdims=False, **kw):
        return max(self, axis=axis, keepdims=keepdims, **kw)

    def min(self, axis=None, keepdims=False, **kw):
        return min(self, axis=axis, keepdims=keepdims, **kw)

    def prod(self, axis=None, keepdims=False, **kw):
        return prod(self, axis=axis, keepdims=keepdims, **kw)

    def norm(self, ord=2, axis=None, keepdims=False):
        return norm(self, ord=ord, axis=axis, keepdims=keepdims)

    def dot(self, other, **kw):
        return dot(self, other, **kw)

    def slice(self, begin, end, step=()):
        return slice_op(self, begin=begin, end=end, step=step)

    def slice_axis(self, axis, begin, end):
        return slice_axis(self, axis=axis, begin=begin, end=end)

    def zeros_like(self):
        return zeros_like(self)

    def ones_like(self):
        return ones_like(self)

    # ------------------------------------------------------- operators --
    def __add__(self, other):
        return add(self, other)

    def __radd__(self, other):
        return add(self, other)

    def __iadd__(self, other):
        out = add(self, other)
        self._data = out._data
        return self

    def __sub__(self, other):
        return subtract(self, other)

    def __rsub__(self, other):
        if isinstance(other, NDArray):
            return subtract(other, self)
        return imperative_invoke("_rminus_scalar", [self], {"scalar": other})

    def __isub__(self, other):
        self._data = subtract(self, other)._data
        return self

    def __mul__(self, other):
        return multiply(self, other)

    def __rmul__(self, other):
        return multiply(self, other)

    def __imul__(self, other):
        self._data = multiply(self, other)._data
        return self

    def __truediv__(self, other):
        return divide(self, other)

    def __rtruediv__(self, other):
        if isinstance(other, NDArray):
            return divide(other, self)
        return imperative_invoke("_rdiv_scalar", [self], {"scalar": other})

    def __itruediv__(self, other):
        self._data = divide(self, other)._data
        return self

    def __mod__(self, other):
        return modulo(self, other)

    def __rmod__(self, other):
        if isinstance(other, NDArray):
            return modulo(other, self)
        return imperative_invoke("_rmod_scalar", [self], {"scalar": other})

    def __pow__(self, other):
        return power(self, other)

    def __rpow__(self, other):
        if isinstance(other, NDArray):
            return power(other, self)
        return imperative_invoke("_rpower_scalar", [self], {"scalar": other})

    def __neg__(self):
        return negative(self)

    def __abs__(self):
        return abs_op(self)

    def __eq__(self, other):
        return equal(self, other)

    def __ne__(self, other):
        return not_equal(self, other)

    def __gt__(self, other):
        return greater(self, other)

    def __ge__(self, other):
        return greater_equal(self, other)

    def __lt__(self, other):
        return lesser(self, other)

    def __le__(self, other):
        return lesser_equal(self, other)

    def __hash__(self):
        return id(self)

    def __bool__(self):
        if self.size == 1:
            return bool(self.asnumpy().reshape(-1)[0])
        raise ValueError("The truth value of an NDArray with multiple "
                         "elements is ambiguous.")

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __repr__(self):
        return "%s\n<NDArray %s @%s>" % (
            str(self.asnumpy()), "x".join(str(s) for s in self.shape), self._ctx)

    # -------------------------------------------------------- indexing --
    def __getitem__(self, key):
        key = self._norm_key(key)
        if autograd.is_recording() and self._requires_tape():
            return imperative_invoke("_index", [self], {"key": key})
        with _large_tensor_ctx([self._data]):
            return NDArray(self._data[key], self._ctx)

    def _norm_key(self, key):
        # index width follows the base array: int32 normally (what the
        # TPU wants), int64 when this array's size exceeds int32 range
        # (large-tensor support — _large_tensor_ctx)
        idx_dt = "int64" if self._data.size > _INT32_MAX else "int32"

        def conv(k):
            if isinstance(k, NDArray):
                return k._data.astype(idx_dt) if not jnp.issubdtype(
                    k._data.dtype, jnp.bool_) else k._data
            return k
        if isinstance(key, tuple):
            return tuple(conv(k) for k in key)
        return conv(key)

    def __setitem__(self, key, value):
        key = self._norm_key(key)
        if isinstance(value, NDArray):
            value = value._data
        if isinstance(key, builtins.slice) and key == builtins.slice(None):
            self._data = jnp.broadcast_to(
                jnp.asarray(value, dtype=self.dtype), self.shape)
        else:
            with _large_tensor_ctx([self._data]):
                self._data = self._data.at[key].set(value)


# `_index` op: getitem with tape support — registered here because the key
# normalization lives with NDArray.
@ops.register(name="_index")
def _index_op(data, key=None):
    return data[key]



# compiled fwd+pullback cache for RECORDED eager ops: a bare jax.vjp
# re-traces the op on every call (~600 us/op measured,
# benchmark/opperf.py --dispatch); jitting the (outs, vjp) pair per
# (op, static attrs, shapes) signature makes recorded dispatch cost the
# same as unrecorded after the first call. The vjp closure is a
# jax.tree_util.Partial — a pytree of residual arrays — so it crosses
# the jit boundary intact (same trick as executor.fwd_res_fn).
_RECORD_VJP_CACHE = {}
_MISSING = object()


def _freeze_attr(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze_attr(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted(
            (_freeze_attr(k), _freeze_attr(x)) for k, x in v.items()))
    # type-tag scalars: True/1 and 2/2.0 hash equal but trace to
    # different programs (bool attr branches, int vs float weak dtypes)
    return (type(v).__name__, v)


def _recorded_vjp(op, kw, arr_names, has_varargs, datas):
    """(outs, vjp_fn, compiled) with per-signature compilation; falls
    back to an uncached jax.vjp (`compiled` False: a new function every
    call) when an attr is unhashable (host callbacks etc.)."""
    rng = kw.get("rng_key", None)
    static = {k: v for k, v in kw.items() if k != "rng_key"}

    def build_f(call):
        def f(*xs):
            if has_varargs:
                return op.fn(*xs, **call)
            c = dict(call)
            c.update(zip(arr_names, xs))
            return op.fn(**c)
        return f

    def _direct():
        call = dict(static)
        if rng is not None:
            call["rng_key"] = rng
        outs, vjp_fn = jax.vjp(build_f(call), *datas)
        return outs, vjp_fn, False

    try:
        from .ops.nn import residual_knobs
        key = (op.name,
               tuple(sorted((k, _freeze_attr(v)) for k, v in static.items())),
               tuple(arr_names) if arr_names is not None else None,
               has_varargs, rng is not None,
               tuple((tuple(d.shape), str(d.dtype)) for d in datas),
               # residual-format env knobs are trace-time state: a
               # toggled knob must not reuse a stale compiled vjp
               residual_knobs())
        hash(key)
    except TypeError:
        return _direct()

    jfn = _RECORD_VJP_CACHE.get(key, _MISSING)
    if jfn is None:          # known jit-incompatible (dynamic shapes)
        return _direct()
    if jfn is _MISSING:
        has_rng = rng is not None

        def fwd_res(xs, rng_key):
            call = dict(static)
            if has_rng:
                call["rng_key"] = rng_key
            return jax.vjp(build_f(call), *xs)

        jfn = jax.jit(fwd_res)
        try:
            outs, vjp_fn = jfn(tuple(datas), rng)
        except jax.errors.JAXTypeError:
            # ops with data-dependent output shapes (boolean_mask ->
            # jnp.nonzero) cannot trace under jit; remember and run the
            # eager vjp path for this signature from now on
            _RECORD_VJP_CACHE[key] = None
            return _direct()
        _RECORD_VJP_CACHE[key] = jfn
        return outs, vjp_fn, True
    outs, vjp_fn = jfn(tuple(datas), rng)
    return outs, vjp_fn, True


_INT32_MAX = 2**31 - 1


def _large_tensor_ctx(arrays):
    """Large-tensor (INT64 index) support, the reference's
    MXNET_LARGE_TENSOR build flag re-thought for jax: when any operand
    exceeds 2^31-1 elements, run dispatch under jax.enable_x64 so index
    arithmetic (gather/scatter/dynamic_slice starts, argmax outputs)
    is 64-bit — outside that scope the framework keeps jax's 32-bit
    default, which is what the MXU wants. Nightly-style coverage:
    tests/test_large_tensor.py (reference tests/nightly/test_large_array.py)."""
    import contextlib
    for a in arrays:
        if getattr(a, "size", 0) > _INT32_MAX:
            return jax.enable_x64(True)
    return contextlib.nullcontext()


def _nan_guard_eager():
    from . import inspector as _inspector
    return _inspector.nan_guard_enabled()


# ---------------------------------------------------------- dispatch ----
def imperative_invoke(op_name, nd_inputs, attrs, out=None, input_names=None):
    """Eager dispatch (analogue of MXImperativeInvoke →
    Imperative::Invoke, src/imperative/imperative.cc:89): close the
    registered pure function over static attrs, run it (XLA compiles &
    caches per shape/dtype), and record a jax.vjp pullback on the tape when
    inside autograd.record()."""
    op = ops.get(op_name)
    datas = [a._data for a in nd_inputs]
    kw = {k: v for k, v in attrs.items() if v is not None or k in ("axis", "axes")}

    has_varargs, param_names = ops.op_dispatch_meta(op)
    if "is_train" in param_names and "is_train" not in kw:
        kw["is_train"] = autograd.is_training()
    if op.stateful_rng and "rng_key" in param_names \
            and kw.get("rng_key") is None:
        kw["rng_key"] = _random_mod.next_key()

    if has_varargs:
        arr_names = None
    elif input_names is not None:
        arr_names = list(input_names)
    else:
        arr_names = [p for p in param_names if p not in kw][:len(datas)]

    def fn(*xs):
        if has_varargs:
            return op.fn(*xs, **kw)
        call = dict(kw)
        call.update(zip(arr_names, xs))
        return op.fn(**call)

    record = (autograd.is_recording() and op.differentiable and datas
              and any(a._requires_tape() for a in nd_inputs))
    ctx = nd_inputs[0]._ctx if nd_inputs else current_context()

    with _large_tensor_ctx(datas):
        if record:
            outs, vjp_fn, compiled = _recorded_vjp(
                op, kw, arr_names, has_varargs, datas)
            multi = isinstance(outs, (tuple, list))
            outs_list = list(outs) if multi else [outs]
            out_shapes = [tuple(o.shape) for o in outs_list]
            out_dtypes = [o.dtype for o in outs_list]
            kind = autograd.TapeNode
            if compiled:
                kind = autograd.ProgramNode
                vjp_fn = autograd.apply_vjp(vjp_fn, out_shapes, out_dtypes)
            node = kind(vjp_fn, list(nd_inputs), len(outs_list),
                        out_shapes, out_dtypes, op_name=op_name)
            autograd._record_node(node)
            results = []
            for k, o in enumerate(outs_list):
                r = NDArray(o, ctx)
                r._ag_node = (node, k)
                results.append(r)
        else:
            outs = fn(*datas)
            multi = isinstance(outs, (tuple, list))
            outs_list = list(outs) if multi else [outs]
            results = [NDArray(o, ctx) for o in outs_list]

    if _nan_guard_eager():
        from . import inspector as _inspector
        for r in results:
            _inspector.guard_value(r._data, "%s (eager)" % op_name)

    if op.mutate_inputs:
        # in-place state semantics (reference FMutateInputs, e.g. the
        # optimizer update ops' momentum/mean/var): the op fn returns its
        # primary outputs followed by the new values of the mutable
        # inputs, which are written back into the passed NDArrays here.
        # A callable spec resolves positions from the attrs (fused multi-
        # weight ops, whose state slots depend on num_weights).
        mutate_spec = op.mutate_inputs(attrs) if callable(op.mutate_inputs) \
            else op.mutate_inputs
        n_mut = len(mutate_spec)
        new_states = results[-n_mut:] if n_mut else []
        results = results[:-n_mut] if n_mut else results
        multi = len(results) > 1
        for spec, new in zip(mutate_spec, new_states):
            if isinstance(spec, int):
                idx = spec
            elif arr_names and spec in arr_names:
                idx = arr_names.index(spec)
            else:
                continue
            if idx < len(nd_inputs):
                nd_inputs[idx]._data = new._data

    datas = [r._data for r in results]
    engine.sync_if_needed(datas)

    # MXNet-facing output arity (reference ops expose aux outputs only on
    # request: BatchNorm output_mean_var, RNN state_outputs)
    if op_name in ("BatchNorm", "_contrib_SyncBatchNorm") and \
            not attrs.get("output_mean_var", False):
        results, multi = results[:1], False
    elif op_name == "RNN" and not attrs.get("state_outputs", False):
        results, multi = results[:1], False

    if out is not None:
        tgt = out if isinstance(out, (tuple, list)) else [out]
        for t, r in zip(tgt, results):
            t._data = r._data
        return out
    return results if multi else results[0]


def _make_op_func(op_name):
    op = ops.get(op_name)
    sig = ops.op_signature(op_name)
    has_varargs = any(p.kind == inspect.Parameter.VAR_POSITIONAL
                      for p in sig.parameters.values())
    pnames = [p.name for p in sig.parameters.values()
              if p.kind == inspect.Parameter.POSITIONAL_OR_KEYWORD]

    def func(*args, out=None, name=None, **kwargs):
        named_inputs = []  # (param_name, NDArray)
        attrs = {}
        if has_varargs:
            arrs = []
            for a in args:
                if isinstance(a, (list, tuple)):
                    arrs.extend(a)
                else:
                    arrs.append(a)
            named_inputs = [(None, _as_nd(a)) for a in arrs]
        else:
            for pname, val in zip(pnames, args):
                if isinstance(val, (NDArray, np.ndarray, jax.Array)):
                    named_inputs.append((pname, _as_nd(val)))
                elif val is not None:
                    attrs[pname] = val
        for k, v in kwargs.items():
            if isinstance(v, (NDArray, np.ndarray, jax.Array)):
                named_inputs.append((k, _as_nd(v)))
            elif k not in ("ctx", "dtype"):
                attrs[k] = _canon_attr(v)
            elif k == "dtype" and v is not None:
                attrs["dtype"] = np.dtype(v).name if not isinstance(v, str) else v
        if has_varargs:
            nd_inputs = [a for _, a in named_inputs]
            input_names = None
        else:
            # bind tensors to op parameters by name, in signature order —
            # not by call-site ordering
            order = {p: i for i, p in enumerate(pnames)}
            named_inputs.sort(key=lambda t: order.get(t[0], 99))
            nd_inputs = [a for _, a in named_inputs]
            input_names = [n for n, _ in named_inputs]
        return imperative_invoke(op_name, nd_inputs, attrs, out=out,
                                 input_names=input_names)

    func.__name__ = op_name
    func.__doc__ = (op.fn.__doc__ or "") + "\n\n(auto-generated from op registry)"
    return func


def _canon_attr(v):
    if isinstance(v, str):
        low = v.strip()
        if low.startswith("(") or low.startswith("["):
            try:
                return tuple(int(x) for x in low.strip("()[]").split(",") if x.strip())
            except ValueError:
                return v
        for cast in (int, float):
            try:
                return cast(low)
            except ValueError:
                pass
        if low in ("True", "true"):
            return True
        if low in ("False", "false"):
            return False
    return v


def _as_nd(v, ctx=None, dtype=None):
    if isinstance(v, NDArray):
        return v
    if isinstance(v, jax.Array):
        return NDArray(v, ctx)
    return array(v, ctx=ctx, dtype=dtype)


# Synthesize the op namespace (mirrors python/mxnet/ndarray/register.py:258).
_g = globals()
for _name in ops.list_ops():
    if _name not in _g:
        _g[_name] = _make_op_func(_name)
for _alias, _real in list(ops._ALIAS.items()):
    if _alias not in _g:
        _g[_alias] = _make_op_func(_alias)

# Friendlier python names used by NDArray methods above
slice_op = _g["slice"]
abs_op = _g["abs"]
maximum_nd = _g["broadcast_maximum"]
minimum_nd = _g["broadcast_minimum"]


def _binary_with_scalar(nd_op, scalar_op):
    def f(lhs, rhs):
        if isinstance(rhs, NDArray) and isinstance(lhs, NDArray):
            return nd_op(lhs, rhs)
        if isinstance(lhs, NDArray):
            return imperative_invoke(scalar_op, [lhs], {"scalar": float(rhs)})
        # lhs scalar
        rev = {"_plus_scalar": "_plus_scalar", "_mul_scalar": "_mul_scalar",
               "_minus_scalar": "_rminus_scalar", "_div_scalar": "_rdiv_scalar",
               "_power_scalar": "_rpower_scalar", "_mod_scalar": "_rmod_scalar",
               "_maximum_scalar": "_maximum_scalar",
               "_minimum_scalar": "_minimum_scalar",
               "_equal_scalar": "_equal_scalar",
               "_not_equal_scalar": "_not_equal_scalar",
               "_greater_scalar": "_lesser_scalar",
               "_greater_equal_scalar": "_lesser_equal_scalar",
               "_lesser_scalar": "_greater_scalar",
               "_lesser_equal_scalar": "_greater_equal_scalar"}[scalar_op]
        return imperative_invoke(rev, [rhs], {"scalar": float(lhs)})
    return f


add = _binary_with_scalar(_g["broadcast_add"], "_plus_scalar")
subtract = _binary_with_scalar(_g["broadcast_sub"], "_minus_scalar")
multiply = _binary_with_scalar(_g["broadcast_mul"], "_mul_scalar")
divide = _binary_with_scalar(_g["broadcast_div"], "_div_scalar")
modulo = _binary_with_scalar(_g["broadcast_mod"], "_mod_scalar")
power = _binary_with_scalar(_g["broadcast_power"], "_power_scalar")
maximum = _binary_with_scalar(_g["broadcast_maximum"], "_maximum_scalar")
minimum = _binary_with_scalar(_g["broadcast_minimum"], "_minimum_scalar")
equal = _binary_with_scalar(_g["broadcast_equal"], "_equal_scalar")
not_equal = _binary_with_scalar(_g["broadcast_not_equal"], "_not_equal_scalar")
greater = _binary_with_scalar(_g["broadcast_greater"], "_greater_scalar")
greater_equal = _binary_with_scalar(_g["broadcast_greater_equal"], "_greater_equal_scalar")
lesser = _binary_with_scalar(_g["broadcast_lesser"], "_lesser_scalar")
lesser_equal = _binary_with_scalar(_g["broadcast_lesser_equal"], "_lesser_equal_scalar")
true_divide = divide


def _logical_pair(nd_op_name):
    nd_op = _g[nd_op_name]

    def f(lhs, rhs):
        return nd_op(_as_nd(lhs), _as_nd(rhs))
    f.__name__ = nd_op_name.replace("broadcast_", "")
    return f


logical_and = _logical_pair("broadcast_logical_and")
logical_or = _logical_pair("broadcast_logical_or")
logical_xor = _logical_pair("broadcast_logical_xor")


def from_numpy(ndarray_np, zero_copy=False):
    """mx.nd.from_numpy — wrap a numpy array. On TPU host->device always
    copies; zero_copy is accepted for API parity (jax may alias host
    memory for CPU backends)."""
    return array(ndarray_np)


# --------------------------------------------------------- dlpack -------
def to_dlpack_for_read(data):
    """DLPack-protocol object for the array. The reference hands out a
    PyCapsule; the modern protocol exchanges the array object itself
    (jax.Array implements __dlpack__/__dlpack_device__), which is what
    torch.from_dlpack / np.from_dlpack consume."""
    data.wait_to_read()
    return data._data


def to_dlpack_for_write(data):
    """TPU buffers are immutable under XLA: the export is readable, and
    writes through it are a documented divergence (no in-place device
    mutation outside donation)."""
    return to_dlpack_for_read(data)


def from_dlpack(dlpack):
    """Build an NDArray from a DLPack-protocol object (torch tensor,
    numpy array, jax array...)."""
    if not hasattr(dlpack, "__dlpack__"):
        raise MXNetError(
            "from_dlpack needs an object implementing the DLPack protocol "
            "(__dlpack__/__dlpack_device__); raw PyCapsules from legacy "
            "exporters are not supported — pass the tensor itself")
    return NDArray(jnp.from_dlpack(dlpack))


def _ndarray_unpickle(arr, dtype_name, device_type):
    from .context import Context
    ctx = Context(device_type, 0)
    out = array(arr, ctx=ctx)
    if str(out.dtype) != dtype_name:
        out = out.astype(dtype_name)
    return out


# --------------------------------------------------------- creation -----
def _resolve_ctx(ctx):
    """Default-ctx resolution for creation ops."""
    return ctx or current_context()


def array(source_array, ctx=None, dtype=None):
    """mx.nd.array (python/mxnet/ndarray/utils.py)."""
    ctx = _resolve_ctx(ctx)
    if isinstance(source_array, NDArray):
        data = source_array._data
        if dtype is not None:
            data = data.astype(_to_jnp_dtype(dtype))
        return NDArray(jax.device_put(data, ctx.jax_device), ctx)
    if dtype is None:
        if isinstance(source_array, np.ndarray):
            dtype = source_array.dtype
            if dtype == np.float64:
                dtype = np.float32
        else:
            dtype = np.float32
            probe = np.asarray(source_array)
            if probe.dtype.kind in "iub":
                dtype = probe.dtype
    if _dtype_name(dtype) in _DTYPE_ALIAS or \
            _dtype_name(dtype) == "bfloat16":
        # numpy can't represent bf16: stage through fp32, cast on device
        arr = np.asarray(source_array, dtype=np.float32)
        data = jnp.asarray(arr).astype(_to_jnp_dtype(dtype))
        return NDArray(jax.device_put(data, ctx.jax_device), ctx)
    arr = np.asarray(source_array, dtype=np.dtype(dtype))
    if arr.dtype in (np.int64, np.uint64):
        # keep 64-bit VALUES intact (large-tensor indices): outside an
        # x64 scope jnp.asarray would truncate to int32
        with jax.enable_x64(True):
            data = jnp.asarray(arr)
        return NDArray(jax.device_put(data, ctx.jax_device), ctx)
    return NDArray(jax.device_put(jnp.asarray(arr), ctx.jax_device), ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    ctx = _resolve_ctx(ctx)
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(jax.device_put(jnp.zeros(shape, _to_jnp_dtype(dtype)),
                                  ctx.jax_device), ctx)


def ones(shape, ctx=None, dtype=None, **kwargs):
    ctx = _resolve_ctx(ctx)
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(jax.device_put(jnp.ones(shape, _to_jnp_dtype(dtype)),
                                  ctx.jax_device), ctx)


def full(shape, val, ctx=None, dtype=None, **kwargs):
    ctx = _resolve_ctx(ctx)
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(jax.device_put(jnp.full(shape, val, _to_jnp_dtype(dtype)),
                                  ctx.jax_device), ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    ctx = _resolve_ctx(ctx)
    r = jnp.arange(start, stop, step, dtype=_to_jnp_dtype(dtype))
    if repeat > 1:
        r = jnp.repeat(r, repeat)
    return NDArray(jax.device_put(r, ctx.jax_device), ctx)


def eye(N, M=0, k=0, ctx=None, dtype="float32"):
    ctx = _resolve_ctx(ctx)
    return NDArray(jnp.eye(int(N), int(M) if M else None, k=int(k),
                           dtype=_to_jnp_dtype(dtype)), ctx)


def linspace(start, stop, num, endpoint=True, ctx=None, dtype="float32"):
    ctx = _resolve_ctx(ctx)
    return NDArray(jnp.linspace(start, stop, num, endpoint=endpoint,
                                dtype=_to_jnp_dtype(dtype)),
                   ctx)


def concatenate(arrays, axis=0, always_copy=True):
    return _g["Concat"](*arrays, dim=axis)


def waitall():
    from . import engine as _engine
    _engine.wait_for_all()


def moveaxis(tensor, source, destination):
    return NDArray(jnp.moveaxis(tensor._data, source, destination), tensor._ctx)


def split_v2(ary, indices_or_sections, axis=0, squeeze_axis=False):
    """Wrapper over the raw `_split_v2` op; like the reference wrapper it
    converts a tuple of split points to section-start boundaries by
    prepending 0 (python/mxnet/ndarray/ndarray.py split_v2)."""
    if isinstance(indices_or_sections, int):
        attrs = {"sections": indices_or_sections}
    else:
        attrs = {"indices": (0,) + tuple(indices_or_sections), "sections": 0}
    out = imperative_invoke("_split_v2", [_as_nd(ary)],
                            dict(attrs, axis=axis, squeeze_axis=squeeze_axis))
    return out if isinstance(out, list) else [out]


# ------------------------------------------------------------ save/load --
_SAVE_MAGIC = b"MXTPU001"


def save(fname, data):
    """mx.nd.save (src/ndarray/ndarray.cc:1603 binary format analogue).

    Format: magic | json header (names, dtypes, shapes) | raw little-endian
    buffers. Not byte-compatible with the reference (documented divergence:
    no mshadow TBlob serialization here)."""
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        names = list(data.keys())
        arrays = [data[k] for k in names]
    else:
        names = None
        arrays = list(data)
    header = {
        "names": names,
        "dtypes": [str(np.dtype(a.dtype) if a.dtype != jnp.bfloat16 else "bfloat16")
                   for a in arrays],
        "shapes": [list(a.shape) for a in arrays],
    }
    hb = json.dumps(header).encode()
    with open(fname, "wb") as f:
        f.write(_SAVE_MAGIC)
        f.write(struct.pack("<Q", len(hb)))
        f.write(hb)
        for a in arrays:
            arr = a.asnumpy() if a.dtype != jnp.bfloat16 else \
                np.asarray(a._data.astype(jnp.float32))
            f.write(arr.tobytes())


def load(fname):
    with open(fname, "rb") as f:
        try:
            return load_frombuffer(f.read())
        except MXNetError:
            raise MXNetError("invalid NDArray file %s" % fname)


def load_frombuffer(buf):
    """mx.nd.load_frombuffer (c_api MXNDArrayLoadFromBuffer analogue) —
    deserialize from an in-memory bytes object instead of a file."""
    import io as _io
    f = _io.BytesIO(buf)
    magic = f.read(8)
    if magic != _SAVE_MAGIC:
        raise MXNetError("invalid NDArray buffer")
    (hlen,) = struct.unpack("<Q", f.read(8))
    header = json.loads(f.read(hlen).decode())
    arrays = []
    for dt, shp in zip(header["dtypes"], header["shapes"]):
        # bf16 payloads are stored widened to fp32 (see save); np.dtype
        # instance, NOT the np.float32 class — the class's .itemsize is
        # a descriptor and broke every bf16 checkpoint load
        npdt = np.dtype(np.float32) if dt == "bfloat16" else np.dtype(dt)
        n = int(np.prod(shp)) if shp else 1
        raw = f.read(n * npdt.itemsize)
        arr = np.frombuffer(raw, dtype=npdt).reshape(shp)
        a = array(arr)
        if dt == "bfloat16":
            a = a.astype("bfloat16")
        arrays.append(a)
    if header["names"] is not None:
        return dict(zip(header["names"], arrays))
    return arrays


# ------------------------------------------------------------- random ---
class _RandomNamespace:
    """mx.nd.random.* (python/mxnet/ndarray/random.py)."""

    @staticmethod
    def _call(op_both, op_scalar, params, shape, dtype, ctx, **extra):
        if any(isinstance(p, NDArray) for p in params):
            nds = [_as_nd(p) for p in params]
            return imperative_invoke(op_both, nds,
                                     dict(shape=shape, dtype=dtype, **extra))
        attrs = dict(shape=shape if shape != () else (1,), dtype=dtype, **extra)
        return imperative_invoke(op_scalar, [],
                                 dict(attrs, **dict(zip(
                                     ops.op_signature(op_scalar).parameters,
                                     params))))

    @staticmethod
    def uniform(low=0, high=1, shape=(), dtype="float32", ctx=None, out=None, **kw):
        return _RandomNamespace._call("sample_uniform", "_random_uniform",
                                      [low, high], shape, dtype, ctx)

    @staticmethod
    def normal(loc=0, scale=1, shape=(), dtype="float32", ctx=None, out=None, **kw):
        return _RandomNamespace._call("sample_normal", "_random_normal",
                                      [loc, scale], shape, dtype, ctx)

    @staticmethod
    def gamma(alpha=1, beta=1, shape=(), dtype="float32", ctx=None, **kw):
        return _RandomNamespace._call("sample_gamma", "_random_gamma",
                                      [alpha, beta], shape, dtype, ctx)

    @staticmethod
    def exponential(lam=1, shape=(), dtype="float32", ctx=None, **kw):
        return _RandomNamespace._call("sample_exponential", "_random_exponential",
                                      [lam], shape, dtype, ctx)

    @staticmethod
    def poisson(lam=1, shape=(), dtype="float32", ctx=None, **kw):
        return _RandomNamespace._call("sample_poisson", "_random_poisson",
                                      [lam], shape, dtype, ctx)

    @staticmethod
    def negative_binomial(k=1, p=1, shape=(), dtype="float32", ctx=None, **kw):
        return imperative_invoke("_random_negative_binomial", [],
                                 {"k": k, "p": p, "shape": shape or (1,),
                                  "dtype": dtype})

    @staticmethod
    def generalized_negative_binomial(mu=1, alpha=1, shape=(), dtype="float32",
                                      ctx=None, **kw):
        return imperative_invoke("_random_generalized_negative_binomial", [],
                                 {"mu": mu, "alpha": alpha,
                                  "shape": shape or (1,), "dtype": dtype})

    @staticmethod
    def multinomial(data, shape=(), get_prob=False, dtype="int32", **kw):
        return imperative_invoke("_sample_multinomial", [_as_nd(data)],
                                 {"shape": shape, "get_prob": get_prob,
                                  "dtype": dtype})

    @staticmethod
    def randint(low, high, shape=(), dtype="int32", ctx=None, **kw):
        return imperative_invoke("_random_randint", [],
                                 {"low": low, "high": high,
                                  "shape": shape or (1,), "dtype": dtype})

    @staticmethod
    def randn(*shape, loc=0, scale=1, dtype="float32", ctx=None, **kw):
        return _RandomNamespace.normal(loc, scale, shape=shape, dtype=dtype,
                                       ctx=ctx)

    @staticmethod
    def shuffle(data, **kw):
        return _g["shuffle"](data)


random = _RandomNamespace()


# -------------------------------------------------------------- sparse ---
# mx.nd.sparse is the compressed-storage module mxnet_tpu.sparse
# (bound by the package __init__ to avoid a circular import).
sparse = None


class _OpNamespace:
    """nd.op — the bare autogenerated operator namespace (reference
    `mxnet.ndarray.op`): every registry op/alias, nothing else."""

    def __getattr__(self, item):
        if ops.exists(item):
            fn = _g.get(item)
            return fn if callable(fn) else _make_op_func(item)
        raise AttributeError(item)

    def __dir__(self):
        return sorted(set(list(ops._REGISTRY) + list(ops._ALIAS)))


op = _OpNamespace()


class _ContribNamespace:
    """nd.contrib — auto-populated from `_contrib_*` registry entries;
    control-flow entries are the eager Python-loop implementations
    (reference ndarray/contrib.py)."""

    def __getattr__(self, item):
        if item in ("foreach", "while_loop", "cond"):
            from . import control_flow
            return getattr(control_flow, item)
        full = "_contrib_" + item
        if ops.exists(full):
            return _g.get(full) or _make_op_func(full)
        if ops.exists(item):
            return _g.get(item) or _make_op_func(item)
        raise AttributeError(item)


contrib = _ContribNamespace()


class _LinalgNamespace:
    def __getattr__(self, item):
        full = "linalg_" + item
        if ops.exists(full):
            return _g.get(full) or _make_op_func(full)
        raise AttributeError(item)


linalg = _LinalgNamespace()


class _ImageNamespace:
    """nd.image — registered `_image_*` ops (src/operator/image/) first,
    falling back to the mx.image pipeline module helpers."""

    def __getattr__(self, item):
        full = "_image_" + item
        if ops.exists(full):
            return _g.get(full) or _make_op_func(full)
        from . import image as _image
        return getattr(_image, item)


image = _ImageNamespace()


def onehot_encode(indices, out):
    return one_hot(indices, depth=out.shape[1], out=out)


# ------------------------------------------------- fluent methods -------
# The reference NDArray carries "fluent" methods (x.sin(), x.ceil(),
# x.slice_like(other)...) delegating to the namespace functions
# (python/mxnet/ndarray/ndarray.py fluent-method block). Synthesize the
# same surface from the op namespace; names already defined on the
# class (reshape, transpose, ...) are left untouched.
_FLUENT_METHODS = [
    "arccos", "arccosh", "arcsin", "arcsinh", "arctan", "arctanh",
    "argmax_channel", "broadcast_axes", "cbrt", "ceil", "cos", "cosh",
    "degrees", "depth_to_space", "diag", "expm1", "fix", "floor",
    "log10", "log1p", "log2", "nanprod", "nansum", "radians", "rcbrt",
    "reciprocal", "rint", "round", "rsqrt", "shape_array", "sin",
    "sinh", "size_array", "slice_like", "softmin", "space_to_depth",
    "tan", "trunc", "exp", "log", "sqrt", "square", "abs", "sign",
    "relu", "sigmoid", "tanh", "softmax", "log_softmax", "argsort",
    "sort", "clip", "norm",
]


def _make_fluent(name):
    fn = _g[name]

    def method(self, *args, **kwargs):
        return fn(self, *args, **kwargs)
    method.__name__ = name
    method.__doc__ = "Fluent form of nd.%s(self, ...)." % name
    return method


for _name in _FLUENT_METHODS:
    if _name in _g and not hasattr(NDArray, _name):
        setattr(NDArray, _name, _make_fluent(_name))


def _nd_slice_assign(self, rhs, begin, end, step=()):
    return _g["_slice_assign"](self, rhs, begin=begin, end=end, step=step)


def _nd_slice_assign_scalar(self, scalar, begin, end, step=()):
    return _g["_slice_assign_scalar"](self, scalar=scalar, begin=begin,
                                      end=end, step=step)


NDArray.slice_assign = _nd_slice_assign
NDArray.slice_assign_scalar = _nd_slice_assign_scalar
NDArray.split_v2 = lambda self, *a, **kw: split_v2(self, *a, **kw)
NDArray.to_dlpack_for_read = lambda self: to_dlpack_for_read(self)
NDArray.to_dlpack_for_write = lambda self: to_dlpack_for_write(self)


def _as_np_ndarray(self):
    """View as an mx.np ndarray (the numpy-semantics array type shares
    the jax buffer)."""
    from . import numpy as _mxnp
    return _mxnp.array(self._data)


NDArray.as_np_ndarray = _as_np_ndarray
NDArray.as_nd_ndarray = lambda self: self
