"""mx.np — NumPy-compatible array API.

Reference: python/mxnet/numpy/ (4.2k LoC) backed by src/operator/numpy/
(np_dot, tensordot, broadcast arithmetic, init, matrix ops, cumsum,
true_divide, np random).

TPU-native design: jax.numpy IS a NumPy-semantics array library, so
this layer is a faithful veneer: every function unwraps `ndarray`
operands to jax arrays, calls the jnp equivalent, and wraps the result.
Ops run on-device and fuse under jit like any other framework op. The
`ndarray` here interoperates with classic mx.nd.NDArray (shared _data)."""

import numpy as _onp

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..context import current_context
from .. import ndarray as _classic


class ndarray(_classic.NDArray):
    """NumPy-semantics array (reference numpy/multiarray.py ndarray)."""

    __slots__ = ()

    def __repr__(self):
        return "array(%s)" % _onp.array2string(self.asnumpy(),
                                               separator=", ")

    def __array__(self, dtype=None):
        out = self.asnumpy()
        return out.astype(dtype) if dtype is not None else out

    def __getitem__(self, key):
        out = super(ndarray, self).__getitem__(key)
        return _wrap(out._data) if isinstance(out, _classic.NDArray) else out

    def as_nd_ndarray(self):
        return _classic.NDArray(self._data, self._ctx)

    def tolist(self):
        return self.asnumpy().tolist()

    def item(self, *args):
        return self.asnumpy().item(*args)

    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return _wrap(jnp.reshape(self._data, shape))

    def transpose(self, *axes):
        if len(axes) == 0:
            axes = None
        elif len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return _wrap(jnp.transpose(self._data, axes))

    def sum(self, axis=None, dtype=None, keepdims=False):
        return _wrap(jnp.sum(self._data, axis=axis, dtype=dtype,
                             keepdims=keepdims))

    def mean(self, axis=None, dtype=None, keepdims=False):
        return _wrap(jnp.mean(self._data, axis=axis, dtype=dtype,
                              keepdims=keepdims))

    def max(self, axis=None, keepdims=False):
        return _wrap(jnp.max(self._data, axis=axis, keepdims=keepdims))

    def min(self, axis=None, keepdims=False):
        return _wrap(jnp.min(self._data, axis=axis, keepdims=keepdims))

    def astype(self, dtype, copy=True):
        return _wrap(self._data.astype(dtype))

    @property
    def T(self):
        return _wrap(jnp.transpose(self._data))


# arithmetic/comparison dunders must return mx.np.ndarray, not the
# classic NDArray the inherited operators construct
def _np_binop(jnp_fn, swap=False):
    def op(self, other):
        o = other._data if isinstance(other, _classic.NDArray) else other
        a, b = (o, self._data) if swap else (self._data, o)
        return _wrap(jnp_fn(a, b))
    return op


for _dunder, _fn, _swap in [
        ("__add__", jnp.add, False), ("__radd__", jnp.add, True),
        ("__sub__", jnp.subtract, False), ("__rsub__", jnp.subtract, True),
        ("__mul__", jnp.multiply, False), ("__rmul__", jnp.multiply, True),
        ("__truediv__", jnp.divide, False),
        ("__rtruediv__", jnp.divide, True),
        ("__floordiv__", jnp.floor_divide, False),
        ("__mod__", jnp.mod, False), ("__pow__", jnp.power, False),
        ("__rpow__", jnp.power, True),
        ("__matmul__", jnp.matmul, False),
        ("__eq__", jnp.equal, False), ("__ne__", jnp.not_equal, False),
        ("__lt__", jnp.less, False), ("__le__", jnp.less_equal, False),
        ("__gt__", jnp.greater, False),
        ("__ge__", jnp.greater_equal, False)]:
    setattr(ndarray, _dunder, _np_binop(_fn, _swap))
ndarray.__neg__ = lambda self: _wrap(jnp.negative(self._data))
ndarray.__abs__ = lambda self: _wrap(jnp.abs(self._data))
ndarray.__hash__ = None


def _wrap(data):
    return ndarray(jnp.asarray(data), current_context())


def _unwrap(x):
    if isinstance(x, _classic.NDArray):
        return x._data
    if isinstance(x, (list, tuple)):
        return type(x)(_unwrap(i) for i in x)
    return x


def array(object, dtype=None, ctx=None):
    return ndarray(jnp.asarray(_unwrap(object), dtype=dtype),
                   ctx or current_context())


def _make(name, fn):
    def wrapper(*args, **kwargs):
        out_arr = kwargs.pop("out", None)
        args = [_unwrap(a) for a in args]
        kwargs = {k: _unwrap(v) for k, v in kwargs.items() if k != "ctx"}
        out = fn(*args, **kwargs)
        if out_arr is not None:
            # honour out= by writing the result into the given array
            out_arr._data = jnp.asarray(out).astype(out_arr.dtype)
            return out_arr
        if isinstance(out, (list, tuple)):
            return type(out)(_wrap(o) if hasattr(o, "shape") else o
                             for o in out)
        return _wrap(out) if hasattr(out, "shape") else out
    wrapper.__name__ = name
    wrapper.__doc__ = "mx.np.%s — jax.numpy-backed (reference " \
        "src/operator/numpy/)" % name
    return wrapper


_FUNCS = [
    # creation
    "zeros", "ones", "full", "empty", "arange", "linspace", "logspace",
    "eye", "identity", "zeros_like", "ones_like", "full_like", "meshgrid",
    "tril", "triu", "diag", "diagflat", "diagonal",
    # manipulation
    "reshape", "transpose", "swapaxes", "moveaxis", "rollaxis",
    "expand_dims", "squeeze", "concatenate", "stack", "vstack", "hstack",
    "dstack", "column_stack", "split", "array_split", "hsplit", "vsplit",
    "dsplit", "tile", "repeat", "flip", "fliplr", "flipud", "roll",
    "rot90", "broadcast_to", "broadcast_arrays", "atleast_1d",
    "atleast_2d", "atleast_3d", "ravel", "flatnonzero", "pad", "append",
    "unique", "trim_zeros",
    # math
    "add", "subtract", "multiply", "divide", "true_divide", "floor_divide",
    "mod", "remainder", "fmod", "power", "float_power", "negative",
    "positive", "absolute", "abs", "fabs", "sign", "rint", "fix", "ceil",
    "floor", "trunc", "around", "round", "clip", "sqrt", "cbrt", "square",
    "reciprocal", "exp", "expm1", "exp2", "log", "log2", "log10", "log1p",
    "logaddexp", "logaddexp2", "sin", "cos", "tan", "arcsin", "arccos",
    "arctan", "arctan2", "hypot", "sinh", "cosh", "tanh", "arcsinh",
    "arccosh", "arctanh", "degrees", "radians", "deg2rad", "rad2deg",
    "maximum", "minimum", "fmax", "fmin", "heaviside", "gcd", "lcm",
    "interp", "ldexp", "nan_to_num", "real", "imag", "conj", "angle",
    # reductions / scans
    "sum", "prod", "mean", "std", "var", "median", "average", "quantile",
    "percentile", "amax", "amin", "max", "min", "ptp", "cumsum", "cumprod",
    "nansum", "nanprod", "nanmean", "nanstd", "nanvar", "nanmax", "nanmin",
    "argmax", "argmin", "nanargmax", "nanargmin", "count_nonzero",
    # products
    "dot", "vdot", "inner", "outer", "tensordot", "matmul", "einsum",
    "kron", "cross", "trace",
    # comparison / logic
    "equal", "not_equal", "greater", "greater_equal", "less", "less_equal",
    "logical_and", "logical_or", "logical_xor", "logical_not", "isnan",
    "isinf", "isfinite", "isposinf", "isneginf", "allclose", "isclose",
    "array_equal", "all", "any", "where", "nonzero", "argwhere",
    # sorting / searching
    "sort", "argsort", "partition", "argpartition", "searchsorted",
    "lexsort", "take", "take_along_axis", "choose", "compress", "extract",
    # misc
    "copysign", "signbit", "spacing", "nextafter", "bincount", "histogram",
    "digitize", "cov", "corrcoef", "convolve", "correlate", "gradient",
    "diff", "ediff1d", "floor_divide", "float_power", "may_share_memory",
    "shares_memory", "result_type", "can_cast", "promote_types",
]

_g = globals()
import warnings as _warnings
with _warnings.catch_warnings():
    # probing jnp attributes must not surface deprecation warnings at
    # import time (e.g. jnp.fix in jax 0.9)
    _warnings.simplefilter("ignore", DeprecationWarning)
    for _n in _FUNCS:
        if hasattr(jnp, _n):
            _g[_n] = _make(_n, getattr(jnp, _n))
# jnp.fix is deprecated (removed in jax 0.10); keep np.fix alive via
# trunc, which is the same round-toward-zero operation
fix = _make("fix", jnp.trunc)

# dtype aliases
float16 = _onp.float16
float32 = _onp.float32
float64 = _onp.float64
bfloat16 = jnp.bfloat16
int8 = _onp.int8
int16 = _onp.int16
int32 = _onp.int32
int64 = _onp.int64
uint8 = _onp.uint8
bool_ = _onp.bool_
pi = _onp.pi
e = _onp.e
euler_gamma = _onp.euler_gamma
inf = _onp.inf
nan = _onp.nan
newaxis = None


class _Linalg(object):
    """mx.np.linalg (reference numpy/linalg.py)."""

    def __getattr__(self, name):
        fn = getattr(jnp.linalg, name, None)
        if fn is None:
            raise AttributeError("np.linalg has no %s" % name)
        return _make("linalg." + name, fn)


linalg = _Linalg()


class _Random(object):
    """mx.np.random (reference numpy/random.py) — stateful seed over the
    framework's threefry key (mxnet_tpu.random)."""

    def _key(self):
        from .. import random as _rand
        return _rand.next_key()

    def seed(self, s):
        from .. import random as _rand
        _rand.seed(s)

    def uniform(self, low=0.0, high=1.0, size=None, dtype=None, ctx=None):
        size = size if size is not None else ()
        out = jax.random.uniform(self._key(), shape=_tup(size),
                                 minval=low, maxval=high,
                                 dtype=dtype or jnp.float32)
        return _wrap(out)

    def normal(self, loc=0.0, scale=1.0, size=None, dtype=None, ctx=None):
        size = size if size is not None else ()
        out = loc + scale * jax.random.normal(
            self._key(), shape=_tup(size), dtype=dtype or jnp.float32)
        return _wrap(out)

    def randint(self, low, high=None, size=None, dtype=None, ctx=None):
        if high is None:
            low, high = 0, low
        size = size if size is not None else ()
        out = jax.random.randint(self._key(), _tup(size), low, high,
                                 dtype=dtype or jnp.int32)
        return _wrap(out)

    def choice(self, a, size=None, replace=True, p=None, ctx=None):
        a = _unwrap(a)
        out = jax.random.choice(self._key(), a, shape=_tup(size or ()),
                                replace=replace,
                                p=_unwrap(p) if p is not None else None)
        return _wrap(out)

    def shuffle(self, x):
        data = jax.random.permutation(self._key(), x._data)
        x._data = data

    def rand(self, *shape):
        return self.uniform(size=shape)

    def randn(self, *shape):
        return self.normal(size=shape)

    def multinomial(self, n, pvals, size=None):
        out = jax.random.multinomial(
            self._key(), n, jnp.asarray(_unwrap(pvals)),
            shape=_tup(size) if size is not None else None)
        return _wrap(out)

    def gamma(self, shape=1.0, scale=1.0, size=None, dtype=None, ctx=None):
        size = size if size is not None else ()
        out = scale * jax.random.gamma(self._key(), shape,
                                       shape=_tup(size))
        return _wrap(out)

    def exponential(self, scale=1.0, size=None, ctx=None):
        size = size if size is not None else ()
        return _wrap(scale * jax.random.exponential(self._key(),
                                                    shape=_tup(size)))


def _tup(size):
    if size is None:
        return ()
    if isinstance(size, int):
        return (size,)
    return tuple(size)


random = _Random()


def shape(a):
    return _unwrap(a).shape


def ndim(a):
    return _unwrap(a).ndim


def size(a):
    return int(_unwrap(a).size)
