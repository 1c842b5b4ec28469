"""Global RNG state.

Reference: python/mxnet/random.py (mx.random.seed) backed by per-device
Philox resource states (src/operator/random/). TPU-native: a functional
threefry key chain. Eager ops split from a host-held key; traced code
(CachedOp / executor / jitted train steps) pushes a *tracer* key onto the
stack so every dropout/sampler inside the trace derives from a key that is
a real input of the compiled computation — which is what keeps compiled
randomness fresh across calls instead of baked in as a constant.
"""

import threading

import jax

_state = threading.local()


def _stack():
    if not hasattr(_state, "keys"):
        _state.keys = [jax.random.PRNGKey(0)]
    return _state.keys


def seed(seed_state, ctx="all"):
    """mx.random.seed (python/mxnet/random.py:38)."""
    _stack()[:] = [jax.random.PRNGKey(int(seed_state))]


def next_key():
    """Split a fresh subkey off the innermost key scope."""
    st = _stack()
    st[-1], sub = jax.random.split(st[-1])
    return sub


class key_scope:
    """Push an explicit (possibly traced) key for the duration of a trace."""

    def __init__(self, key):
        self.key = key

    def __enter__(self):
        _stack().append(self.key)
        return self

    def __exit__(self, *a):
        _stack().pop()


# Convenience samplers mirroring mx.random.* (python/mxnet/ndarray/random.py)
def _nd():
    from . import ndarray as nd
    return nd


def uniform(low=0, high=1, shape=(), dtype="float32", ctx=None, out=None, **kw):
    return _nd().random.uniform(low, high, shape=shape, dtype=dtype, ctx=ctx)


def normal(loc=0, scale=1, shape=(), dtype="float32", ctx=None, out=None, **kw):
    return _nd().random.normal(loc, scale, shape=shape, dtype=dtype, ctx=ctx)


def randn(*shape, **kw):
    return normal(shape=shape, **kw)


def poisson(lam=1, shape=(), dtype="float32", ctx=None, **kw):
    return _nd().random.poisson(lam, shape=shape, dtype=dtype, ctx=ctx)


def exponential(scale=1, shape=(), dtype="float32", ctx=None, **kw):
    return _nd().random.exponential(1.0 / scale, shape=shape, dtype=dtype, ctx=ctx)


def gamma(alpha=1, beta=1, shape=(), dtype="float32", ctx=None, **kw):
    return _nd().random.gamma(alpha, beta, shape=shape, dtype=dtype, ctx=ctx)


def negative_binomial(k=1, p=1, shape=(), dtype="float32", ctx=None, **kw):
    return _nd().random.negative_binomial(k, p, shape=shape, dtype=dtype, ctx=ctx)


def generalized_negative_binomial(mu=1, alpha=1, shape=(), dtype="float32",
                                  ctx=None, **kw):
    return _nd().random.generalized_negative_binomial(mu, alpha, shape=shape,
                                                      dtype=dtype, ctx=ctx)


def multinomial(data, shape=(), get_prob=False, dtype="int32", **kw):
    return _nd().random.multinomial(data, shape=shape, get_prob=get_prob,
                                    dtype=dtype)


def randint(low, high, shape=(), dtype="int32", ctx=None, **kw):
    return _nd().random.randint(low, high, shape=shape, dtype=dtype, ctx=ctx)


def shuffle(data, **kw):
    return _nd().shuffle(data)
