"""Optimizers.

Reference: python/mxnet/optimizer/optimizer.py:48-1672 (Optimizer base with
registry + 17 optimizers) and the fused C++ update kernels in
src/operator/optimizer_op.cc:47-893.

TPU-native design: each update rule is a pure jnp function jit-compiled by
XLA (the analogue of the fused `sgd_mom_update`/`adam_update` kernels —
XLA fuses the elementwise chain into one HBM pass). Hyper-parameters that
change per step (lr, wd, rescale) are passed as traced scalars so a
changing schedule never recompiles. States live as jax.Arrays inside
NDArrays, matching `create_state`/`update` semantics that kvstore's
server-side Updater also consumes. Given every parameter of a step in one
call (gluon.Trainer), the Updater runs the rules that have a pure kernel
(SGD, NAG, Adam) as ONE multi-tensor program: the analogue of the
reference's `multi_mp_sgd_mom_update` aggregation.
"""

import functools
import math
import pickle

import numpy as np
import jax
import jax.numpy as jnp

from . import ndarray as nd
from .ndarray import NDArray
from .base import MXNetError
from .observability import core as _obs

__all__ = ["Optimizer", "SGD", "NAG", "Signum", "FTML", "DCASGD", "LBSGD",
           "SGLD", "Adam", "AdaGrad", "AdaDelta", "RMSProp", "Ftrl",
           "Adamax", "Nadam", "Test", "Updater", "get_updater", "create",
           "register"]

_OPT_REGISTRY = {}


def register(klass):
    """Optimizer.register decorator (optimizer.py:93)."""
    name = klass.__name__.lower()
    _OPT_REGISTRY[name] = klass
    return klass


def create(name, **kwargs):
    """mx.optimizer.create (optimizer.py:139)."""
    if name.lower() not in _OPT_REGISTRY:
        raise ValueError("Cannot find optimizer %s" % name)
    return _OPT_REGISTRY[name.lower()](**kwargs)


def _align_update_devices(weight, grad, state):
    """Reconcile weight/grad device placement before a fused update.

    Data-parallel training with a batch sharded over a Mesh produces
    grads committed to the mesh (replicated — XLA inserted the psum),
    while weights initialized before the mesh existed sit committed to
    one device; jit refuses to mix them. Promote the weight (and its
    optimizer state) onto the wider device set — the update then runs
    replicated on the mesh with no per-step broadcast, the sharded-
    global-array analogue of the reference's per-device weight copies
    (module/executor_group.py DP semantics). If instead the WEIGHT
    spans more devices, bring the grad to it (pull-to-master)."""
    gdata = getattr(grad, "_data", None)
    wdata = getattr(weight, "_data", None)
    gs = getattr(gdata, "sharding", None)
    ws = getattr(wdata, "sharding", None)
    if gs is None or ws is None:
        return grad
    try:
        gdev, wdev = gs.device_set, ws.device_set
    except AttributeError:
        return grad
    if gdev == wdev:
        # weight/grad agree, but state buffers created lazily by the
        # Updater land on the default device — align them to the
        # weight's (possibly mesh-replicated) sharding or the fused
        # update kernel refuses the device mix
        _align_state_tree(state, ws)
        return grad
    if len(gdev) > len(wdev):
        weight._data = jax.device_put(wdata, gs)
        _align_state_tree(state, gs)
    else:
        # shallow wrapper: the caller's grad must stay untouched, but
        # the moved buffer needs no copy of the original
        grad = NDArray(jax.device_put(gdata, ws), grad.context)
    return grad


def _align_state_tree(state, sharding):
    for leaf in _state_leaves(state):
        data = getattr(leaf, "_data", None)
        if data is not None and getattr(data, "sharding", None) is not None \
                and data.sharding.device_set != sharding.device_set:
            leaf._data = jax.device_put(data, sharding)


def _flt(x):
    return jnp.asarray(x, dtype=jnp.float32)


def _state_leaves(state):
    """The NDArrays of one optimizer state (None, one, or tuples of
    them, as ``(master, (mean, var))``), flat and in order."""
    if state is None:
        return ()
    if isinstance(state, (tuple, list)):
        return tuple(leaf for s in state for leaf in _state_leaves(s))
    return (state,)


class Optimizer(object):
    """Base optimizer (optimizer.py:48): lr/wd multipliers resolved per
    param index, gradient rescale + clip, update-count tracking for
    schedulers and bias correction."""

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        if lr_scheduler is not None:
            lr_scheduler.base_lr = learning_rate
        assert param_idx2name is None or isinstance(param_idx2name, dict)
        self.__dict__.update(
            rescale_grad=rescale_grad, lr=learning_rate,
            lr_scheduler=lr_scheduler, wd=wd,
            begin_num_update=begin_num_update,
            num_update=begin_num_update, _index_update_count={},
            clip_gradient=clip_gradient,
            multi_precision=multi_precision,
            idx2name=dict(param_idx2name or {}),
            sym_info=(sym.attr_dict(), sym.list_arguments())
            if sym is not None else (),
            param_dict=param_dict or {})
        self.set_lr_mult({})
        self.set_wd_mult({})

    create_optimizer = staticmethod(create)
    opt_registry = _OPT_REGISTRY

    @staticmethod
    def register(klass):
        return register(klass)

    def _take(self, **hyper):
        """Bind rule hyperparameters as attributes in one shot."""
        self.__dict__.update(hyper)

    # ------------------------------------------------------------ state --
    def create_state(self, index, weight):
        return None

    def _zeros_like(self, weight, dtype=None):
        """Fresh state buffer shaped/placed like the weight."""
        return nd.zeros(weight.shape, weight.context,
                        dtype=dtype or weight.dtype)

    def create_state_multi_precision(self, index, weight):
        """fp32 master copy for bf16 weights (optimizer.py:278)."""
        if self.multi_precision and weight.dtype == jnp.bfloat16:
            master = weight.astype("float32")
            return (master, self.create_state(index, master))
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        """Apply one step. The base implementation is a template: it
        advances the per-index step count, resolves the scheduled/
        multiplied hyperparameters, and hands off to the subclass's
        ``_apply_rule`` — so rule implementations hold ONLY math.
        Subclasses may still override update() wholesale (the
        reference's extension contract, honored for external code)."""
        self._update_count(index)
        self._apply_rule(self._index_update_count[index],
                         self._get_lr(index), self._get_wd(index),
                         weight, grad, state)

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        raise NotImplementedError()

    # A rule that is a pure function of its arrays states it ONCE, as
    # ``_kernel(w, g, states, lr, wd, hyper) -> (w, states)`` over jax
    # arrays (a staticmethod): ``_apply_kernel`` runs it for one
    # parameter, the Updater's fused program for all of them.
    _kernel = None

    def _kernel_hyper(self):
        """The rule's own scalars (momentum, betas), traced operands."""
        return ()

    def _kernel_lr(self, t, lr):
        """The learning rate the kernel gets at a parameter's step ``t``
        (Adam folds its bias correction in here, on the host)."""
        return lr

    def _apply_kernel(self, t, lr, wd, weight, grad, state):
        """``_apply_rule`` of a rule that has a ``_kernel``."""
        leaves = _state_leaves(state)
        weight._data, new = self._kernel(
            weight._data, self._preprocess_grad(grad),
            tuple(s._data for s in leaves),
            _flt(self._kernel_lr(t, lr)), _flt(wd),
            tuple(_flt(h) for h in self._kernel_hyper()))
        for leaf, data in zip(leaves, new):
            leaf._data = data

    def update_multi_precision(self, index, weight, grad, state):
        grad = _align_update_devices(weight, grad, state)
        if self.multi_precision and weight.dtype == jnp.bfloat16:
            weight_master_copy, original_state = state
            grad32 = grad.astype("float32")
            self.update(index, weight_master_copy, grad32, original_state)
            weight._data = weight_master_copy._data.astype(jnp.bfloat16)
        else:
            # keep the weight's storage dtype: fp32 state/lr arithmetic
            # promotes bf16 weights to fp32 inside update(), and writing
            # that back would silently un-cast a low-precision network
            wdtype = weight.dtype
            self.update(index, weight, grad, state)
            if weight.dtype != wdtype:
                weight._data = weight._data.astype(wdtype)

    # -------------------------------------------------------- lr/wd mult --
    @property
    def learning_rate(self):
        """Current base lr (optimizer.py learning_rate property)."""
        return self.lr if self.lr_scheduler is None \
            else self.lr_scheduler(self.num_update)

    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise UserWarning("LRScheduler of the optimizer has already "
                              "been defined; setting lr directly would "
                              "be overridden at the next update")
        self.lr = lr

    def _sym_multipliers(self, attr_key):
        """Per-name multipliers declared as symbol attributes
        (``__lr_mult__`` / ``__wd_mult__``) when the optimizer was built
        from a Symbol."""
        if not self.sym_info:
            return {}
        attrs, arg_names = self.sym_info
        found = ((name, attrs.get(name, {}).get(attr_key))
                 for name in arg_names)
        return {name: float(mult) for name, mult in found
                if mult is not None}

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = self._sym_multipliers("__lr_mult__")
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        # decay applies to weights and BN gammas; every other named
        # param (bias, beta, moving stats) defaults to no decay
        self.wd_mult = {n: 0.0 for n in self.idx2name.values()
                        if not n.endswith(("_weight", "_gamma"))}
        self.wd_mult.update(self._sym_multipliers("__wd_mult__"))
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        indices = index if isinstance(index, (list, tuple)) else (index,)
        for idx in indices:
            seen = self._index_update_count.get(idx,
                                                self.begin_num_update) + 1
            self._index_update_count[idx] = seen
            if seen > self.num_update:
                self.num_update = seen

    def _scaled_hyper(self, indices, base, which):
        """``base`` scaled by each param's multiplier. Precedence: the
        Parameter object's own mult (param_dict, Gluon path), then an
        explicit per-index entry, then the index's resolved name in the
        mult table (Module path); absent everywhere = 1."""
        table = getattr(self, which + "_mult")
        out = []
        for index in indices:
            if index in self.param_dict:
                mult = getattr(self.param_dict[index], which + "_mult")
            elif index in table:
                mult = table[index]
            else:
                mult = table.get(self.idx2name.get(index), 1.0)
            out.append(base * mult)
        return out

    def _get_lrs(self, indices):
        base = self.lr_scheduler(self.num_update) \
            if self.lr_scheduler is not None else self.lr
        return self._scaled_hyper(indices, base, "lr")

    def _get_lr(self, index):
        return self._get_lrs([index])[0]

    def _get_wds(self, indices):
        return self._scaled_hyper(indices, self.wd, "wd")

    def _get_wd(self, index):
        return self._get_wds([index])[0]

    def _preprocess_grad(self, grad):
        g = grad._data * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g

    def _sparse_rows(self, grad):
        """(row_indices, row_grads) when grad is row_sparse, else None —
        enables lazy updates touching only referenced rows (reference
        sparse sgd/adagrad kernels, optimizer_op.cc:47-893)."""
        from .sparse import RowSparseNDArray
        if not isinstance(grad, RowSparseNDArray):
            return None
        g = grad._sp_data * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return grad._sp_indices, g

# --------------------------------------------------------------- rules ---
# Pure jitted update kernels (analogues of src/operator/optimizer_op.cc).

@jax.jit
def _sgd_update(w, g, lr, wd):
    return w - lr * (g + wd * w)


@jax.jit
def _sgd_mom_update(w, g, mom, lr, wd, momentum):
    mom = momentum * mom - lr * (g + wd * w)
    return w + mom, mom


@jax.jit
def _nag_mom_update(w, g, mom, lr, wd, momentum):
    g = g + wd * w
    mom = momentum * mom + g
    return w - lr * (momentum * mom + g), mom


@jax.jit
def _adam_update(w, g, m, v, lr, wd, beta1, beta2, eps):
    g = g + wd * w
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    return w - lr * m / (jnp.sqrt(v) + eps), m, v


def _momentum_kernel(mom_update):
    """``Optimizer._kernel`` of a rule that is plain SGD without a
    momentum buffer and ``mom_update`` with one (``hyper`` = (momentum,))."""
    def kernel(w, g, states, lr, wd, hyper):
        if not states:
            return _sgd_update(w, g, lr, wd), ()
        w, mom = mom_update(w, g, states[0], lr, wd, hyper[0])
        return w, (mom,)
    return staticmethod(kernel)


@register
class SGD(Optimizer):
    """SGD with momentum and optional multi-precision (optimizer.py:479;
    kernels optimizer_op.cc sgd_update/sgd_mom_update). lazy_update applies
    only to row_sparse — dense-backed here, so it is a no-op flag."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self._take(momentum=momentum, lazy_update=lazy_update)

    def create_state(self, index, weight):
        return self._zeros_like(weight) if self.momentum else None

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        sparse = self._sparse_rows(grad) if self.lazy_update else None
        if sparse is not None:
            rows, g = sparse
            w = weight._data
            if state is not None:
                m = state._data[rows] * self.momentum - lr * (
                    g + wd * w[rows])
                state._data = state._data.at[rows].set(m)
                weight._data = w.at[rows].add(m)
            else:
                weight._data = w.at[rows].add(-lr * (g + wd * w[rows]))
            return
        self._apply_kernel(t, lr, wd, weight, grad, state)

    _kernel = _momentum_kernel(_sgd_mom_update)

    def _kernel_hyper(self):
        return (self.momentum,)


@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (optimizer.py:1137)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self._take(momentum=momentum)

    def create_state(self, index, weight):
        return self._zeros_like(weight) if self.momentum else None

    _apply_rule = Optimizer._apply_kernel

    _kernel = _momentum_kernel(_nag_mom_update)
    _kernel_hyper = SGD._kernel_hyper


@register
class Signum(Optimizer):
    """signSGD / Signum (optimizer.py:699): takes sign of (momentum) grad."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self._take(momentum=momentum, wd_lh=wd_lh)

    def create_state(self, index, weight):
        return self._zeros_like(weight) if self.momentum else None

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        g = self._preprocess_grad(grad)
        if state is not None:
            mom = self.momentum * state._data - (1 - self.momentum) * (g + wd * weight._data)
            weight._data = (1 - lr * self.wd_lh) * weight._data + lr * jnp.sign(mom)
            state._data = mom
        else:
            weight._data = (1 - lr * (self.wd_lh + wd)) * weight._data \
                - lr * jnp.sign(g)


@register
class FTML(Optimizer):
    """Follow the Moving Leader (optimizer.py:636)."""

    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self._take(beta1=beta1, beta2=beta2, epsilon=epsilon)

    def create_state(self, index, weight):
        z = (self._zeros_like(weight),
             self._zeros_like(weight),
             self._zeros_like(weight))
        return z  # (prev_d, prev_v, prev_z)

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        g = self._preprocess_grad(grad) + wd * weight._data
        prev_d, prev_v, prev_z = state
        v = self.beta2 * prev_v._data + (1 - self.beta2) * g * g
        d = (1 - self.beta1 ** t) / lr * (
            jnp.sqrt(v / (1 - self.beta2 ** t)) + self.epsilon)
        sigma = d - self.beta1 * prev_d._data
        z = self.beta1 * prev_z._data + (1 - self.beta1) * g \
            - sigma * weight._data
        weight._data = -z / d
        prev_d._data, prev_v._data, prev_z._data = d, v, z


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (optimizer.py:769)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self._take(momentum=momentum, lamda=lamda, weight_previous={})

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (self._zeros_like(weight),
                weight.copy())

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        g = self._preprocess_grad(grad)
        mon, previous_weight = state
        comp = g + wd * weight._data + self.lamda * g * g * \
            (weight._data - previous_weight._data)
        if mon is not None:
            mon._data = self.momentum * mon._data - lr * comp
            delta = mon._data
        else:
            delta = -lr * comp
        previous_weight._data = weight._data
        weight._data = weight._data + delta


@register
class LBSGD(Optimizer):
    """Large-batch SGD with LARS-style layer-wise adaptive rate + warmup
    (optimizer.py:860). Simplified: warmup strategies collapse to 'linear'
    scaling of lr; adaptive ratio = ||w||/||g|| as in the reference."""

    def __init__(self, momentum=0.0, multi_precision=False,
                 warmup_strategy="linear", warmup_epochs=5, batch_scale=1,
                 updates_per_epoch=32, begin_epoch=0, num_epochs=60, **kwargs):
        super().__init__(multi_precision=multi_precision, **kwargs)
        self._take(momentum=momentum, warmup_strategy=warmup_strategy,
                   warmup_epochs=warmup_epochs, batch_scale=batch_scale,
                   updates_per_epoch=updates_per_epoch,
                   init_updates=begin_epoch * updates_per_epoch,
                   num_epochs=num_epochs,
                   adaptive=warmup_strategy.startswith("lars"))

    def create_state(self, index, weight):
        return self._zeros_like(weight) if self.momentum else None

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        g = self._preprocess_grad(grad)
        if self.adaptive:
            wnorm = jnp.linalg.norm(weight._data)
            gnorm = jnp.linalg.norm(g)
            ratio = jnp.where(gnorm > 0, wnorm / (gnorm + wd * wnorm + 1e-9), 1.0)
            lr = lr * jnp.clip(ratio, 0.0, 10.0)
        if state is not None:
            weight._data, state._data = _sgd_mom_update(
                weight._data, g, state._data, _flt(lr), _flt(wd),
                _flt(self.momentum))
        else:
            weight._data = _sgd_update(weight._data, g, _flt(lr), _flt(wd))


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (optimizer.py:1599)."""

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        g = self._preprocess_grad(grad)
        noise = nd.random.normal(0, math.sqrt(lr), shape=weight.shape,
                                 dtype="float32")
        weight._data = weight._data - lr / 2 * (g + wd * weight._data) \
            + noise._data.astype(weight.dtype)


@register
class Adam(Optimizer):
    """Adam (optimizer.py:1181; kernel optimizer_op.cc adam_update)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self._take(beta1=beta1, beta2=beta2, epsilon=epsilon,
                   lazy_update=lazy_update)

    def create_state(self, index, weight):
        return (self._zeros_like(weight),
                self._zeros_like(weight))

    _apply_rule = Optimizer._apply_kernel

    @staticmethod
    def _kernel(w, g, states, lr, wd, hyper):
        w, mean, var = _adam_update(w, g, *states, lr, wd, *hyper)
        return w, (mean, var)

    def _kernel_hyper(self):
        return (self.beta1, self.beta2, self.epsilon)

    def _kernel_lr(self, t, lr):
        coef1 = 1. - self.beta1 ** t
        coef2 = 1. - self.beta2 ** t
        return lr * (math.sqrt(coef2) / coef1)


@register
class AdaGrad(Optimizer):
    """AdaGrad (optimizer.py:1369; sparse adagrad in optimizer_op.cc:893)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self._take(float_stable_eps=eps)

    def create_state(self, index, weight):
        return self._zeros_like(weight)

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        sparse = self._sparse_rows(grad)
        if sparse is not None:
            # sparse adagrad (optimizer_op.cc:893): history/update only on
            # referenced rows
            rows, g = sparse
            g = g + wd * weight._data[rows]
            hist = state._data[rows] + g * g
            state._data = state._data.at[rows].set(hist)
            weight._data = weight._data.at[rows].add(
                -lr * g / (jnp.sqrt(hist) + self.float_stable_eps))
            return
        g = self._preprocess_grad(grad) + wd * weight._data
        state._data = state._data + g * g
        weight._data = weight._data - lr * g / (
            jnp.sqrt(state._data) + self.float_stable_eps)


@register
class AdaDelta(Optimizer):
    """AdaDelta (optimizer.py:1467)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._take(rho=rho, epsilon=epsilon)

    def create_state(self, index, weight):
        return (self._zeros_like(weight),
                self._zeros_like(weight))

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        g = self._preprocess_grad(grad) + wd * weight._data
        acc_g, acc_delta = state
        acc_g._data = self.rho * acc_g._data + (1. - self.rho) * g * g
        delta = jnp.sqrt(acc_delta._data + self.epsilon) / \
            jnp.sqrt(acc_g._data + self.epsilon) * g
        acc_delta._data = self.rho * acc_delta._data + (1. - self.rho) * delta * delta
        weight._data = weight._data - delta


@register
class RMSProp(Optimizer):
    """RMSProp, non-centered (Hinton) and centered (Graves) variants
    (optimizer.py:1270)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self._take(gamma1=gamma1, gamma2=gamma2, centered=centered,
                   epsilon=epsilon, clip_weights=clip_weights)

    def create_state(self, index, weight):
        if self.centered:
            return (self._zeros_like(weight),
                    self._zeros_like(weight),
                    self._zeros_like(weight))
        return (self._zeros_like(weight),)

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        g = self._preprocess_grad(grad) + wd * weight._data
        if self.centered:
            n, gmean, delta = state
            n._data = (1 - self.gamma1) * g * g + self.gamma1 * n._data
            gmean._data = (1 - self.gamma1) * g + self.gamma1 * gmean._data
            delta._data = self.gamma2 * delta._data - lr * g / jnp.sqrt(
                n._data - gmean._data * gmean._data + self.epsilon)
            weight._data = weight._data + delta._data
        else:
            (n,) = state
            n._data = (1 - self.gamma1) * g * g + self.gamma1 * n._data
            weight._data = weight._data - lr * g / jnp.sqrt(n._data + self.epsilon)
        if self.clip_weights:
            weight._data = jnp.clip(weight._data, -self.clip_weights,
                                    self.clip_weights)


@register
class Ftrl(Optimizer):
    """FTRL-proximal (optimizer.py:1518)."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self._take(lamda1=lamda1, beta=beta)

    def create_state(self, index, weight):
        return (self._zeros_like(weight),  # z
                self._zeros_like(weight))  # n

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        g = self._preprocess_grad(grad)
        z, n = state
        sigma = (jnp.sqrt(n._data + g * g) - jnp.sqrt(n._data)) / lr
        z._data = z._data + g - sigma * weight._data
        n._data = n._data + g * g
        weight._data = jnp.where(
            jnp.abs(z._data) <= self.lamda1,
            jnp.zeros_like(weight._data),
            -(z._data - jnp.sign(z._data) * self.lamda1) /
            ((self.beta + jnp.sqrt(n._data)) / lr + wd))


@register
class Adamax(Optimizer):
    """AdaMax (optimizer.py:1613)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self._take(beta1=beta1, beta2=beta2)

    def create_state(self, index, weight):
        return (self._zeros_like(weight),
                self._zeros_like(weight))

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        lr /= (1. - self.beta1 ** t)
        g = self._preprocess_grad(grad) + wd * weight._data
        m_t, u_t = state
        m_t._data = self.beta1 * m_t._data + (1. - self.beta1) * g
        u_t._data = jnp.maximum(self.beta2 * u_t._data, jnp.abs(g))
        weight._data = weight._data - lr * m_t._data / (u_t._data + 1e-12)


@register
class Nadam(Optimizer):
    """Nesterov Adam (optimizer.py:1660)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self._take(beta1=beta1, beta2=beta2, epsilon=epsilon,
                   schedule_decay=schedule_decay, m_schedule=1.)

    def create_state(self, index, weight):
        return (self._zeros_like(weight),
                self._zeros_like(weight))

    def _apply_rule(self, t, lr, wd, weight, grad, state):
        g = self._preprocess_grad(grad) + wd * weight._data
        momentum_t = self.beta1 * (1. - 0.5 * (pow(0.96, t * self.schedule_decay)))
        momentum_t_1 = self.beta1 * (1. - 0.5 *
                                     (pow(0.96, (t + 1) * self.schedule_decay)))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m_t, v_t = state
        m_t._data = self.beta1 * m_t._data + (1. - self.beta1) * g
        v_t._data = self.beta2 * v_t._data + (1. - self.beta2) * g * g
        grad_prime = g / (1. - self.m_schedule)
        m_t_prime = m_t._data / (1. - m_schedule_next)
        v_t_prime = v_t._data / (1. - pow(self.beta2, t))
        m_t_bar = (1. - momentum_t) * grad_prime + momentum_t_1 * m_t_prime
        weight._data = weight._data - lr * m_t_bar / (
            jnp.sqrt(v_t_prime) + self.epsilon)


@register
class Test(Optimizer):
    """Test optimizer that stores the weight delta (optimizer.py:437)."""

    def create_state(self, index, weight):
        return nd.zeros(weight.shape, weight.context)

    def update(self, index, weight, grad, state):
        weight._data = weight._data + grad._data * self.rescale_grad
        state._data = weight._data


# alias used in examples (ccSGD was deprecated alias of SGD in 1.x)
_OPT_REGISTRY["ccsgd"] = SGD


# ------------------------------------------------- multi-tensor update ---
# What a list call of the Updater runs for every parameter it can fuse: ONE
# program a step instead of a cast, a rescale, three scalar puts and a
# kernel a parameter.

_FUSED_RULES = (SGD, NAG, Adam)
# what a subclass may not override and still be fused: the fused program
# stands in for all of these
_RULE_METHODS = ("update", "update_multi_precision", "_apply_rule",
                 "_kernel", "_kernel_hyper", "_kernel_lr",
                 "_preprocess_grad", "_update_count", "_get_lr", "_get_lrs",
                 "_get_wd", "_get_wds", "create_state_multi_precision")


def _fused_kernel(optimizer):
    """The pure kernel of a built-in SGD / NAG / Adam, or None for any
    other rule and for a subclass that overrides part of the update (its
    override would silently not run; same rule as FlatOptimizer.supports)."""
    kind = type(optimizer)
    base = next((b for b in _FUSED_RULES if isinstance(optimizer, b)), None)
    if base is None or any(getattr(kind, m) is not getattr(base, m)
                           for m in _RULE_METHODS):
        return None
    return base._kernel


def _shared_devices(weight, grad, state):
    """The one device set a weight, its dense gradient and its state all
    sit on, or None: a sparse gradient takes its rule's lazy path and
    unlike placements are ``_align_update_devices``' work, both a
    parameter at a time."""
    if grad._stype != "default":
        return None
    try:
        devices = weight._data.sharding.device_set
        if grad._data.sharding.device_set != devices:
            return None
        for leaf in _state_leaves(state):
            if leaf._data.sharding.device_set != devices:
                return None
    except AttributeError:
        return None
    return frozenset(devices)


@functools.lru_cache(maxsize=None)
def _fused_program(kernel, has_clip):
    """The jitted multi-tensor update of one rule. Per parameter, in the
    order ``update_multi_precision`` + ``_preprocess_grad`` + the kernel
    apply them one by one: gradient to the master's dtype, rescale, clip,
    the rule, the new master cast back to the weight's dtype. Shapes,
    dtypes and the set of parameters key jit's own cache; every
    hyperparameter is a traced operand, so a schedule, a new
    ``rescale_grad`` or a loss scale never compiles again. Donated are the
    buffers the Updater alone owns (masters and states): weights and
    gradients are also held by the CachedOp and the tape."""

    def run(weights, grads, masters, states, lrs, wds, rescale, clip,
            hyper):
        out_w, out_m, out_s = [], [], []
        for k, (w, g, master, state) in enumerate(
                zip(weights, grads, masters, states)):
            if master is not None:
                g = g.astype(master.dtype)
            g = g * rescale.astype(g.dtype)
            if has_clip:
                bound = clip.astype(g.dtype)
                g = jnp.clip(g, -bound, bound)
            new, state = kernel(w if master is None else master, g, state,
                                lrs[k], wds[k], hyper)
            out_m.append(None if master is None else new)
            out_w.append(new.astype(w.dtype))
            out_s.append(state)
        return out_w, out_m, out_s

    return jax.jit(run, donate_argnums=(2, 3))


class Updater(object):
    """Applies an optimizer to (index, grad, weight) triples — the object
    the reference ships to kvstore servers (optimizer.py get_updater /
    kvstore_dist_server.h ApplyUpdates).

    Called with one triple (Module, KVStore) it runs the optimizer's own
    per-parameter update. Called with lists (``gluon.Trainer``, once a
    step) it aggregates as the reference's ``aggregate_num`` kernels do:
    every parameter whose update is a built-in pure rule on a dense
    gradient goes into one program a device set, the rest a parameter at a
    time. ``states[i]`` has the same structure either way."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def __call__(self, index, grad, weight):
        opt = self.optimizer
        if not isinstance(index, (list, tuple)):
            opt.update_multi_precision(index, weight, grad,
                                       self._state(index, weight))
            return
        kernel = _fused_kernel(opt)
        groups, rest = {}, []
        for triple in zip(index, grad, weight):
            i, g, w = triple
            state = self._state(i, w)
            devices = kernel and _shared_devices(w, g, state)
            if devices:
                groups.setdefault(devices, []).append(triple)
            else:
                rest.append(triple)
        for triples in groups.values():
            self._update_fused(kernel, triples)
        for i, g, w in rest:
            opt.update_multi_precision(i, w, g, self.states[i])
        if _obs.enabled():
            _obs.counter("optimizer.fused_params").add(
                len(index) - len(rest))
            _obs.counter("optimizer.fallback_params").add(len(rest))

    def _state(self, index, weight):
        if index not in self.states:
            self.states[index] = \
                self.optimizer.create_state_multi_precision(index, weight)
            self.states_synced[index] = True
        return self.states[index]

    def _update_fused(self, kernel, triples):
        opt = self.optimizer
        indices = [i for i, _, _ in triples]
        opt._update_count(indices)
        steps = opt._index_update_count
        lrs = [opt._kernel_lr(steps[i], lr)
               for i, lr in zip(indices, opt._get_lrs(indices))]
        masters, leaves = [], []
        for i, _, w in triples:
            state = self.states[i]
            master = None
            if opt.multi_precision and w.dtype == jnp.bfloat16:
                master, state = state
            masters.append(master)
            leaves.append(_state_leaves(state))
        clip = opt.clip_gradient
        with _obs.span("optimizer.fused", cat="step", params=len(triples)):
            new_w, new_m, new_s = _fused_program(kernel, clip is not None)(
                [w._data for _, _, w in triples],
                [g._data for _, g, _ in triples],
                [m if m is None else m._data for m in masters],
                [tuple(s._data for s in ls) for ls in leaves],
                np.asarray(lrs, np.float32),
                np.asarray(opt._get_wds(indices), np.float32),
                np.float32(opt.rescale_grad),
                np.float32(0.0 if clip is None else clip),
                tuple(np.float32(h) for h in opt._kernel_hyper()))
        for (_, _, w), master, ls, w_data, m_data, s_data in zip(
                triples, masters, leaves, new_w, new_m, new_s):
            w._data = w_data
            if master is not None:
                master._data = m_data
            for leaf, data in zip(ls, s_data):
                leaf._data = data

    def get_states(self, dump_optimizer=False):
        payload = (self.states, self.optimizer) if dump_optimizer \
            else self.states
        return pickle.dumps(payload)

    def set_states(self, states):
        loaded = pickle.loads(states)
        # two wire formats: bare state dict, or (states, optimizer)
        # when the sender dumped its optimizer too
        if isinstance(loaded, tuple) and len(loaded) == 2:
            self.states, self.optimizer = loaded
        else:
            self.states = loaded
        self.states_synced = {i: False for i in self.states}


def get_updater(optimizer):
    """mx.optimizer.get_updater (optimizer.py end)."""
    return Updater(optimizer)


@register
class ccSGD(SGD):
    """Deprecated reference alias of SGD (optimizer.py ccSGD) — kept so
    old configs creating 'ccsgd' resolve."""
