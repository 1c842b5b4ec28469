"""Autograd — imperative differentiation.

Reference: src/imperative/imperative.cc (tape via AGInfo nodes,
Imperative::RecordOp / Backward) and python/mxnet/autograd.py (record /
pause / train_mode scopes, backward, grad, custom Function).

TPU-native design: instead of re-deriving a gradient graph from per-op
FGradient registrations, each recorded op calls jax.vjp at invoke time —
the pullback closure (with its residuals living on device) IS the tape
node. backward() walks nodes in reverse execution order accumulating
cotangents; exactness comes from XLA's AD rules rather than 345 hand-written
gradient registrations.
"""

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as _np

from .base import MXNetError
from . import engine
from .observability import core as _obs

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
        _state.tape = []
    return _state


class TapeNode:
    """One recorded op: pullback + input/output bookkeeping
    (the analogue of nnvm::Node + AGInfo, include/mxnet/imperative.h:42-79).

    `vjp_fn` is a bare pullback (`jax.vjp` outside any jit, a custom
    Function's backward): it wants an array for every output, so the sweep
    builds the missing cotangents itself, each a program of its own."""

    __slots__ = ("vjp_fn", "inputs", "num_outputs", "cotangents", "out_shapes",
                 "out_dtypes", "op_name")

    def __init__(self, vjp_fn, inputs, num_outputs, out_shapes, out_dtypes,
                 op_name=""):
        self.vjp_fn = vjp_fn
        self.inputs = inputs          # list of NDArray (kept alive for leaves)
        self.num_outputs = num_outputs
        self.cotangents = [None] * num_outputs
        self.out_shapes = out_shapes
        self.out_dtypes = out_dtypes
        self.op_name = op_name


class ProgramNode(TapeNode):
    """A node whose pullback came out of a jitted forward (a CachedOp's,
    `ndarray._recorded_vjp`'s compiled ops) and runs as ONE program:
    `vjp_fn` takes the cotangents as they are, `None` for an output
    nothing flowed back to, and fills those inside the program
    (`apply_vjp`)."""

    __slots__ = ()


@functools.partial(jax.jit, static_argnums=0)
def _apply_vjp(avals, vjp, cts):
    # `avals` ((shape, dtype) an output) is read only where a cotangent
    # is missing; a node with one output is never swept without it
    if isinstance(cts, tuple):
        cts = tuple(jnp.zeros(*a) if c is None else c
                    for a, c in zip(avals, cts))
    return vjp(cts)


def apply_vjp(vjp, out_shapes, out_dtypes):
    """The `vjp_fn` of a ProgramNode: `vjp`, a pullback returned by a
    jitted function, applied INSIDE jit. Such a closure is a pytree of
    residuals whose structure repeats call after call, so one compiled
    program a signature serves; called bare it would interpret the
    backward jaxpr operation by operation (no XLA fusion, and on the CPU
    mesh the flock of in-flight collective launches deadlocks,
    engine.py). A pullback from a bare `jax.vjp` is a new function every
    call and must NOT come here: every backward would recompile."""
    return functools.partial(_apply_vjp, tuple(zip(out_shapes, out_dtypes)),
                             vjp)


# ------------------------------------------------------------- scopes --
class _RecordingStateScope:
    def __init__(self, is_record, train_mode):
        self._enter_is_record = is_record
        self._enter_train_mode = train_mode
        self._prev = None

    def __enter__(self):
        st = _st()
        self._prev = (st.recording, st.training)
        if self._enter_is_record is not None:
            st.recording = self._enter_is_record
        if self._enter_train_mode is not None:
            st.training = self._enter_train_mode
        return self

    def __exit__(self, *a):
        st = _st()
        st.recording, st.training = self._prev


def record(train_mode=True):
    """python/mxnet/autograd.py:93 — enter recording (and by default train)
    scope."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(is_record):
    st = _st()
    prev, st.recording = st.recording, is_record
    return prev


def set_training(train):
    st = _st()
    prev, st.training = st.training, train
    return prev


# --------------------------------------------------------------- tape --
def _tape():
    return _st().tape


def _record_node(node):
    _st().tape.append(node)


def mark_variables(variables, gradients, grad_reqs="write"):
    """python/mxnet/autograd.py mark_variables."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, r in zip(variables, gradients, grad_reqs):
        v._mark_variable(g, r)


def _collect(outputs):
    out = []
    for o in outputs:
        if o._ag_node is None and not o._ag_leaf:
            raise MXNetError(
                "cannot differentiate %s: it was not computed inside an "
                "autograd.record() scope" % repr(o))
        out.append(o)
    return out


def backward(outputs, head_grads=None, retain_graph=False, train_mode=True):
    """Reverse pass (analogue of Imperative::Backward,
    src/imperative/imperative.cc:280): reverse-iterate the tape, feed each
    node its accumulated output cotangents, pull back to inputs."""
    with _obs.span("backward", cat="step", heads=len(outputs)
                   if isinstance(outputs, (list, tuple)) else 1):
        return _backward_impl(outputs, head_grads, retain_graph,
                              train_mode)


def _backward_impl(outputs, head_grads=None, retain_graph=False,
                   train_mode=True):
    from .ndarray import NDArray

    if isinstance(outputs, NDArray):
        outputs = [outputs]
    if head_grads is not None and isinstance(head_grads, NDArray):
        head_grads = [head_grads]
    outputs = _collect(outputs)

    tape = _tape()
    # seed cotangents
    grad_acc = {}  # id(leaf NDArray) -> (leaf, jnp grad)
    # tape nodes pulled back, and cotangents built outside a program
    pulled = seeds = 0

    def add_ct(node, idx, ct):
        cur = node.cotangents[idx]
        node.cotangents[idx] = ct if cur is None else cur + ct

    needed = set()
    for i, o in enumerate(outputs):
        hg = None
        if head_grads is not None and head_grads[i] is not None:
            hg = head_grads[i]._data
        else:
            # the default head gradient is a host constant handed over,
            # not a program (`jnp.ones` is two)
            hg = jnp.asarray(_np.ones(o.shape, dtype=o.dtype))
            seeds += 1
        if o._ag_leaf and o._ag_node is None:
            _acc_leaf(o, hg, grad_acc)
            continue
        node, idx = o._ag_node
        add_ct(node, idx, hg)
        needed.add(id(node))

    # mark ancestry (reverse sweep marks needed nodes as it goes)
    for node in reversed(tape):
        if id(node) not in needed:
            # might become needed if a later-position node feeds it... cannot:
            # tape order == execution order so consumers come after producers;
            # reverse order visits consumers first and marks producers below.
            if all(c is None for c in node.cotangents):
                continue
        cts = node.cotangents
        if not isinstance(node, ProgramNode):
            for k, c in enumerate(cts):
                if c is None:
                    cts[k] = jnp.zeros(node.out_shapes[k],
                                       dtype=node.out_dtypes[k])
                    seeds += 1
        pulled += 1
        in_grads = node.vjp_fn(tuple(cts) if node.num_outputs > 1
                               else cts[0])
        engine.sync_if_needed([g for g in in_grads
                               if hasattr(g, "block_until_ready")])
        for inp, g in zip(node.inputs, in_grads):
            if g is None or (hasattr(g, "dtype") and g.dtype == jax.dtypes.float0):
                continue
            if inp._ag_leaf:
                _acc_leaf(inp, g, grad_acc)
            if inp._ag_node is not None:
                pnode, pidx = inp._ag_node
                add_ct(pnode, pidx, g)
                needed.add(id(pnode))
        # cotangents are this sweep's state: a retained graph must not
        # carry them into its next backward
        node.cotangents = [None] * node.num_outputs
        if not retain_graph:
            # free the graph's buffers now, as the reference does: the
            # pullback closes over the saved activations (9 GB for a
            # hybridized ResNet-50 at batch 128), and an output the user
            # still holds (`loss`) would otherwise pin them through
            # _ag_node into the next iteration's forward
            node.vjp_fn = None
            node.inputs = ()

    # write accumulated grads into .grad respecting grad_req
    for leaf, g in grad_acc.values():
        if leaf._grad_req == "add":
            leaf._grad._data = leaf._grad._data + g.astype(leaf._grad.dtype)
        elif leaf._grad_req == "write":
            leaf._grad._data = g.astype(leaf._grad.dtype)
        leaf._fresh_grad = True  # consumed by Trainer stale-grad detection

    if not retain_graph:
        tape.clear()
    if _obs.enabled():
        _obs.counter("autograd.pullbacks").add(pulled)
        _obs.counter("autograd.eager_seeds").add(seeds)


def _acc_leaf(leaf, g, grad_acc):
    if leaf._grad is None or leaf._grad_req == "null":
        return
    cur = grad_acc.get(id(leaf))
    grad_acc[id(leaf)] = (leaf, g if cur is None else cur[1] + g)


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """python/mxnet/autograd.py grad — return grads instead of writing
    .grad. create_graph (higher-order) is supported by replay through
    jax.grad at the CachedOp level; here first-order only."""
    from .ndarray import NDArray
    if isinstance(heads, NDArray):
        heads = [heads]
    if isinstance(variables, NDArray):
        variables = [variables]
    saved = [(v._grad, v._grad_req) for v in variables]
    for v in variables:
        if v._grad is None:
            v._mark_variable(None, "write")
        v._grad_req = "write"
        from .ndarray import zeros
        v._grad = zeros(v.shape, dtype=v.dtype)
    backward(heads, head_grads, retain_graph=bool(retain_graph), train_mode=train_mode)
    out = [v._grad for v in variables]
    for v, (g, r) in zip(variables, saved):
        v._grad, v._grad_req = (g, r) if g is not None else (v._grad, r)
    return out


def get_symbol(x):
    raise MXNetError("autograd.get_symbol: the TPU build records jax vjp "
                     "closures, not nnvm symbols; use gluon.HybridBlock "
                     "tracing to obtain a Symbol")


class Function:
    """Custom differentiable function (python/mxnet/autograd.py:Function).

    Subclass and implement forward(self, *inputs) and
    backward(self, *output_grads), both over NDArray.
    """

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *args):
        self._saved = args

    @property
    def saved_tensors(self):
        return self._saved

    def __call__(self, *inputs):
        from .ndarray import NDArray, array
        with pause():
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (list, tuple))
        outs = [outputs] if single else list(outputs)

        if is_recording() and any(i._requires_tape() for i in inputs):
            func = self

            def vjp_fn(cts):
                cts_list = [cts] if len(outs) == 1 else list(cts)
                with pause():
                    igrads = func.backward(
                        *[NDArray(c) for c in cts_list])
                if not isinstance(igrads, (list, tuple)):
                    igrads = [igrads]
                return [g._data if g is not None else None for g in igrads]

            node = TapeNode(vjp_fn, list(inputs), len(outs),
                            [o.shape for o in outs], [o.dtype for o in outs],
                            op_name=type(self).__name__)
            _record_node(node)
            for k, o in enumerate(outs):
                o._ag_node = (node, k)
        return outs[0] if single else outs

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError
