"""Executor — bound symbolic graphs, compiled to single XLA computations.

Reference: src/executor/graph_executor.cc (SimpleBind:1913, Bind:1995,
Forward:78, Backward:91) — there, the graph is executed node-by-node
through the dependency engine with a hand-built memory plan
(src/nnvm/plan_memory.cc) and manual op bulking (InitOpSegs:1288).

TPU-native design: binding lowers the WHOLE graph (and its backward) to
one jit-compiled XLA computation. XLA subsumes the reference passes:
memory planning (buffer assignment), inplace/addto detection (buffer
aliasing), op bulking (fusion), and the gradient pass (jax.vjp). The
train-mode path compiles forward+backward together so TPU sees a single
fused program per (shapes, dtypes) signature.
"""

import jax
import jax.numpy as jnp

from . import ops
from . import engine as _engine
from . import inspector as _inspector
from .base import MXNetError
from .observability import attribution as _obs_attr
from .observability import core as _obs
from .observability import membudget as _membudget
from .observability import recompile as _obs_recompile
from .symbol import OP_AUX

_META_ATTRS = ("__input_names__", "__shape__", "__dtype__", "__lr_mult__",
               "__wd_mult__", "__init__", "__aux__", "__ctx_group__",
               "__storage_type__")


def _clean_attrs(attrs):
    return {k: v for k, v in attrs.items() if not k.startswith("__")}


# ------------------------------------------------- gradient mirroring ----
# Reference: MXNET_BACKWARD_DO_MIRROR (src/nnvm/gradient.cc:285, switch at
# src/executor/graph_executor.cc:351-357) — recompute cheap forward
# activations in backward instead of storing them, trading FLOPs for
# memory. TPU-native mapping: jax.checkpoint (remat) around the traced
# graph. The reference leaves it OFF unless asked; here it is ON unless
# refused (PR 49): a forward that hands every intermediate to its
# backward fills the chip's memory, and the next forward's buffers then
# wait for the backward to return them. The policy mirrors the
# reference's mirror_fun granularity:
#   dots (default)  save MXU results (matmul/conv outputs), reductions'
#                   results (a batch norm's statistics) and what a host
#                   callback returned, recompute elementwise/norm
#                   activations — the reference's "mirror everything but
#                   heavy ops" heuristic
#   full            save only what a host callback returned
# With the variable at 0, or hybridize(backward_do_mirror=False), every
# intermediate jax.vjp asks for is saved.
_TRUE = ("1", "true", "yes")
# never recomputed: the MXU's results (the reference's mirror pass
# likewise keeps Convolution/FullyConnected, gradient.cc mirror_fun) ...
_MXU_RESULTS = ("dot_general", "conv_general_dilated")
# ... a reduction's result, which is smaller than what it read by the
# reduced axes and costs a whole pass over it to make again (a batch
# norm's mean and variance: recomputed, they were two more reads of every
# convolution's result in the backward, 3.8 of a ResNet-50 step's 58.6 ms
# on a v5e, PR 49) ...
_REDUCTIONS = ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
               "argmax", "argmin")
# ... and, under either policy, what came back from the host: a Custom
# operator's Python forward (operator.py) runs once a step, whatever it
# counts, prints or draws
_HOST_RESULTS = ("pure_callback", "io_callback")


def mirror_enabled(flags=None):
    """Resolve the mirror knob: explicit flag wins, then the reference's
    env var; with neither, on."""
    import os
    if flags:
        for key in ("backward_do_mirror", "do_mirror"):
            if key in flags:
                v = flags[key]
                return v if isinstance(v, bool) else str(v).lower() in _TRUE
    return os.environ.get("MXNET_BACKWARD_DO_MIRROR", "1").lower() in _TRUE


def _saving(names):
    """A jax.checkpoint policy: the results of the primitives `names`
    are saved, everything else is rematerialized in backward."""
    def policy(prim, *_, **__):
        return getattr(prim, "name", str(prim)) in names
    return policy


_save_mxu_results = _saving(_MXU_RESULTS + _REDUCTIONS + _HOST_RESULTS)
_POLICIES = {"dots": _save_mxu_results, "full": _saving(_HOST_RESULTS)}


def _mirror_policy():
    import os
    name = os.environ.get("MXNET_MIRROR_POLICY", "dots")
    if name not in _POLICIES:
        raise MXNetError(
            "MXNET_MIRROR_POLICY must be 'dots' or 'full', got %r" % name)
    return _POLICIES[name]


def apply_mirror(fn, enabled):
    """Wrap a traced graph function in jax.checkpoint when mirroring is
    on; identity otherwise. No barrier against common-subexpression
    elimination: forward and backward are two programs here (fwd_res_fn,
    autograd._apply_vjp), so the backward holds the recomputation alone
    and XLA has no first computation to merge it back into."""
    if not enabled:
        return fn
    return jax.checkpoint(fn, policy=_mirror_policy(), prevent_cse=False)


@jax.tree_util.register_pytree_node_class
class SavedForBackward:
    """What a compiled forward hands its backward: the leaves of the
    `jax.vjp` closure that the forward COMPUTED. A leaf that is one of
    the forward's own inputs (a weight, the batch) is not returned: a
    program's output is a fresh buffer even where it is an argument
    unchanged, and the caller still holds the array. `slots` says, leaf
    by leaf of the closure, which input stands there (-1: the next of
    `saved`); with the closure's structure it is static data, so the
    whole crosses the jit boundary as a pytree of `saved` alone."""

    def __init__(self, saved, treedef, slots):
        self.saved, self.treedef, self.slots = saved, treedef, slots

    @classmethod
    def split(cls, vjp, inputs):
        leaves, treedef = jax.tree.flatten(vjp)
        where = {id(x): i for i, x in enumerate(jax.tree.leaves(inputs))}
        slots = tuple(where.get(id(leaf), -1) for leaf in leaves)
        return cls([leaf for leaf, s in zip(leaves, slots) if s < 0],
                   treedef, slots)

    def tree_flatten(self):
        return (self.saved,), (self.treedef, self.slots)

    @classmethod
    def tree_unflatten(cls, static, children):
        return cls(children[0], *static)

    def bind(self, inputs):
        """The closure again, over `inputs` as the forward got them."""
        ins, saved = jax.tree.leaves(inputs), iter(self.saved)
        return jax.tree.unflatten(
            self.treedef,
            [next(saved) if s < 0 else ins[s] for s in self.slots])

    def nbytes(self):
        return sum(leaf.size * leaf.dtype.itemsize for leaf in self.saved)


def fwd_res_fn(graph_fn, diff_names, mirror):
    """fn(diff_list, rest, aux, key) -> ((outs, aux_up), saved) over a
    build_graph_fn plan: forward + pullback residuals with respect to
    `diff_names`, for Executor and CachedOp alike; `call_fwd_res` runs
    it and gives the pullback. The vjp closure is a pytree of residual
    arrays, so it crosses the jit boundary intact and backward replays
    ONLY the transposed computation (the reference executor also keeps
    fwd/bwd as two engine segments, graph_executor.cc RunOps); what
    crosses is a `SavedForBackward`, the closure less the forward's own
    inputs. Auxiliary states (BatchNorm running statistics) leave as
    `has_aux` outputs: nothing differentiates them, and as differentiated
    outputs the pullback would demand a zero cotangent for each and
    transpose their updates against it.

    WHAT IS SAVED. With `mirror` (the default, `mirror_enabled`) the
    graph is rematerialized under the mirror policy: the closure holds
    the results of the MXU operations (`dot_general`,
    `conv_general_dilated`), of the reductions (a batch norm's mean and
    variance: a vector a channel), what a host callback returned, and
    the graph's inputs; batch norm's normalisation, ReLU, casts, adds
    and pooling are recomputed inside the backward program from the
    convolution results it reads anyway (the same operations on the same
    saved values, so the mathematics and every dtype stay; Dropout
    recomputes its mask from the same explicit key). A hybridized
    ResNet-50 at batch 128 hands over 2.7 GB in 160 arrays where it
    handed 9.0 GB in 683.
    Without `mirror` every intermediate jax.vjp asks for is saved."""
    def fwd_res(diff_list, rest, aux, key):
        def f(diff):
            full = dict(rest)
            full.update(zip(diff_names, diff))
            outs, aux_up = graph_fn(full, aux, key)
            return tuple(outs), aux_up
        outs, vjp, aux_up = jax.vjp(apply_mirror(f, mirror),
                                    list(diff_list), has_aux=True)
        return (outs, aux_up), SavedForBackward.split(
            vjp, (diff_list, rest, aux, key))
    return fwd_res


def call_fwd_res(fn, diff_list, rest, aux, key):
    """Run a (jitted) `fwd_res_fn` program: (outs, aux_up, vjp, saved),
    `vjp` the pullback over `saved` and the arrays that went in."""
    (outs, aux_up), saved = fn(diff_list, rest, aux, key)
    return outs, aux_up, saved.bind((diff_list, rest, aux, key)), saved


def node_eval_fn(node, for_inference=False):
    """Pure fn(*input_arrays) for one graph node (used by eval_shape)."""
    op = ops.get(node.op)
    attrs = _clean_attrs(node.attrs)
    has_varargs, param_names = ops.op_dispatch_meta(op)
    if "is_train" in param_names:
        attrs.setdefault("is_train", False)
    if op.stateful_rng and "rng_key" in param_names:
        attrs.setdefault("rng_key", jax.random.PRNGKey(0))
    in_names = node.attrs.get("__input_names__")

    def fn(*arrays):
        if has_varargs:
            return op.fn(*arrays, **attrs)
        call = dict(attrs)
        if in_names:
            call.update({n: a for n, a in zip(in_names, arrays)})
        else:
            pnames = [p for p in param_names if p not in attrs]
            call.update({n: a for n, a in zip(pnames, arrays)})
        return op.fn(**call)

    return fn


def build_graph_fn(symbol, is_train, node_device=None):
    """Compile plan: returns fn(arg_dict, aux_dict, rng_key) ->
    (outputs_list, new_aux_dict).

    node_device: optional callable node -> jax.Device | None. When it
    returns a device, the node's outputs are constrained there with
    device_put — the model-parallel group2ctx placement pass
    (graph_executor.cc:997 AssignContext + cross_device_copy insertion:
    XLA/jax materializes the transfers at group boundaries)."""
    all_nodes = symbol._nodes
    nodes = symbol._active_nodes()
    out_refs = [(all_nodes[ni], oi) for ni, oi in symbol._outputs]

    def _place(node, arr):
        if node_device is None:
            return arr
        dev = node_device(node)
        return arr if dev is None else jax.device_put(arr, dev)

    def graph_fn(arg_arrays, aux_arrays, rng_key):
        # per-operator attribution (observability/attribution.py): when
        # telemetry is on at TRACE time, every node's primitives are
        # emitted under jax.named_scope(node.name) so the optimized
        # HLO's op_name metadata names the originating block/op even
        # after fusion. One guarded branch per trace when off.
        use_scopes = _obs_attr.ops_enabled()
        vals = {}
        aux_updates = {}
        key = rng_key
        for node in nodes:
            if node.is_var():
                name = node.name
                if name in arg_arrays:
                    vals[(id(node), 0)] = _place(node, arg_arrays[name])
                elif name in aux_arrays:
                    vals[(id(node), 0)] = _place(node, aux_arrays[name])
                else:
                    raise MXNetError("unbound variable %s" % name)
                continue
            op = ops.get(node.op)
            attrs = _clean_attrs(node.attrs)
            has_varargs, param_names = ops.op_dispatch_meta(op)
            if "is_train" in param_names:
                attrs["is_train"] = is_train
            if op.stateful_rng and "rng_key" in param_names:
                key, sub = jax.random.split(key)
                attrs["rng_key"] = sub
            ins = []
            for s, oi in node.inputs:
                src = s._nodes[s._outputs[0][0]]
                ins.append(_place(node, vals[(id(src), oi)]))
            in_names = node.attrs.get("__input_names__")

            def _eval_node(op=op, attrs=attrs, ins=ins,
                           has_varargs=has_varargs,
                           param_names=param_names, in_names=in_names):
                if has_varargs:
                    return op.fn(*ins, **attrs)
                call = dict(attrs)
                if in_names:
                    call.update({n: a for n, a in zip(in_names, ins)})
                else:
                    pnames = [p for p in param_names if p not in attrs]
                    call.update({n: a for n, a in zip(pnames, ins)})
                return op.fn(**call)

            if use_scopes:
                _obs_attr.note_scope(node.name)
                with jax.named_scope(node.name):
                    out = _eval_node()
            else:
                out = _eval_node()

            if _inspector.nan_guard_enabled():
                # MXNET_NAN_GUARD: host-side finite-ness check on every
                # node output, tagged with its producer (TensorInspector
                # parity, tensor_inspector.h NaNChecker). Staged at
                # trace time via jax.debug.callback.
                tag = "%s:%s" % (node.op, node.name)
                if isinstance(out, (tuple, list)):
                    out = type(out)(
                        _inspector.guard_value(o, tag) for o in out)
                else:
                    out = _inspector.guard_value(out, tag)
            if node.op in ("BatchNorm", "_contrib_SyncBatchNorm"):
                # fold running-stat update (reference mutates aux in-place,
                # src/operator/nn/batch_norm.cc; we return new values)
                y, mean, var = out
                vals[(id(node), 0)] = y
                if is_train and not node.attrs.get("use_global_stats", False):
                    mom = float(node.attrs.get("momentum", 0.9))
                    names = node.attrs.get("__input_names__", ())
                    for pname, stat in (("moving_mean", mean), ("moving_var", var)):
                        try:
                            idx = list(names).index(pname)
                        except ValueError:
                            continue
                        s, _ = node.inputs[idx]
                        aux_name = s._nodes[s._outputs[0][0]].name
                        old = aux_arrays[aux_name]
                        aux_updates[aux_name] = mom * old + (1 - mom) * stat
                continue
            outs = list(out) if isinstance(out, (tuple, list)) else [out]
            for k, o in enumerate(outs):
                vals[(id(node), k)] = _place(node, o)

        outputs = []
        for node, oi in out_refs:
            outputs.append(vals[(id(node), oi)])
        return outputs, aux_updates

    return graph_fn


class Executor:
    """Bound executor (python/mxnet/executor.py wrapper semantics)."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None):
        from . import ndarray as nd
        self._symbol = symbol
        self._ctx = ctx
        self._group2ctx = group2ctx or {}
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()

        if isinstance(args, (list, tuple)):
            args = dict(zip(arg_names, args))
        self.arg_dict = {k: v if isinstance(v, nd.NDArray) else nd.array(v)
                         for k, v in args.items()}
        if isinstance(args_grad, (list, tuple)):
            args_grad = dict(zip(arg_names, args_grad))
        self.grad_dict = args_grad or {}
        if isinstance(aux_states, (list, tuple)):
            aux_states = dict(zip(aux_names, aux_states))
        self.aux_dict = aux_states or {}

        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(arg_names, grad_req))
        else:
            self._grad_req = dict(grad_req)

        self._diff_args = [n for n in arg_names
                           if self._grad_req.get(n, "null") != "null"
                           and n in self.grad_dict]

        self.outputs = []
        self._saved_vjp = None
        # RNG-free graphs skip the per-forward host-side key split
        # (benchmark/opperf.py --dispatch)
        self._needs_rng = any(
            ops.get(n.op).stateful_rng
            for n in symbol._active_nodes() if not n.is_var())
        self._zero_key = None

        node_device = None
        if self._group2ctx:
            # model parallelism (graph_executor.cc:997): nodes carrying a
            # __ctx_group__ attr are pinned to group2ctx[group]'s device;
            # ungrouped nodes follow the default ctx. Arg/aux arrays move
            # to their owning node's device at bind time.
            dev_by_group = {g: c.jax_device
                            for g, c in self._group2ctx.items()}
            default_dev = ctx.jax_device if ctx is not None else None

            def node_device(node):
                group = node.attrs.get("__ctx_group__")
                return dev_by_group.get(group, default_dev)

            for node in symbol._active_nodes():
                if not node.is_var():
                    continue
                tgt = self.arg_dict.get(node.name)
                if tgt is None:
                    tgt = self.aux_dict.get(node.name)
                if tgt is not None:
                    tgt._data = jax.device_put(tgt._data,
                                               node_device(node))
            self._node_device = node_device
        fwd_infer = build_graph_fn(symbol, is_train=False,
                                   node_device=node_device)
        fwd_train = build_graph_fn(symbol, is_train=True,
                                   node_device=node_device)
        diff_names = tuple(self._diff_args)

        def infer_fn(arg_arrays, aux_arrays, key):
            outs, _ = fwd_infer(arg_arrays, aux_arrays, key)
            return outs

        # a placed graph keeps its intermediates where they were made:
        # jax.checkpoint's recompute is ONE program, on one device
        fwd_res = fwd_res_fn(fwd_train, diff_names,
                             mirror_enabled() and node_device is None)

        def bwd_fn(vjp, heads):
            (grads,) = vjp(heads)
            return grads

        self._jitted = node_device is None
        if node_device is None:
            # single-placement graphs compile whole-program; placed
            # (group2ctx) graphs run op-by-op so each segment can live on
            # its own device with transfers at group boundaries
            infer_fn = jax.jit(infer_fn)
            fwd_res = jax.jit(fwd_res)
            bwd_fn = jax.jit(bwd_fn)
        self._infer_fn = infer_fn
        self._fwd_res_fn = fwd_res
        self._bwd_fn = bwd_fn
        self._obs_sig = None

    # ------------------------------------------------------------ run ---
    def forward(self, is_train=False, **kwargs):
        """is_train=True runs the forward program that also emits pullback
        residuals; backward() then replays only the transposed computation
        for whatever head gradients are supplied (defaults to ones). Use
        is_train=False for pure inference — the residual-free program."""
        from . import ndarray as nd
        from . import random as rnd
        for k, v in kwargs.items():
            if k in self.arg_dict:
                self.arg_dict[k]._data = v._data if isinstance(v, nd.NDArray) \
                    else jnp.asarray(v)
            else:
                raise MXNetError(
                    "forward got unknown argument %r (bound arguments: %s)"
                    % (k, sorted(self.arg_dict)))
        arg_arrays = {k: v._data for k, v in self.arg_dict.items()}
        aux_arrays = {k: v._data for k, v in self.aux_dict.items()}
        if self._needs_rng:
            key = rnd.next_key()
        else:
            if self._zero_key is None:
                self._zero_key = jax.random.PRNGKey(0)
            key = self._zero_key
        sig = None
        if _obs.enabled():
            sig = _obs_recompile.signature_of(
                arg_arrays.values(), train=is_train)
            _obs_recompile.note_call(
                "Executor[%s]" % self._symbol.list_outputs()[0], sig)
            self._obs_sig = sig
        fwd_span = _obs.span("forward", cat="step", executor=True,
                             train=is_train).start()
        if is_train:
            diff = [arg_arrays[n] for n in self._diff_args]
            rest = {k: v for k, v in arg_arrays.items()}
            if sig is not None and self._jitted \
                    and _obs_attr.ops_enabled():
                _obs_attr.register_program(
                    "Executor[%s].fwd" % self._symbol.list_outputs()[0],
                    sig, self._fwd_res_fn, (diff, rest, aux_arrays, key))
            if _membudget.enabled() and self._jitted:
                _membudget.preflight(
                    "Executor[%s].fwd" % self._symbol.list_outputs()[0],
                    self._fwd_res_fn, (diff, rest, aux_arrays, key),
                    signature=sig)
            try:
                outs, aux_up, vjp, _ = call_fwd_res(
                    self._fwd_res_fn, diff, rest, aux_arrays, key)
            except Exception as exc:
                _membudget.note_oom(
                    "Executor[%s].fwd" % self._symbol.list_outputs()[0],
                    exc)
                raise
            self._saved_vjp = (vjp, outs)
            for name, val in aux_up.items():
                self.aux_dict[name]._data = val
        else:
            self._saved_vjp = None
            if sig is not None and self._jitted \
                    and _obs_attr.ops_enabled():
                _obs_attr.register_program(
                    "Executor[%s].infer"
                    % self._symbol.list_outputs()[0],
                    sig, self._infer_fn, (arg_arrays, aux_arrays, key))
            if _membudget.enabled() and self._jitted:
                _membudget.preflight(
                    "Executor[%s].infer"
                    % self._symbol.list_outputs()[0],
                    self._infer_fn, (arg_arrays, aux_arrays, key),
                    signature=sig)
            try:
                outs = self._infer_fn(arg_arrays, aux_arrays, key)
            except Exception as exc:
                _membudget.note_oom(
                    "Executor[%s].infer"
                    % self._symbol.list_outputs()[0], exc)
                raise
        _engine.sync_if_needed(outs)
        fwd_span.stop()
        self.outputs = [nd.NDArray(o, self._ctx) for o in outs]
        return self.outputs

    def backward(self, out_grads=None):
        from . import ndarray as nd
        if self._saved_vjp is None:
            raise MXNetError("backward called before forward(is_train=True)")
        bwd_span = _obs.span("backward", cat="step",
                             executor=True).start()
        vjp, outs = self._saved_vjp
        if out_grads is None:
            heads = [jnp.ones_like(o) for o in outs]
        else:
            if isinstance(out_grads, nd.NDArray):
                out_grads = [out_grads]
            heads = [g._data if isinstance(g, nd.NDArray) else jnp.asarray(g)
                     for g in out_grads]
        cotangent = type(outs)(heads) if isinstance(outs, (tuple, list)) \
            else heads[0]
        if self._obs_sig is not None and self._jitted \
                and _obs_attr.ops_enabled():
            _obs_attr.register_program(
                "Executor[%s].bwd" % self._symbol.list_outputs()[0],
                self._obs_sig, self._bwd_fn, (vjp, cotangent))
        if _membudget.enabled() and self._jitted:
            _membudget.preflight(
                "Executor[%s].bwd" % self._symbol.list_outputs()[0],
                self._bwd_fn, (vjp, cotangent),
                signature=self._obs_sig)
        try:
            grads = self._bwd_fn(vjp, cotangent)
        except Exception as exc:
            _membudget.note_oom(
                "Executor[%s].bwd" % self._symbol.list_outputs()[0],
                exc)
            raise
        _engine.sync_if_needed(grads)
        for name, g in zip(self._diff_args, grads):
            req = self._grad_req.get(name, "write")
            tgt = self.grad_dict[name]
            if req == "add":
                tgt._data = tgt._data + g
            else:
                tgt._data = g
        bwd_span.stop()

    # ------------------------------------------------------- utilities --
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._symbol.list_arguments()]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._symbol.list_arguments()]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._symbol.list_auxiliary_states()]

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in arg_params.items():
            if k in self.arg_dict:
                self.arg_dict[k]._data = v._data.astype(self.arg_dict[k].dtype)
            elif not allow_extra_params:
                raise MXNetError("unknown parameter %s" % k)
        if aux_params:
            for k, v in aux_params.items():
                if k in self.aux_dict:
                    self.aux_dict[k]._data = v._data
                elif not allow_extra_params:
                    raise MXNetError("unknown aux state %s" % k)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """graph_executor.cc:876 Reshape — with jit, reshape is free: new
        shapes trigger a cached recompile keyed on the new signature."""
        from . import ndarray as nd
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        for name, shp in zip(self._symbol.list_arguments(), arg_shapes):
            cur = self.arg_dict[name]
            if tuple(cur.shape) != tuple(shp):
                self.arg_dict[name] = nd.zeros(shp, ctx=self._ctx, dtype=cur.dtype)
                if name in self.grad_dict and self.grad_dict[name] is not None:
                    self.grad_dict[name] = nd.zeros(shp, ctx=self._ctx,
                                                    dtype=cur.dtype)
        return self
