"""CachedOp — compiled trace for Gluon hybridize.

Reference: src/imperative/cached_op.{cc,h} (CachedOp::Forward:904,
DynamicForward:815, StaticForward:742, Backward:1128) — there, the traced
graph is replayed through the dependency engine with optional
static_alloc/static_shape memory planning.

TPU-native design: the traced Symbol is lowered to ONE jit-compiled XLA
computation per (is_train, shapes, dtypes, diff-set) signature via
executor.build_graph_fn. XLA subsumes static_alloc/static_shape (buffer
assignment), op bulking (fusion) and the backward-graph pass (jax.vjp).
Autograd integration records a single tape node whose pullback is the
compiled transpose of the whole computation — the reference's
CachedOp::Backward analogue. Auxiliary states (BatchNorm running
statistics) leave the compiled forward as `has_aux` outputs, here and in
Executor (executor.fwd_res_fn): their updates are computed and written
back, never differentiated, so a backward is one program however many
the block has.

What a recorded call hands its backward (executor.fwd_res_fn, PR 49): by
default the results of the MXU operations (matmuls, convolutions), of
the reductions (a batch norm's statistics) and what a host callback
returned; batch norm's normalisation, ReLU, casts,
adds and pooling are recomputed inside the backward program, and the
parameters and the batch a pullback reads are the caller's own arrays,
bound again on the host, not copies the forward returns. The reference's
mirror switch is off unless asked; here it is on unless refused
(hybridize(backward_do_mirror=False) or the variable at 0, see
executor.mirror_enabled: every intermediate is saved). While spans
record, the counters
cachedop.recorded_calls / cachedop.saved_buffers / cachedop.saved_bytes
count the recorded calls and what each returned for its backward.
"""

import jax

from . import autograd
from . import engine as _engine
from . import random as _random
from .base import MXNetError
from .executor import (build_graph_fn, call_fwd_res, fwd_res_fn,
                       mirror_enabled)
from .observability import attribution as _obs_attr
from .observability import core as _obs
from .observability import membudget as _membudget
from .observability import recompile as _obs_recompile

# fixed key fed to RNG-free graphs (never consumed; avoids a per-call
# host-side split)
_ZERO_KEY = None


def _zero_key():
    global _ZERO_KEY
    if _ZERO_KEY is None:
        _ZERO_KEY = jax.random.PRNGKey(0)
    return _ZERO_KEY


class CachedOp:
    """Compiled callable over a Symbol.

    ``__call__(*inputs)`` takes NDArrays ordered as ``sym.list_inputs()``
    (arguments and auxiliary states in declaration order), mirroring
    MXInvokeCachedOp (src/c_api/c_api_ndarray.cc:192). Auxiliary states
    (e.g. BatchNorm running stats) are updated in place on the passed
    NDArrays after each call.
    """

    def __init__(self, sym, flags=()):
        from . import ops as _ops
        self._sym = sym
        self._flags = dict(flags) if flags else {}
        self._arg_names = sym.list_arguments()
        self._aux_names = sym.list_auxiliary_states()
        self._input_names = sym.list_inputs()
        self._num_outputs = len(sym.list_outputs())
        # (is_train, diff_names, nan_guard, mirror) -> jitted fn;
        # guard/mirror toggles force a retrace on purpose
        self._fns = {}
        # RNG-free graphs (the common case) skip the per-call host-side
        # key split — a measurable slice of per-call latency
        # (benchmark/opperf.py --dispatch)
        self._needs_rng = any(
            _ops.get(n.op).stateful_rng
            for n in sym._active_nodes() if not n.is_var())

    @property
    def symbol(self):
        return self._sym

    def _obs_name(self):
        outs = self._sym.list_outputs()
        return outs[0] if outs else "cached_op"

    # ------------------------------------------------------------------
    def _get_fn(self, is_train, diff_names):
        from . import inspector as _inspector
        from .ops.nn import residual_knobs
        # keyed on the NaN-guard flag so toggling set_nan_guard()
        # retraces with/without the staged checks; ditto the residual-
        # format env knobs (int8/bn/relu/pool), which are read at trace
        # time
        key = (is_train, diff_names, _inspector.nan_guard_enabled(),
               mirror_enabled(self._flags) if diff_names else False,
               residual_knobs())
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        if _obs.enabled() and self._fns:
            # a second+ python-level variant of this op — legitimate
            # when a toggle (train/diff-set/guard) flipped, but the
            # detector records it so a variant storm is visible
            _obs_recompile.record_retrace(
                "CachedOp[%s]" % self._obs_name(),
                "train=%s diff=%d guard=%s mirror=%s"
                % (key[0], len(key[1]), key[2], key[3]))
        graph_fn = build_graph_fn(self._sym, is_train=is_train)

        if diff_names:
            # compile forward + residuals ONCE per signature (a per-call
            # jax.vjp would re-trace the whole graph); unless
            # hybridize(backward_do_mirror=False) or the environment
            # refuse it, backward recomputes the cheap activations under
            # the mirror policy instead of reading stored ones
            # (executor.fwd_res_fn: what is saved)
            fn = jax.jit(fwd_res_fn(graph_fn, diff_names,
                                    mirror_enabled(self._flags)))
        else:
            def pure(args, aux, rng_key):
                outs, aux_up = graph_fn(args, aux, rng_key)
                return tuple(outs), aux_up
            fn = jax.jit(pure)
        self._fns[key] = fn
        return fn

    # ------------------------------------------------------------------
    def __call__(self, *inputs):
        from . import ndarray as nd

        if len(inputs) != len(self._input_names):
            raise MXNetError(
                "CachedOp expects %d inputs (%s), got %d"
                % (len(self._input_names), self._input_names, len(inputs)))
        by_name = dict(zip(self._input_names, inputs))
        args = {n: by_name[n]._data for n in self._arg_names}
        aux = {n: by_name[n]._data for n in self._aux_names}
        rng_key = _random.next_key() if self._needs_rng else _zero_key()
        is_train = autograd.is_training()
        recording = autograd.is_recording()

        diff_names = tuple(
            n for n in self._arg_names
            if recording and by_name[n]._requires_tape())

        sig = None
        if _obs.enabled():
            # jit-boundary breadcrumb: if XLA re-traces inside the call
            # below, the detector attributes it to this signature
            sig = _obs_recompile.signature_of(
                inputs, train=is_train, diff=len(diff_names))
            _obs_recompile.note_call(
                "CachedOp[%s]" % self._obs_name(), sig)

        ctx = inputs[0]._ctx if inputs else None

        if diff_names:
            fn = self._get_fn(is_train, diff_names)
            diff_list = [args[n] for n in diff_names]
            if _membudget.enabled():
                _membudget.preflight(
                    "CachedOp[%s].fwd" % self._obs_name(), fn,
                    (diff_list, args, aux, rng_key), signature=sig)
            try:
                outs, aux_up, vjp_fn, saved = call_fwd_res(
                    fn, diff_list, args, aux, rng_key)
            except Exception as exc:
                _membudget.note_oom(
                    "CachedOp[%s].fwd" % self._obs_name(), exc)
                raise
            if _obs.active():
                # what this recorded call handed its backward, from the
                # outputs' avals (no device work)
                _obs.counter("cachedop.recorded_calls").add()
                _obs.counter("cachedop.saved_buffers").add(
                    len(saved.saved))
                _obs.counter("cachedop.saved_bytes", "bytes").add(
                    saved.nbytes())

            diff_nds = [by_name[n] for n in diff_names]

            out_shapes = [tuple(o.shape) for o in outs]
            out_dtypes = [o.dtype for o in outs]
            pull = autograd.apply_vjp(vjp_fn, out_shapes, out_dtypes)

            def tape_vjp(cts):
                # the pullback takes the outputs' cotangents ONLY (the
                # auxiliary states left the forward as has_aux outputs),
                # in the structure of the forward's `outs`
                cts_t = cts if isinstance(cts, tuple) else (cts,)
                origin = "CachedOp[%s].step" % self._obs_name()
                if sig is not None and _obs_attr.ops_enabled() \
                        and _obs_attr.needs_program(origin, sig):
                    # per-operator attribution: register a combined
                    # fwd+vjp analysis program. The runtime executes
                    # fn and the pullback as two programs, but replaying
                    # the stored vjp closure in a separate jit drops
                    # the op_name name-stack metadata — re-deriving the
                    # vjp inside ONE traced program keeps every
                    # backward instruction attributed to its block.
                    def _step(diff, rest, aux_a, key, ct):
                        _o, _a, v, _s = call_fwd_res(fn, diff, rest,
                                                     aux_a, key)
                        return (_o, _a), autograd.apply_vjp(
                            v, out_shapes, out_dtypes)(ct)
                    _obs_attr.register_program(
                        origin, sig, jax.jit(_step),
                        (diff_list, args, aux, rng_key, cts_t))
                if _membudget.enabled():
                    _membudget.preflight(origin, signature=sig)
                try:
                    (grads,) = pull(cts_t)
                except Exception as exc:
                    _membudget.note_oom(origin, exc)
                    raise
                return grads

            node = autograd.ProgramNode(
                tape_vjp, diff_nds, len(outs), out_shapes, out_dtypes,
                op_name="CachedOp")
            autograd._record_node(node)
            results = []
            for k, o in enumerate(outs):
                r = nd.NDArray(o, ctx)
                r._ag_node = (node, k)
                results.append(r)
        else:
            fn = self._get_fn(is_train, ())
            if sig is not None and _obs_attr.ops_enabled():
                _obs_attr.register_program(
                    "CachedOp[%s].fwd" % self._obs_name(), sig, fn,
                    (args, aux, rng_key))
            if _membudget.enabled():
                _membudget.preflight(
                    "CachedOp[%s].fwd" % self._obs_name(), fn,
                    (args, aux, rng_key), signature=sig)
            try:
                outs, aux_up = fn(args, aux, rng_key)
            except Exception as exc:
                _membudget.note_oom(
                    "CachedOp[%s].fwd" % self._obs_name(), exc)
                raise
            results = [nd.NDArray(o, ctx) for o in outs]

        for name, val in aux_up.items():
            by_name[name]._data = val

        datas = [r._data for r in results]
        _engine.sync_if_needed(datas)

        return results
