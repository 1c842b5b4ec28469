"""Gluon Trainer.

Reference: python/mxnet/gluon/trainer.py:27 (step:305,
_allreduce_grads:356, _update:399). Applies an Optimizer to a set of
Parameters; gradient aggregation across data-parallel devices goes through
the KVStore layer, which on this build is XLA collectives over the active
device mesh.

Store: resolved by the reference's rule (model.py _create_kvstore, "no
need to use kv for single device and single machine"): a string spec with
no "dist" in it on one gradient copy a parameter is NO store, and the
step's allreduce phase launches nothing (docs/GRAD_FUSION.md).

Comm path: with a store, gradients travel BUCKETED by default
(parallel/fusion.py) — keys pack into ~25 MB buckets in
reverse-registration order (the last layers' grads, ready first in
backward, reduce first — the reference's priority push, trainer.py:356
priority=-idx) and each bucket is one fused collective dispatch; XLA's
async dispatch overlaps a bucket's all-reduce with the packing of the
next. MXNET_KVSTORE_FUSION=0 restores the per-key path.
MXNET_KVSTORE_SHARD_UPDATE=1 additionally moves the optimizer into the
store as a reduce-scatter -> sharded update -> all-gather per bucket
(PAPERS.md cross-replica sharding), which cuts per-replica optimizer
state by (N-1)/N.

Update path: with the update outside the store (the default), _update
hands the Updater EVERY fresh parameter in one call, and the Updater
(optimizer.py) runs one multi-tensor program a step for all it can fuse:
built-in SGD (+momentum), NAG and Adam on dense gradients, multi_precision
masters included. Row-sparse gradients, the other rules, Optimizer
subclasses that override the update and parameters on unlike device sets
are updated one at a time inside the same call (docs/GRAD_FUSION.md).
"""

import time as _time

from .. import optimizer as opt
from .. import kvstore as kvs
from ..base import MXNetError
from ..model import _create_kvstore
from ..observability import chaos as _chaos
from ..observability import core as _obs
from ..observability import dist as _obs_dist
from ..observability import goodput as _obs_goodput
from ..observability import integrity as _integrity
from ..observability import membudget as _membudget
from ..observability import recompile as _obs_recompile
from ..parallel import elastic as _elastic
from ..parallel import fusion
from .parameter import Parameter

__all__ = ["Trainer"]


class Trainer(object):
    """Applies an Optimizer on a set of Parameters.

    Parameters
    ----------
    params : ParameterDict or list of Parameter
    optimizer : str or Optimizer
    optimizer_params : dict
    kvstore : str, KVStore or None, default 'device'
        A string with no 'dist' in it ('device', 'local') means no store
        when every parameter holds one gradient copy, as every Gluon
        parameter does: with nobody else contributing the all-reduce is
        the identity, so `_kvstore` stays None, the update is local and
        the step's `allreduce` span is empty. Such a string still builds
        its store when `compression_params` or `update_on_kvstore=True`
        ask for what only a store does. A 'dist*' type
        ('dist_tpu_sync' over the device mesh: data parallelism) and a
        KVStore instance are always used as given; None is no store.
    compression_params : dict, optional (gradient compression config)
    update_on_kvstore : bool, optional
    """

    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None):
        seq = list(params.values()) if hasattr(params, "values") \
            else params
        if not isinstance(seq, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                "got %s." % (type(params)))
        outsider = next(
            (p for p in seq if not isinstance(p, Parameter)), None)
        if outsider is not None:
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                "got list of %s." % (type(outsider)))
        self._params = list(seq)
        self._param2idx = {p.name: i
                           for i, p in enumerate(self._params)}
        for p in self._params:
            p._trainer = self
        self._compression_params = compression_params
        hyper = dict(optimizer_params or {})
        self._scale = float(hyper.get("rescale_grad", 1.0))
        self._init_optimizer(optimizer, hyper)
        self._kvstore_type = kvstore
        self._kvstore = None
        self._update_on_kvstore = update_on_kvstore
        self._kv_initialized = False
        self._states = {}

    def _init_optimizer(self, optimizer, hyper):
        ready_made = isinstance(optimizer, opt.Optimizer)
        assert not (ready_made and hyper), \
            "optimizer_params must be None if optimizer is an " \
            "Optimizer instance"
        self._optimizer = optimizer if ready_made \
            else opt.create(optimizer, **hyper)
        self._updaters = [opt.get_updater(self._optimizer)]

    def _resolve_store(self):
        """The reference's rule (model.py:69 _create_kvstore, which its
        Trainer._init_kvstore calls): a string spec with no ``dist`` in
        it, on parameters that hold one gradient copy each, needs no
        store — a reduction over one contributor is the identity. A
        KVStore instance and ``None`` are taken as given, and a string
        whose caller also asked for what only a store does (gradient
        compression, the update on the store) still builds one."""
        spec = self._kvstore_type
        if isinstance(spec, str) and (self._compression_params
                                      or self._update_on_kvstore):
            return kvs.create(spec)
        copies = max((len(p.list_grad()) for _, p in self._trainable()
                      if p._data is not None), default=1)
        return _create_kvstore(
            spec, copies, {p.name: p for p in self._params})[0]

    def _init_kvstore(self):
        kv = self._kvstore = self._resolve_store()
        if self._update_on_kvstore is None:
            # the sharded weight update runs INSIDE the store (its
            # reduce-scatter -> sharded-update -> all-gather program
            # owns the optimizer state), so requesting it flips the
            # update onto the kvstore; every other config updates
            # locally as before
            self._update_on_kvstore = bool(
                kv is not None
                and fusion.shard_update_enabled()
                and kv.supports_shard_update()
                and fusion.FlatOptimizer.supports(self._optimizer)
                is not None)
        if kv is not None:
            if self._compression_params:
                kv.set_gradient_compression(self._compression_params)
            for slot, param in enumerate(self._params):
                if param._data is not None:
                    kv.init(slot, param.data())
            if self._update_on_kvstore:
                kv.set_optimizer(self._optimizer)
        self._kv_initialized = True

    def _ready(self):
        """Lazy kvstore bring-up shared by every entry point."""
        if not self._kv_initialized:
            self._init_kvstore()

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @learning_rate.setter
    def learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # ------------------------------------------------------------- step --
    def step(self, batch_size, ignore_stale_grad=False):
        """Makes one parameter update step: rescale grads by 1/batch_size,
        allreduce across data-parallel replicas, apply optimizer
        (gluon/trainer.py:305)."""
        self._ready()
        _t_step_ns = _time.perf_counter_ns() if _obs.enabled() else None
        try:
            with _obs.span("trainer.step", cat="step"):
                self._optimizer.rescale_grad = self._scale / batch_size
                if _chaos.enabled():
                    # chaos site: an "oom" rule raises a real-shaped
                    # RESOURCE_EXHAUSTED here — the membudget taxonomy
                    # and recovery paths' replayable prey
                    _chaos.fire("trainer.step")
                    # a "nan" rule poisons this step's local gradients
                    # — the fault the step guard below exists for
                    _chaos.poison_ndarrays(
                        "trainer.grads",
                        [p.grad() for _, p in self._trainable()
                         if p._data is not None])
                    # silent weight corruption on this rank — the
                    # integrity cross-rank vote's prey
                    _chaos.poison_bitflip(
                        "trainer.weights",
                        [p.data() for _, p in self._trainable()
                         if p._data is not None])
                if _chaos.step_guard_enabled() \
                        and not self._grads_finite():
                    # non-finite loss/grads: skip allreduce AND update
                    # (the update may live inside the store), back off
                    # the AMP loss scale when one rides the trainer,
                    # and count the skip — one bad batch must never
                    # poison the weights
                    _chaos.count_skipped_step(
                        "trainer",
                        getattr(self, "_amp_loss_scaler", None))
                    return
                self._allreduce_grads()
                # AMP fp16 dynamic loss scaling
                # (contrib.amp.init_trainer): check overflow, fold
                # 1/scale into the update, skip the step when any grad
                # is non-finite
                scaler = getattr(self, "_amp_loss_scaler", None)
                if scaler is not None:
                    skip = scaler.has_overflow(self._params)
                    scaler.update_scale(skip)
                    if skip:
                        return
                    self._optimizer.rescale_grad /= scaler.loss_scale
                self._update(ignore_stale_grad)
        except Exception as exc:
            # OOM taxonomy: classify a RESOURCE_EXHAUSTED (and, under
            # MXNET_MEM_OOM_ACTION=checkpoint, route through the
            # emergency provider + exit 47 for the supervisor). A
            # non-OOM error — or an unarmed run — re-raises untouched.
            _membudget.handle_trainer_oom(exc)
            raise
        if _obs.enabled():
            # bounded-memory step-time distribution (p99 over the whole
            # run, not the ring suffix); per-rank histograms merge
            # bucket-wise in merged traces
            if _t_step_ns is not None:
                _obs.histogram("trainer.step_ms", "ms").observe(
                    (_time.perf_counter_ns() - _t_step_ns) / 1e6)
            # arm the recompile detector once the step's graphs exist,
            # and (multi-worker, every MXNET_OBS_SKEW_EVERY steps) run
            # the cross-rank straggler exchange
            _obs_recompile.step_boundary()
            _obs_dist.step_boundary(self._kvstore)
            # goodput ledger: this step committed (skip paths returned
            # above) — count it and, once per elastic generation, write
            # the first-commit sideband record that closes the
            # recovery interval (goodput.elastic_downtime)
            _obs_goodput.note_step_commit(
                getattr(self, "_elastic_steps", None))
            # step-cadence mem.device.* gauge refresh (no-op unless
            # MXNET_MEM_GAUGE_EVERY is set) — headroom-driven brownout
            # and routing act on live data, not dump-time snapshots
            from .. import storage as _storage
            _storage.maybe_publish_device_memory_gauges()
        if _elastic.enabled():
            # elastic membership: heartbeat + dead-peer check at the
            # step boundary (the fast path — a peer detected here
            # shrinks BEFORE the next collective can wedge this rank)
            self._elastic_steps = getattr(self, "_elastic_steps", 0) + 1
            _elastic.step_boundary(self._elastic_steps)
        if _integrity.enabled():
            # silent-corruption detectors: replay-audit the lanes
            # recorded during this step's fused all-reduce and, on
            # cadence, run the cross-rank parameter fingerprint vote
            _integrity.step_boundary(self._integrity_items(),
                                     kv=self._kvstore)

    def allreduce_grads(self):
        self._ready()
        self._allreduce_grads()

    def _grads_finite(self):
        """Device-side finiteness verdict over this step's gradients
        (one scalar sync). Only consulted when MXNET_STEP_GUARD=1."""
        return _chaos.all_finite(
            [p.grad()._data for _, p in self._trainable()
             if p._data is not None])

    def _trainable(self):
        """(kvstore slot, param) for every param that receives grads."""
        return ((slot, p) for slot, p in enumerate(self._params)
                if p.grad_req != "null")

    def _integrity_items(self):
        """(slot, weight jax array) in the same reverse-registration
        order the fused gradient path uses, so vote evidence names the
        same bucket/lane a corrupt gradient would ride."""
        items = [(slot, p.data()._data) for slot, p in self._trainable()
                 if p._data is not None]
        items.reverse()
        return items

    def _allreduce_grads(self):
        with _obs.span("allreduce", cat="step",
                       fused=fusion.fusion_enabled()):
            if self._kvstore is None:
                # nobody else contributes: the phase opens, so a step's
                # spans read the same with and without a store, and
                # launches nothing
                if _obs.enabled():
                    _obs.counter("trainer.allreduce_noop").add()
                return
            self._allreduce_grads_impl()

    def _allreduce_grads_impl(self):
        if fusion.fusion_enabled():
            items = [(slot, p) for slot, p in self._trainable()
                     if p._data is not None]
            if not items:
                return
            # reverse-registration (priority) order: backward produces
            # the LAST layers' gradients first, so their bucket's
            # collective dispatches first and overlaps the rest
            items.reverse()
            keys = [slot for slot, _ in items]
            grads = [p.grad() for _, p in items]
            self._kvstore.pushpull_fused(
                keys, grads,
                out=None if self._update_on_kvstore else grads)
            return
        for slot, param in self._trainable():
            self._kvstore.push(slot, param.grad(), priority=-slot)
            if not self._update_on_kvstore:
                self._kvstore.pull(slot, param.grad(), priority=-slot)

    def update(self, batch_size, ignore_stale_grad=False):
        self._ready()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        with _obs.span("update", cat="step",
                       on_kvstore=bool(self._update_on_kvstore)):
            self._update_impl(ignore_stale_grad)

    def _update_impl(self, ignore_stale_grad=False):
        on_kvstore = self._update_on_kvstore and self._kvstore is not None
        indices, grads, weights = [], [], []
        for i, param in self._trainable():
            if param._data is None:
                if not ignore_stale_grad:
                    raise MXNetError(
                        "Parameter %s has not been initialized" % param.name)
                continue
            if not getattr(param._data, "_fresh_grad", False):
                # grad array still holds a previous iteration's value
                # (reference: trainer.py _update fresh-grad check)
                if ignore_stale_grad:
                    continue
                raise UserWarning(
                    "Gradient of Parameter `%s` on context %s has not been "
                    "updated by backward since last `step`. This could mean "
                    "a bug in your model that made it only use a subset of "
                    "the Parameters (Blocks) for this iteration. If you are "
                    "intentionally only using a subset, call step with "
                    "ignore_stale_grad=True to suppress this warning"
                    % (param.name, str(param.list_ctx()[0])))
            if on_kvstore:
                self._kvstore.pull(i, param.data(), priority=-i)
                param._data._fresh_grad = False
            else:
                indices.append(i)
                grads.append(param.grad())
                weights.append(param.data())
        if indices:
            # every parameter in ONE call, as the reference's _update
            # does: the Updater fuses what it can into one program
            self._updaters[0](indices, grads, weights)
            for weight in weights:
                weight._fresh_grad = False

    # ------------------------------------------------------------ states --
    def save_states(self, fname):
        assert self._optimizer is not None
        self._ready()
        if self._update_on_kvstore and self._kvstore is not None:
            # the store owns the states (including sharded flat slots)
            self._kvstore.save_optimizer_states(fname)
            return
        with open(fname, "wb") as f:
            f.write(self._updaters[0].get_states(dump_optimizer=False))

    def load_states(self, fname):
        self._ready()
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.load_optimizer_states(fname)
            return
        with open(fname, "rb") as f:
            self._updaters[0].set_states(f.read())
