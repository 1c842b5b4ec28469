"""Bucketed gradient fusion — the DDP-class comm optimization.

The reference amortizes per-key communication three ways: comm.h groups
keys before reducing, MXNET_KVSTORE_BIGARRAY_BOUND shards big arrays,
and engine priorities overlap comm with remaining backward compute
(SURVEY §2.3). On this stack every per-key push is one XLA collective
dispatch, so a ResNet/LM-sized model pays hundreds of small dispatches
per step — exactly the per-key tax this module removes:

* ``plan_buckets`` packs keys, in the caller's (priority) order, into
  fixed-byte buckets (``MXNET_KVSTORE_BUCKET_BYTES``, default 25 MB —
  the same knob class as the reference's bigarray bound). Segments of
  different dtypes never share a flat buffer (bit-exactness first), so
  a bucket holds one *lane* per dtype.
* ``pack_lane`` / ``unpack_lane`` are pure jnp (trace-friendly) flatten/
  concat/slice helpers shared by the eager KVStore path and the in-jit
  path.
* ``bucketed_all_reduce`` is the in-jit form: inside shard_map/pjit it
  emits ONE psum per bucket lane, which XLA schedules asynchronously —
  collectives for already-finished buckets overlap the remaining
  backward compute (the reference's priority overlap, expressed in the
  graph as "Automatic Cross-Replica Sharding of Weight Update ..."
  (PAPERS.md) and the TF design argue it should be).
* ``FlatOptimizer`` + ``ShardSlot`` implement the cross-replica-sharded
  weight update (``MXNET_KVSTORE_SHARD_UPDATE=1``): per bucket lane,
  reduce-scatter the flat gradient, update a 1/N shard of the flat
  master weight + optimizer state per device, all-gather the updated
  weight. Optimizer FLOPs and master/optimizer state bytes per replica
  drop by (N-1)/N (the PAPERS.md win).
"""

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import _fastenv
from ..observability import chaos as _chaos
from ..observability import watchdog as _wd

__all__ = ["DEFAULT_BUCKET_BYTES", "bucket_bytes", "fusion_enabled",
           "shard_update_enabled", "Segment", "Lane", "Bucket",
           "plan_buckets", "plan_signature", "pack_lane", "unpack_lane",
           "bucketed_all_reduce", "FlatOptimizer", "ShardSlot"]

DEFAULT_BUCKET_BYTES = 25 << 20          # ~25 MB, torch-DDP-class default


def bucket_bytes(override=None):
    """Bucket byte budget: explicit arg > env knob > 25 MB default."""
    if override is not None:
        return int(override)
    return int(_fastenv.get("MXNET_KVSTORE_BUCKET_BYTES",
                            DEFAULT_BUCKET_BYTES))


def fusion_enabled():
    """MXNET_KVSTORE_FUSION gates the bucketed Trainer/Module paths
    (default ON; =0 restores per-key push/pull)."""
    return _fastenv.get("MXNET_KVSTORE_FUSION", "1").lower() \
        not in ("0", "false")


def shard_update_enabled():
    """MXNET_KVSTORE_SHARD_UPDATE=1 lowers each bucket to
    reduce-scatter -> sharded optimizer update -> all-gather."""
    return _fastenv.get("MXNET_KVSTORE_SHARD_UPDATE", "0").lower() \
        in ("1", "true")


DEFAULT_BIGARRAY_BOUND = 1_000_000       # elements — the reference default


def bigarray_bound():
    """MXNET_KVSTORE_BIGARRAY_BOUND (elements, reference kvstore_dist.h
    default 1e6): arrays above the bound travel ALONE. A single-segment
    lane packs as a reshape view — no concat copy — so big tensors pay
    zero packing overhead while the small-tensor tail still fuses."""
    return int(_fastenv.get("MXNET_KVSTORE_BIGARRAY_BOUND",
                            DEFAULT_BIGARRAY_BOUND))


# ------------------------------------------------------------ planning --

class Segment(object):
    """One key's slice of a lane's flat buffer."""
    __slots__ = ("key", "shape", "dtype", "size", "offset")

    def __init__(self, key, shape, dtype, size, offset):
        self.key, self.shape, self.dtype = key, tuple(shape), dtype
        self.size, self.offset = size, offset

    def __repr__(self):
        return "Segment(%r, %s, %s, @%d)" % (self.key, self.shape,
                                             self.dtype, self.offset)


class Lane(object):
    """All same-dtype segments of one bucket, flattened back to back.
    Mixed dtypes never share a buffer: concatenating them would force a
    cast and break bit-exactness with the per-key path."""
    __slots__ = ("dtype", "segments", "size")

    def __init__(self, dtype):
        self.dtype = dtype
        self.segments = []
        self.size = 0

    @property
    def nbytes(self):
        return self.size * np.dtype(self.dtype).itemsize


class Bucket(object):
    __slots__ = ("index", "lanes", "nbytes")

    def __init__(self, index):
        self.index = index
        self.lanes = []                  # ordered by first appearance
        self.nbytes = 0

    def _lane(self, dtype):
        for lane in self.lanes:
            if lane.dtype == dtype:
                return lane
        lane = Lane(dtype)
        self.lanes.append(lane)
        return lane

    def add(self, key, shape, dtype):
        lane = self._lane(dtype)
        size = int(np.prod(shape)) if len(shape) else 1
        lane.segments.append(Segment(key, shape, dtype, size, lane.size))
        lane.size += size
        self.nbytes += size * np.dtype(dtype).itemsize


def plan_buckets(entries, max_bytes=None):
    """Greedy fixed-byte bucketing in the given (priority) order.

    entries: iterable of (key, shape, dtype). A bucket closes when the
    next entry would push it past the byte budget. Arrays above
    MXNET_KVSTORE_BIGARRAY_BOUND elements travel ALONE (the reference's
    bigarray rule, kvstore_dist.h): a single-segment lane flattens as a
    reshape view instead of a concat copy, so big tensors pay no
    packing overhead while the small-tensor tail still fuses. Callers
    pass entries in reverse-registration order so the bucket holding
    the LAST layers' gradients — ready first in backward — reduces
    first.
    """
    max_bytes = bucket_bytes(max_bytes)
    solo_elems = bigarray_bound()
    buckets = []
    cur = None
    for key, shape, dtype in entries:
        dtype = str(np.dtype(dtype))
        size = int(np.prod(shape)) if len(shape) else 1
        nbytes = size * np.dtype(dtype).itemsize
        if size > solo_elems:
            solo = Bucket(len(buckets))
            buckets.append(solo)
            solo.add(key, shape, dtype)
            cur = None                   # never append after a bigarray
            continue
        if cur is None or (cur.nbytes and cur.nbytes + nbytes > max_bytes):
            cur = Bucket(len(buckets))
            buckets.append(cur)
        cur.add(key, shape, dtype)
    return buckets


def plan_signature(entries, max_bytes=None):
    """Hashable identity of a plan — kvstore caches plans per signature."""
    return (bucket_bytes(max_bytes), bigarray_bound(),
            tuple((k, tuple(s), str(np.dtype(d))) for k, s, d in entries))


# ------------------------------------------------------- pack / unpack --

def pack_lane(lane, values, pad_to=None):
    """Concat one worker's arrays for this lane into a flat buffer.
    ``values``: key -> array. Pure jnp — usable eagerly and under jit.
    ``pad_to`` zero-pads the tail (shard paths need length % n == 0)."""
    flats = [jnp.ravel(values[seg.key]) for seg in lane.segments]
    flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
    if pad_to is not None and pad_to > lane.size:
        flat = jnp.concatenate(
            [flat, jnp.zeros(pad_to - lane.size, dtype=flat.dtype)])
    return flat


def unpack_lane(flat, lane):
    """Inverse of pack_lane: flat buffer -> {key: array} views. A
    single-segment (bigarray) lane is just a reshape — no slice op."""
    if len(lane.segments) == 1 and lane.segments[0].size == flat.shape[0]:
        seg = lane.segments[0]
        return {seg.key: flat.reshape(seg.shape)}
    return {seg.key: jax.lax.slice_in_dim(
        flat, seg.offset, seg.offset + seg.size).reshape(seg.shape)
        for seg in lane.segments}


# ------------------------------------------------------- in-jit fusion --

def bucketed_all_reduce(values, axis_name="dp", max_bytes=None,
                        keys=None):
    """Fused all-reduce for use INSIDE shard_map/pjit.

    ``values``: list of (traced) arrays, already in priority order.
    Emits one ``lax.psum`` per bucket lane instead of one per array, so
    a jitted train step dispatches O(total_bytes / bucket_bytes)
    collectives; XLA overlaps each bucket's psum with whatever backward
    compute has not produced the next bucket yet. Returns the reduced
    arrays in input order.
    """
    keys = list(range(len(values))) if keys is None else list(keys)
    by_key = dict(zip(keys, values))
    plan = plan_buckets(
        [(k, by_key[k].shape, by_key[k].dtype) for k in keys], max_bytes)
    out = {}
    for bucket in plan:
        for lane in bucket.lanes:
            flat = pack_lane(lane, by_key)
            red = jax.lax.psum(flat, axis_name)
            out.update(unpack_lane(red, lane))
    return [out[k] for k in keys]


# ---------------------------------------------- sharded weight update --

class FlatOptimizer(object):
    """Flat elementwise form of an Optimizer's update rule.

    The sharded update applies the optimizer to a 1/N shard of a flat
    bucket, so the rule must be elementwise over the flat buffer with
    scalar (or per-element) hyperparameters. Supported rules mirror the
    jitted kernels in optimizer.py exactly (same math, same order of
    operations): sgd (+momentum), nag, adam. ``supports`` returns None
    for anything else and callers fall back to the replicated per-key
    update.
    """

    RULES = {
        "sgd": 1, "nag": 1, "adam": 2,          # name -> n state buffers
    }

    def __init__(self, optimizer, name):
        self.optimizer = optimizer
        self.name = name
        self.n_states = 0 if name in ("sgd", "nag") \
            and not getattr(optimizer, "momentum", 0.0) \
            else self.RULES[name]

    @classmethod
    def supports(cls, optimizer):
        """A FlatOptimizer when the rule is shardable, else None.
        Subclass instances are rejected: an override of update()/
        _apply_rule would silently diverge from the flat rule."""
        if optimizer is None:
            return None
        for name, klass in (("sgd", "SGD"), ("nag", "NAG"),
                            ("adam", "Adam")):
            mod = type(optimizer).__module__
            if type(optimizer).__name__ == klass \
                    and mod.endswith("optimizer"):
                return cls(optimizer, name)
        return None

    # hyperparameters resolved host-side per step (cheap scalars); the
    # compiled shard function takes them as traced operands so schedules
    # never recompile
    def step_scalars(self, t):
        o = self.optimizer
        lr = o.learning_rate
        if self.name == "adam":
            lr = lr * math.sqrt(1.0 - o.beta2 ** t) / (1.0 - o.beta1 ** t)
        return (np.float32(lr), np.float32(o.wd),
                np.float32(o.rescale_grad))

    def extra_scalars(self):
        o = self.optimizer
        if self.name == "adam":
            return (np.float32(o.beta1), np.float32(o.beta2),
                    np.float32(o.epsilon))
        return (np.float32(getattr(o, "momentum", 0.0)),)

    @property
    def clip(self):
        c = self.optimizer.clip_gradient
        return None if c is None else float(c)

    def apply(self, w, g, states, lr, wd, extra, clip, lr_mult=None,
              wd_mult=None):
        """The elementwise rule — called inside the compiled shard map.
        Matches optimizer.py's _sgd_update/_sgd_mom_update/
        _nag_mom_update/_adam_update bit for bit on each element."""
        if clip is not None:
            g = jnp.clip(g, -clip, clip)
        if lr_mult is not None:
            lr = lr * lr_mult
        if wd_mult is not None:
            wd = wd * wd_mult
        if self.name == "adam":
            beta1, beta2, eps = extra
            m, v = states
            g = g + wd * w
            m = beta1 * m + (1.0 - beta1) * g
            v = beta2 * v + (1.0 - beta2) * g * g
            return w - lr * m / (jnp.sqrt(v) + eps), (m, v)
        (momentum,) = extra
        if not self.n_states:
            return w - lr * (g + wd * w), ()
        (mom,) = states
        if self.name == "nag":
            g = g + wd * w
            mom = momentum * mom + g
            return w - lr * (momentum * mom + g), (mom,)
        mom = momentum * mom - lr * (g + wd * w)
        return w + mom, (mom,)


@functools.lru_cache(maxsize=256)
def _shard_update_fn(devices, n, l_pad, wdtype, gdtype, rule_name,
                     n_states, has_clip, has_mults, scatter):
    """Compiled reduce-scatter -> shard update -> (sharded out) program
    for one bucket lane. Cached per lane geometry; hyperparameters ride
    as traced scalars.

    ``scatter=True`` takes [n, l_pad] per-worker gradients and
    reduce-scatters them (the multi-worker push path). ``scatter=False``
    takes one already-reduced flat gradient laid out P('worker') — each
    device just updates its slice (the Trainer path, where XLA reduced
    the grad inside the step already)."""
    mesh = Mesh(np.asarray(devices), ("worker",))
    g_spec = P("worker", None) if scatter else P("worker")
    s_spec = P("worker")                     # flat shards [l_pad/n]
    r_spec = P()                             # replicated scalars

    def local(g, w, states, scalars, mults):
        lr, wd, rescale, clip, extra = scalars
        if scatter:
            g = jax.lax.psum_scatter(g.reshape(-1), "worker",
                                     scatter_dimension=0, tiled=True)
        g = g.astype(w.dtype) * rescale
        lr_mult, wd_mult = mults if has_mults else (None, None)
        rule = _RULE_CACHE[(rule_name, n_states)]
        w, states = rule(w, g, states, lr, wd, extra,
                         clip if has_clip else None, lr_mult, wd_mult)
        return w, states

    in_specs = (g_spec, s_spec, tuple(s_spec for _ in range(n_states)),
                (r_spec, r_spec, r_spec, r_spec,
                 tuple(r_spec for _ in range(_N_EXTRA[rule_name]))),
                (s_spec, s_spec) if has_mults else (r_spec, r_spec))
    out_specs = (s_spec, tuple(s_spec for _ in range(n_states)))
    mapped = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs)
    return jax.jit(mapped, donate_argnums=(1, 2))


# rule fns used inside the compiled program; mirrors FlatOptimizer.apply
_N_EXTRA = {"sgd": 1, "nag": 1, "adam": 3}


def _make_rule(name, n_states):
    def rule(w, g, states, lr, wd, extra, clip, lr_mult, wd_mult):
        shim = FlatOptimizer.__new__(FlatOptimizer)
        shim.name = name
        shim.n_states = n_states
        return shim.apply(w, g, states, lr, wd, extra, clip,
                          lr_mult, wd_mult)
    return rule


_RULE_CACHE = {}


@functools.lru_cache(maxsize=64)
def _gather_fn(devices, l_pad, dtype):
    """All-gather a sharded flat buffer back to replicated (the third
    leg of reduce-scatter -> update -> all-gather)."""
    mesh = Mesh(np.asarray(devices), ("worker",))
    return jax.jit(lambda x: x,
                   out_shardings=NamedSharding(mesh, P()))


class ShardSlot(object):
    """Persistent sharded state for one bucket lane: flat master weight
    plus optimizer state, each a [l_pad] global array sharded 1/N per
    device over the worker axis. Per-replica bytes for master+state are
    total/N — the (N-1)/N cut of "Automatic Cross-Replica Sharding of
    Weight Update in Data-Parallel Training" (PAPERS.md).
    """

    def __init__(self, lane, devices, weights, flat_opt, t0=0):
        self.lane = lane
        self.devices = tuple(devices)
        self.n = len(self.devices)
        self.l_pad = -(-lane.size // self.n) * self.n   # ceil to n
        self.flat_opt = flat_opt
        self.t = int(t0)
        mesh = Mesh(np.asarray(self.devices), ("worker",))
        self._mesh = mesh
        self._shard = NamedSharding(mesh, P("worker"))
        self._g_shard = NamedSharding(mesh, P("worker", None))
        # master weight: fp32 when the optimizer runs multi-precision on
        # a low-precision lane (the fp32-master-state the paper shards)
        wdtype = np.dtype(lane.dtype)
        self.master_fp32 = bool(
            getattr(flat_opt.optimizer, "multi_precision", False)
            and wdtype == np.dtype(jnp.bfloat16))
        mdtype = np.dtype(np.float32) if self.master_fp32 else wdtype
        self.mdtype = mdtype
        flat_w = pack_lane(lane, weights, pad_to=self.l_pad)
        self.flat_w = jax.device_put(flat_w.astype(mdtype), self._shard)
        self.states = tuple(
            jax.device_put(jnp.zeros(self.l_pad, mdtype), self._shard)
            for _ in range(flat_opt.n_states))
        self._mults = self._build_mults()
        rule_name = flat_opt.name
        rule_key = (rule_name, flat_opt.n_states)
        if rule_key not in _RULE_CACHE:
            _RULE_CACHE[rule_key] = _make_rule(rule_name,
                                               flat_opt.n_states)
        self._fns = {
            scatter: _shard_update_fn(
                self.devices, self.n, self.l_pad, str(mdtype),
                str(lane.dtype), rule_name, flat_opt.n_states,
                flat_opt.clip is not None, self._mults is not None,
                scatter)
            for scatter in (True, False)}

    def _build_mults(self):
        """Per-element lr/wd multiplier vectors — only materialized when
        some segment's multiplier differs from 1 (Module set_lr_mult /
        set_wd_mult tables); the common case stays scalar."""
        o = self.flat_opt.optimizer
        idxs = [int(s.key) if str(s.key).isdigit() else s.key
                for s in self.lane.segments]
        try:
            lrs = [o._get_lr(i) for i in idxs]
            wds = [o._get_wd(i) for i in idxs]
        except Exception:
            return None
        base_lr = o.learning_rate or 1.0
        lr_r = [l / base_lr if base_lr else 1.0 for l in lrs]
        wd_r = [w / o.wd if o.wd else 1.0 for w in wds]
        if all(abs(r - 1.0) < 1e-12 for r in lr_r + wd_r):
            return None
        lr_vec = np.ones(self.l_pad, np.float32)
        wd_vec = np.ones(self.l_pad, np.float32)
        for seg, lm, wm in zip(self.lane.segments, lr_r, wd_r):
            lr_vec[seg.offset:seg.offset + seg.size] = lm
            wd_vec[seg.offset:seg.offset + seg.size] = wm
        return (jax.device_put(jnp.asarray(lr_vec), self._shard),
                jax.device_put(jnp.asarray(wd_vec), self._shard))

    @property
    def state_bytes_total(self):
        per = self.l_pad * self.mdtype.itemsize
        return per * (len(self.states) + (1 if self.master_fp32 else 0))

    @property
    def state_bytes_per_replica(self):
        return self.state_bytes_total // self.n

    def step(self, per_worker_flats):
        """One sharded update from per-worker flat gradient buffers
        (each already padded to l_pad). With exactly n buffers the
        reduction is a reduce-scatter; with one (the Trainer path — XLA
        already reduced the grad) or a mismatched count, the summed
        flat gradient is sliced across devices instead. Returns the
        updated flat weight REPLICATED (the all-gather leg), in the
        lane dtype."""
        self.t += 1
        scatter = len(per_worker_flats) == self.n and self.n > 1
        if scatter:
            shards = [jax.device_put(f[None], d)
                      for f, d in zip(per_worker_flats, self.devices)]
            g = jax.make_array_from_single_device_arrays(
                (self.n, self.l_pad), self._g_shard, shards)
        else:
            g = per_worker_flats[0]
            for f in per_worker_flats[1:]:
                g = g + f
            g = jax.device_put(g, self._shard)
        lr, wd, rescale = self.flat_opt.step_scalars(self.t)
        clip = self.flat_opt.clip
        scalars = (jnp.float32(lr), jnp.float32(wd),
                   jnp.float32(rescale),
                   jnp.float32(0.0 if clip is None else clip),
                   tuple(jnp.float32(x)
                         for x in self.flat_opt.extra_scalars()))
        mults = self._mults if self._mults is not None \
            else (jnp.float32(1.0), jnp.float32(1.0))
        # reduce-scatter -> update -> all-gather is two collective
        # dispatches; a post-mortem should name the lane that hung
        with _wd.watch("fusion.shard_update", lane=str(self.lane.dtype),
                       bytes=self.l_pad * self.mdtype.itemsize,
                       keys=len(self.lane.segments)):
            if _chaos.enabled():
                # chaos site: the sharded-update program is one of the
                # named collectives the injection harness can hang
                _chaos.fire("fusion.shard_update",
                            lane=str(self.lane.dtype))
            self.flat_w, self.states = self._fns[scatter](
                g, self.flat_w, self.states, scalars, mults)
            gathered = _gather_fn(self.devices, self.l_pad,
                                  str(self.mdtype))(self.flat_w)
        if self.master_fp32:
            gathered = gathered.astype(np.dtype(self.lane.dtype))
        return gathered

    # ------------------------------------------------- state (de)hydrate --
    def get_state(self):
        """Host snapshot for save_optimizer_states round-trips."""
        return {"t": self.t,
                "flat_w": np.asarray(self.flat_w),
                "states": [np.asarray(s) for s in self.states]}

    def set_state(self, snap):
        self.t = int(snap["t"])
        self.flat_w = jax.device_put(
            jnp.asarray(snap["flat_w"], self.mdtype), self._shard)
        self.states = tuple(
            jax.device_put(jnp.asarray(s, self.mdtype), self._shard)
            for s in snap["states"])
