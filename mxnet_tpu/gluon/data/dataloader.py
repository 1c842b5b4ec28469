"""Gluon DataLoader.

Reference: python/mxnet/gluon/data/dataloader.py:77-285 — worker
processes decode/augment and ship batches through POSIX-shm pickled
NDArrays (src/storage/cpu_shared_storage_manager.h:269).

TPU-native design, two tiers:

* ``num_workers>0, thread_pool=True`` — thread pool. Decode/augment is
  numpy/cv2-side and releases the GIL; cheapest when the per-sample work
  is native.
* ``num_workers>0`` (default) — PROCESS pool with shared-memory batch
  passing, the reference's architecture. Each worker runs
  ``dataset[i]`` + a numpy-level batchify and writes the batch into one
  ``multiprocessing.shared_memory`` segment; the parent maps it
  zero-copy and converts to NDArray (the only device transfer).
  Workers NEVER touch jax: the runtime is not fork-safe, so all
  device work stays in the parent (divergence from the reference, where
  workers build shm NDArrays directly — here the NDArray conversion is
  the parent's single cheap step).

Worker start method: ``spawn`` (divergence from the reference's fork:
the parent holds a live multi-threaded jax runtime, which is not
fork-safe). Workers boot clean CPU-pinned interpreters; the dataset and
batchify must be picklable (NDArray implements __reduce__). Set
``MXNET_MP_START_METHOD=fork`` for jax-free parents that need instant
worker startup, or ``thread_pool=True`` for unpicklable datasets.
"""

import os
import pickle
import sys
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ... import ndarray as nd
from .sampler import SequentialSampler, RandomSampler, BatchSampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch NDArray (recursively for tuples)."""
    if isinstance(data[0], nd.NDArray):
        return nd.stack(*data, axis=0)
    if isinstance(data[0], (tuple, list)):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = np.asarray(data)
    return nd.array(data, dtype=data.dtype)


def default_mp_batchify_fn(data):
    """Worker-side batchify: numpy only (jax is not fork-safe; the
    parent converts to NDArray after the shm hop). Reference counterpart:
    default_mp_batchify_fn building shared-mem NDArrays
    (gluon/data/dataloader.py:77)."""
    first = data[0]
    if isinstance(first, nd.NDArray):  # dataset already made NDArrays
        return np.stack([d.asnumpy() for d in data])
    if isinstance(first, (tuple, list)):
        return [default_mp_batchify_fn(list(i)) for i in zip(*data)]
    return np.stack([np.asarray(d) for d in data])


# ------------------------------------------------------ shm transport ---
def _dtype_token(dtype):
    """Round-trippable dtype spelling. `.str` turns ml_dtypes bfloat16
    into an opaque '<V2' void dtype; names survive."""
    name = dtype.name if dtype.names is None else dtype.str
    return name


def _dtype_from_token(token):
    try:
        return np.dtype(token)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, token))


def _tree_arrays(tree, out):
    """Flatten nested lists/tuples of ndarrays, collecting leaves."""
    if isinstance(tree, (list, tuple)):
        return type(tree)(_tree_arrays(t, out) for t in tree)
    arr = np.ascontiguousarray(np.asarray(tree))
    out.append(arr)
    return len(out) - 1  # leaf placeholder: index into the array list


def _tree_fill(tree, leaves):
    if isinstance(tree, (list, tuple)):
        return type(tree)(_tree_fill(t, leaves) for t in tree)
    return leaves[tree]


def _batch_to_shm(batch):
    """Write every array leaf of `batch` into ONE SharedMemory segment.
    Returns (shm_name, structure, specs) — specs are (offset, shape,
    dtype_str) per leaf. The worker closes its mapping but does NOT
    unlink; the consumer unlinks after mapping (see _batch_from_shm)."""
    from multiprocessing import shared_memory
    arrays = []
    structure = _tree_arrays(batch, arrays)
    total = sum(a.nbytes for a in arrays)
    shm = shared_memory.SharedMemory(create=True, size=max(total, 1))
    specs = []
    off = 0
    for a in arrays:
        view = np.ndarray(a.shape, a.dtype, buffer=shm.buf, offset=off)
        view[...] = a
        specs.append((off, a.shape, _dtype_token(a.dtype)))
        off += a.nbytes
    name = shm.name
    shm.close()
    return name, structure, specs


def _batch_from_shm(name, structure, specs, convert):
    """Map the segment, rebuild the batch tree, unlink. The numpy views
    keep the mapping alive via the shm buffer; `convert` turns each leaf
    into its final form (NDArray in the parent) BEFORE the local handle
    is dropped, so no view outlives the segment."""
    from multiprocessing import shared_memory
    shm = shared_memory.SharedMemory(name=name)
    try:
        leaves = [convert(np.ndarray(shape, _dtype_from_token(dt),
                                     buffer=shm.buf, offset=off))
                  for off, shape, dt in specs]
        return _tree_fill(structure, leaves)
    finally:
        try:
            shm.close()
        finally:
            try:
                shm.unlink()
            except FileNotFoundError:
                pass


def _spawn_worker_entry(payload, key_queue, data_queue):
    """Spawn-mode entry: pin the CPU platform BEFORE unpickling anything
    (unpickling NDArrays re-creates them through jax — and the chip
    belongs to the parent: one process per chip, so a worker that
    touched it would fail or hang)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        dataset, batchify_fn = pickle.loads(payload)
    except Exception:
        # a worker that cannot even build its dataset must say so, or
        # the parent would block forever on an empty data queue
        data_queue.put((-1, -1, "fatal", traceback.format_exc()))
        os._exit(1)
    _worker_loop(dataset, key_queue, data_queue, batchify_fn)


def _worker_loop(dataset, key_queue, data_queue, batchify_fn):
    """Worker process body — PERSISTENT across epochs (spawn startup is
    seconds; the reference likewise keeps its worker pool alive for the
    DataLoader's lifetime). Batches go out through shm; only
    (generation, index, shm-spec) crosses the queue."""
    os.environ["JAX_PLATFORMS"] = "cpu"  # if anything strays into jax
    while True:
        item = key_queue.get()
        if item is None:
            break
        gen, idx, indices = item
        try:
            batch = batchify_fn([dataset[i] for i in indices])
            payload = _batch_to_shm(batch)
            data_queue.put((gen, idx, "ok", payload))
        except Exception:
            data_queue.put((gen, idx, "error", traceback.format_exc()))
    # skip atexit: a forked child inherits jax/XLA state whose teardown
    # hooks can hang without the parent's threads
    data_queue.close()
    data_queue.join_thread()
    os._exit(0)


def _shutdown_pool(key_queue, data_queue, workers):
    """Finalizer for the persistent pool (module-level: must not retain
    the DataLoader). Sends one sentinel per worker, then reaps."""
    try:
        for _ in workers:
            key_queue.put(None)
    except Exception:
        pass
    # drain so worker feeder threads can flush and exit, and so
    # outstanding shm segments get unlinked
    try:
        while True:
            rgen, idx, status, payload = data_queue.get(timeout=0.2)
            if status == "ok":
                _batch_from_shm(*payload, convert=lambda a: None)
    except Exception:
        pass
    for w in workers:
        w.join(timeout=5)
        if w.is_alive():
            w.terminate()


class DataLoader(object):
    """Loads data from a Dataset and returns mini-batches.

    Parameters mirror the reference loader: dataset, batch_size, shuffle,
    sampler, last_batch, batch_sampler, batchify_fn, num_workers,
    pin_memory (accepted, no-op on TPU), prefetch, thread_pool.
    """

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, prefetch=None,
                 thread_pool=False):
        self._dataset = dataset
        self._pin_memory = pin_memory
        self._thread_pool = thread_pool
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError(
                    "batch_size must be specified unless batch_sampler is "
                    "specified")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle \
                    else SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError(
                    "shuffle must not be specified if sampler is specified")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch if last_batch else "keep")
        elif (batch_size is not None or shuffle or sampler is not None or
              last_batch is not None):
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")
        self._batch_sampler = batch_sampler
        self._user_batchify = batchify_fn
        self._batchify_fn = batchify_fn if batchify_fn is not None \
            else default_batchify_fn
        self._num_workers = max(0, num_workers)
        self._prefetch = max(0, int(prefetch) if prefetch is not None
                             else 2 * self._num_workers)

    def _make_batch(self, indices):
        return self._batchify_fn([self._dataset[i] for i in indices])

    # ------------------------------------------------------ iteration ---
    def __iter__(self):
        if self._num_workers == 0:
            for batch in self._batch_sampler:
                yield self._make_batch(batch)
            return
        if self._thread_pool:
            yield from self._iter_threads()
        else:
            yield from self._iter_processes()

    def _iter_threads(self):
        with ThreadPoolExecutor(max_workers=self._num_workers) as pool:
            futures = []
            it = iter(self._batch_sampler)
            try:
                for _ in range(self._num_workers + self._prefetch):
                    futures.append(pool.submit(self._make_batch, next(it)))
            except StopIteration:
                it = None
            while futures:
                batch = futures.pop(0).result()
                if it is not None:
                    try:
                        futures.append(pool.submit(self._make_batch,
                                                   next(it)))
                    except StopIteration:
                        it = None
                yield batch

    def _ensure_pool(self):
        """Start (once) the persistent worker pool; respawning per epoch
        would pay seconds of spawn startup every __iter__."""
        if getattr(self, "_pool_workers", None):
            return
        import multiprocessing as mp
        import weakref
        # default SPAWN, not the reference's fork: the parent holds a
        # live multi-threaded jax runtime, and forking it deadlocks
        # probabilistically (a forked child inherits whatever locks
        # other threads held). Spawned workers boot clean interpreters
        # pinned to the CPU platform. MXNET_MP_START_METHOD=fork remains
        # available for jax-free parents that need instant startup.
        method = os.environ.get("MXNET_MP_START_METHOD", "spawn")
        ctx = mp.get_context(method)
        batchify = self._user_batchify if self._user_batchify is not None \
            else default_mp_batchify_fn
        self._key_queue = ctx.Queue()
        self._data_queue = ctx.Queue()
        if method == "fork":
            workers = [ctx.Process(
                target=_worker_loop,
                args=(self._dataset, self._key_queue, self._data_queue,
                      batchify), daemon=True)
                for _ in range(self._num_workers)]
        else:
            payload = pickle.dumps((self._dataset, batchify))
            workers = [ctx.Process(
                target=_spawn_worker_entry,
                args=(payload, self._key_queue, self._data_queue),
                daemon=True) for _ in range(self._num_workers)]
        for w in workers:
            w.start()
        self._pool_workers = workers
        self._pool_gen = 0
        # shut the pool down when the loader is garbage collected, via a
        # finalizer that must NOT hold a reference back to self
        weakref.finalize(self, _shutdown_pool, self._key_queue,
                         self._data_queue, workers)

    def _get_result(self, data_queue):
        """data_queue.get with worker-liveness checks: a dead pool must
        raise, not hang the parent forever."""
        import queue as _queue
        from ...base import MXNetError
        while True:
            try:
                return data_queue.get(timeout=5)
            except _queue.Empty:
                dead = [w.pid for w in self._pool_workers
                        if w.exitcode is not None]
                if dead:
                    raise MXNetError(
                        "DataLoader worker process(es) %s died without "
                        "reporting a result (killed? failed to start?); "
                        "aborting iteration" % dead)

    def _iter_processes(self):
        from ...base import MXNetError
        if getattr(self, "_iter_active", False):
            # one persistent pool, shared queues: two interleaved epochs
            # would consume each other's results. Fail loudly (the
            # reference's per-iterator worker sets allow this; here use
            # separate DataLoaders or thread_pool=True instead).
            raise MXNetError(
                "concurrent iteration of a multiprocess DataLoader is "
                "not supported; create separate DataLoader objects or "
                "use thread_pool=True")
        self._iter_active = True
        self._ensure_pool()
        self._pool_gen += 1
        gen = self._pool_gen
        key_queue, data_queue = self._key_queue, self._data_queue

        def to_nd(arr):
            # the parent's one device hop. The copy is REQUIRED: jax's
            # CPU backend aliases host numpy buffers zero-copy, so an
            # NDArray built directly on the shm view would dangle once
            # the segment is unlinked (observed as a segfault).
            return nd.array(np.array(arr, copy=True))

        sent = 0
        received = {}
        next_idx = 0
        it = iter(self._batch_sampler)
        try:
            for _ in range(self._num_workers + self._prefetch):
                try:
                    key_queue.put((gen, sent, next(it)))
                    sent += 1
                except StopIteration:
                    it = None
                    break
            while next_idx < sent:
                while next_idx not in received:
                    rgen, idx, status, payload = self._get_result(
                        data_queue)
                    if status == "fatal":
                        raise MXNetError(
                            "DataLoader worker failed to start:\n%s"
                            % payload)
                    if rgen != gen:   # stale epoch (early break): drop
                        if status == "ok":
                            _batch_from_shm(*payload,
                                            convert=lambda a: None)
                        continue
                    if status == "error":
                        raise MXNetError(
                            "DataLoader worker failed:\n%s" % payload)
                    received[idx] = payload
                payload = received.pop(next_idx)
                next_idx += 1
                if it is not None:
                    try:
                        key_queue.put((gen, sent, next(it)))
                        sent += 1
                    except StopIteration:
                        it = None
                yield _batch_from_shm(*payload, convert=to_nd)
        finally:
            # results of this epoch that were never consumed (early
            # break) stay queued; the NEXT epoch's stale-generation
            # check unlinks them lazily. The pool outlives the epoch.
            self._iter_active = False
            for payload in received.values():
                _batch_from_shm(*payload, convert=lambda a: None)

    def __len__(self):
        return len(self._batch_sampler)
