"""Execution engine shim.

Reference: src/engine/ — ThreadedEnginePerDevice schedules every kernel as an
async op with read/write NDArray-var dependencies to hide CUDA launch latency
(include/mxnet/engine.h:117-318, src/engine/threaded_engine.cc:288).

TPU-native stance: XLA's runtime already executes dispatched computations
asynchronously and in dependency order (jax.Array futures), so a user-space
dependency scheduler for device kernels would only add latency. What remains
engine-shaped on this stack:
  * `wait_to_read` / `WaitForVar`  -> jax.Array.block_until_ready()
  * `WaitForAll`                   -> sync over live arrays
  * host-side async work (IO prefetch, checkpoint writes) -> a small thread
    pool with FIFO ordering per key, mirroring FnProperty queues
    (include/mxnet/engine.h:95-112).

`set_bulk_size` is kept as an API no-op: op bulking is what XLA fusion +
jit tracing do natively. `MXNET_ENGINE_TYPE=NaiveEngine` IS honored: it
makes every eager dispatch block until its outputs are materialized —
the same synchronous, deterministic-ordering debug mode the reference's
NaiveEngine provides (src/engine/naive_engine.cc). With
`MXNET_ENFORCE_DETERMINISM=1` the RNG key chain is pinned to the
partitionable threefry derivation so random streams are reproducible
across process topologies (the TPU compute itself is already
deterministic — there is no atomics-ordering nondeterminism to forbid,
which is what the reference flag guards against in cuDNN).
"""

import os
import queue
import threading

import jax

_BULK_SIZE = int(os.environ.get("MXNET_ENGINE_BULK_SIZE", "15"))
_ENGINE_TYPE = os.environ.get("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice")
_ENFORCE_DETERMINISM = os.environ.get(
    "MXNET_ENFORCE_DETERMINISM", "0").lower() not in ("0", "", "false")

if _ENFORCE_DETERMINISM:  # pragma: no cover - env-dependent
    jax.config.update("jax_threefry_partitionable", True)


def engine_type():
    return _ENGINE_TYPE


def set_engine_type(name):
    """Switch engines at runtime (reference: MXNET_ENGINE_TYPE is
    read once at startup; runtime switching is a debugging convenience)."""
    global _ENGINE_TYPE
    prev = _ENGINE_TYPE
    _ENGINE_TYPE = name
    return prev


def is_naive():
    return _ENGINE_TYPE == "NaiveEngine"


def enforce_determinism():
    return _ENFORCE_DETERMINISM


def sync_outputs(arrays):
    """NaiveEngine semantics: the dispatch that produced `arrays` does
    not return until they are materialized on device."""
    for a in arrays:
        if hasattr(a, "block_until_ready"):
            a.block_until_ready()


_BACKEND_IS_CPU = None


def needs_serial_dispatch(arrays):
    """True when an eager dispatch must block before the next one: CPU
    backend with an output sharded over more than one device. Concurrent
    in-flight CPU executions containing collectives can interleave their
    rendezvous differently across the per-device threads and deadlock;
    TPU per-device streams execute programs in enqueue order (identical
    across devices from the single dispatching thread), so the real
    hardware path never pays this sync."""
    global _BACKEND_IS_CPU
    if _BACKEND_IS_CPU is None:
        # the backend is fixed once jax initializes; default_backend()
        # re-resolves config every call — too slow for the dispatch path
        _BACKEND_IS_CPU = jax.default_backend() == "cpu"
    if not _BACKEND_IS_CPU:
        return False
    for a in arrays:
        s = getattr(a, "sharding", None)
        if s is not None and len(getattr(s, "device_set", ())) > 1:
            return True
    return False


def sync_if_needed(arrays):
    """The one dispatch-exit barrier every eager/compiled launch site
    calls: blocks when NaiveEngine is active (synchronous debug mode) or
    when `needs_serial_dispatch` flags a multi-device CPU output (see
    its docstring for the rendezvous-interleave hazard)."""
    if is_naive() or needs_serial_dispatch(arrays):
        sync_outputs(arrays)


class _Worker(threading.Thread):
    def __init__(self):
        super().__init__(daemon=True)
        self.q = queue.Queue()
        self.start()

    def run(self):
        while True:
            fn, done = self.q.get()
            try:
                fn()
            finally:
                done.set()


class Engine:
    """Host-side async executor with per-key FIFO ordering."""

    def __init__(self):
        self._workers = {}
        self._pending = []
        self._lock = threading.Lock()

    def push(self, fn, key="default"):
        """Run `fn` asynchronously; ops with the same key run in FIFO order
        (mirrors per-var queues in src/engine/threaded_engine.h:104-229)."""
        with self._lock:
            w = self._workers.get(key)
            if w is None:
                w = self._workers[key] = _Worker()
            done = threading.Event()
            self._pending.append(done)
            w.q.put((fn, done))
        return done

    def wait_for_all(self):
        with self._lock:
            pending, self._pending = self._pending, []
        for ev in pending:
            ev.wait()


_ENGINE = Engine()


def get():
    return _ENGINE


def push(fn, key="default"):
    return _ENGINE.push(fn, key)


def wait_for_var(arr):
    """Engine::WaitForVar — block until `arr` is materialized."""
    if hasattr(arr, "block_until_ready"):
        arr.block_until_ready()


def wait_for_all():
    """MXNDArrayWaitAll: drain host-side queues and device work."""
    _ENGINE.wait_for_all()
    jax.effects_barrier()


def set_bulk_size(size):
    """Kept for API parity (engine op bulking == XLA fusion here)."""
    global _BULK_SIZE
    prev = _BULK_SIZE
    _BULK_SIZE = size
    return prev


def bulk(size):
    """Context manager parity with mx.engine bulking (no-op under XLA)."""
    class _Bulk:
        def __enter__(self):
            self._prev = set_bulk_size(size)

        def __exit__(self, *a):
            set_bulk_size(self._prev)
    return _Bulk()
