"""Device contexts.

Reference: python/mxnet/context.py — Context(device_type, device_id) with
`with ctx:` scoping and a thread-default. TPU-native mapping: a Context wraps
a concrete `jax.Device`. `gpu(i)` is accepted for source compatibility and
resolves to the i-th accelerator (TPU) when one exists.
"""

import threading

import jax

from . import chip
from .base import MXNetError

_thread_local = threading.local()


class Context:
    """Device context, usable as a `with` scope (python/mxnet/context.py:28)."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        # Contexts are constructed at import time (model_zoo ctx=cpu()
        # default args) and stay free of backend discovery; devices are
        # looked up at RESOLUTION (jax_device/_accelerators).
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            self.device_type = device_type
            self.device_id = device_id
        self._old_ctx = None

    # -- identity ---------------------------------------------------------
    @property
    def device_typeid(self):
        return self.devstr2type[self.device_type]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    # -- jax mapping ------------------------------------------------------
    @property
    def jax_device(self):
        """The concrete jax.Device this context denotes. An accelerator
        context (tpu/gpu) resolves to the host only when the process was
        explicitly pinned to the CPU (JAX_PLATFORMS=cpu — the tests'
        8-virtual-device stand-in); otherwise a missing accelerator is an
        error, and so is an index past the device count."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _devices_by_platform("cpu")
        else:
            devs = _accelerators()
            if not devs:
                if not _cpu_pinned():
                    raise MXNetError(
                        "%r: no accelerator in this process (jax found "
                        "platform %r); set JAX_PLATFORMS=cpu to run "
                        "accelerator contexts on the host on purpose"
                        % (self, jax.default_backend()))
                devs = _devices_by_platform("cpu")
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                "%r: device index out of range, %d %s device(s) present"
                % (self, len(devs),
                   devs[0].platform if devs else self.device_type))
        return devs[self.device_id]

    def empty_cache(self):
        """Release pooled memory (reference Context.empty_cache). XLA manages
        HBM arenas itself; provided as a no-op hook."""

    # -- scoping ----------------------------------------------------------
    def __enter__(self):
        if not hasattr(_thread_local, "ctx_stack"):
            _thread_local.ctx_stack = []
        _thread_local.ctx_stack.append(self)
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        _thread_local.ctx_stack.pop()

    @classmethod
    def default_ctx(cls):
        stack = getattr(_thread_local, "ctx_stack", None)
        if stack:
            return stack[-1]
        return _default_context()


def _devices_by_platform(platform):
    """Devices a Context index may denote. In a multi-process SPMD job
    only THIS process's devices are addressable for eager placement, so
    cpu(0)/tpu(0) means local device 0 (reference semantics: each worker
    sees its own GPUs); the global mesh is the parallel layer's job."""
    try:
        chip.devices()              # the first query: startup.backend
        if jax.process_count() > 1:
            return [d for d in jax.local_devices()
                    if d.platform == platform]
        return jax.devices(platform)
    except RuntimeError:
        return []


def _cpu_pinned():
    """True when the process was explicitly held to the CPU platform
    (JAX_PLATFORMS=cpu, or the same through jax.config)."""
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def _accelerators():
    devs = chip.devices()
    if jax.process_count() > 1:
        devs = jax.local_devices()
    return [d for d in devs if d.platform != "cpu"]


def _default_context():
    if _accelerators():
        return Context("tpu", 0)
    return Context("cpu", 0)


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Source-compat alias: reference scripts say `mx.gpu(0)`; on this stack
    it denotes the i-th accelerator (TPU) chip."""
    return Context("gpu", device_id)


def num_gpus():
    return len(_accelerators())


def num_tpus():
    return len(_accelerators())


def current_context():
    return Context.default_ctx()


def gpu_memory_info(device_id=0):
    """(free, total) bytes of the accelerator (reference
    context.gpu_memory_info over cudaMemGetInfo)."""
    from .util import get_gpu_memory
    return get_gpu_memory(device_id)
