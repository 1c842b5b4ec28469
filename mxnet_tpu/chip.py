"""The chip a process runs on: what JAX reports, the published peak
rates by ``device_kind``, and where the compile cache lives.

One place for three things every measuring script needs, so that none
of them guesses: a script that must run on a chip asks
:func:`require_accelerator` and fails when there is none (no CPU
fall-through); a utilization or roofline share divides by
:func:`peaks` of the device it actually met (an unknown device is an
error, not a v5e default); and the persistent compilation cache is
placed by :func:`use_compile_cache` — from outside when
``JAX_COMPILATION_CACHE_DIR`` is set, else at one fixed path (the path
is part of the cache key, so a directory that moves never hits).
"""

import collections
import os
import time

import jax

from .base import MXNetError

ChipPeaks = collections.namedtuple(
    "ChipPeaks", ["bf16_flops", "hbm_bytes_per_s", "hbm_bytes"])

# Keyed by jax's ``device_kind`` string as the chip reports it.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 819 GB/s HBM bandwidth, 16 GB HBM per chip.
    "TPU v5 lite": ChipPeaks(197e12, 819e9, 16e9),
}

# The kind a CPU-pinned process models its roofline columns for: there
# the columns describe a program (flops and bytes from its HLO), never
# a measurement, and the chip this repo is written for is the v5e.
MODELLED_KIND = "TPU v5 lite"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_asked = False


def devices():
    """``jax.devices()``. The process's first call brings the backend up
    (on a TPU the runtime's 12-17 s of every run): it runs under the
    span ``startup.backend``, after the compile ledger's listener is
    installed, so that every program built on those devices is in it."""
    global _asked
    if _asked:
        return jax.devices()
    from .observability import core, recompile
    recompile.install()
    t0 = time.perf_counter_ns()
    devs = jax.devices()
    core.record_startup("startup.backend", t0)
    _asked = True
    return devs


def describe():
    """{"platform", "kind", "count"} exactly as JAX reports them."""
    devs = devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_accelerator(what="this script"):
    """describe(), or MXNetError when JAX found no accelerator: a
    measurement path never falls back to the CPU."""
    dev = describe()
    if dev["platform"] == "cpu":
        raise MXNetError(
            "%s needs an accelerator and jax found only platform %r "
            "(%d device(s)); nothing was run" %
            (what, dev["platform"], dev["count"]))
    return dev


def peaks(device_kind=None):
    """Published peaks of ``device_kind`` (default: the first device of
    this process; :data:`MODELLED_KIND` in a CPU-pinned process)."""
    if device_kind is None:
        dev = devices()[0]
        device_kind = (MODELLED_KIND if dev.platform == "cpu"
                       else dev.device_kind)
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise MXNetError(
            "no published peaks for device_kind %r: add it to "
            "mxnet_tpu/chip.py PEAKS with its source" % (device_kind,))


def use_compile_cache():
    """Turn on JAX's persistent compilation cache and return its
    directory. With ``JAX_COMPILATION_CACHE_DIR`` set JAX reads the
    variable itself and nothing is set here; otherwise the cache is at
    ``<checkout>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
