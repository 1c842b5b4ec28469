"""Parallelism primitives — the TPU-native communication substrate.

Replaces the reference's three comm stacks (src/kvstore/comm.h CPU/P2P
tree reduce, kvstore_nccl.h NCCL, kvstore_dist.h ps-lite) with one layer:
jax.sharding Mesh + XLA collectives (psum/all_gather/reduce_scatter/
ppermute) over ICI within a slice and DCN across slices.

Axis convention (used across the framework):
  'dp' — data parallel          'tp' — tensor (model) parallel
  'pp' — pipeline parallel      'sp' — sequence/context parallel
  'ep' — expert parallel

The reference has only DP (kvstore) + manual-placement model parallelism
(group2ctx, graph_executor.cc:997). TP/PP/SP/EP here are capability
extensions enabled by GSPMD (SURVEY §2.3 'NOT PRESENT' row).
"""

from contextlib import contextmanager

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["Mesh", "NamedSharding", "P", "make_mesh", "current_mesh",
           "use_mesh", "set_mesh", "shard", "replicate", "all_reduce",
           "all_gather", "reduce_scatter", "ring_permute", "device_count",
           "init_distributed", "fusion", "elastic",
           "bucketed_all_reduce"]

_CURRENT_MESH = None


def init_distributed(coordinator=None, num_processes=None, process_id=None):
    """Join a multi-host SPMD job (the tools/launch.py bootstrap).

    Replaces the reference's ps-lite scheduler rendezvous
    (DMLC_PS_ROOT_URI / DMLC_ROLE env contract consumed by
    tools/launch.py + dmlc_tracker): every worker calls in with a shared
    coordinator address and its process id, after which jax.devices()
    spans all hosts and the mesh/collective layer works unchanged.
    Arguments default to the MXNET_TPU_* environment set by the
    launcher. No-op when the job has a single process and no
    coordinator is configured.
    """
    import os
    coordinator = coordinator or os.environ.get("MXNET_TPU_COORDINATOR")
    num_processes = int(num_processes or
                        os.environ.get("MXNET_TPU_NUM_PROC", "1"))
    process_id = int(process_id if process_id is not None
                     else os.environ.get("MXNET_TPU_PROC_ID", "0"))
    if coordinator is None and num_processes == 1:
        return False
    if (os.environ.get("JAX_PLATFORMS") or "").startswith("cpu"):
        # cross-process collectives on the CPU backend need the gloo
        # implementation (XLA:CPU's default rejects multiprocess
        # computations); must be set before the backend initializes
        try:
            jax.config.update("jax_cpu_collectives_implementation",
                              "gloo")
        except Exception:       # jaxlib built without gloo: leave as-is
            pass
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def device_count():
    return jax.device_count()


def make_mesh(axes=None, devices=None):
    """Build a Mesh from an axis-name -> size dict.

    make_mesh({'dp': 4, 'tp': 2}) lays 8 devices out as a 4x2 grid.
    Sizes of -1 are inferred (at most one). Defaults to pure DP over all
    devices."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if axes is None:
        axes = {"dp": n}
    names = list(axes.keys())
    sizes = list(axes.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n // known
    assert int(np.prod(sizes)) == n, \
        "mesh axes %s don't cover %d devices" % (dict(zip(names, sizes)), n)
    arr = np.asarray(devices).reshape(sizes)
    return Mesh(arr, tuple(names))


def current_mesh():
    """The active mesh (creates a default all-DP mesh on first use)."""
    global _CURRENT_MESH
    if _CURRENT_MESH is None:
        _CURRENT_MESH = make_mesh()
    return _CURRENT_MESH


def set_mesh(mesh):
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


@contextmanager
def use_mesh(mesh):
    global _CURRENT_MESH
    prev = _CURRENT_MESH
    _CURRENT_MESH = mesh
    try:
        yield mesh
    finally:
        _CURRENT_MESH = prev


def shard(x, spec, mesh=None):
    """Place an array (jax.Array / NDArray data) with a PartitionSpec."""
    mesh = mesh or current_mesh()
    data = x._data if hasattr(x, "_data") else x
    return jax.device_put(data, NamedSharding(mesh, spec))


def replicate(x, mesh=None):
    return shard(x, P(), mesh)


# ---------------------------------------------------------------------
# Collectives — inside shard_map/pjit these lower to ICI/DCN collectives.
# Outside a mapped context they operate on sharded global arrays via jnp
# (XLA inserts the communication).
# ---------------------------------------------------------------------

def all_reduce(x, axis_name="dp"):
    """psum over a mesh axis (usable inside shard_map)."""
    return jax.lax.psum(x, axis_name)


def all_gather(x, axis_name="dp", axis=0, tiled=True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name="dp", scatter_dimension=0):
    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=True)


def ring_permute(x, axis_name, shift=1):
    """ppermute by `shift` around the ring — building block for ring
    attention / pipeline transfers."""
    n = jax.lax.psum(1, axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm=perm)


# bucketed gradient fusion (one psum per ~25 MB bucket instead of one
# per array) — importable as mxnet_tpu.parallel.fusion; the in-jit
# entry point re-exported here for train-step authors
from . import fusion                                    # noqa: E402
from .fusion import bucketed_all_reduce                 # noqa: E402,F401
