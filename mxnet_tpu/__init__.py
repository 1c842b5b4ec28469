"""mxnet_tpu — a TPU-native deep-learning framework with the MXNet 1.5 API.

Brand-new implementation (NOT a port): the compute path is JAX/XLA/Pallas,
parallelism is jax.sharding Mesh + collectives over ICI/DCN, and eager /
hybridized execution maps onto XLA tracing + jit instead of an async CUDA
dependency engine.

API surface mirrors the reference (nswamy/incubator-mxnet):
  python/mxnet/__init__.py — top-level namespaces nd, sym, gluon, module,
  autograd, optimizer, kvstore, io, metric, initializer, ...
"""

import time as _time
_import_t0 = _time.perf_counter_ns()     # span startup.import, closed below

__version__ = "0.1.0"

from . import base
from .base import MXNetError
from . import context
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import engine
from . import storage
from . import ops
from . import ndarray
from . import ndarray as nd
from . import random
from . import autograd
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from . import executor
from . import lr_scheduler
from . import optimizer
from . import initializer
from . import initializer as init
from . import metric
from . import recordio
from . import image
from . import io
from . import kvstore
from . import callback
from . import model
from . import sparse
ndarray.sparse = sparse  # compressed-storage sparse module (nd.sparse)
ndarray.csr_matrix = sparse.csr_matrix
ndarray.row_sparse_array = sparse.row_sparse_array
from . import parallel
from . import module
mod = module  # reference alias (mx.mod)
from . import inspector
from .inspector import TensorInspector
from . import monitor
from .monitor import Monitor
from . import observability
from . import profiler
from . import runtime
from . import test_utils
from . import visualization
from . import operator
# the reference exposes custom ops as the `Custom` op in the nd namespace
# (src/operator/custom/custom.cc); symbolic Custom is unsupported — host
# callbacks cannot live inside a single compiled XLA graph (operator.py).
ndarray.Custom = operator.Custom
from . import registry
from . import rtc
from . import library
from . import libinfo
from . import util
from . import name
from .name import NameManager, Prefix
from . import attribute
from .attribute import AttrScope
from . import contrib
from . import log
from . import executor_manager
from . import kvstore_server
from . import torch
from . import utils
from . import models
from . import gluon
from . import rnn
from . import numpy as np
from . import numpy_extension as npx

# the package's import, jax's included, as a start-up span
observability.core.record_startup("startup.import", _import_t0)
